"""Sabotage `state_zeroed_at_restore` (rehearsal only, for
benchmark/tests; read at the cell's size from a scratch copy, PERF.md):
every restore of a row's state from a prefix-tree snapshot ZEROES the
row's slot instead — a join that resumes from a prefix-cache hit starts
its convolutions from an empty register, as if the tokens before the
hit's boundary had never been: the first conv_L_cache - 1 tokens of
its suffix see wrong taps in every convolution layer, while prompt,
pages, keys and tokens stay sound.  Only a join whose answer follows
within a few tokens of the boundary can show it."""


def apply() -> None:
    from libsplinter_tpu.models import lfm2
    model = lfm2.ConvCompletionModel

    def zeroed(self, cache, src: int, row: int):
        self.state_zero(cache, row)
    model.state_restore = zeroed
