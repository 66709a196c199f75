"""Sabotage `window_sink_dropped` (rehearsal only, for benchmark/tests;
read at the cell's size from a scratch copy, PERF.md): the window
layers' softmax WITHOUT its learned sink — decode and suffix attention
alike normalise over the keys alone, as a kernel would that forgot the
online softmax's initial state (m = b_h, l = 1).  Every window layer's
output grows by 1 / (1 - the sink's share of the row's mass); global
layers, pages, prompt and tokens stay sound."""


def apply() -> None:
    from libsplinter_tpu.models import afmoe
    attend = afmoe.window_paged_attention

    def no_sink(*args, sinks=None, **kw):
        return attend(*args, sinks=None, **kw)
    afmoe.window_paged_attention = no_sink
