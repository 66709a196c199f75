"""Sabotage `latent_join_token_off_by_one` (rehearsal only, for
benchmark/tests): a row that joins reports the token its prefill
sampled but hands the NEXT id to its first decode step — one position
of every answer is computed from another token's embedding, its
logits unrelated to the reference's, while prompt, reported tokens and
every other position stay sound.  The planted fault of the
worst-position limit."""
import numpy as np


def apply() -> None:
    from libsplinter_tpu.models import mla
    model = mla.LatentCompletionModel
    chunk = model.paged_decode_chunk_async

    def wrong_first(self, cache, tokens, n, carry=None):
        t = np.asarray(tokens)
        return chunk(self, cache, np.where(
            t >= 0, (t + 1) % self.cfg.vocab_size, t), n, carry=carry)
    model.paged_decode_chunk_async = wrong_first
