"""Sabotage `sparse_select_recent_only` (rehearsal only, for
benchmark/tests; read at the cell's size from a scratch copy, PERF.md):
the indexer's selection replaced by the LAST `topk` positions — decode
and joins alike attend a sliding window of 2,048 keys and never read a
score.  The cheapest wrong program: it would look like a speed-up (no
scan, no selection, 17 pages walked instead of 258).  Pages, prompt
and tokens stay sound; a row under `topk` tokens is untouched."""


def apply() -> None:
    import jax.numpy as jnp

    from libsplinter_tpu.ops import sparse_attention as sa

    def recent_only(scores, limits, *, topk, **kw):
        pos = jnp.arange(scores.shape[-1])
        lim = jnp.asarray(limits, jnp.int32)[..., None]
        return ((pos < lim) & (pos >= lim - topk)).astype(jnp.float32)
    sa.select_topk = recent_only
