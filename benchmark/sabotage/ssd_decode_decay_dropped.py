"""Sabotage `ssd_decode_decay_dropped`: the state-space decode step
leaves the decay out (S_t = S_{t-1} + dt x B^T): every generated token
adds to the state and nothing ever fades.  The prefill is sound, so an
answer's first logits are right and its later ones drift."""


def apply() -> None:
    import jax.numpy as jnp

    from libsplinter_tpu.models import nemotron_h
    from libsplinter_tpu.ops import ssd_scan

    def step(x, dt, a, bm, cm, states, **kw):
        # the input keeps its dt; the decay sees A = 0
        return ssd_scan.ssd_decode_step(x, dt, jnp.zeros_like(a), bm, cm,
                                        states, **kw)

    nemotron_h.ssd_decode_step = step
