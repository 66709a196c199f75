"""Sabotage `latent_decode_detuned` (rehearsal only, for
benchmark/tests): the latent decode and suffix attention run with
their softmax scale a fifth too large — the timed path broken where a
logit is produced, prompt and tokens untouched."""


def apply() -> None:
    from libsplinter_tpu.models import mla
    attend = mla.latent_paged_attention

    def detuned(q, pool, tables, lengths, *, scale, **kw):
        return attend(q, pool, tables, lengths, scale=scale * 1.2, **kw)
    mla.latent_paged_attention = detuned
