"""The two limits of reference/latent_moe_block.py each come out
FAILED through run.py under the fault planted for it (CPU rehearsal):
the softmax scale a fifth too large moves every position (the
percentile), a joiner's first decode step fed another token moves one
position of every answer by the distance between unrelated logits
(the worst position).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q"""
import pytest

from test_rehearse import last, run

CELL = "pangu-docqa-shared-prefix"


def compared(lines) -> dict:
    """name -> 'ok' | 'FAILED' of the run's `compared:` lines."""
    return {ln.split()[1]: ln.split()[-1] for ln in lines
            if ln.startswith("compared: ")}


@pytest.mark.parametrize("sabotage, trips", [
    ("latent_decode_detuned", "logit_err_p90"),
    ("latent_join_token_off_by_one", "logit_err_worst_position"),
])
def test_each_limit_fails_under_its_planted_fault(sabotage, trips):
    p, lines = run(CELL, "--sabotage", sabotage)
    assert p.returncode == 0, p.stderr[-2000:]
    assert last(lines)["correct"] is False
    assert compared(lines)[trips] == "FAILED", lines[-12:]
