"""Sabotage `state_stale_at_cold_seat`: a cold seat skips the zeroing
of its state slot, so a newcomer starts from what the slot's last row
left — the hazard traffic of fresh prompts is made of (every join is a
seat into a slot another row just left).  The prompt's pages are its
own; only the recurrent state and the convolution's register are
stale."""


def apply() -> None:
    from libsplinter_tpu.models import kda

    kda.StateSlotPrograms.state_zero = lambda self, cache, row: None
