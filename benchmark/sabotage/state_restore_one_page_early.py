"""Sabotage `state_restore_one_page_early` (rehearsal only, for
benchmark/tests): every state snapshot is taken ONE PAGE SHORT of the
page boundary the prefix tree files it under, so a join that resumes
from a prefix-cache hit restores the recurrent state of a page earlier
than the pages it maps, without re-running that page: every later turn
of a session starts from a state that lacks a page of its history,
while prompt, pages and tokens stay sound."""


def apply() -> None:
    import jax.numpy as jnp

    from libsplinter_tpu.models import kda
    model = kda.HybridCompletionModel
    program = model._suffix_program

    def a_page_short(self, sb):
        fn = program(self, sb)

        def run(params, pools, states, table, length, ids, n_valid, row,
                n_snap, snap_slot):
            page = pools[0].shape[2]
            return fn(params, pools, states, table, length, ids, n_valid,
                      row, jnp.int32(max(int(n_snap) - page, 0)),
                      snap_slot)
        return run
    model._suffix_program = a_page_short
