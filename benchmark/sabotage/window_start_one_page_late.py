"""Sabotage `window_start_one_page_late` (rehearsal only, for
benchmark/tests; read at the cell's size from a scratch copy,
PERF.md): every sliding-window layer's first live key lies ONE PAGE
LATE — decode and suffix attention alike see `sliding_window - page`
keys instead of `sliding_window`, as an allocator would make them that
gave the oldest live page of the window group back a page too early.
Rows shorter than the window less a page, global layers, prompt and
tokens stay sound."""


def apply() -> None:
    from libsplinter_tpu.models import afmoe
    attend = afmoe.window_paged_attention

    def a_page_late(q, k_pool, v_pool, tables, lengths, *, window=0, **kw):
        page = k_pool.shape[3]
        return attend(q, k_pool, v_pool, tables, lengths,
                      window=window - page if window > page else window,
                      **kw)
    afmoe.window_paged_attention = a_page_late
