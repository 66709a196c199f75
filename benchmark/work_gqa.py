"""Operations and bytes of grouped-query attention over paged keys and
values, from shapes alone (the yardstick's arithmetic, kept with the
benchmark like work.py, work_mla.py and work_kda.py: the MODEL's work,
not the implementation's).

A query head of width d attends `keys` LIVE keys: 2 d FLOPs a key for
the score and 2 d for the value sum.  A key is live for a token if the
layer lets the token see it: every earlier token in a global layer,
the last `sliding_window` in a window layer — pages behind the window,
pages past the row's length, padding tokens of a bucket and dead rows
earn nothing.  Each DISTINCT token whose key and value a kernel event
reads costs its K and V rows once (2 x kv_heads x d values at the
pool's width) however many query heads share them; a query token costs
its q row in and its o row out."""
from __future__ import annotations


def gqa_attention(keys: float, kv_tokens: float, q_tokens: float,
                  heads: int, kv_heads: int, d: int,
                  itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one kernel event — one layer of one decode
    step or one suffix piece: `keys` the live (query token, key) pairs
    summed over its query tokens, `kv_tokens` the distinct tokens whose
    K and V it reads, `q_tokens` its live query tokens."""
    flops = 4.0 * heads * d * keys
    bytes_ = itemsize * d * (2.0 * kv_heads * kv_tokens
                             + 2.0 * heads * q_tokens)
    return flops, bytes_
