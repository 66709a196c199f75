"""Operations and bytes of grouped-query attention whose keys and
values DIFFER IN WIDTH and whose key/value heads differ by layer kind,
over paged keys and values, from shapes alone (the yardstick's
arithmetic, kept with the benchmark like work.py, work_gqa.py,
work_mla.py and work_kda.py: the MODEL's work, not the
implementation's).

A query head attends `keys` LIVE keys: 2 d_qk FLOPs a key for the
score and 2 d_v for the value sum.  A learned sink adds one exponential
a (query, head) and no matrix work: it earns nothing here.  A key is
live for a token if the layer lets the token see it: every earlier
token in a global layer, the last `sliding_window` in a window layer —
pages behind the window, pages past the row's length, padding tokens
of a bucket and dead rows earn nothing.  Each DISTINCT token whose key
and value a kernel event reads costs its K row at d_qk and its V row
at d_v once a kv head, however many query heads share them and
however the pool pads or lays them out; a query token costs its q row
in (d_qk a head) and its o row out (d_v a head)."""
from __future__ import annotations


def swa_attention(keys: float, kv_tokens: float, q_tokens: float,
                  heads: int, kv_heads: int, d_qk: int, d_v: int,
                  itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one kernel event — one layer of one decode
    step or one suffix piece: `keys` the live (query token, key) pairs
    summed over its query tokens, `kv_tokens` the distinct tokens whose
    K and V it reads, `q_tokens` its live query tokens."""
    flops = 2.0 * heads * (d_qk + d_v) * keys
    bytes_ = itemsize * (d_qk + d_v) * (kv_heads * kv_tokens
                                        + heads * q_tokens)
    return flops, bytes_
