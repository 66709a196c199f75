"""Operations and bytes of attention under a learned indexer (the
DeepSeek-V3.2-Exp report's sparse attention) over paged keys, from
shapes alone (the yardstick's arithmetic, kept with the benchmark like
work.py, work_gqa.py and work_swa.py: the MODEL's work, not the
implementation's).

The indexer scores a query against a key with `heads` products of
`dim` and a ReLU-weighted sum: 2 (dim + 1) FLOPs a head a (query, key)
pair.  Each DISTINCT indexer key an event reads costs its `dim` values
once, however many queries score it; a query token costs its heads x
dim queries and its heads weights in.  What the scan writes (a score a
pair) and what the selection reads are the implementation's and earn
nothing.

The attention that follows is grouped-query attention over the
SELECTED pairs only (work_gqa's arithmetic): 2 d FLOPs a head a pair
for the score and 2 d for the value sum; each distinct token whose K
and V an event reads costs 2 d values a kv head once — for a decode
step the tokens it selected, for a join the context under its queries
(each query has a selection of its own, and together they cover it)."""
from __future__ import annotations


def index_scan(pairs: float, key_tokens: float, q_tokens: float,
               heads: int, dim: int, itemsize: int = 2
               ) -> tuple[float, float]:
    """(FLOPs, bytes) of the scan of one kernel event: `pairs` the
    (query, key) pairs scored, `key_tokens` the distinct indexer keys
    read, `q_tokens` its live query tokens."""
    flops = 2.0 * heads * (dim + 1) * pairs
    bytes_ = itemsize * dim * (key_tokens + heads * q_tokens) \
        + 4.0 * heads * q_tokens
    return flops, bytes_


def selected_attention(pairs: float, kv_tokens: float, q_tokens: float,
                       heads: int, kv_heads: int, d: int,
                       itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of the attention of one kernel event: `pairs` the
    SELECTED (query token, key) pairs, `kv_tokens` the distinct tokens
    whose K and V it reads, `q_tokens` its live query tokens."""
    flops = 2.0 * heads * 2 * d * pairs
    bytes_ = itemsize * 2 * d * (kv_heads * kv_tokens + heads * q_tokens)
    return flops, bytes_
