"""Operations and bytes of the latent-attention decode kernel, from
shapes alone (the yardstick's arithmetic, kept with the benchmark like
work.py: no PR that claims a gain can change what the kernel is
credited with).

One event of the kernel is one layer of one decode step over the whole
batch: every live row's query heads attend that row's LIVE tokens in
the latent space.  Credited is what that HAS to do: read each live
token's latent row once for all heads (width values of `itemsize`
bytes), read the folded queries and write the outputs, and two matrix
products a head — scores over the full latent width, the weighted sum
over its first kv_rank values.  Trash pages, the padding of a ragged
last page and dead rows earn nothing."""
from __future__ import annotations


def latent_decode(rows: float, live_tokens: float, heads: int,
                  kv_rank: int, rope_dim: int,
                  itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one kernel event: `rows` live rows whose
    contexts add up to `live_tokens` tokens."""
    width = kv_rank + rope_dim
    flops = 2.0 * heads * live_tokens * (width + kv_rank)
    bytes_ = itemsize * (live_tokens * width
                         + rows * heads * (width + kv_rank))
    return flops, bytes_
