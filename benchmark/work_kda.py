"""Operations and bytes of the gated delta-rule (KDA) kernels, from
shapes alone (the yardstick's arithmetic, kept with the benchmark like
work.py and work_mla.py: the MODEL's work, not the implementation's).

Per token and head of width d the recurrence is three d x d products'
worth of work — S'^T k, the rank-one update k u^T, S^T q — and the
decay's d x d multiply: 6 d^2 + d^2 FLOPs.

  decode step   all of it, in one kernel: 7 d^2 FLOPs a live row and
                head; its state read and written once a layer at its
                stored width (2 x heads x d x d x state_itemsize), its
                q, k, v, g rows and b in, its o row out (float32)
  chunk prefill the chunkwise form splits the recurrence in two.  What
                carries the state from chunk to chunk is the kernel a
                trace can name, and ONLY that part is counted here: a
                token's three products against the state (6 d^2), its
                row of the chunk's (C x C) score matrix against the
                chunk's pseudo values (2 C d), the decay once a chunk
                (d^2 / C); a token's four d-wide float32 operand rows
                and its C-wide score row in, its o row out, and the
                row's state in and out once a call.  The state-free
                part inside a chunk (pairwise decays, the unit
                triangular solve) is the same for every chunked form
                but runs as unnamed XLA fusions: it is in neither the
                work nor the seconds of kda_prefill_roofline.

Dead rows, padding tokens of a bucket and snapshot slots earn nothing."""
from __future__ import annotations


def kda_decode(rows: float, heads: int, d: int,
               state_itemsize: int = 4) -> tuple[float, float]:
    """(FLOPs, bytes) of one kernel event: one layer of one decode
    step over `rows` live rows."""
    flops = rows * heads * 7.0 * d * d
    bytes_ = rows * heads * (2.0 * d * d * state_itemsize
                             + 4.0 * (5 * d + 1))
    return flops, bytes_


def kda_prefill(tokens: float, heads: int, d: int, chunk: int,
                state_itemsize: int = 4) -> tuple[float, float]:
    """(FLOPs, bytes) of one kernel event: the state's walk over one
    layer of one prefill call, `tokens` live tokens of one row in
    chunks of `chunk`."""
    flops = tokens * heads * (6.0 * d * d + 2.0 * chunk * d
                              + float(d * d) / chunk)
    bytes_ = heads * (tokens * 4.0 * (5 * d + chunk)
                      + 2.0 * d * d * state_itemsize)
    return flops, bytes_
