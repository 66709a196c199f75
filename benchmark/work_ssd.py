"""Operations and bytes of the Mamba-2 (SSD) state-space kernels and of
UN-GATED routed experts, from shapes alone (the yardstick's arithmetic,
kept with the benchmark like work.py and work_kda.py: the MODEL's work,
not the implementation's).

Per token and head (P the head's width, N the state size) the
recurrence S_t = exp(dt A) S_{t-1} + dt x B^T, y = S_t C is five P x N
passes' worth of work: the decay's multiply, the outer product's
multiply and its add, and the output's multiply-add — 5 P N FLOPs.

  decode step    all of it, in one kernel: 5 P N FLOPs a live row and
                 head; its state read and written once a layer at its
                 stored width (2 x heads x P x N x state_itemsize), its
                 x row (heads x P), its dt (heads), its B and C (2 x
                 groups x N) in, its y row (heads x P) out (float32)
  chunk prefill  the SAME count a live token, whatever implements it:
                 the chunked form trades the per-token outer products
                 for matrix products over a chunk (C B^T, the masked
                 (chunk x chunk) product, the state's products), which
                 all run in the one named kernel and whose seconds are
                 all in the share — a form that needs more operations
                 than 5 P N a token-head reads a lower share, as it
                 should.  Bytes: a token's x, dt, B, C in and y out
                 (float32), and the row's state in and out once a call.

An un-gated expert is down(relu(up(x))^2): TWO matrices.  A (token,
expert) SLOT costs 2 x hidden x width FLOPs for each of the up and
down products.  An expert that received at least one slot is LIVE: its
two matrices (2 x hidden x width values) cross HBM once, however many
slots it serves.  A slot's row goes in for the up product and its
result comes out (2 x hidden values), its intermediate is written and
its square read (2 x width values).

Dead rows, padding tokens of a bucket, snapshot slots and experts
nobody chose earn nothing."""
from __future__ import annotations


def ssd_decode(rows: float, heads: int, p: int, n: int, groups: int,
               state_itemsize: int = 4) -> tuple[float, float]:
    """(FLOPs, bytes) of one kernel event: one layer of one decode
    step over `rows` live rows."""
    flops = rows * heads * 5.0 * p * n
    bytes_ = rows * (heads * 2.0 * p * n * state_itemsize
                     + 4.0 * (2 * heads * p + heads + 2 * groups * n))
    return flops, bytes_


def ssd_prefill(tokens: float, heads: int, p: int, n: int, groups: int,
                state_itemsize: int = 4) -> tuple[float, float]:
    """(FLOPs, bytes) of one kernel event: one layer of one prefill
    call over `tokens` live tokens of one row."""
    flops = tokens * heads * 5.0 * p * n
    bytes_ = tokens * 4.0 * (2 * heads * p + heads + 2 * groups * n) \
        + heads * 2.0 * p * n * state_itemsize
    return flops, bytes_


def expert_ffn_ungated(live_experts: float, slots: float, hidden: int,
                       width: int, itemsize: int = 2
                       ) -> tuple[float, float]:
    """(FLOPs, bytes) of ONE expert layer's two grouped products for
    `slots` (token, expert) slots over `live_experts` experts."""
    flops = 4.0 * slots * hidden * width
    bytes_ = itemsize * (2.0 * live_experts * hidden * width
                         + 2.0 * slots * (hidden + width))
    return flops, bytes_
