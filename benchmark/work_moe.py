"""Operations and bytes of a routed expert layer's three grouped
products, from shapes alone (the yardstick's arithmetic, kept with the
benchmark like work.py and work_gqa.py: the MODEL's work, not the
implementation's).

A (token, expert) SLOT costs the expert's SwiGLU on one row: 2 x
hidden x width FLOPs for each of the gate, up and down products.  An
expert that received at least one slot is LIVE: its three matrices
(3 x hidden x width values) cross HBM once, however many slots it
serves; an expert nobody chose earns nothing, whatever the program
reads.  A slot's row goes in for the gate and the up product and its
result comes out (3 x hidden values), its two intermediates are
written and their product read (3 x width values).  Padding rows of a
tile, dead rows of a batch and pad tokens of a bucket earn nothing."""
from __future__ import annotations


def expert_ffn(live_experts: float, slots: float, hidden: int, width: int,
               itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of ONE expert layer's grouped products for
    `slots` (token, expert) slots over `live_experts` experts."""
    flops = 6.0 * slots * hidden * width
    bytes_ = itemsize * (3.0 * live_experts * hidden * width
                         + 3.0 * slots * (hidden + width))
    return flops, bytes_
