"""Operations and bytes of the device work, from shapes alone.

The yardstick's arithmetic: kept here so that no PR that claims a gain
can change what a kernel is credited with.  A kernel of another kind
brings a file of its own beside this one.
"""
from __future__ import annotations


def topk_scan(rows: int, dim: int, queries: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one cosine top-k dispatch over a lane that
    holds `rows` vectors: what a scan HAS to do — read every stored row
    once (f32) with its mask entry and score it against `queries` query
    rows.  Slots that hold no row are not credited, whatever the
    program reads: a lane staged with empty slots earns nothing for
    them, and one compacted later cannot pass 100% by it."""
    flops = 2.0 * rows * dim * queries
    bytes_ = 4.0 * rows * dim + 4.0 * rows + 4.0 * queries * dim
    return flops, bytes_


def peak_for(peaks: dict, device_kind: str) -> dict:
    """The row of peaks.json whose key is a substring of device_kind.
    An unknown device is an error, never a default."""
    kind = (device_kind or "").lower()
    for key, row in peaks["devices"].items():
        if key in kind:
            return row
    raise KeyError(f"no peaks known for device_kind {device_kind!r}: add "
                   "it to benchmark/peaks.json with its source")
