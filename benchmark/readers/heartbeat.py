"""Reader `heartbeat`: counters and span totals of the lane's own
heartbeat JSON, as value or as delta over the window.

args: {"num": [paths], "den": [paths]?, "delta": true, "scale": 1.0}
value = scale * sum(num) / sum(den), each term the difference between
the heartbeat after the window and the one before it (delta true) or
the later heartbeat's value.  A path is slash-separated, because span
names hold dots: "spans/embed.commit/total_ms".  Span sections exist
only in a traced run (SPTPU_TRACE=1).  A path the heartbeat lacks
counts 0 beside others that are there; all of them missing, or a zero
denominator, is nothing to read -> None.

Span means are means per recorded span over the window, taken from the
section `spans` (n, total_ms), whose histograms the daemon never
resets: the difference of two snapshots is the window's own."""


def dig(d, path: str):
    for part in path.split("/"):
        if not isinstance(d, dict) or part not in d:
            return None
        d = d[part]
    return d if isinstance(d, (int, float)) else None


def read(ctx, num, den=None, delta: bool = True, scale: float = 1.0):
    a, b = ctx.get("hb_start") or {}, ctx.get("hb_end") or {}

    def total(paths):
        out, found = 0.0, False
        for p in paths:
            hi = dig(b, p)
            if hi is None:
                continue              # e.g. a program that never ran
            found = True
            out += hi - ((dig(a, p) or 0.0) if delta else 0.0)
        return out if found else None

    top = total(num)
    if top is None:
        return None
    if not den:
        return scale * top
    bottom = total(den)
    if not bottom:
        return None
    return scale * top / bottom
