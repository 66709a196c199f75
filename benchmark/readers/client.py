"""Reader `client`: a statistic of what the generator's own clock saw.

args: {"sample": "write_us" | "latency_ms", "stat": "p50" | "p95" | "mean"}
Samples come from run.py's client_samples(): `write_us` is the time of
the client's own store write calls per request (the bulk protocol's
set+label+bump, or the query vector's vec_set), `latency_ms` the time of
each one-request client call.  Nothing to read -> None."""
import numpy as np


def read(ctx, sample: str, stat: str = "p50"):
    values = ctx["client"].get(sample)
    if not values:
        return None
    if stat == "mean":
        return float(np.mean(values))
    return float(np.percentile(values, float(stat.lstrip("p"))))
