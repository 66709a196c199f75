"""Payload `queries`: (pool, dim) unit query vectors — even ones a
stored row plus `noise` of a random direction (a clear top-1), odd ones
random directions.  spec: {"kind": "queries", "pool": 4096, "noise":
0.05}.  Needs a configuration whose set-up filled the lane
(prepared["stored_rows"]).  Follows chip_smoke._queries."""
import numpy as np


def make(spec: dict, seed: int, st, prepared: dict) -> np.ndarray:
    rows = prepared.get("stored_rows")
    if rows is None:
        raise ValueError("a query mix needs a configuration that fills "
                         "its lane")
    return make_queries(spec, seed, st.vectors, rows)


def make_queries(spec: dict, seed: int, vectors, stored_rows) -> np.ndarray:
    rng = np.random.default_rng([int(seed), 3])
    n, d = int(spec["pool"]), vectors.shape[1]
    q = rng.standard_normal((n, d), dtype=np.float32)
    rows = rng.choice(np.asarray(stored_rows), (n + 1) // 2)
    q[0::2] = np.asarray(vectors[rows]) \
        + np.float32(spec.get("noise", 0.05)) * q[0::2]
    return q / np.linalg.norm(q, axis=1, keepdims=True)
