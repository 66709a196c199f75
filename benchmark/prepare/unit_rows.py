"""Set-up step `unit_rows`: fill the store's vector lane with seeded
unit vectors under keys <key_prefix><i>, before the daemon starts.
spec: {"name": "unit_rows", "key_prefix": "vec/", "threads": 8}; the
number of rows is the configuration's own `rows`.  The keys go in by
st.set from one thread (the calls hold the GIL, more threads only queue
for it), then the vectors in bulk through the writable st.vectors view
from `threads` threads (NumPy lets go of the GIL).  Gives
prepared["stored_rows"]: the slot of each row."""
import threading

import numpy as np


def prepare(st, cfg: dict, spec: dict, seed: int, work_dir: str) -> dict:
    n = int(cfg["rows"])
    if n >= st.nslots:
        raise ValueError(f"{n} rows do not fit {st.nslots} slots")
    prefix = spec.get("key_prefix", "vec/")
    rows = np.zeros(n, np.int64)
    for i in range(n):
        key = f"{prefix}{i:07d}"
        st.set(key, "x")
        rows[i] = st.find_index(key)
    nthreads = int(spec.get("threads", 8))
    view = st.vectors

    def part(t: int) -> None:
        rng = np.random.default_rng([int(seed), 7, t])
        lo, hi = n * t // nthreads, n * (t + 1) // nthreads
        for a in range(lo, hi, 32768):
            b = min(a + 32768, hi)
            v = rng.standard_normal((b - a, view.shape[1]),
                                    dtype=np.float32)
            v /= np.linalg.norm(v, axis=1, keepdims=True)
            view[rows[a:b]] = v

    ts = [threading.Thread(target=part, args=(t,)) for t in range(nthreads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return {"stored_rows": rows}
