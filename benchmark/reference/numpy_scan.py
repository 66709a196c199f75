"""The plain reference of the search cells: an exact NumPy cosine scan
of the store's vector lane (float32 BLAS), independent of the daemon.

Candidates are the rows the benchmark itself filled (keys
<prefix><i>): every other live key of the lane store is a system key
("__..."), which a search never returns.  An answer is right when it
names k rows, its keys are the keys of those rows, every score is
within `max_score_err` of the scan's score for that row, and the rows
are the scan's top k — a swapped row has to tie the scan's k-th score
within the same tolerance.  Copied from chip_smoke.NumpyScan, without
the copy of the lane.

The control (run.py --control) is this scan with rows and queries
rounded to float8_e4m3, put in the daemon's place: the daemon scores
float32 rows on the MXU at default precision, which on the chip reads
as bfloat16 operands with float32 accumulation (max score error 3.1e-4
to 3.9e-4, PR 23), and its own --fast path reads the same (3.9e-4), so
the nearest precision BELOW what it runs at is float8."""
from __future__ import annotations

import time

import numpy as np


class Scan:
    def __init__(self, vectors, rows):
        self.vecs = vectors                  # the store's own view
        self.rows = np.asarray(rows)
        self.ok = np.zeros(vectors.shape[0], bool)
        self.ok[self.rows] = True

    def scores(self, queries: np.ndarray, chunk: int = 131072,
               low_precision: bool = False):
        """(nslots, Q) cosine of every candidate row; -inf elsewhere.
        low_precision: the control — rows and queries rounded to
        float8_e4m3 first, the rest as before."""
        n = self.vecs.shape[0]
        out = np.full((n, len(queries)), -np.inf, np.float32)
        if low_precision:
            import ml_dtypes
            f8 = ml_dtypes.float8_e4m3fn
            queries = queries.astype(f8).astype(np.float32)
        qn = queries / np.maximum(
            np.linalg.norm(queries, axis=1, keepdims=True), 1e-12)
        for lo in range(0, n, chunk):
            v = np.asarray(self.vecs[lo: lo + chunk])
            if low_precision:
                v = v.astype(f8).astype(np.float32)
            norms = np.maximum(np.linalg.norm(v, axis=1), 1e-12)
            s = (v @ qn.T) / norms[:, None]
            ok = self.ok[lo: lo + chunk]
            out[lo: lo + chunk][ok] = s[ok]
        return out

    @staticmethod
    def judge(s: np.ndarray, got_rows, got_scores, k: int, tol: float):
        """(right, score err) of one answer against its column `s`."""
        if len(got_rows) != k or len(got_scores) != k:
            return False, float("inf")
        got_rows = np.asarray(got_rows)
        if got_rows.min() < 0 or got_rows.max() >= len(s):
            return False, float("inf")
        ref_rows = np.argpartition(-s, k)[:k]
        kth = s[ref_rows].min()
        err = float(np.max(np.abs(np.asarray(got_scores) - s[got_rows])))
        swapped = set(map(int, got_rows)) - set(map(int, ref_rows))
        ties = all(s[r] >= kth - tol for r in swapped)
        return bool(ties and len(set(map(int, got_rows))) == k), err


def check(run) -> dict:
    """Search cells: a seeded sample of the answers the window returned
    (at least `sample` of them) against the scan."""
    t0 = time.perf_counter()
    spec, st = run.cfg["reference"], run.st
    lim = spec["limits"]
    recs = [r for r in run.res["records"]
            if isinstance(r.get("out"), dict) and "i" in r["out"]]
    rng = np.random.default_rng([int(run.args.seed), 13])
    n = min(int(spec["sample"]), len(recs))
    pick = [recs[int(i)] for i in rng.choice(len(recs), n, replace=False)] \
        if n else []
    k = int(run.traffic.get("k", 10))
    rows = run.prepared["stored_rows"]
    scan = Scan(st.vectors, rows)
    wrong = badkeys = 0
    worst = 0.0
    control = []                 # (right, err) of the float8 scan's answers
    for lo in range(0, len(pick), 64):
        part = pick[lo: lo + 64]
        qs = run.mix.payload[[r["q"] for r in part]]
        s = scan.scores(qs)
        for j, r in enumerate(part):
            out = r["out"]
            ok, err = scan.judge(s[:, j], out["i"], out["s"], k,
                                 lim["max_score_err"])
            wrong += not ok
            worst = max(worst, err)
            badkeys += out["keys"] != [st.key_at(int(i)) for i in out["i"]]
        if run.args.control and not run.cfg.get("control", {}).get("argv"):
            # the control: the scan itself, in float8, put in the
            # daemon's place and judged like the daemon's answers
            low = scan.scores(qs, low_precision=True)
            for j in range(len(part)):
                rows = np.argsort(-low[:, j])[:k]
                control.append(scan.judge(s[:, j], rows, low[rows, j], k,
                                          lim["max_score_err"]))
    sound = f"sound: max score err {worst:.3e}, {wrong} answers not the top k"
    if control:
        wrong = sum(not ok for ok, _ in control)
        worst = max(err for _, err in control)
        sound += (f"; CONTROL (float8 operands): max score err {worst:.3e}"
                  f", min over answers "
                  f"{min(err for _, err in control):.3e}, {wrong} not "
                  f"the top k")
    return {"compared": [
        ("max_score_err", worst, lim["max_score_err"], "<="),
        ("answers_not_the_scan_top_k", wrong, 0, "<="),
        ("answers_whose_keys_mismatch_rows", badkeys, 0, "<="),
        ("answers_sampled", len(pick), min(int(spec["sample"]),
                                           max(len(recs), 1)), ">=")],
        "note": f"{len(pick)} of {len(recs)} answers against an exact "
                f"NumPy scan of {len(rows)} rows, "
                f"{time.perf_counter() - t0:.1f}s; {sound}"}
