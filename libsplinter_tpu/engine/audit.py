"""Audit records of the completion daemon's continuous lane.

For a sample of the requests it serves, the daemon writes down what a
plain reference needs to hold the SERVED path to account: the prompt's
token ids as it admitted them, how many of them a prefix-cache hit
mapped, the ids it generated, and its logits (float32, over the
vocabulary it serves) behind EVERY generated token — the first are the
join's own, already on the host for sampling; the others stay on the
device with the decode chunk that sampled them
(models/mla.LatentPendingChunk.audit, one row's logits a step) and are
fetched once, when the row finishes.  One row is audited at a time and
nothing else waits for it: no extra forward pass, no extra sync on the
decode path.  A model may keep more than one row's logits a step
(`audit_lanes`): each LANE then audits one row at a time and counts its
own admissions — models/afmoe.py keeps two, lane 0 for admissions that
resumed from the prefix cache and lane 1 for those that prefilled from
nothing, so that a queue of both kinds of request is sampled on both
paths whatever order they arrive in; models/nemotron_h.py keeps three,
by the answer's BUDGET (short, middle, long): a long answer holds its
lane for hundreds of steps, and the short ones would never be sampled
behind it.

A record is `<dir>/<n>.npz` (written under a temporary name first):
key, wall-clock `t_admit` / `t_done`, `prompt`, `n_prefix`, `tokens`
(n,), `logits` (n, V): row i is what token i was sampled from.
`every`: admit one request in that many (a free slot permitting).
completer.main's --audit-dir / --audit-every turn it on.
"""
from __future__ import annotations

import os
import time

import numpy as np


class AuditRecord:
    __slots__ = ("key", "prompt", "n_prefix", "logits_first", "tokens",
                 "steps", "t_admit", "lane")

    def __init__(self, key, prompt, n_prefix, logits_first, lane=0):
        self.key = key
        self.lane = lane
        self.prompt = np.asarray(prompt, np.int32)
        self.n_prefix = int(n_prefix)
        self.logits_first = np.array(logits_first, np.float32)
        self.tokens: list[int] = []
        # for each token after the join's: (device array (n, V) of the
        # decode chunk that sampled it, the step in it)
        self.steps: list[tuple] = []
        self.t_admit = time.time()


class AuditLog:
    def __init__(self, dir: str, every: int = 16, lanes: int = 1):
        self.dir = dir
        self.every = max(1, int(every))
        self.lanes = max(1, int(lanes))
        self.written = 0
        self._seen = [0] * self.lanes
        self._open = [False] * self.lanes
        os.makedirs(dir, exist_ok=True)

    def wants(self, lane: int = 0) -> bool:
        """Called once per admission that prefilled something: True
        for one in `every` of the lane's, while the lane audits no
        other row."""
        self._seen[lane] += 1
        return not self._open[lane] \
            and self._seen[lane] % self.every == 0

    def open(self, key, prompt, n_prefix, logits_first,
             lane: int = 0) -> AuditRecord:
        self._open[lane] = True
        return AuditRecord(key, prompt, n_prefix, logits_first, lane)

    def drop(self, lane: int = 0) -> None:
        self._open[lane] = False

    def close(self, rec: AuditRecord) -> None:
        """The row finished: fetch its chunks' logits (each chunk
        once), write the record."""
        self._open[rec.lane] = False
        fetched: dict[int, np.ndarray] = {}
        rows = [rec.logits_first]
        for chunk, step in rec.steps[:len(rec.tokens) - 1]:
            if id(chunk) not in fetched:
                fetched[id(chunk)] = np.asarray(chunk, np.float32)
            got = fetched[id(chunk)][step]
            # (V,) from a model with one lane, (lanes, V) otherwise
            rows.append(got if got.ndim == 1 else got[rec.lane])
        path = os.path.join(self.dir, f"{self.written}.npz")
        with open(path + ".tmp", "wb") as f:
            np.savez(f, key=rec.key, t_admit=rec.t_admit,
                     t_done=time.time(), prompt=rec.prompt,
                     n_prefix=rec.n_prefix,
                     tokens=np.asarray(rec.tokens, np.int32),
                     logits=np.stack(rows[:max(len(rec.tokens), 1)]))
        os.replace(path + ".tmp", path)
        self.written += 1
