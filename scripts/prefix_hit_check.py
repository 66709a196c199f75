"""CI gate: a hot-prefix admission maps its pages instead of
prefilling them, and the answer does not change (`make prefix-check`).

Serves the SAME long prompt repeatedly through one in-process
continuous-batching completer (real tiny decoder, CPU) two ways:
with the prefix cache DISABLED (every admission dispatches the dense
bucket prefill) and ENABLED (the first admission warms the tree,
every later one maps the shared pages).  It counts, it does not
time — what the mapping is worth in seconds is the benchmark's
`pangu-docqa-shared-prefix` cell to say (PERF.md §5), on the chip.

Asserted:

  - greedy bytes identical with and without the cache;
  - every hot admission maps ALL of its prompt tokens
    (`prefix_tokens` == `prompt_tokens`, as the completer counts
    them) and dispatches no prefill, bucket or suffix;
  - the cache-disabled run maps nothing and dispatches one bucket
    prefill per admission (the counter counts what it should).
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from libsplinter_tpu import Store  # noqa: E402
from libsplinter_tpu.engine import protocol as P  # noqa: E402
from libsplinter_tpu.engine.completer import Completer  # noqa: E402
from libsplinter_tpu.models.decoder import (CompletionModel,  # noqa: E402
                                            DecoderConfig)

PAGE = 32
PROMPT_PAGES = 33
# chars = pages*PAGE - 1 because the byte tokenizer prepends BOS — the
# repeated prompt must land exactly on a page boundary so the hot path
# is the pure map + replay (zero prefill) form.
PROMPT = ("retrieval context: " * 70)[: PROMPT_PAGES * PAGE - 1]
TRIALS = 6


def complete(st, key: str, prompt: str) -> bytes:
    """Submit one completion and wait for its READY bytes."""
    st.set(key, prompt)
    st.label_or(key, P.LBL_INFER_REQ | P.LBL_WAITING)
    st.bump(key)
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        if st.labels(key) & P.LBL_READY:
            return st.get(key).rstrip(b"\0")
        time.sleep(0.001)
    raise SystemExit(f"request {key} never READY")


def count_calls(model, name: str) -> list[int]:
    """Count the model's dispatches of one prefill program from the
    outside: the completer calls `model.<name>` once per dispatch."""
    calls = [0]
    inner = getattr(model, name)

    def counted(*a, **kw):
        calls[0] += 1
        return inner(*a, **kw)

    setattr(model, name, counted)
    return calls


def run_lane(tag: str, enable_cache: bool) -> dict:
    name = f"/spt-pfxchk-{tag}-{os.getpid()}"
    Store.unlink(name)
    st = Store.create(name, nslots=256, max_val=8192, vec_dim=8)
    try:
        cfg = DecoderConfig.tiny(max_len=2048)
        model = CompletionModel(cfg, buckets=(1088,), temp=0.0,
                                seed=1, suffix_buckets=(16,))
        comp = Completer(st, model=model, max_new_tokens=6,
                         flush_tokens=1, template="none", batch_cap=4,
                         page_size=PAGE, pool_pages=110,
                         inflight_depth=1,
                         prefix_cache=enable_cache)
        comp.attach()
        comp.warmup_paged()
        bucket = count_calls(model, "paged_prefill_row")
        suffix = count_calls(model, "paged_append_prefill")
        th = threading.Thread(
            target=comp.run_continuous,
            kwargs=dict(idle_timeout_ms=5, stop_after=180.0),
            daemon=True)
        th.start()
        # the warmer is the one COLD admission of the cached lane: it
        # prefills the prompt and seeds the tree
        complete(st, f"{tag}/warm", PROMPT)
        prompt0 = comp.stats.prompt_tokens
        prefix0 = comp.stats.prefix_tokens
        bucket0, suffix0 = bucket[0], suffix[0]
        outs = [complete(st, f"{tag}/{i}", PROMPT)
                for i in range(TRIALS)]
        comp.stop()
        th.join(timeout=20)
        if th.is_alive():
            raise SystemExit("completer loop did not stop")
        return {
            "outs": outs,
            "admissions": TRIALS,
            "prompt_tokens": comp.stats.prompt_tokens - prompt0,
            "prefix_tokens": comp.stats.prefix_tokens - prefix0,
            "bucket_prefills": bucket[0] - bucket0,
            "suffix_prefills": suffix[0] - suffix0,
            "warmer_bucket_prefills": bucket0,
            "cache_hits": (comp.prefix_cache.stats.hits
                           if enable_cache else 0),
        }
    finally:
        st.close()
        Store.unlink(name)


def main() -> int:
    cold = run_lane("cold", enable_cache=False)
    hot = run_lane("hot", enable_cache=True)
    cold_out, hot_out = cold.pop("outs"), hot.pop("outs")
    per_prompt = PROMPT_PAGES * PAGE
    fails = []
    if cold_out != hot_out:
        fails.append("prefix-shared output diverged from the cache-"
                     f"disabled path: cold {cold_out[0]!r} hot "
                     f"{hot_out[0]!r}")
    if len(set(hot_out)) != 1:
        fails.append("greedy output differs between hot admissions")
    for tag, rep in (("cold", cold), ("hot", hot)):
        if rep["prompt_tokens"] != TRIALS * per_prompt:
            fails.append(f"{tag}: admitted {rep['prompt_tokens']} "
                         f"prompt tokens, expected "
                         f"{TRIALS * per_prompt}")
    if hot["prefix_tokens"] != hot["prompt_tokens"]:
        fails.append(f"hot admissions mapped {hot['prefix_tokens']} of "
                     f"{hot['prompt_tokens']} prompt tokens")
    if hot["bucket_prefills"] or hot["suffix_prefills"]:
        fails.append(f"hot admissions dispatched prefills: "
                     f"{hot['bucket_prefills']} bucket, "
                     f"{hot['suffix_prefills']} suffix")
    if hot["warmer_bucket_prefills"] != 1:
        fails.append("the cached lane's cold warmer dispatched "
                     f"{hot['warmer_bucket_prefills']} bucket "
                     "prefills, expected 1")
    if hot["cache_hits"] < TRIALS:
        fails.append(f"hot run missed the cache: {hot['cache_hits']} "
                     f"hits of {TRIALS}")
    if cold["prefix_tokens"]:
        fails.append(f"cache-disabled run mapped "
                     f"{cold['prefix_tokens']} tokens")
    if cold["bucket_prefills"] != TRIALS:
        fails.append(f"cache-disabled run dispatched "
                     f"{cold['bucket_prefills']} bucket prefills for "
                     f"{TRIALS} admissions")
    print(json.dumps({"check": "prefix_hit", "ok": not fails,
                      "fails": fails,
                      "bytes_identical": cold_out == hot_out,
                      "cold": cold, "hot": hot}))
    return 1 if fails else 0


if __name__ == "__main__":
    raise SystemExit(main())
