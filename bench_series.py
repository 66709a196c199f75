"""The bench series: ONE process holds the chip and measures
everything.

A chip belongs to one process at a time, and every process pays its
own start-up and compiles.  So the whole series runs in one process:
every phase back to back, each record appended to bench_results.jsonl
the moment it completes:

  embed          e2e embedding throughput + event-driven p50
                 set->vector with per-stage histogram quantiles
                 (the headline metric)
  embed_sweep    e2e throughput across (batch_cap, inflight_depth)
                 configs — the which-knob-next data for the
                 throughput gap
  profile        device / sync / pipelined ms per (batch, bucket)
                 with TFLOP/s + MFU on TPU
  kernels        every Pallas kernel executed + checked vs the jnp
                 math on the same backend: flash fwd, blockwise bwd,
                 causal prefill w/ GQA, fused cosine top-k (f32+bf16)
  search         cosine top-k queries/sec over the largest lane the
                 remaining window affords (target 1M rows)
  decode         prefill / chunked / per-token-sync / batched /
                 speculative tokens per second, plus the paged-vs-
                 dense KV sweep (batch {8,32,64} over a fixed
                 8-window page pool)
  decode_quant   the same core decode with int8 weight residency
  multichip      pod-sharded paged decode: aggregate tok/s through
                 ShardedCompletionModel (kv-head-sharded pools,
                 shard_map'd ragged kernel) at batch {32,64} over a
                 tp mesh of every visible device — vs the r05
                 single-chip row; CPU-mesh rows are labeled smoke
  loadgen        open-loop multi-tenant serving under QoS: a full
                 in-process stack (tiny real models) serves mixed
                 3-tenant embed/search/complete traffic from `spt
                 loadgen`'s clock-driven arrivals — goodput vs shed
                 + per-tenant p99, cpu_smoke-labeled off-TPU
  decode_daemon  completion-daemon e2e + continuous serving (the
                 only phase that ever hung on-chip, so it runs LAST)

Phases are ordered headline-first and each is fenced: a phase failure
logs, the later phases still run and record, and the run then EXITS
NON-ZERO — no path reports success after a failed phase.  Every phase
checks the remaining window before starting.  The ledger
(bench_results.jsonl) is the single source of truth; docs quote it,
never the other way around.

A run is meant for the chip: it raises at start when JAX finds no TPU,
and on a device_kind whose peak it does not know.  BENCH_CPU=1 is the
explicit CPU quick-track: small sizes, kernels interpreted, every
record labelled `"cpu_quick_track": true` — never a device number.

Entry points:
  python bench.py / bench_series.py  run BENCH_PHASES (default: all)
  bench_profile/decode/search.py     thin shims over single phases

Env: BENCH_CPU=1 (host CPU), BENCH_PHASES=embed,kernels,...,
SPTPU_BENCH_DEADLINE_EPOCH (wall-clock budget; phases that can't fit
are skipped), plus the per-phase knobs documented on each phase
function.
"""
from __future__ import annotations

import functools
import io
import json
import os
import re
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

RESULTS_LOG = os.environ.get(
    "SPTPU_BENCH_LEDGER", os.path.join(REPO, "bench_results.jsonl"))
BASELINE_PER_CHIP = 12_500.0
# ledger timestamp format — shared with bench.py's age check
TS_FMT = "%Y-%m-%dT%H:%M:%S%z"

ALL_PHASES = ("embed", "embed_sweep", "profile", "dispatch", "kernels",
              "search", "restage", "decode", "decode_quant",
              "multichip", "loadgen", "prefix", "disagg", "tier",
              "decode_daemon", "store_ops")

# conservative floor (seconds) a phase needs to be worth starting;
# compile costs dominate these on a cold .xla_cache
PHASE_MIN_S = {"embed": 0, "embed_sweep": 120, "profile": 90,
               "dispatch": 20,
               "kernels": 120, "search": 150, "restage": 180,
               "decode": 180, "decode_quant": 150, "multichip": 120,
               "loadgen": 60, "prefix": 90, "disagg": 90, "tier": 60,
               "decode_daemon": 120, "store_ops": 15}


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def append_ledger(rec: dict, *, stamp: bool = True) -> dict:
    """THE ledger append (every bench entry point routes here so the
    path, timestamp format, and durability stay in one place).
    Atomic single write + fsync: evidence must survive a later hang.

    A run with SPTPU_FAULT armed is a chaos drill, not a performance
    claim: the record is labeled so a before/after comparison can
    never mistake fault-degraded numbers for a regression."""
    rec = dict(rec)
    if stamp:
        rec["ts"] = time.strftime(TS_FMT)
    try:
        from libsplinter_tpu.utils import faults
        if faults.armed():
            rec["faults_armed"] = sorted(
                p["spec"] for p in faults.stats().values())
    except Exception:
        pass
    try:
        # devtime attribution columns (PR 17): runtime-cause compile
        # count so far (a non-zero here poisons the perf claim the
        # same way armed faults do) and the device-ms share of wall —
        # how much of this run the accelerator was actually working
        from libsplinter_tpu.obs.devtime import DEVTIME
        rec.setdefault("compile_events", DEVTIME.compile_events())
        rec.setdefault("device_ms_share",
                       round(DEVTIME.device_ms_share(), 4))
    except Exception:
        pass
    try:
        with open(RESULTS_LOG, "a") as f:
            f.write(json.dumps(rec) + "\n")
            f.flush()
            os.fsync(f.fileno())
    except OSError as e:
        log(f"[series] ledger append failed: {e}")
    return rec


def cpu_quick_track() -> bool:
    """BENCH_CPU=1: the explicit CPU quick-track (small sizes,
    interpreted kernels, labelled records)."""
    return os.environ.get("BENCH_CPU") == "1"


def require_chip(backend: str) -> None:
    """A run that is meant for the chip fails when JAX finds none —
    it never shrinks to CPU sizes or interpret mode on its own."""
    if backend != "tpu" and not cpu_quick_track():
        raise RuntimeError(
            f"no TPU: JAX backend is {backend!r}.  The bench measures "
            "the chip; set BENCH_CPU=1 for the labelled CPU "
            "quick-track")


class SeriesCtx:
    """Shared state for one series run: backend, deadline, ledger."""

    def __init__(self, deadline_epoch: float | None = None):
        self.deadline = deadline_epoch or float(os.environ.get(
            "SPTPU_BENCH_DEADLINE_EPOCH", time.time() + 86400))
        self.backend = "?"
        self.n_devices = 0
        self.headline: dict | None = None
        self.records: list[dict] = []
        # phase name -> "ok" | "failed" | "skipped" (set by run_series)
        self.phase_status: dict[str, str] = {}

    def remaining(self) -> float:
        return self.deadline - time.time()

    def record(self, rec: dict) -> dict:
        """Append one measurement to the ledger immediately."""
        if cpu_quick_track():
            rec = {**rec, "cpu_quick_track": True}
        rec = append_ledger(rec)
        self.records.append(rec)
        return rec


def _stage(name: str) -> None:
    """Stage marker on stderr: where a hang or a crash happened."""
    log(f"STAGE {name} t={time.strftime('%H:%M:%S')}")


# ---------------------------------------------------------------------------
# phase: embed — the headline metric
# ---------------------------------------------------------------------------

def make_texts(n: int) -> list[str]:
    import numpy as np

    rng = np.random.default_rng(0)
    words = ["tpu", "vector", "store", "seqlock", "arena", "signal",
             "epoch", "shard", "bloom", "label", "kernel", "mesh",
             "gather", "commit", "batch", "embed"]
    return [" ".join(rng.choice(words, size=int(rng.integers(4, 24))))
            for _ in range(n)]


def _arm_texts(st, texts) -> None:
    """(Re-)arm bench keys: content write + VARTEXT type + the embed
    request label — the one protocol the embed phases share."""
    from libsplinter_tpu import T_VARTEXT
    from libsplinter_tpu.engine import protocol as P

    for i, t in enumerate(texts):
        key = f"bench/{i}"
        st.set(key, t)
        st.set_type(key, T_VARTEXT)
        st.label_or(key, P.LBL_EMBED_REQ)


def _bench_store_name(suffix: str) -> str:
    """Per-process store name (phases run sequentially; each
    closes+unlinks before the next creates)."""
    return f"/spt-{suffix}-{os.getpid()}"


def phase_embed(ctx: SeriesCtx) -> dict:
    """End-to-end embedding throughput per chip + p50 set->vector on
    the event-driven wake path, with per-stage p50/p95/p99 sourced
    from the span histograms riding the __embedder_stats heartbeat
    (PIPELINE_STAGES: drain / tokenize / dispatch / device_wait /
    commit).

    Env: BENCH_TEXTS (16384), BENCH_BATCH (4096), BENCH_BUCKET (64),
    BENCH_BUCKETS (16,32,BUCKET), BENCH_P50_PROBES (30).

    Defaults are the best config from the measured on-chip
    (batch_cap x inflight_depth) sweep (2026-07-31: 512->3,237,
    2048->6,860/7,197, 4096->8,260 emb/s/chip — per-dispatch runtime
    RTT amortizes with batch, device_ms stays MXU-bound), not a guess."""
    import threading

    import numpy as np

    from libsplinter_tpu import Store, T_VARTEXT
    from libsplinter_tpu.engine import protocol as P
    from libsplinter_tpu.engine.embedder import Embedder
    from libsplinter_tpu.models import (EmbeddingModel, EncoderConfig,
                                        default_tokenizer)
    from libsplinter_tpu.utils.trace import tracer

    # tuned-for-TPU defaults; the CPU quick-track (BENCH_CPU=1) keeps
    # its fast contract — 16384 full-width texts on a CPU would run
    # for tens of minutes
    require_chip(ctx.backend)
    on_cpu = cpu_quick_track()
    n_texts = int(os.environ.get("BENCH_TEXTS",
                                 "256" if on_cpu else "16384"))
    batch = int(os.environ.get("BENCH_BATCH",
                               "64" if on_cpu else "4096"))
    bucket = int(os.environ.get("BENCH_BUCKET", "64"))
    buckets = tuple(int(x) for x in os.environ.get(
        "BENCH_BUCKETS", f"16,32,{bucket}").split(",")) \
        if os.environ.get("BENCH_BUCKETS") != "" else (bucket,)
    # f16 on the wire halves the vector-fetch bytes (the measured
    # bottleneck when link bandwidth caps the drain); "f32" opts out
    fetch = os.environ.get("BENCH_FETCH", "int8")
    fetch_dtype = None if fetch in ("f32", "", "none") else fetch

    cfg = EncoderConfig(out_dim=768, max_len=2048)
    model = EmbeddingModel(cfg, buckets=buckets, fetch_dtype=fetch_dtype)
    tok = default_tokenizer(cfg.vocab_size)

    _stage("compile")
    t0 = time.perf_counter()
    for bsz in (1, batch):          # p50 probe path + throughput path
        for b in model.buckets[:-1] if len(model.buckets) > 1 \
                else model.buckets:
            ids = np.zeros((bsz, b), np.int32)
            lens = np.full((bsz,), b, np.int32)
            model.encode_ids(ids, lens)
    compile_s = time.perf_counter() - t0
    log(f"compile: {compile_s:.1f}s")

    _stage("stage-store")
    name = _bench_store_name("series")
    Store.unlink(name)
    # max_val 4096: the traced heartbeat (counters + spans + stage
    # quantiles + slow log) must land un-degraded for the stage table
    st = Store.create(name, nslots=max(8192, n_texts * 2), max_val=4096,
                      vec_dim=768)
    runner = None
    try:
        texts = make_texts(n_texts)
        _arm_texts(st, texts)

        emb = Embedder(st, model=model, tokenizer=tok, max_ctx=2048,
                       batch_cap=batch)
        emb.attach()

        # untimed first drain: absorbs every data-dependent program
        # compile (tail batches pad to powers of two)
        _stage("throughput-warm-drain")
        t0 = time.perf_counter()
        done = emb.run_once()
        log(f"warm drain: {done}/{n_texts} in "
            f"{time.perf_counter() - t0:.2f}s (compiles included)")

        _arm_texts(st, texts)               # re-arm every key

        _stage("throughput")
        t0 = time.perf_counter()
        done = emb.run_once()
        dt = time.perf_counter() - t0
        eps = done / dt if dt > 0 else 0.0
        log(f"embedded={done}/{n_texts} in {dt:.2f}s -> "
            f"{eps:,.0f} emb/s/chip")

        # p50 set->vector on the EVENT-DRIVEN wake path, with spans
        # enabled so the latency decomposes into per-stage HISTOGRAM
        # QUANTILES (obs/hist.py via utils/trace.py) riding the
        # __embedder_stats heartbeat — true p50/p95/p99 per stage,
        # never means dressed as percentiles.
        # The daemon thread MUST be stopped on every exit path: later
        # phases share this process, and a still-running daemon would
        # use the store after the finally below closes/unlinks it.
        _stage("p50-wake")
        was_enabled = tracer.enabled
        tracer.enabled = True
        tracer.reset()
        runner = threading.Thread(
            target=emb.run,
            kwargs=dict(idle_timeout_ms=20, sweep_interval_s=3600.0),
            daemon=True)
        try:
            runner.start()
            time.sleep(0.05)

            lat, lat_timeouts = [], 0
            n_probes = int(os.environ.get("BENCH_P50_PROBES", "30"))
            for i in range(n_probes):
                key = f"lat/{i}"
                t1 = time.perf_counter()
                st.set(key, "latency probe text sample")
                st.set_type(key, T_VARTEXT)
                st.label_or(key, P.LBL_EMBED_REQ)
                st.bump(key)
                idx = st.find_index(key)
                deadline = t1 + 10.0
                timed_out = False
                while st.labels_at(idx) & P.LBL_EMBED_REQ:
                    if time.perf_counter() > deadline:
                        timed_out = True
                        break
                    time.sleep(0.0001)
                if timed_out:
                    lat_timeouts += 1
                else:
                    lat.append((time.perf_counter() - t1) * 1000)
        finally:
            emb.stop()
            runner.join(timeout=5.0)
            # the stage quantiles ride the heartbeat (the contract the
            # obs layer pins: bench consumes what any watcher could)
            emb.publish_stats()
            hb = {}
            try:
                hb = json.loads(st.get(P.KEY_EMBED_STATS)
                                .rstrip(b"\0"))
            except (KeyError, OSError, ValueError):
                pass
            stage_q = hb.get("quantiles") or tracer.quantiles("embed.")
            slow_log = hb.get("slow_log") or []
            tracer.enabled = was_enabled
        p50 = float(np.percentile(lat, 50)) if lat else -1.0
        p95 = float(np.percentile(lat, 95)) if lat else -1.0
        p99 = float(np.percentile(lat, 99)) if lat else -1.0

        # per-stage p50/p95/p99 from the span histograms, keyed by the
        # PIPELINE_STAGES contract.  The old table reported arithmetic
        # means over drains under a "p50" name; these are true
        # percentiles of per-drain stage wall (the p50 loop drains one
        # request at a time, so per-drain ~= per-request here).
        # device_wait is host-BLOCKED time only; overlapped device
        # time shows up in overlap_ratio, not as a stage.
        def _q(stage: str) -> dict:
            a = stage_q.get(stage) or {}
            return {k: a.get(k, 0.0)
                    for k in ("p50_ms", "p95_ms", "p99_ms",
                              "max_ms", "n")}

        stage_tbl = {s: _q(s) for s in P.PIPELINE_STAGES}
        n_req = int(stage_tbl["commit"]["n"]) or 1
        pipeline_counters = {
            "requests": n_req,
            "overlap_ratio": round(emb.stats.overlap_ratio(), 4),
            "probe_lane_hits": emb.stats.probe_lane_hits,
            "blocking_waits": emb.stats.blocking_waits,
            "ready_commits": emb.stats.ready_commits,
            "inflight_peak": emb.stats.inflight_peak,
            # resident-ring evidence (PR 7): how many device dispatches
            # the throughput drains actually paid per batch
            "ring_dispatches": emb.stats.ring_dispatches,
            "resident_iterations": emb.stats.resident_iterations,
            "ring_occupancy_peak": emb.stats.ring_occupancy_peak,
        }
        log(f"p50 set->vector (event-driven): {p50:.2f} ms  p95: "
            f"{p95:.2f} ms  p99: {p99:.2f} ms  "
            f"timeouts={lat_timeouts}  stage_quantiles={stage_tbl}  "
            f"counters={pipeline_counters}")
    finally:
        if runner is not None and runner.is_alive():
            # a wedged daemon thread still holds the mapping: closing
            # it under the thread could crash the whole series — leak
            # the store instead (the bench parent unlinks the name on
            # every failure path)
            log("[series] WARNING: daemon thread did not stop; "
                "leaking the bench store to avoid use-after-close")
        else:
            st.close()
            Store.unlink(name)

    rec = ctx.record({
        "metric": "embeddings_per_sec_per_chip",
        "value": round(eps, 1),
        "unit": "embeddings/s",
        "vs_baseline": round(eps / BASELINE_PER_CHIP, 4),
        "detail": {
            "backend": ctx.backend, "n_chips_visible": ctx.n_devices,
            "bucket": bucket, "buckets": list(model.buckets[:-1]),
            "batch": batch, "n_texts": n_texts,
            "fetch_dtype": fetch_dtype or "f32",
            "compile_s": round(compile_s, 1),
            "p50_set_to_vector_ms": round(p50, 2),
            "p95_set_to_vector_ms": round(p95, 2),
            "p99_set_to_vector_ms": round(p99, 2),
            "p50_samples": len(lat), "p50_timeouts": lat_timeouts,
            "stage_quantiles": stage_tbl,
            "pipeline_counters": pipeline_counters,
            "slow_log": slow_log[-4:],
        }})
    ctx.headline = rec
    return rec


# ---------------------------------------------------------------------------
# phase: embed_sweep — throughput vs (batch_cap, inflight_depth)
# ---------------------------------------------------------------------------

def phase_embed_sweep(ctx: SeriesCtx) -> dict:
    """The which-knob-next data collector: e2e drain throughput across
    (batch_cap, inflight_depth) configs so the claim window that
    measures the baseline ALSO says which knob to turn next.  Config
    order puts the no-new-compile points first (depth variations reuse
    the embed phase's batch-512 programs); the batch-256/1024 points
    pay their own compiles (absorbed by an untimed first drain each).

    Env: SWEEP_TEXTS (4096), SWEEP_CONFIGS
    ("512x2,512x1,512x4,256x2,1024x2" as batchxdepth; an optional
    third field picks the wire dtype per config, e.g.
    "4096x2xf32,4096x2xf16" — host conditions drift between runs,
    so a fetch-dtype comparison is only meaningful run back-to-back
    inside ONE process)."""
    from libsplinter_tpu import Store
    from libsplinter_tpu.engine.embedder import Embedder
    from libsplinter_tpu.models import (EmbeddingModel, EncoderConfig,
                                        default_tokenizer)

    n_texts = int(os.environ.get("SWEEP_TEXTS", "4096"))
    default_fetch = os.environ.get("BENCH_FETCH", "int8")

    def _parse(c: str) -> tuple[int, int, str]:
        parts = c.split("x")
        batch, depth = int(parts[0]), int(parts[1])
        return batch, depth, (parts[2] if len(parts) > 2
                              else default_fetch)

    # default set (2026-07-31): the f32/f16/int8 wire A/B at the tuned
    # batch_cap (same process, so host drift can't confound it) and
    # the 8192 scaling point
    cfgs = [_parse(c) for c in os.environ.get(
        "SWEEP_CONFIGS",
        "4096x2xf32,4096x2xf16,4096x2xint8,8192x2xf16").split(",")]
    bucket = int(os.environ.get("BENCH_BUCKET", "64"))
    buckets = tuple(int(x) for x in os.environ.get(
        "BENCH_BUCKETS", f"16,32,{bucket}").split(","))

    cfg = EncoderConfig(out_dim=768, max_len=2048)
    models: dict[str, EmbeddingModel] = {}

    def _model(fetch: str) -> EmbeddingModel:
        key = "f32" if fetch in ("f32", "", "none") else fetch
        if key not in models:
            # share one param set across wire dtypes: only the jitted
            # output cast differs, and a duplicate flax init would
            # burn claim-window seconds and device memory for nothing
            donor = next(iter(models.values()), None)
            models[key] = EmbeddingModel(
                cfg, buckets=buckets,
                params=None if donor is None else donor.params,
                fetch_dtype=None if key == "f32" else key)
        return models[key]

    tok = default_tokenizer(cfg.vocab_size)
    texts = make_texts(n_texts)

    name = _bench_store_name("sweep")
    Store.unlink(name)
    st = Store.create(name, nslots=max(8192, n_texts * 2),
                      max_val=2048, vec_dim=768)
    rows = []
    try:
        # (batch_cap, fetch) pairs whose programs (incl. pow2 tail
        # shapes) are compiled — each wire dtype is its own XLA program
        warmed: set[tuple[int, str]] = set()
        for batch, depth, fetch in cfgs:
            # a compile-paying config costs a full untimed warm drain
            # on top of the timed one; starting it in a thin window
            # overruns the attempt budget -> killed child -> wedge
            need = 90 if (batch, fetch) in warmed else 300
            if ctx.remaining() < need:
                log(f"[sweep] {ctx.remaining():.0f}s left < {need}s "
                    f"needed; stopping before {batch}x{depth}x{fetch}")
                break
            # one config must not lose the window's already-measured
            # rows: a device OOM at an aggressive batch_cap records an
            # error row and the sweep moves on
            try:
                emb = Embedder(st, model=_model(fetch), tokenizer=tok,
                               max_ctx=2048, batch_cap=batch,
                               inflight_depth=depth)
                emb.attach()
                if (batch, fetch) not in warmed:
                    # untimed drain absorbs this batch_cap's compiles
                    # (tail shapes are texts+bucket-mix determined, so
                    # one warm per batch_cap covers its depth variants)
                    _arm_texts(st, texts)
                    emb.run_once()
                    warmed.add((batch, fetch))
                _arm_texts(st, texts)
                t0 = time.perf_counter()
                done = emb.run_once()
                dt = time.perf_counter() - t0
                r = {"batch_cap": batch, "inflight_depth": depth,
                     "fetch": fetch,
                     "emb_s": round(done / dt, 1) if dt > 0 else 0.0,
                     "drained": done}
            except Exception as exc:                # noqa: BLE001
                r = {"batch_cap": batch, "inflight_depth": depth,
                     "fetch": fetch, "emb_s": 0.0, "drained": 0,
                     "error": f"{type(exc).__name__}: {exc}"[:300]}
            rows.append(r)
            log(f"[sweep] {json.dumps(r)}")
    finally:
        st.close()
        Store.unlink(name)

    if not rows or all(r["emb_s"] <= 0 for r in rows):
        # a scarce claim window must never ledger a measured-looking
        # 0.0 — fail the phase instead (run_series marks it failed)
        raise RuntimeError("sweep window expired before any config ran"
                           if not rows else
                           f"every sweep config failed: {rows}")
    best = max(rows, key=lambda r: r["emb_s"])
    return ctx.record({
        "metric": "embed_sweep_best",
        "value": best["emb_s"], "unit": "embeddings/s",
        "vs_baseline": round(best["emb_s"] / BASELINE_PER_CHIP, 4),
        "detail": {"backend": ctx.backend, "n_texts": n_texts,
                   "buckets": list(buckets), "configs": rows,
                   "best": best}})


# ---------------------------------------------------------------------------
# phase: profile — device / sync / pipelined per shape
# ---------------------------------------------------------------------------

# bf16 peak FLOP/s per chip for MFU accounting, by device_kind
# substring (a v5e reports "TPU v5 lite"; peaks from Google Cloud's
# per-generation TPU documentation).  Rows record the peak they were
# normalized against so the ledger stays self-describing.  A device
# that is not in the table is an error, not a default.
_TPU_PEAKS = (("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12),
              ("v4", 275e12), ("v6", 918e12))


def _tpu_peak_flops() -> tuple[float, str]:
    import jax

    kind = getattr(jax.devices()[0], "device_kind", "") or ""
    for pat, peak in _TPU_PEAKS:
        if pat in kind.lower():
            return peak, kind
    raise RuntimeError(
        f"no peak FLOP/s known for device_kind {kind!r}: add it to "
        "_TPU_PEAKS with its source instead of assuming one")


def _encoder_flops(cfg, batch: int, seq: int) -> float:
    """Forward matmul FLOPs for one (batch, seq) encode.  Per token
    per layer (matmul = 2*m*n*k): QKV+O projections 8h^2, attention
    score+apply 4*S*h, MLP 6*h*mlp for the SwiGLU 'nomic' variant
    (gate+up+down) or 4*h*mlp for 'bert' (up+down); elementwise/norm
    terms are noise at these shapes."""
    h, f = cfg.hidden, cfg.mlp_dim
    mlp_mats = 6 if cfg.variant == "nomic" else 4
    per_tok_layer = 8 * h * h + 4 * seq * h + mlp_mats * h * f
    return float(batch * seq * cfg.layers * per_tok_layer)


def phase_profile(ctx: SeriesCtx) -> dict:
    """Decomposition: steady-state device ms, sync-dispatch ms, and
    async-pipelined ms per (batch, bucket) shape, with TFLOP/s and MFU
    (vs bf16 peak) on TPU so the gap to target is a measured number.
    Env: PROFILE_SHAPES (512x16,512x32,512x64,8x1024,1x16,1x64),
    PROFILE_REPS (10)."""
    import numpy as np

    import jax

    from libsplinter_tpu.models import EmbeddingModel, EncoderConfig

    shapes_env = os.environ.get(
        "PROFILE_SHAPES", "512x16,512x32,512x64,8x1024,1x16,1x64")
    reps = int(os.environ.get("PROFILE_REPS", "10"))
    cfg = EncoderConfig(out_dim=768, max_len=2048)
    shapes = [tuple(int(x) for x in s.split("x"))
              for s in shapes_env.split(",")]
    buckets = tuple(sorted({b for _, b in shapes}))
    model = EmbeddingModel(cfg, buckets=buckets)

    # Runtime floor probes: what ONE round trip through the PJRT
    # runtime costs regardless of work.  These
    # attribute the e2e numbers — if null_dispatch_ms ~= the p50
    # set->vector, the latency lives in the runtime, not this stack.
    #   null_dispatch_ms: scalar add on device, block_until_ready
    #   h2d_put_ms:       device_put of a 512x16 int32 id batch (32 KB)
    #   d2h_fetch_ms:     np.asarray of a (768,) f32 device vector
    floor_reps = int(os.environ.get("PROFILE_FLOOR_REPS", "30"))
    # 0 disables the (auxiliary) probes instead of crashing the phase
    # on np.percentile([])

    def _p50(fn) -> float:
        fn()                                   # warm/compile
        ts = []
        for _ in range(floor_reps):
            t0 = time.perf_counter()
            fn()
            ts.append((time.perf_counter() - t0) * 1e3)
        return float(np.percentile(ts, 50))

    if floor_reps > 0:
        x_dev = jax.device_put(np.float32(1.0))
        add1 = jax.jit(lambda x: x + 1.0)
        ids_probe = np.zeros((512, 16), np.int32)
        # a FRESH device array per rep: jax.Array caches the host copy
        # on first np.asarray, so re-fetching one array times a no-op
        vec_pool = iter([jax.device_put(np.zeros(768, np.float32))
                         for _ in range(floor_reps + 1)])
        floor = {
            "reps": floor_reps,
            "null_dispatch_ms": round(
                _p50(lambda: add1(x_dev).block_until_ready()), 3),
            "h2d_put_ms": round(
                _p50(lambda: jax.device_put(ids_probe)
                     .block_until_ready()), 3),
            "d2h_fetch_ms": round(
                _p50(lambda: np.asarray(next(vec_pool))), 3),
        }
        log(f"[profile] runtime floor: {json.dumps(floor)}")
    else:
        floor = {"reps": 0, "disabled": True}

    rows = []
    for bsz, bucket in shapes:
        ids_h = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (bsz, bucket)).astype(np.int32)
        lens_h = np.full((bsz,), bucket, np.int32)
        model.encode_ids(ids_h, lens_h)          # compile

        ids_d, lens_d = jax.device_put(ids_h), jax.device_put(lens_h)
        fn = model._fn
        fn(model.params, ids_d, lens_d).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(model.params, ids_d, lens_d)
        out.block_until_ready()
        dev_ms = (time.perf_counter() - t0) / reps * 1e3

        t0 = time.perf_counter()
        for _ in range(reps):
            model.encode_ids(ids_h, lens_h)
        e2e_ms = (time.perf_counter() - t0) / reps * 1e3

        t0 = time.perf_counter()
        pends = [model.encode_ids_async(ids_h, lens_h)
                 for _ in range(reps)]
        for p in pends:
            p.materialize()
        pipe_ms = (time.perf_counter() - t0) / reps * 1e3

        r = {"batch": bsz, "bucket": bucket,
             "device_ms": round(dev_ms, 2),
             "sync_ms": round(e2e_ms, 2),
             "pipelined_ms": round(pipe_ms, 2),
             "device_emb_s": round(bsz / dev_ms * 1e3, 0),
             "pipelined_emb_s": round(bsz / pipe_ms * 1e3, 0)}
        tflops = _encoder_flops(cfg, bsz, bucket) / (dev_ms / 1e3) / 1e12
        r["device_tflops"] = round(tflops, 2)
        if ctx.backend == "tpu":
            peak, kind = _tpu_peak_flops()
            r["mfu_pct"] = round(100 * tflops * 1e12 / peak, 1)
            r["mfu_peak_tflops"] = round(peak / 1e12)
            r["device_kind"] = kind
        rows.append(r)
        log(json.dumps(r))

    big = max(rows, key=lambda r: r["batch"])
    return ctx.record({
        "metric": "encode_device_ms_per_batch",
        "value": big["device_ms"], "unit": "ms", "vs_baseline": 0.0,
        "detail": {"backend": ctx.backend, "reps": reps,
                   "runtime_floor": floor, "shapes": rows}})


# ---------------------------------------------------------------------------
# phase: dispatch — the runtime dispatch floor and its depth amortization
# ---------------------------------------------------------------------------

def dispatch_depth_rows(depths=(1, 2, 4, 8), reps: int = 30) -> list:
    """Per-drain runtime dispatch cost amortized over depth, for BOTH
    PR-7 mechanisms (ISSUE 7; engine/resident.py):

      overlap    K un-awaited null dispatches held, then one blocking
                 drain of them all (the InflightWindow discipline) —
                 amortized per-drain cost = wall / K;
      resident   ONE dispatch whose lax.while_loop runs K iterations
                 (the resident-ring discipline; the trip count is a
                 scalar OPERAND, so every depth reuses one compiled
                 program) — amortized = wall / K.

    The work per iteration is a scalar add — pure dispatch/loop
    overhead, no compute to hide behind — so the rows attribute the
    floor itself, the way null_dispatch_ms did for depth 1 in r05.
    Returns [{depth, overlap_ms_per_drain, resident_ms_per_drain,
    ...}] with p50s over `reps`."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    x = jax.device_put(np.float32(1.0))
    add1 = jax.jit(lambda v: v + 1.0)

    @jax.jit
    def ring(v, n):
        def body(c):
            i, acc = c
            return i + 1, acc + 1.0

        return jax.lax.while_loop(lambda c: c[0] < n, body,
                                  (jnp.int32(0), v))[1]

    add1(x).block_until_ready()                    # compile both once
    ring(x, jnp.int32(max(depths))).block_until_ready()

    def _p50(fn) -> float:
        fn()
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append((time.perf_counter() - t0) * 1e3)
        return float(np.percentile(ts, 50))

    rows = []
    for k in depths:
        def overlap(k=k):
            futs = [add1(x) for _ in range(k)]
            for f in futs:
                f.block_until_ready()

        def resident(k=k):
            ring(x, jnp.int32(k)).block_until_ready()

        o = _p50(overlap)
        rt = _p50(resident)
        rows.append({"depth": k,
                     "overlap_total_ms": round(o, 4),
                     "overlap_ms_per_drain": round(o / k, 4),
                     "resident_total_ms": round(rt, 4),
                     "resident_ms_per_drain": round(rt / k, 4)})
    return rows


def phase_dispatch(ctx: SeriesCtx) -> dict:
    """Dispatch-floor attribution arm: r05 measured null_dispatch_ms
    ~63 ms (94% of the 67.2 ms p50 set->vector) at depth 1 — the
    before-row.  This sweeps dispatch_depth in {1,2,4,8} and ledgers
    the amortized per-drain dispatch cost for the resident-ring and
    K-overlap paths, so the serving knobs (--ring-depth /
    --inflight-depth) have attribution data on the same backend the
    latencies were measured on.  Env: DISPATCH_DEPTHS (1,2,4,8),
    DISPATCH_REPS (30)."""
    depths = tuple(int(x) for x in os.environ.get(
        "DISPATCH_DEPTHS", "1,2,4,8").split(","))
    reps = int(os.environ.get("DISPATCH_REPS", "30"))
    rows = dispatch_depth_rows(depths, reps)
    d1 = rows[0]
    dk = rows[-1]

    def _x(a: float, b: float) -> float:
        return round(a / max(b, 1e-9), 1)

    detail = {
        "backend": ctx.backend, "reps": reps,
        "rows": rows,
        "resident_amortization_x": _x(d1["resident_ms_per_drain"],
                                      dk["resident_ms_per_drain"]),
        "overlap_amortization_x": _x(d1["overlap_ms_per_drain"],
                                     dk["overlap_ms_per_drain"]),
    }
    log(f"[dispatch] {json.dumps(detail['rows'])}")
    return ctx.record({
        "metric": "dispatch_depth",
        "value": dk["resident_ms_per_drain"],
        "unit": f"ms/drain (amortized, depth {dk['depth']})",
        "vs_baseline": 0.0,
        "detail": detail})


# ---------------------------------------------------------------------------
# phase: kernels — every Pallas kernel executed + checked on this backend
# ---------------------------------------------------------------------------

def phase_kernels(ctx: SeriesCtx) -> dict:
    """Run the full Pallas tier on the real backend once —
    flash forward, blockwise backward (grad check vs naive), causal
    prefill with GQA head routing, and the fused cosine top-k (f32 and
    bf16-MXU) over a large lane — asserting numerics against the jnp
    path on the SAME device and recording timings.

    On TPU the kernels lower through Mosaic (the thing interpret-mode
    tests cannot prove); on CPU (BENCH_CPU=1 quick-tracking) the same
    comparisons run with interpret=True at reduced sizes.

    Env: KERNELS_SEQ (512), KERNELS_ROWS (262144; auto-shrunk to fit
    the window), KERNELS_REPS (10)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from libsplinter_tpu.ops.flash_attention import (
        _causal_jnp, _mha_jnp, causal_flash_attention, flash_attention)
    from libsplinter_tpu.ops.similarity import cosine_topk

    require_chip(ctx.backend)
    on_tpu = ctx.backend == "tpu"
    interp = not on_tpu              # BENCH_CPU=1 only (require_chip)
    S = int(os.environ.get("KERNELS_SEQ", "512" if on_tpu else "128"))
    n_rows = int(os.environ.get("KERNELS_ROWS",
                                "262144" if on_tpu else "8192"))
    reps = int(os.environ.get("KERNELS_REPS", "10"))
    detail: dict = {"backend": ctx.backend, "interpret": interp,
                    "seq": S, "rows": n_rows}
    rng = np.random.default_rng(7)

    def timed(fn, *args, **kw):
        out = fn(*args, **kw)           # compile + warm
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args, **kw)
        jax.block_until_ready(out)
        return out, (time.perf_counter() - t0) / reps * 1e3

    # -- flash forward (bidirectional, masked) ------------------------------
    B, H, D = 4, 12, 64
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
               for _ in range(3))
    lens = np.asarray([S, S - 3, S // 2, 5])
    mask = jnp.asarray(np.arange(S)[None, :] < lens[:, None])

    flash = lambda: flash_attention(q, k, v, mask, interpret=interp,
                                    force_pallas=True)
    out_f, flash_ms = timed(flash)
    out_ref = _mha_jnp(q, k, v, mask)
    # compare only valid rows: fully-masked rows are don't-care by the
    # encoder-pooling contract (see flash_attention.py docstring)
    w = mask.astype(jnp.float32)[:, :, None, None]
    fwd_diff = float(jnp.max(jnp.abs((out_f - out_ref) * w)))
    detail["flash_fwd"] = {"ms": round(flash_ms, 2),
                           "max_abs_diff": fwd_diff,
                           "ok": bool(fwd_diff < 2e-3)}
    log(f"flash fwd S={S}: {flash_ms:.2f} ms, diff={fwd_diff:.2e}")

    # -- flash blockwise backward (grad check vs naive) ---------------------
    # Correctness and timing are SEPARATE arms.  At default precision
    # Mosaic truncates f32 dot inputs to bf16 exactly like XLA does for
    # the naive einsums, so kernel-vs-naive diffs there are dominated
    # by the two paths' different rounding orders (~5e-3 relative,
    # deterministic — measured on-chip 2026-08-02), not kernel bugs.
    # The check therefore runs BOTH paths at Precision.HIGHEST, which
    # isolates the algorithm; the timing runs the production default.
    def loss_flash(q_, k_, v_, hi=False):
        return jnp.sum(flash_attention(q_, k_, v_, mask,
                                       interpret=interp,
                                       force_pallas=True,
                                       hi_prec=hi) * w)

    def loss_naive(q_, k_, v_):
        return jnp.sum(_mha_jnp(q_, k_, v_, mask) * w)

    grad_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))
    grad_flash_hi = jax.jit(jax.grad(
        functools.partial(loss_flash, hi=True), argnums=(0, 1, 2)))
    with jax.default_matmul_precision("highest"):
        grad_naive = jax.jit(jax.grad(loss_naive, argnums=(0, 1, 2)))
        nq, nk, nv = grad_naive(q, k, v)
    (dq, dk, dv), bwd_ms = timed(grad_flash, q, k, v)  # production arm
    gq, gk, gv = grad_flash_hi(q, k, v)                # checked arm
    bwd_diff = float(max(jnp.max(jnp.abs(a - b))
                         for a, b in ((gq, nq), (gk, nk), (gv, nv))))
    grad_scale = float(max(jnp.max(jnp.abs(g)) for g in (nq, nk, nv)))
    bwd_rel = bwd_diff / (grad_scale + 1e-9)
    # the production-precision gradients get their own (looser) sanity
    # bound vs the f32 oracle so a default-arm-only regression (e.g. a
    # demoted accumulator the HIGHEST decomposition would mask) still
    # fails the phase; 5e-2 clears the measured ~5e-3 rounding-order
    # noise with margin while catching order-of-magnitude breakage
    def_diff = float(max(jnp.max(jnp.abs(a - b))
                         for a, b in ((dq, nq), (dk, nk), (dv, nv))))
    def_rel = def_diff / (grad_scale + 1e-9)
    detail["flash_bwd"] = {"ms": round(bwd_ms, 2),
                           "max_abs_diff": bwd_diff,
                           "grad_scale": round(grad_scale, 3),
                           "rel_diff": bwd_rel,
                           "checked_at": "highest-vs-highest",
                           "default_rel_diff": def_rel,
                           "ok": bool(bwd_rel < 1e-3
                                      and def_rel < 5e-2)}
    log(f"flash bwd S={S}: {bwd_ms:.2f} ms, diff={bwd_diff:.2e} "
        f"(rel {bwd_rel:.2e} of grad scale {grad_scale:.1f}, "
        f"checked at highest precision; default-arm rel "
        f"{def_rel:.2e})")

    # -- causal prefill with GQA head routing -------------------------------
    Bp, Sp, T, Hq, KH = 2, max(S // 2, 64), S, 8, 2
    pos = T - Sp
    qc = jnp.asarray(rng.normal(size=(Bp, Sp, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(Bp, T, KH, D)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(Bp, T, KH, D)), jnp.float32)
    start = jnp.asarray([0, 7], jnp.int32)

    causal = lambda: causal_flash_attention(
        qc, kc, vc, pos, start, interpret=interp, force_pallas=True)
    out_c, causal_ms = timed(causal)
    rep = Hq // KH
    out_cr = _causal_jnp(qc, jnp.repeat(kc, rep, axis=2),
                         jnp.repeat(vc, rep, axis=2),
                         pos, start)
    causal_diff = float(jnp.max(jnp.abs(out_c - out_cr)))
    detail["causal_prefill_gqa"] = {
        "ms": round(causal_ms, 2), "max_abs_diff": causal_diff,
        "gqa_rep": rep, "ok": bool(causal_diff < 2e-3)}
    log(f"causal prefill S={Sp} T={T} GQA x{rep}: {causal_ms:.2f} ms, "
        f"diff={causal_diff:.2e}")

    # -- fused cosine top-k over a large lane (f32 + bf16 MXU) --------------
    lane = rng.normal(size=(n_rows, 768)).astype(np.float32)
    t0 = time.perf_counter()
    lane_dev = jax.device_put(lane)
    jax.block_until_ready(lane_dev)
    stage_s = time.perf_counter() - t0
    detail["lane_stage_s"] = round(stage_s, 2)
    detail["lane_stage_mb_s"] = round(lane.nbytes / 1e6 / stage_s, 1) \
        if stage_s > 0 else None
    query = lane[12345 % n_rows] + 0.05 * rng.normal(size=768) \
        .astype(np.float32)
    k_top = 10

    # the pallas path is what we're proving; the jnp path on the SAME
    # device is the oracle
    (s_p, i_p), pal_ms = timed(
        cosine_topk, lane_dev, query, k_top,
        use_pallas=(True if on_tpu else None))
    if on_tpu:
        (s_j, i_j), jnp_ms = timed(cosine_topk, lane_dev, query, k_top,
                                   use_pallas=False)
        overlap = len(set(map(int, i_p)) & set(map(int, i_j))) / k_top
        sdiff = float(np.max(np.abs(s_p - s_j)))
        (s_b, i_b), bf16_ms = timed(cosine_topk, lane_dev, query, k_top,
                                    use_pallas=True, mxu_bf16=True)
        bf16_overlap = len(set(map(int, i_b))
                           & set(map(int, i_j))) / k_top
        # tile-size sweep: which N-block suits this chip's VMEM (the
        # default-1024 timing seeds the dict so every tile lives in
        # one comparable field)
        bn_sweep = {"1024": round(pal_ms, 2)}
        for bn in (512, 2048, 4096):
            try:
                (_, _), bn_ms = timed(cosine_topk, lane_dev, query,
                                      k_top, use_pallas=True,
                                      block_n=bn)
                bn_sweep[str(bn)] = round(bn_ms, 2)
            except Exception as e:
                # first line only, ANSI escapes dropped: compile-server
                # errors are multiline and colorized
                stripped = re.sub(r"\x1b\[[0-9;]*m", "", str(e))
                msg = (stripped.splitlines() or [""])[0]
                bn_sweep[str(bn)] = f"failed: {msg}"[:120]
        detail["cosine_topk"] = {
            "pallas_ms": round(pal_ms, 2), "jnp_ms": round(jnp_ms, 2),
            "bf16_ms": round(bf16_ms, 2),
            "block_n_sweep_ms": bn_sweep,
            "topk_overlap_vs_jnp": overlap,
            "score_max_abs_diff": sdiff,
            "bf16_topk_overlap": bf16_overlap,
            "ok": bool(overlap >= 0.9 and sdiff < 1e-3
                       and bf16_overlap >= 0.8)}
        log(f"cosine_topk {n_rows}x768: pallas {pal_ms:.2f} ms vs jnp "
            f"{jnp_ms:.2f} ms, overlap={overlap:.2f}, bf16 {bf16_ms:.2f}"
            f" ms overlap={bf16_overlap:.2f}")
    else:
        detail["cosine_topk"] = {"jnp_ms": round(pal_ms, 2),
                                 "ok": True,
                                 "note": "cpu: jnp path only"}
        log(f"cosine_topk {n_rows}x768 (jnp/cpu): {pal_ms:.2f} ms")

    all_ok = all(v.get("ok", True) for v in detail.values()
                 if isinstance(v, dict))
    return ctx.record({
        "metric": "kernels_smoke",
        "value": 1.0 if all_ok else 0.0, "unit": "ok",
        "vs_baseline": 0.0, "detail": detail})


# ---------------------------------------------------------------------------
# phase: search — cosine top-k q/s at the largest affordable lane
# ---------------------------------------------------------------------------

def phase_search(ctx: SeriesCtx) -> dict:
    """BASELINE.md: cosine top-k over a 1M-vector arena.  Stages the
    lane (staging time is itself reported — it is the StagedLane
    restage cost at full-lane granularity), then measures:

      - legacy (unfused) single-query / QB=32 / QB=256 q/s — the rows
        comparable with BENCH_r05's 12.1 q/s single-query cliff;
      - the FUSED streaming kernel (score+select in VMEM, O(k*Q)
        off-chip) single-query and a QB sweep {1, 32, 256};
      - the coalescing search daemon end to end, with stage quantiles
        sourced from its own heartbeat (SEARCH_STAGES histograms).

    Env: SEARCH_N (1,000,000 on TPU / 100,000 on CPU), SEARCH_D (768),
    SEARCH_K (10), SEARCH_REPS (20), SEARCHD_N (8192), SEARCHD_WAVES
    (8)."""
    import numpy as np

    import jax

    from libsplinter_tpu.ops.similarity import cosine_topk, \
        cosine_topk_batch

    d = int(os.environ.get("SEARCH_D", "768"))
    k = int(os.environ.get("SEARCH_K", "10"))
    reps = int(os.environ.get("SEARCH_REPS", "20"))
    on_tpu = ctx.backend == "tpu"
    n = int(os.environ.get("SEARCH_N",
                           "1000000" if on_tpu else "100000"))
    use_pallas = on_tpu

    log(f"search lane=({n}, {d})")
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    lane = rng.normal(size=(n, d)).astype(np.float32)
    gen_s = time.perf_counter() - t0
    QB = 32
    # the big batch exposes the device's aggregate rate through a
    # runtime with a per-dispatch round trip: single-query q/s
    # measures the dispatch, QB amortizes it
    QB2 = int(os.environ.get("SEARCH_QB2", "256"))
    queries = rng.normal(size=(max(reps, QB, QB2), d)) \
        .astype(np.float32)

    # probe the host->device bandwidth on a small slice first: it
    # is an unknown, and a 2.9 GB device_put that takes
    # most of the window would starve the remaining phases.  The probe
    # is 4096 rows (~12 MB — bounded even at 1 MB/s); n then shrinks
    # in 2x steps to an 8192-row floor until the projected staging
    # fits the budget, and a projection that exceeds the budget even
    # at the floor is logged rather than silently tolerated.
    probe_rows = min(4096, n)
    t0 = time.perf_counter()
    probe = jax.device_put(lane[:probe_rows])
    jax.block_until_ready(probe)
    probe_s = max(time.perf_counter() - t0, 1e-6)
    mb_s = probe_rows * d * 4 / 1e6 / probe_s
    budget_s = max(ctx.remaining() - 150, 30)

    def proj_s(rows: int) -> float:
        return rows * d * 4 / 1e6 / mb_s

    while n > 8192 and proj_s(n) > budget_s:
        n //= 2
    if n < lane.shape[0]:
        log(f"[search] staging at {mb_s:,.0f} MB/s would blow the "
            f"window; lane shrunk to {n} rows")
        lane = lane[:n]
    if proj_s(n) > budget_s:
        log(f"[search] WARNING: even {n} rows project to "
            f"{proj_s(n):.0f}s staging (> {budget_s:.0f}s budget); "
            f"proceeding — later phases may be skipped")
    del probe

    t0 = time.perf_counter()
    lane_dev = jax.device_put(lane)
    jax.block_until_ready(lane_dev)
    stage_s = time.perf_counter() - t0
    vnorm_dev = jax.device_put(np.linalg.norm(lane, axis=1)
                               .astype(np.float32))
    log(f"lane host-gen {gen_s:.1f}s, staged to device in {stage_s:.1f}s"
        f" ({lane.nbytes / 1e6 / max(stage_s, 1e-9):,.0f} MB/s)")

    def bench_kernel(mxu_bf16: bool, fused: bool | None = False) -> float:
        cosine_topk(lane_dev, queries[0], k, use_pallas=use_pallas,
                    mxu_bf16=mxu_bf16, vnorm=vnorm_dev, fused=fused)
        t0 = time.perf_counter()
        for i in range(reps):
            cosine_topk(lane_dev, queries[i], k, use_pallas=use_pallas,
                        mxu_bf16=mxu_bf16, vnorm=vnorm_dev, fused=fused)
        return reps / (time.perf_counter() - t0)

    def bench_batch(qb: int, fused: bool | None) -> float:
        qs_in = queries[:qb]
        qb = len(qs_in)          # queries may be shorter than the ask:
        # the rate must count the rows actually scored, not the target
        cosine_topk_batch(lane_dev, qs_in, k, use_pallas=use_pallas,
                          vnorm=vnorm_dev, fused=fused)
        reps_b = max(2, reps // qb)
        t0 = time.perf_counter()
        for _ in range(reps_b):
            cosine_topk_batch(lane_dev, qs_in, k, use_pallas=use_pallas,
                              vnorm=vnorm_dev, fused=fused)
        return reps_b * qb / (time.perf_counter() - t0)

    # legacy (unfused) rows stay fused=False so they remain comparable
    # with BENCH_r05's 12.1 q/s single / 2262.8 q/s QB=256 cliff
    qps_f32 = bench_kernel(False)
    qps_bf16 = bench_kernel(True) if on_tpu else 0.0
    log(f"kernel: {qps_f32:.1f} q/s f32 (unfused)"
        + (f", {qps_bf16:.1f} q/s bf16" if qps_bf16 else ""))

    qps_batch = bench_batch(QB, False)
    log(f"batched: {qps_batch:.1f} q/s aggregate (QB={QB}, unfused)")
    qps_batch_big = bench_batch(QB2, False) if QB2 > QB else 0.0
    if qps_batch_big:
        log(f"batched: {qps_batch_big:.1f} q/s aggregate (QB={QB2}, "
            f"unfused)")

    # fused streaming kernel (score + select in VMEM, O(k*Q) off-chip):
    # the QB sweep is the daemon's coalescing schedule.  On CPU the
    # fused selector falls back to the jnp score-matrix path, so the
    # sweep only measures something new on the pallas backend.
    fused_sweep = {}
    qps_fused_single = 0.0
    if on_tpu:
        # fenced per measurement: a Mosaic lowering failure on one
        # toolchain must cost that row, not the daemon section below
        try:
            qps_fused_single = bench_kernel(False, fused=True)
            log(f"fused kernel: {qps_fused_single:.1f} q/s single")
        except Exception as e:
            log(f"[search] fused single failed: {e}")
        for qb in (1, 32, 256):
            try:
                fused_sweep[str(qb)] = round(bench_batch(qb, True), 1)
                log(f"fused batched: {fused_sweep[str(qb)]} q/s "
                    f"aggregate (QB={qb})")
            except Exception as e:
                fused_sweep[str(qb)] = f"failed: {e}"[:120]

    # host numpy scan: vectorized stand-in for the reference's scalar C
    # scan (splinter_cli_cmd_search.c:374-412), i.e. a GENEROUS baseline
    nn = min(n, 100_000)
    sub = lane[:nn]
    norms = np.linalg.norm(sub, axis=1)
    t0 = time.perf_counter()
    reps_np = max(3, reps // 4)
    for i in range(reps_np):
        qv = queries[i]
        s = sub @ qv / np.maximum(norms * np.linalg.norm(qv), 1e-12)
        np.argpartition(-s, k)[:k]
    qps_np = reps_np / (time.perf_counter() - t0) * (nn / n)
    log(f"numpy scan (scaled to {n} rows): {qps_np:.2f} q/s")

    # search-daemon micro-bench: concurrent requests coalesce into
    # batched dispatches, stage quantiles come from the daemon's OWN
    # heartbeat (the histogram surface operators see), never re-timed
    # ad hoc here.  Fenced: a daemon failure costs this section only.
    daemon_detail = None
    try:
        daemon_detail = _search_daemon_bench(lane, queries, d, k)
    except Exception:
        log("[search] daemon micro-bench failed:")
        log(traceback.format_exc())

    best = max(qps_f32, qps_bf16, qps_fused_single)
    detail = {
        "backend": ctx.backend, "n": n, "d": d, "k": k,
        "qps_f32": round(qps_f32, 1),
        "qps_bf16_fast": round(qps_bf16, 1),
        "qps_batch32_aggregate": round(qps_batch, 1),
        "qb_big": QB2,
        "qps_batch_big_aggregate": round(qps_batch_big, 1),
        "bf16_speedup": round(qps_bf16 / qps_f32, 2)
        if qps_f32 > 0 and qps_bf16 > 0 else None,
        "qps_fused_single": round(qps_fused_single, 1),
        "qps_fused_qb_sweep": fused_sweep or None,
        "fused_vs_unfused_single": round(qps_fused_single / qps_f32, 2)
        if qps_fused_single > 0 and qps_f32 > 0 else None,
        "qps_numpy_hostscan": round(qps_np, 2),
        "lane_stage_s": round(stage_s, 2),
        "lane_mb": round(lane.nbytes / 1e6, 1),
    }
    if daemon_detail is not None:
        detail["daemon"] = daemon_detail
    return ctx.record({
        "metric": "search_queries_per_sec",
        "value": round(best, 1),
        "unit": "queries/s",
        "vs_baseline": round(best / qps_np, 2) if qps_np > 0 else 0.0,
        "detail": detail})


def _search_daemon_bench(lane, queries, d: int, k: int) -> dict:
    """Coalescing search daemon against a real store: waves of 32
    concurrent requests per drain, fused top-k dispatches, heartbeat-
    sourced SEARCH_STAGES quantiles.  Env: SEARCHD_N (store slots,
    default 8192), SEARCHD_WAVES (default 8)."""
    import json as _json

    from libsplinter_tpu import Store as _Store
    from libsplinter_tpu.engine import protocol as P
    from libsplinter_tpu.engine.searcher import Searcher
    from libsplinter_tpu.utils.trace import tracer

    nslots = int(os.environ.get("SEARCHD_N", "8192"))
    waves = int(os.environ.get("SEARCHD_WAVES", "8"))
    per_wave = 32
    name = _bench_store_name("srchd")
    _Store.unlink(name)
    st = _Store.create(name, nslots=nslots, max_val=4096, vec_dim=d)
    prev_traced = tracer.enabled
    tracer.enabled = True
    try:
        rows = min(nslots // 2, len(lane))
        for i in range(rows):
            st.set(f"doc/{i}", "x")
            st.vec_set(f"doc/{i}", lane[i])
        sr = Searcher(st)
        sr.attach()
        t0 = time.perf_counter()
        for w in range(waves):
            for j in range(per_wave):
                key = f"__sqtmp_bench{j}"
                st.set(key, _json.dumps({"k": k}))
                st.vec_set(key, queries[(w * per_wave + j)
                                        % len(queries)])
                st.label_or(key, P.LBL_SEARCH_REQ)
                st.bump(key)
            served = sr.run_once()
            assert served == per_wave, (served, per_wave)
        el = time.perf_counter() - t0
        sr.publish_stats()
        snap = _json.loads(st.get(P.KEY_SEARCH_STATS).rstrip(b"\0"))
        quant = {
            stage: {f: round(v[f], 3) for f in
                    ("p50_ms", "p95_ms", "p99_ms") if f in v}
            for stage, v in (snap.get("quantiles") or {}).items()}
        out = {
            "nslots": nslots, "rows": rows,
            "requests": sr.stats.requests,
            "served": sr.stats.served,
            "dispatches": sr.stats.dispatches,
            "coalesce_ratio": round(sr.stats.coalesce_ratio(), 2),
            "daemon_qps": round(waves * per_wave / el, 1),
            "stage_quantiles": quant,
        }
        log(f"[search] daemon: {out['served']} reqs in "
            f"{out['dispatches']} dispatches "
            f"({out['coalesce_ratio']}x coalesced), "
            f"{out['daemon_qps']} q/s e2e")
        return out
    finally:
        tracer.enabled = prev_traced
        st.close()
        _Store.unlink(name)


# ---------------------------------------------------------------------------
# phase: restage — StagedLane O(dirty) refresh cost at scale
# ---------------------------------------------------------------------------

def phase_restage(ctx: SeriesCtx) -> dict:
    """StagedLane full-upload vs O(dirty) refresh on a real store
    (the O(dirty) scaling property; the 1M CPU record is the
    at-size evidence, this phase adds the CHIP's transfer numbers at
    a bounded default).  Env: RESTAGE_N (131072 on TPU / 1,000,000 on
    CPU), RESTAGE_DIM (768)."""
    import resource

    import numpy as np

    import jax

    from libsplinter_tpu import Store
    from libsplinter_tpu.ops.staged_lane import StagedLane

    on_tpu = ctx.backend == "tpu"
    n = int(os.environ.get("RESTAGE_N",
                           "131072" if on_tpu else "1000000"))
    dim = int(os.environ.get("RESTAGE_DIM", "768"))
    name = _bench_store_name("restage")
    Store.unlink(name)
    nslots = 1
    while nslots < n * 2:
        nslots *= 2
    log(f"[restage] store nslots={nslots} dim={dim} "
        f"({nslots * dim * 4 / 1e9:.2f} GB lane)")
    st = Store.create(name, nslots=nslots, max_val=64, vec_dim=dim)
    try:
        t0 = time.perf_counter()
        for i in range(n):
            st.set(f"v/{i}", "x")
        fill_keys_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        rng = np.random.default_rng(0)
        view = st.vectors
        chunk = 65536
        for lo in range(0, nslots, chunk):
            hi = min(lo + chunk, nslots)
            view[lo:hi] = rng.standard_normal(
                (hi - lo, dim), dtype=np.float32)
        log(f"[restage] populated {n} keys in {fill_keys_s:.1f}s, "
            f"lane in {time.perf_counter() - t0:.1f}s")

        lane = StagedLane(st)
        t0 = time.perf_counter()
        jax.block_until_ready(lane.refresh())
        full_upload_s = time.perf_counter() - t0
        log(f"[restage] full upload: {full_upload_s:.2f}s "
            f"({nslots * dim * 4 / 1e6 / full_upload_s:,.0f} MB/s)")

        # f16-wire A/B in the SAME window (link conditions drift
        # between claims): second full upload with half the bytes.
        # TPU only — on the CPU backend the duplicate lane is host
        # RSS and would corrupt this phase's max_rss memory-diet
        # evidence (on TPU it is HBM, freed right after).
        f16_upload_s = None
        if on_tpu:
            lane16 = StagedLane(st, wire="f16")
            t0 = time.perf_counter()
            jax.block_until_ready(lane16.refresh())
            f16_upload_s = time.perf_counter() - t0
            del lane16                    # free the duplicate HBM lane
            log(f"[restage] f16-wire upload: {f16_upload_s:.2f}s "
                f"({nslots * dim * 2 / 1e6 / f16_upload_s:,.0f} "
                f"MB/s wire)")

        def timed_refresh() -> float:
            t0 = time.perf_counter()
            jax.block_until_ready(lane.refresh())
            return (time.perf_counter() - t0) * 1e3

        timed_refresh()
        clean_ms = min(timed_refresh() for _ in range(5))

        results = {}
        chunk_detail = {}
        # tolerant parse: a trailing comma or stray token must not
        # abort the phase, and counts past n are silently dropped
        dirty_counts = tuple(
            int(x.strip()) for x in os.environ.get(
                "RESTAGE_DIRTY", "128,8192,40000").split(",")
            if x.strip().isdigit() and int(x.strip()) <= n)
        for k in dirty_counts:
            # round 1 compiles this pad bucket's scatter; round 2 is
            # the steady state a live session pays
            for _ in (0, 1):
                staged_before = lane.rows_staged
                chunks_before = lane.scatter_chunks
                padded_before = lane.rows_padded
                idx = rng.choice(n, size=k, replace=False)
                for i in idx:
                    st.set(f"v/{i}", "y")
                ms = timed_refresh()
                moved = lane.rows_staged - staged_before
                assert moved == k, (moved, k)
                results[k] = ms
                chunk_detail[k] = {
                    "chunks": lane.scatter_chunks - chunks_before,
                    "rows_padded": lane.rows_padded - padded_before,
                }
            log(f"[restage] refresh after {k} dirty: "
                f"{results[k]:.1f} ms (warm, "
                f"{chunk_detail[k]['chunks']} chunks, "
                f"{chunk_detail[k]['rows_padded']} rows padded)")
    finally:
        st.close()
        Store.unlink(name)

    head = max(results) if results else None
    return ctx.record({
        "metric": "staged_lane_restage",
        "value": round(results[head], 1) if head is not None else 0.0,
        "unit": (f"ms ({head} dirty of {n})" if head is not None
                 else f"ms (no dirty counts <= {n} requested)"),
        "vs_baseline": 0.0,
        "detail": {
            "backend": ctx.backend, "n_keys": n, "nslots": nslots,
            "dim": dim,
            "lane_gb": round(nslots * dim * 4 / 1e9, 2),
            "full_upload_s": round(full_upload_s, 2),
            "upload_mb_s": round(nslots * dim * 4 / 1e6
                                 / full_upload_s, 1),
            "f16_wire_upload_s": round(f16_upload_s, 2)
            if f16_upload_s else None,
            "f16_wire_speedup": round(full_upload_s / f16_upload_s, 2)
            if f16_upload_s else None,
            "refresh_clean_ms": round(clean_ms, 1),
            **{f"refresh_{k}_dirty_ms": round(v, 1)
               for k, v in sorted(results.items())},
            # chunked-refresh accounting (the piecewise-linearity
            # evidence: chunks x bucket size, padding waste <= 2x)
            "refresh_chunks": {str(k): v for k, v
                               in sorted(chunk_detail.items())},
            "max_rss_gb": round(resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss / 1e6, 2),
        }})


# ---------------------------------------------------------------------------
# phases: decode / decode_quant / decode_daemon
# ---------------------------------------------------------------------------

def _decode_model(quant: bool):
    from libsplinter_tpu.models import CompletionModel, DecoderConfig

    geometry = os.environ.get("DECODE_GEOMETRY", "flagship")
    if geometry == "tiny":
        cfg = DecoderConfig.tiny(quantized=quant)
    else:
        # the completion daemon's default geometry (completer.py):
        # llama-tiny-class 12x768 with the byte tokenizer's padded vocab
        cfg = DecoderConfig(vocab_size=512, quantized=quant)
    return CompletionModel(cfg), cfg, geometry


def _decode_core(ctx: SeriesCtx, quant: bool) -> dict:
    """Prefill latency + chunked / per-token / wide-chunk / batched /
    speculative decode tokens per second.  Env: DECODE_TOKENS (256),
    DECODE_CHUNK (8), DECODE_GEOMETRY, DECODE_SPEC, DECODE_GAMMA.

    Every arm past the core measurement is BUDGET-GUARDED: BENCH_r05's
    series timed out inside phase-decode_quant after a second 57 s
    warmup compile (the chunk-32 program, freshly compiled for the
    int8 graph), which erased the later phases from the evidence set.
    Optional arms (chunk-32, the paged sweep, speculative) now check
    the remaining window — minus a tail reserve for decode_daemon +
    store_ops — before compiling anything, and skipped arms are
    ledgered in `budget_skipped` so a missing number reads as a
    deliberate skip, never a silent gap."""
    import numpy as np

    n_tokens = int(os.environ.get("DECODE_TOKENS", "256"))
    chunk = int(os.environ.get("DECODE_CHUNK", "8"))
    model, cfg, geometry = _decode_model(quant)

    # tail reserve: decode_daemon's floor + store_ops + slack — an
    # optional arm here must never eat the phases that follow
    tail_reserve = (PHASE_MIN_S["decode_daemon"]
                    + PHASE_MIN_S["store_ops"] + 30)
    budget_skipped: list[str] = []

    def room(arm: str, need_s: float) -> bool:
        left = ctx.remaining() - tail_reserve
        if left < need_s:
            budget_skipped.append(arm)
            log(f"[decode] SKIP {arm}: {left:.0f}s left after the "
                f"{tail_reserve}s tail reserve < {need_s:.0f}s")
            return False
        return True

    log(f"decode{' int8' if quant else ''}: warmup compile ...")
    t0 = time.perf_counter()
    model.warmup(chunk=chunk)
    model._chunk_program(1)
    log(f"compile: {time.perf_counter() - t0:.1f}s")

    prompt = np.ones((48,), np.int32)
    times = []
    for _ in range(5):
        model.reset()
        t0 = time.perf_counter()
        model.prefill(prompt)
        times.append((time.perf_counter() - t0) * 1000)
    prefill_ms = float(np.median(times))

    def tokens_per_sec(ch: int, n: int, m=None) -> float:
        m = model if m is None else m
        m.reset()
        m.prefill(prompt)
        n = min(n, cfg.max_len - m.pos - ch - 1)
        t0 = time.perf_counter()
        got = 0
        tok = 1
        while got < n:
            toks = m.decode_chunk(tok, ch)
            tok = int(toks[-1])
            got += ch
        return got / (time.perf_counter() - t0)

    tokens_per_sec(chunk, chunk * 2)
    tps_chunked = tokens_per_sec(chunk, n_tokens)
    tps_serial = tokens_per_sec(1, max(32, n_tokens // 4))
    tps_c32 = None
    if room("chunk32", 120):
        # the r05 killer: warmup(chunk=32) compiles a SECOND chunk
        # program (57 s on-chip for the int8 graph) — only worth it
        # when the window still fits the phases behind this one
        model.warmup(chunk=32)
        tokens_per_sec(32, 64)
        tps_c32 = tokens_per_sec(32, max(n_tokens, 128))
    log(f"decode: {tps_chunked:,.1f} tok/s (chunk={chunk}), "
        + (f"{tps_c32:,.1f} (chunk=32), " if tps_c32 is not None
           else "chunk=32 budget-skipped, ")
        + f"{tps_serial:,.1f} per-token sync")

    def batch_tokens_per_sec(bsz: int, n: int) -> float:
        prompts = [np.ones((24 + r,), np.int32) for r in range(bsz)]
        model.reset()
        t0 = time.perf_counter()
        got = 0
        for _col in model.generate_batch(prompts, n, chunk=chunk):
            got += bsz
        model.reset()
        return got / (time.perf_counter() - t0)

    batch_tokens_per_sec(8, chunk * 2)
    tps_b8 = batch_tokens_per_sec(8, n_tokens)
    log(f"batched decode: {tps_b8:,.1f} aggregate tok/s (batch=8)")

    # paged-vs-dense: the block-paged pool decodes the same geometry
    # at growing batch widths inside a FIXED cache budget (8 full
    # windows of pages — the r05 dense batch=8 HBM envelope), so the
    # sweep shows batch width, not cache padding, consuming HBM.
    # Env: DECODE_PAGED=0 skips, DECODE_PAGED_SWEEP=8,32,64 overrides
    # (CPU default stops at 8 to keep the host run bounded).
    paged_tps: dict[str, float] = {}
    paged_skipped: list[int] = []
    paged_int8_tps: dict[str, float] = {}
    paged_int8_skipped: list[int] = []
    paged_int4_tps: dict[str, float] = {}
    paged_int4_skipped: list[int] = []
    paged_page = 128
    paged_pool = 8 * (-(-cfg.max_len // paged_page))
    # the SAME byte envelope holds itemsize-times the pages when the
    # pool stores int8 (+ per-page scales, <1% at page 128) — that
    # page headroom IS the quantized lane's batch-width claim
    native_bytes = np.dtype(cfg.dtype).itemsize
    paged_pool_int8 = paged_pool * native_bytes
    # int4 packs two codes per byte: 2x int8's pages, 4x bf16's —
    # batch 256 inside the envelope that holds bf16 batch 64 (PR 20)
    paged_pool_int4 = paged_pool * native_bytes * 2

    def paged_row_budget(bsz: int, pool: int) -> int:
        """Decode tokens each row can take inside the FIXED pool.
        Pages allocate whole: rows grow in near-lockstep (prompts
        24..31, same chunk cadence), so each of the bsz rows can
        own at most pool // bsz pages — budgeting raw tokens
        (pool*page // bsz) would overshoot at the page boundary
        and exhaust the pool mid-sweep.  Margin: max prompt 31 +
        up to chunk-1 of final-chunk overshoot."""
        row_cap = (pool // bsz) * paged_page
        return min(row_cap, cfg.max_len) - 32 - chunk

    def paged_tokens_per_sec(bsz: int, n: int, pool: int,
                             kv_dtype: str | None = None) -> float:
        cache = model.init_paged(bsz, page=paged_page,
                                 pool_pages=pool, kv_dtype=kv_dtype)
        toks = np.zeros((bsz,), np.int32)
        for r in range(bsz):
            lg = model.paged_prefill_row(
                cache, np.ones((24 + r % 8,), np.int32), r)
            toks[r] = int(np.argmax(lg))
        n = min(n, paged_row_budget(bsz, pool))
        t0 = time.perf_counter()
        got = 0
        while got < n * bsz:
            blk = model.paged_decode_chunk(cache, toks, chunk)
            toks = blk[:, -1].astype(np.int32)
            got += bsz * chunk
        dt = time.perf_counter() - t0
        cache.reset()
        return got / dt

    def paged_sweep(widths, pool, kv_dtype, tps_out, skipped_out,
                    tag):
        for bsz in widths:
            if not room(f"{tag}_b{bsz}", 60):
                continue  # every unaffordable width gets its own
                          # budget_skipped entry, never a silent gap
            if paged_row_budget(bsz, pool) < chunk:
                # the claim under test is batch width inside the
                # FIXED envelope; growing the pool to fit a width it
                # can't hold would measure a different (bigger)
                # cache budget — skip loudly
                skipped_out.append(bsz)
                log(f"{tag} decode: batch={bsz} SKIPPED — the fixed "
                    f"{pool}-page pool leaves its rows no decode "
                    f"budget at this width")
                continue
            paged_tokens_per_sec(bsz, chunk * 2, pool,
                                 kv_dtype)       # warm/compile
            tps_out[str(bsz)] = round(
                paged_tokens_per_sec(bsz, n_tokens, pool, kv_dtype),
                1)
            log(f"{tag} decode: {tps_out[str(bsz)]:,.1f} aggregate "
                f"tok/s (batch={bsz}, pool={pool} pages of "
                f"{paged_page}"
                + (f", kv={kv_dtype}" if kv_dtype else "") + ")")

    if os.environ.get("DECODE_PAGED", "1") == "1" \
            and getattr(model, "paged_supported", False) \
            and room("paged_sweep", 120):
        sweep_default = "8" if os.environ.get("BENCH_CPU") == "1" \
            else "8,32,64"
        sweep = [int(x) for x in os.environ.get(
            "DECODE_PAGED_SWEEP", sweep_default).split(",") if x]
        paged_sweep(sweep, paged_pool, None, paged_tps,
                    paged_skipped, "paged")

        # int8 arm: the SAME byte envelope, kv_dtype=int8 — the
        # widths the doubled page count newly affords (the bf16
        # envelope can't hold batch 64/128 at all: their rows would
        # have no decode budget).  Env: DECODE_PAGED_INT8_SWEEP.
        int8_default = "32" if os.environ.get("BENCH_CPU") == "1" \
            else "32,64,128"
        int8_sweep = [int(x) for x in os.environ.get(
            "DECODE_PAGED_INT8_SWEEP", int8_default).split(",") if x]
        if room("paged_int8", 120):
            paged_sweep(int8_sweep, paged_pool_int8, "int8",
                        paged_int8_tps, paged_int8_skipped,
                        "paged_int8")

        # int4 arm (PR 20): the SAME byte envelope once more, packed
        # two codes per byte — the widths only the quarter-byte pool
        # affords (bf16 batch 64's bytes hold int4 batch 256).  Env:
        # DECODE_PAGED_INT4_SWEEP.
        int4_default = "64" if os.environ.get("BENCH_CPU") == "1" \
            else "64,128,256"
        int4_sweep = [int(x) for x in os.environ.get(
            "DECODE_PAGED_INT4_SWEEP", int4_default).split(",") if x]
        if room("paged_int4", 120):
            paged_sweep(int4_sweep, paged_pool_int4, "int4",
                        paged_int4_tps, paged_int4_skipped,
                        "paged_int4")

    tps_spec = accept = None
    draft_layers = 0
    if os.environ.get("DECODE_SPEC", "1") == "1" \
            and room("speculative", 120):
        from libsplinter_tpu.models import (SpeculativeCompletionModel,
                                            self_draft_model)
        gamma = int(os.environ.get("DECODE_GAMMA", "4"))
        # SELF-DRAFT (PR 9): the first ~3/4 of the target's own
        # layers propose — r05's random tiny draft measured 6.0 tok/s
        # at acceptance 0.05 and was demoted dead weight; the
        # truncated-view draft has REAL acceptance even on random
        # weights (~0.5 at 3/4 depth), and shares every byte with
        # the target
        draft_layers = int(os.environ.get(
            "DECODE_DRAFT_LAYERS", str(max(1, (3 * cfg.layers) // 4))))
        draft = self_draft_model(model, draft_layers)
        spec = SpeculativeCompletionModel(model, draft, gamma=gamma)
        spec.warmup()
        t0 = time.perf_counter()
        n_spec = sum(1 for _ in spec.generate_tokens(prompt, n_tokens))
        tps_spec = n_spec / (time.perf_counter() - t0)
        accept = spec.acceptance_rate
        spec.reset()
        log(f"speculative: {tps_spec:,.1f} tok/s (self-draft "
            f"layers={draft_layers}/{cfg.layers}, gamma={gamma}, "
            f"acceptance={accept:.2f}; r05 before-row: 6.0 tok/s at "
            f"0.05 with the random tiny draft)")

    # weights_int8 arm (PR 20): the SAME geometry with every
    # attention/MLP kernel held per-output-channel int8
    # (ChannelQuantDense — matmul on int8-resident weights, dequant
    # on the f32 MXU output).  Weight reads at half bf16 bandwidth
    # make the decode path's claim >=1.3x dense where it is
    # weight-bandwidth bound; off-TPU this row is a MECHANICAL smoke
    # (the graph runs, the ratio is ledgered), the TPU row is
    # BENCH_r06 debt.  Skipped in the Q8_0 phase: the residencies
    # are mutually exclusive.  Env: DECODE_WEIGHTS_INT8=0 skips.
    wq_tps = None
    if not quant and os.environ.get("DECODE_WEIGHTS_INT8", "1") == "1" \
            and room("weights_int8", 180):
        import dataclasses as _dc

        from libsplinter_tpu.models import CompletionModel
        log("weights_int8: warmup compile ...")
        wq_model = CompletionModel(_dc.replace(cfg, weights_int8=True))
        wq_model.warmup(chunk=chunk)
        tokens_per_sec(chunk, chunk * 2, wq_model)
        wq_tps = tokens_per_sec(chunk, n_tokens, wq_model)
        log(f"weights_int8 decode: {wq_tps:,.1f} tok/s (chunk={chunk},"
            f" {wq_tps / tps_chunked:.2f}x dense same-run)")

    return ctx.record({
        "metric": "decode_tokens_per_sec",
        "value": round(tps_chunked, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tps_chunked / tps_serial, 3)
        if tps_serial > 0 else 0.0,
        "detail": {
            "backend": ctx.backend, "geometry": geometry,
            "quantized": quant,
            "layers": cfg.layers, "hidden": cfg.hidden,
            "chunk": chunk, "n_tokens": n_tokens,
            "prefill_ms_bucket64": round(prefill_ms, 2),
            "tokens_per_sec_serial_sync": round(tps_serial, 1),
            "tokens_per_sec_chunk32": (round(tps_c32, 1)
                                       if tps_c32 is not None else None),
            # arms the window could not afford (deliberate skips, not
            # silent gaps — the r05 timeout fix)
            "budget_skipped": budget_skipped,
            "tokens_per_sec_batch8_aggregate": round(tps_b8, 1),
            # the paged/dense ledger label: dense is the batch8 row
            # above, paged entries are keyed by sweep batch width
            "kv_cache_dense": {"batch": 8,
                               "tokens_per_sec": round(tps_b8, 1)},
            "kv_cache_paged": {
                "page": paged_page, "pool_pages": paged_pool,
                "tokens_per_sec_by_batch": paged_tps,
                # widths the FIXED envelope cannot hold are skipped,
                # never measured against a silently grown pool
                "skipped_batches": paged_skipped,
                "vs_dense_batch8": (
                    round(max(paged_tps.values()) / tps_b8, 3)
                    if paged_tps and tps_b8 > 0 else None),
            },
            # int8 arm: SAME byte envelope (pool_pages x itemsize
            # pages of int8 + scales), the widths quantization newly
            # affords.
            "kv_cache_paged_int8": {
                "page": paged_page, "pool_pages": paged_pool_int8,
                "envelope_bytes_vs_native": "equal",
                "tokens_per_sec_by_batch": paged_int8_tps,
                "skipped_batches": paged_int8_skipped,
                "vs_dense_batch8": (
                    round(max(paged_int8_tps.values()) / tps_b8, 3)
                    if paged_int8_tps and tps_b8 > 0 else None),
                # the >=2x-batch-width-inside-the-envelope claim:
                # widest int8-MEASURED width over widest native one
                "max_batch_vs_native": (
                    round(max(map(int, paged_int8_tps))
                          / max(map(int, paged_tps)), 2)
                    if paged_int8_tps and paged_tps else None),
            },
            # int4 arm (PR 20): SAME byte envelope at two codes per
            # byte — 2x int8's pages, 4x native bf16's.  The headline
            # row is batch 256 inside bf16 batch 64's bytes.
            "kv_cache_paged_int4": {
                "page": paged_page, "pool_pages": paged_pool_int4,
                "envelope_bytes_vs_native": "equal",
                "tokens_per_sec_by_batch": paged_int4_tps,
                "skipped_batches": paged_int4_skipped,
                "vs_dense_batch8": (
                    round(max(paged_int4_tps.values()) / tps_b8, 3)
                    if paged_int4_tps and tps_b8 > 0 else None),
                # the 4x-batch-width-inside-the-envelope claim
                "max_batch_vs_native": (
                    round(max(map(int, paged_int4_tps))
                          / max(map(int, paged_tps)), 2)
                    if paged_int4_tps and paged_tps else None),
            },
            # weights_int8 arm (PR 20): per-output-channel int8
            # weight residency, dequant on the MXU f32 output.  The
            # acceptance bar (>=1.3x dense) is a WEIGHT-BANDWIDTH
            # claim — off-TPU the ratio is ledgered as a mechanical
            # smoke and the TPU row is explicit BENCH_r06 debt.
            "weights_int8": ({
                "tokens_per_sec": round(wq_tps, 1),
                "vs_dense_same_run": (round(wq_tps / tps_chunked, 3)
                                      if tps_chunked > 0 else None),
                "target": ">=1.3x dense bf16 (TPU, weight-bandwidth "
                          "bound)",
                "tpu_row": "BENCH_r06 debt — this run is a CPU/"
                           "mechanical smoke unless backend is tpu",
            } if wq_tps is not None else None),
            "tokens_per_sec_speculative": (round(tps_spec, 1)
                                           if tps_spec else None),
            "speculative_acceptance": (round(accept, 3)
                                       if accept is not None else None),
            "speculative_draft": (
                {"kind": "self", "layers": draft_layers,
                 "of_layers": cfg.layers}
                if draft_layers else None),
        }})


def phase_decode(ctx: SeriesCtx) -> dict:
    return _decode_core(ctx, quant=False)


def phase_decode_quant(ctx: SeriesCtx) -> dict:
    return _decode_core(ctx, quant=True)


def phase_multichip(ctx: SeriesCtx) -> dict:
    """Pod-sharded paged decode (PR 8; ROADMAP item 1): aggregate
    paged tok/s through ShardedCompletionModel over a tp mesh spanning
    every visible device, batch {32, 64}, ledgered against the
    single-chip r05 row (612.3 aggregate tok/s, batch=8).  On a TPU
    pod the acceptance bar is >= 6x the single-chip aggregate on 8
    chips; on any other backend the row is a CPU-MESH SMOKE — labeled
    loudly as such in the record — proving the sharded lane runs
    mechanically, never a performance claim.

    Env: MULTICHIP_BATCHES (32,64), MULTICHIP_TOKENS (per-row decode
    budget; 16 CPU / 256 TPU), DECODE_CHUNK (8), DECODE_GEOMETRY."""
    import numpy as np

    n_dev = ctx.n_devices
    require_chip(ctx.backend)
    on_cpu = cpu_quick_track()
    chunk = int(os.environ.get("DECODE_CHUNK", "8"))
    base_rec = {"metric": "multichip_paged_tokens_per_sec",
                "unit": "tokens/s (aggregate)"}
    if n_dev < 2:
        # a single-chip claim cannot exercise the arm — ledger the
        # skip explicitly so the series stays complete and honest
        log("[multichip] single device visible: no tp mesh to shard "
            "over; ledgering a skip row")
        return ctx.record({
            **base_rec, "value": 0.0, "vs_baseline": 0.0,
            "detail": {"backend": ctx.backend, "n_devices": n_dev,
                       "skipped": "single device — the paged "
                                  "multi-chip arm needs a pod claim"}})

    from libsplinter_tpu.models import DecoderConfig
    from libsplinter_tpu.parallel import ShardedCompletionModel
    from libsplinter_tpu.parallel.mesh import make_mesh

    geometry = os.environ.get("DECODE_GEOMETRY",
                              "tiny" if on_cpu else "flagship")
    if geometry == "tiny":
        cfg = DecoderConfig.tiny()
    else:
        cfg = DecoderConfig(vocab_size=512)
    # widest tp that divides the heads, the kv heads, and the device
    # count (the rest becomes dp; kv-head pool sharding needs tp | KH)
    tp = max(t for t in range(1, n_dev + 1)
             if cfg.heads % t == 0 and cfg.kv_heads % t == 0
             and n_dev % t == 0)
    mesh = make_mesh(tp=tp)
    model = ShardedCompletionModel(cfg, mesh)
    assert model.paged_supported, "sharded paged lane regressed"
    page = 16 if on_cpu else 128
    ppr = -(-cfg.max_len // page)
    batches = [int(x) for x in os.environ.get(
        "MULTICHIP_BATCHES", "32,64").split(",") if x]
    n_tokens = int(os.environ.get("MULTICHIP_TOKENS",
                                  "16" if on_cpu else "256"))

    def pool_for(bsz: int) -> int:
        if not on_cpu:
            # the r05 HBM envelope: 8 full windows of pages, same
            # fixed-budget discipline as _decode_core's paged sweep
            return 8 * ppr
        # CPU smoke: 2 pages per row so every width decodes a few
        # chunks (the envelope claim is the TPU arm's job)
        return max(8 * ppr, bsz * 2)

    def paged_tps(bsz: int, n: int) -> float:
        cache = model.init_paged(bsz, page=page,
                                 pool_pages=pool_for(bsz))
        row_cap = (pool_for(bsz) // bsz) * page
        n = max(chunk, min(n, min(row_cap, cfg.max_len) - 8 - chunk))
        toks = np.zeros((bsz,), np.int32)
        for r in range(bsz):
            lg = model.paged_prefill_row(
                cache, np.ones((4 + r % 4,), np.int32), r)
            toks[r] = int(np.argmax(lg))
        t0 = time.perf_counter()
        got = 0
        while got < n * bsz:
            blk = model.paged_decode_chunk(cache, toks, chunk)
            toks = blk[:, -1].astype(np.int32)
            got += bsz * chunk
        dt = time.perf_counter() - t0
        cache.reset()
        return got / dt

    tps_by_batch: dict[str, float] = {}
    budget_skipped: list[str] = []
    for bsz in batches:
        if ctx.remaining() < 120:
            # ledgered below, never a silent gap (same discipline as
            # _decode_core's budget_skipped)
            budget_skipped.append(f"batch{bsz}")
            log(f"[multichip] batch={bsz} budget-skipped "
                f"({ctx.remaining():.0f}s left)")
            continue
        paged_tps(bsz, chunk * 2)                 # warm/compile
        tps_by_batch[str(bsz)] = round(paged_tps(bsz, n_tokens), 1)
        log(f"multichip paged: {tps_by_batch[str(bsz)]:,.1f} aggregate "
            f"tok/s (batch={bsz}, tp={tp} over {n_dev} devices)")

    best = max(tps_by_batch.values()) if tps_by_batch else 0.0
    return ctx.record({
        **base_rec,
        "value": best,
        # the >=6x-single-chip acceptance ratio needs a one-chip row
        # of the same run on the same machine; none exists yet
        "vs_baseline": 0.0,
        "detail": {
            "backend": ctx.backend, "geometry": geometry,
            "n_devices": n_dev, "tp": tp, "dp": n_dev // tp,
            "page": page, "chunk": chunk,
            "pool_pages_by_batch": {str(b): pool_for(b)
                                    for b in batches},
            "tokens_per_sec_by_batch": tps_by_batch,
            "budget_skipped": budget_skipped,
            "target": ">=6x single-chip aggregate tok/s on 8 chips",
            # LOUD smoke label: a CPU virtual mesh measures host
            # arithmetic, not ICI-sharded HBM bandwidth — this row is
            # mechanical evidence only until a pod claim lands
            "cpu_mesh_smoke": ctx.backend != "tpu",
        }})


def phase_loadgen(ctx: SeriesCtx) -> dict:
    """Open-loop multi-tenant serving under QoS (`spt loadgen`,
    cli/loadgen.py): a full in-process stack — real tiny encoder +
    decoder, the fused-top-k searcher — serves mixed 3-tenant
    embed/search/complete traffic with per-tenant admission
    (admit_cap + queue high water on the search lane) while the
    generator's clock, not the server, decides arrivals.  Ledgers
    goodput vs shed and per-tenant p99 sourced from the PR 2 log
    histograms — the first bench row that measures the system AS a
    multi-tenant server instead of a closed benchmark loop.  Off-TPU
    rows carry a LOUD cpu_smoke label.  Env: LOADGEN_S (duration,
    default 8), LOADGEN_RATE (aggregate req/s, default 60)."""
    import threading

    import numpy as np  # noqa: F401  (loadgen pulls it anyway)

    from libsplinter_tpu import Store
    from libsplinter_tpu.cli.loadgen import LoadGenerator, TenantSpec
    from libsplinter_tpu.engine.completer import Completer
    from libsplinter_tpu.engine.embedder import Embedder
    from libsplinter_tpu.engine.searcher import Searcher
    from libsplinter_tpu.models import default_tokenizer
    from libsplinter_tpu.models.decoder import (CompletionModel,
                                                DecoderConfig)
    from libsplinter_tpu.models.encoder import (EmbeddingModel,
                                                EncoderConfig)

    duration = float(os.environ.get("LOADGEN_S", "8"))
    rate = float(os.environ.get("LOADGEN_RATE", "60"))
    name = _bench_store_name("loadgen")
    Store.unlink(name)
    st = Store.create(name, nslots=1024, max_val=2048, vec_dim=32)
    daemons: list = []
    ths: list = []
    try:
        ecfg = EncoderConfig.tiny(out_dim=st.vec_dim)
        emb = Embedder(st, model=EmbeddingModel(ecfg),
                       tokenizer=default_tokenizer(ecfg.vocab_size),
                       max_ctx=ecfg.max_len, batch_cap=32)
        dcfg = DecoderConfig.tiny()
        comp = Completer(
            st, model=CompletionModel(dcfg, temp=0.0, seed=1),
            max_new_tokens=8, flush_tokens=4, template="none",
            queue_high_water=256)
        sr = Searcher(st, admit_cap=64, queue_high_water=256)
        for d in (emb, sr, comp):
            d.attach()
            daemons.append(d)
        run_s = duration + 60
        ths = [threading.Thread(
            target=d.run, kwargs=dict(idle_timeout_ms=10,
                                      stop_after=run_s), daemon=True)
            for d in daemons]
        for t in ths:
            t.start()

        # 3 tenants at 3:2:1 offered rates, one shared deadline —
        # aggregate LOADGEN_RATE req/s open loop
        unit = rate / 6.0
        tenants = [TenantSpec(1, 3 * unit, deadline_ms=10_000),
                   TenantSpec(2, 2 * unit, deadline_ms=10_000),
                   TenantSpec(3, 1 * unit, deadline_ms=10_000)]
        gen = LoadGenerator(st, tenants, duration_s=duration,
                            corpus=32, seed=7, drain_s=30.0)
        rep = gen.run()

        per_tenant_p99 = {
            t: {lane: row.get("p99_ms") for lane, row in lanes.items()
                if "p99_ms" in row}
            for t, lanes in rep["per_tenant"].items()}
        rec = {
            "metric": "loadgen_goodput",
            "backend": ctx.backend,
            "duration_s": rep["duration_s"],
            "offered_rps": rate,
            "issued": rep["issued"],
            "goodput_rps": rep["goodput_rps"],
            "goodput_ratio": rep["goodput_ratio"],
            "shed": rep["shed"],
            "expired": rep["expired"],
            "lost": rep["lost"],
            "unserved": rep["unserved"],
            "per_tenant_p99_ms": per_tenant_p99,
            "tenant_rates": {"1": 3 * unit, "2": 2 * unit,
                             "3": unit},
        }
        if ctx.backend != "tpu":
            # tiny models on host CPU: a serving-layer smoke, not a
            # throughput claim — label it so no before/after compare
            # ever mistakes it for chip evidence
            rec["label"] = "cpu_smoke"
        log(f"loadgen: {rep['issued']} issued, goodput "
            f"{rep['goodput_rps']:.1f} rps "
            f"({rep['goodput_ratio']:.1%}), shed={rep['shed']} "
            f"lost={rep['lost']}")
        return ctx.record(rec)
    finally:
        for d in daemons:
            d.stop()
        for t in ths:
            t.join(timeout=15)
        st.close()
        Store.unlink(name)


def phase_prefix(ctx: SeriesCtx) -> dict:
    """Cross-request prefix sharing (ISSUE 14, ROADMAP item 2):
    hot-vs-cold admission-to-first-token through a real continuous
    completer (the radix prefix cache maps shared pages, cold pays
    the dense bucket prefill), plus the rows-per-page-envelope
    multiplier vs PR 5's private paging at a fixed pool budget.
    Off-TPU rows carry the LOUD cpu_smoke label — the >= 10x
    admission claim is a TPU ledger row; CPU gates at >= 5x via
    `make prefix-check`.  Env: PREFIX_TRIALS (default 5)."""
    import threading

    import numpy as np

    from libsplinter_tpu import Store
    from libsplinter_tpu.engine import protocol as P
    from libsplinter_tpu.engine.completer import Completer
    from libsplinter_tpu.models.decoder import (CompletionModel,
                                                DecoderConfig)

    trials = int(os.environ.get("PREFIX_TRIALS", "5"))
    page = 32
    prompt = ("retrieval context: " * 70)[: 33 * page - 1]

    def first_token_ms(st, key: str) -> float:
        st.set(key, prompt)
        rendered = len(prompt.encode())
        t0 = time.perf_counter()
        st.label_or(key, P.LBL_INFER_REQ | P.LBL_WAITING)
        st.bump(key)
        deadline = t0 + 120.0
        while time.perf_counter() < deadline:
            try:
                if st.value_len(key) > rendered:
                    return (time.perf_counter() - t0) * 1e3
            except KeyError:
                pass
            time.sleep(0.0002)
        raise RuntimeError(f"{key} never streamed")

    lat: dict[str, list[float]] = {}
    pfx_stats = None
    for tag, enable in (("cold", False), ("hot", True)):
        name = _bench_store_name(f"prefix-{tag}")
        Store.unlink(name)
        st = Store.create(name, nslots=256, max_val=8192, vec_dim=8)
        try:
            cfg = DecoderConfig.tiny(max_len=2048)
            model = CompletionModel(cfg, buckets=(1088,), temp=0.0,
                                    seed=1, suffix_buckets=(16,))
            comp = Completer(st, model=model, max_new_tokens=6,
                             flush_tokens=1, template="none",
                             batch_cap=4, page_size=page,
                             pool_pages=110, inflight_depth=1,
                             prefix_cache=enable)
            comp.attach()
            comp.warmup_paged()
            th = threading.Thread(
                target=comp.run_continuous,
                kwargs=dict(idle_timeout_ms=5, stop_after=300.0),
                daemon=True)
            th.start()
            time.sleep(0.1)
            first_token_ms(st, f"{tag}/warm")   # seed tree / warm lane
            lat[tag] = []
            for i in range(trials):
                key = f"{tag}/{i}"
                lat[tag].append(first_token_ms(st, key))
                done_by = time.monotonic() + 60.0
                while not st.labels(key) & P.LBL_READY:
                    if time.monotonic() > done_by:
                        raise RuntimeError(f"{key} never READY")
                    time.sleep(0.001)
            if enable:
                pfx_stats = comp.prefix_cache.stats
            comp.stop()
            th.join(timeout=30)
        finally:
            st.close()
            Store.unlink(name)

    # rows-per-envelope at cache level: the same reservation math
    # run_continuous uses (worst case minus hit pages plus COW page)
    from libsplinter_tpu.engine.prefix_cache import PrefixCache
    cfg = DecoderConfig.tiny()
    m2 = CompletionModel(cfg, buckets=(32,), temp=0.0, seed=1)
    budget, prompt_pages, pg = 64, 15, 8
    ids = (np.arange(1, 1 + prompt_pages * pg, dtype=np.int32)
           % 200) + 1
    worst = (prompt_pages + 1) * pg
    private = m2.init_paged(32, page=pg, pool_pages=budget)
    rows_private = 0
    for r in range(32):
        if not private.ensure(r, worst):
            break
        rows_private += 1
    shared = m2.init_paged(32, page=pg, pool_pages=budget)
    pc = PrefixCache(pg)
    pc.attach(shared)
    shared.prefix_cache = pc
    m2.paged_prefill_row(shared, ids, 0)
    shared.ensure(0, worst)
    pc.insert(ids, shared, 0)
    rows_shared = 1
    for r in range(1, 32):
        bids, match = pc.lookup(ids)
        if (shared.pages_needed(worst) - len(bids) + 1
                > shared.available_pages):
            break
        shared.map_shared(r, bids)
        shared.lengths[r] = match - 1
        shared.ensure(r, worst)
        m2._cow_fixups(shared)          # the replay page is real cost
        rows_shared += 1

    cold_p50 = float(np.median(lat["cold"]))
    hot_p50 = float(np.median(lat["hot"]))
    rec = {
        "metric": "prefix_cache",
        "backend": ctx.backend,
        "prompt_tokens": len(prompt) + 1,
        "page": page,
        "cold_first_token_p50_ms": round(cold_p50, 3),
        "hot_first_token_p50_ms": round(hot_p50, 3),
        "admission_speedup": round(cold_p50 / hot_p50, 2)
        if hot_p50 > 0 else None,
        "rows_private": rows_private,
        "rows_shared": rows_shared,
        "rows_multiplier": round(rows_shared / rows_private, 2)
        if rows_private else None,
        "pool_budget_pages": budget,
        "detail": {
            "cold_ms": [round(x, 2) for x in lat["cold"]],
            "hot_ms": [round(x, 2) for x in lat["hot"]],
            "hits": pfx_stats.hits if pfx_stats else 0,
            "cow_copies": pfx_stats.cow_copies if pfx_stats else 0,
            "bytes_saved": pfx_stats.bytes_saved if pfx_stats else 0,
        },
    }
    if ctx.backend != "tpu":
        # tiny models on host CPU: a mechanism smoke, not the >= 10x
        # TPU claim — label it so no before/after compare ever
        # mistakes it for chip evidence
        rec["label"] = "cpu_smoke"
    log(f"prefix: first-token p50 cold {cold_p50:.1f} ms -> hot "
        f"{hot_p50:.1f} ms ({rec['admission_speedup']}x); rows "
        f"{rows_private} -> {rows_shared} in {budget} pages")
    return ctx.record(rec)


def phase_disagg(ctx: SeriesCtx) -> dict:
    """Disaggregated prefill/decode lanes (ISSUE 18): the same
    prefill-burst workload (steady decode floor + a prompt-heavy rate
    step) is served twice — once by a unified continuous completer,
    once by the split PrefillLane + DecodeLane pair — and the decode
    floor's inter-chunk p99 during the burst phase is ledgered for
    both (the split/unified ratio IS the disaggregation win: prefill
    bubbles stop landing inside decode token gaps).  A post-drain
    probe on the quiet split stack times DECODE_READY -> adoption
    (the page-handoff hop itself), and the row carries both lanes'
    heartbeat counters (handoffs, wire MB, refills).  The store uses
    max_val=16384 so the real wire-page export/import path is what
    gets measured, not the re-prefill fallback.  Off-TPU rows carry
    the LOUD cpu_smoke label.  Env: DISAGG_RATE (per-class req/s,
    default 3), DISAGG_PROFILE (default 1x:3,8x:5,1x:3)."""
    import threading

    import jax.numpy as jnp
    import numpy as np

    from libsplinter_tpu import Store
    from libsplinter_tpu.cli.loadgen import (LoadGenerator, TenantSpec,
                                             parse_rate_profile)
    from libsplinter_tpu.engine import protocol as P
    from libsplinter_tpu.engine.completer import Completer
    from libsplinter_tpu.engine.disagg import DecodeLane, PrefillLane
    from libsplinter_tpu.models.decoder import (CompletionModel,
                                                DecoderConfig)

    rate = float(os.environ.get("DISAGG_RATE", "3"))
    prof = parse_rate_profile(
        os.environ.get("DISAGG_PROFILE", "1x:3,8x:5,1x:3"))
    burst_phase = max(range(len(prof)), key=lambda p: prof[p][0])

    # one model for both modes: identical buckets, zero recompiles
    # between the unified and split runs
    dcfg = DecoderConfig.tiny(dtype=jnp.float32)
    model = CompletionModel(dcfg, buckets=(32,), temp=0.0, seed=1,
                            suffix_buckets=(8,))
    KW = dict(max_new_tokens=10, flush_tokens=2, template="none",
              batch_cap=4, page_size=8)
    duration = sum(d for _, d in prof)

    def probe_handoff(st, key: str) -> float | None:
        """Time the DECODE_READY -> adopted (SERVICING re-raised) hop
        for one quiet request; None when the window was too short to
        observe (adoption faster than the poll resolution)."""
        st.set(key, f"probe {key}")
        st.label_or(key, P.LBL_INFER_REQ | P.LBL_WAITING)
        st.bump(key)
        t_ho = None
        deadline = time.perf_counter() + 60.0
        while time.perf_counter() < deadline:
            lb = st.labels(key)
            now = time.perf_counter()
            if lb & P.LBL_DECODE_READY:
                if lb & P.LBL_SERVICING:
                    # adopted: only a valid sample if we saw the bare
                    # DECODE_READY window first
                    return (now - t_ho) * 1e3 if t_ho is not None \
                        else None
                if t_ho is None:
                    t_ho = now
            if lb & P.LBL_READY:
                return None
            time.sleep(0.0002)
        raise RuntimeError(f"{key} never handed off")

    def run_mode(tag: str, split: bool) -> tuple[dict, dict]:
        name = _bench_store_name(f"disagg-{tag}")
        Store.unlink(name)
        st = Store.create(name, nslots=1024, max_val=16384, vec_dim=8)
        daemons: list = []
        ths: list = []
        stats: dict = {}
        try:
            if split:
                daemons = [PrefillLane(st, model=model, **KW),
                           DecodeLane(st, model=model, **KW)]
            else:
                daemons = [Completer(st, model=model, **KW)]
            for d in daemons:
                d.attach()
            ths = [threading.Thread(
                target=d.run_continuous,
                kwargs=dict(idle_timeout_ms=10,
                            stop_after=duration + 90), daemon=True)
                for d in daemons]
            for t in ths:
                t.start()
            gen = LoadGenerator(st, [TenantSpec(1, rate,
                                                deadline_ms=30_000)],
                                duration_s=duration,
                                scenario="prefill-burst",
                                rate_profile=prof, corpus=32, seed=7,
                                drain_s=45.0)
            rep = gen.run()
            if split:
                # post-drain, quiet lanes: time the handoff hop itself
                samples = [probe_handoff(st, f"__probe/{i}")
                           for i in range(5)]
                samples = [s for s in samples if s is not None]
                stats["handoff_ms"] = samples
                stats["prefill"] = dict(daemons[0]._lane_stats)
                stats["decode"] = dict(daemons[1]._lane_stats)
            return rep, stats
        finally:
            for d in daemons:
                d.stop()
            for t in ths:
                t.join(timeout=30)
            st.close()
            Store.unlink(name)

    def floor_p99(rep: dict, phase: int) -> float | None:
        for row in rep.get("prefill_burst", []):
            if row.get("phase") == phase:
                return row.get("decode-floor", {}).get(
                    "interchunk_p99_ms")
        return None

    rep_u, _ = run_mode("unified", split=False)
    rep_s, lane_stats = run_mode("split", split=True)

    u99 = floor_p99(rep_u, burst_phase)
    s99 = floor_p99(rep_s, burst_phase)
    idle99 = floor_p99(rep_s, 0)
    ho = sorted(lane_stats.get("handoff_ms", []))
    rec = {
        "metric": "disagg_decode_p99",
        "backend": ctx.backend,
        "offered_rps_per_class": rate,
        "profile": [[m, d] for m, d in prof],
        "burst_phase": burst_phase,
        "unified_burst_interchunk_p99_ms": u99,
        "split_burst_interchunk_p99_ms": s99,
        "split_vs_unified": round(s99 / u99, 3)
        if u99 and s99 else None,
        "split_idle_interchunk_p99_ms": idle99,
        "handoff_p50_ms": round(float(np.median(ho)), 3)
        if ho else None,
        "handoff_samples": len(ho),
        "lane_stats": {k: lane_stats.get(k) for k in
                       ("prefill", "decode")},
        "detail": {"unified_burst": rep_u.get("prefill_burst"),
                   "split_burst": rep_s.get("prefill_burst")},
    }
    if ctx.backend != "tpu":
        # tiny models on host CPU: a mechanism smoke, not the decode
        # isolation claim — label it so no before/after compare ever
        # mistakes it for chip evidence
        rec["label"] = "cpu_smoke"
    log(f"disagg: burst-phase floor inter-chunk p99 unified "
        f"{u99} ms -> split {s99} ms (ratio "
        f"{rec['split_vs_unified']}); handoff p50 "
        f"{rec['handoff_p50_ms']} ms over {len(ho)} probes; "
        f"prefill {lane_stats.get('prefill')}")
    return ctx.record(rec)


def phase_tier(ctx: SeriesCtx) -> dict:
    """Tiered KV spill/readmit (ISSUE 19): price an evicted hot
    prompt's way back into HBM — tier readmission (one device_put +
    block-table write per page) vs the full re-prefill a tierless
    cache pays for the same prompt — plus the warm-restart snapshot
    round-trip (save + cold-attach restore) and the warm-footprint
    multiplier the DRAM tier buys per HBM pool envelope.  Off-TPU
    rows carry the LOUD cpu_smoke label — the readmit-vs-reprefill
    ratio is a TPU ledger claim; CPU correctness gates live in
    `make warm-check`.  Env: TIER_TRIALS (default 5), TIER_PAGES
    (prompt length in pages, default 12)."""
    import jax
    import numpy as np

    from libsplinter_tpu.engine.kv_tier import (HostTier, TierPersist,
                                                tier_geometry)
    from libsplinter_tpu.engine.prefix_cache import PrefixCache
    from libsplinter_tpu.models.decoder import (CompletionModel,
                                                DecoderConfig)

    trials = int(os.environ.get("TIER_TRIALS", "5"))
    n_pages = int(os.environ.get("TIER_PAGES", "12"))
    pg = 8
    pool = 4 * n_pages
    cfg = DecoderConfig.tiny(max_len=max(256, 2 * n_pages * pg))
    model = CompletionModel(cfg, buckets=(n_pages * pg + 32,),
                            temp=0.0, seed=1)
    ids = (np.arange(1, 1 + n_pages * pg, dtype=np.int32) % 200) + 1

    cache = model.init_paged(4, page=pg, pool_pages=pool)
    pc = PrefixCache(pg)
    pc.attach(cache)
    cache.prefix_cache = pc
    tier = HostTier(2 * n_pages)
    pc.bind_tier(
        tier,
        export_page=lambda bid: model.export_page_bytes(cache, bid),
        import_page=lambda bid, buf, sbuf: model.import_page_bytes(
            cache, bid, buf, sbuf))
    model.paged_prefill_row(cache, ids, 0)
    assert pc.insert(ids, cache, 0) == n_pages   # write-through spill
    cache.free_row(0)

    def demote_all():
        assert pc.reclaim(n_pages) == n_pages
        assert pc.demoted_pages() == n_pages

    def readmit_once(row: int) -> float:
        t0 = time.perf_counter()
        _, _, nodes = pc.lookup_tiered(ids)
        got = pc.readmit(nodes, cache)
        for b in got:
            cache._decref(b)
        cache.map_shared(row, got)
        cache.lengths[row] = len(ids) - 1
        jax.block_until_ready(cache.k_pools)
        dt = (time.perf_counter() - t0) * 1e3
        assert len(got) == n_pages
        cache.free_row(row)
        return dt

    demote_all()
    readmit_once(1)                     # compile the import program
    readmit_ms = []
    for _ in range(trials):
        demote_all()
        readmit_ms.append(readmit_once(1))

    # baseline: the same prompt re-prefilled into a tierless pool
    cache_b = model.init_paged(4, page=pg, pool_pages=pool)
    jax.block_until_ready(
        model.paged_prefill_row(cache_b, ids, 0))    # compile
    cache_b.free_row(0)
    reprefill_ms = []
    for _ in range(trials):
        t0 = time.perf_counter()
        jax.block_until_ready(model.paged_prefill_row(cache_b, ids, 0))
        reprefill_ms.append((time.perf_counter() - t0) * 1e3)
        cache_b.free_row(0)

    # warm-restart round-trip: checkpoint the demoted chain, restore
    # it into a cold cache (what a respawned lane pays at attach)
    demote_all()
    geom = tier_geometry(model, cache)
    pname = _bench_store_name("tier") + "-kvtier"
    TierPersist.unlink(pname)
    persist = TierPersist(pname, capacity_pages=2 * n_pages,
                          max_len=cfg.max_len,
                          page_bytes=geom["page_bytes"])
    try:
        t0 = time.perf_counter()
        assert persist.save(pc, tier, geom)
        save_ms = (time.perf_counter() - t0) * 1e3
        cache_c = model.init_paged(4, page=pg, pool_pages=pool)
        pc_c = PrefixCache(pg)
        pc_c.attach(cache_c)
        tier_c = HostTier(2 * n_pages)
        pc_c.bind_tier(tier_c)
        t0 = time.perf_counter()
        restored, reason = persist.load(pc_c, tier_c, geom)
        restore_ms = (time.perf_counter() - t0) * 1e3
        assert restored == n_pages and reason == "", (restored, reason)
    finally:
        persist.close()
        TierPersist.unlink(pname)

    # rows-per-HBM-envelope, tier on vs off: stream distinct 3-page
    # prompt chains through a SMALL pool under zero-ref eviction
    # pressure, then count how many stay servable (full radix match,
    # HBM or DRAM) — the warm working set one HBM envelope retains
    chain_pages, n_chains = 3, 20
    envelope = 4 * chain_pages            # HBM holds 4 chains
    chains = [((np.arange(chain_pages * pg, dtype=np.int32)
                + 37 * i) % 199) + 1 for i in range(n_chains)]
    # short-context model so the tiny envelope still holds one full
    # window (the pool floor is max_len/page pages)
    model_e = CompletionModel(DecoderConfig.tiny(max_len=8 * pg),
                              buckets=(chain_pages * pg + pg,),
                              temp=0.0, seed=1)
    # write-through shadowing makes the DRAM tier a SUPERSET of the
    # HBM pool, so the warm set is bounded by the tier's capacity:
    # 2x the envelope of host RAM doubles the warm working set
    warm_chains = {}
    for tag, cap in (("off", 0), ("on", 2 * envelope)):
        c = model_e.init_paged(4, page=pg, pool_pages=envelope)
        p = PrefixCache(pg)
        p.attach(c)
        c.prefix_cache = p
        if cap:
            t2 = HostTier(cap)
            p.bind_tier(
                t2,
                export_page=lambda bid, c=c:
                model_e.export_page_bytes(c, bid),
                import_page=lambda bid, buf, sbuf, c=c:
                model_e.import_page_bytes(c, bid, buf, sbuf))
        for ch in chains:
            if c.available_pages < chain_pages:
                p.reclaim(chain_pages)
            model_e.paged_prefill_row(c, ch, 0)
            p.insert(ch, c, 0)
            c.free_row(0)
        warm_chains[tag] = sum(
            1 for ch in chains
            if (lambda r: (len(r[0]) * pg + len(r[2]) * pg)
                == chain_pages * pg)(p.lookup_tiered(ch)))

    re_p50 = float(np.median(readmit_ms))
    pf_p50 = float(np.median(reprefill_ms))
    rec = {
        "metric": "kv_tier",
        "backend": ctx.backend,
        "prompt_tokens": int(n_pages * pg),
        "page": pg,
        "page_bytes": geom["page_bytes"],
        "readmit_p50_ms": round(re_p50, 3),
        "reprefill_p50_ms": round(pf_p50, 3),
        "readmit_speedup": round(pf_p50 / re_p50, 2)
        if re_p50 > 0 else None,
        "readmit_us_per_page": round(re_p50 * 1e3 / n_pages, 1),
        "snapshot_save_ms": round(save_ms, 3),
        "snapshot_restore_ms": round(restore_ms, 3),
        "restored_pages": restored,
        "hbm_pool_pages": pool,
        "envelope_pages": envelope,
        "tier_capacity_pages": 2 * envelope,
        "warm_chains_tier_off": warm_chains["off"],
        "warm_chains_tier_on": warm_chains["on"],
        "warm_multiplier": round(
            warm_chains["on"] / warm_chains["off"], 2)
        if warm_chains["off"] else None,
        "detail": {
            "readmit_ms": [round(x, 2) for x in readmit_ms],
            "reprefill_ms": [round(x, 2) for x in reprefill_ms],
            "spills": tier.spills,
            "demotions": tier.demotions,
            "readmits": tier.readmits,
        },
    }
    if ctx.backend != "tpu":
        # tiny models on host CPU: a mechanism smoke, not the
        # readmit-vs-reprefill chip claim — label it so no
        # before/after compare ever mistakes it for chip evidence
        rec["label"] = "cpu_smoke"
    log(f"tier: readmit p50 {re_p50:.2f} ms vs re-prefill "
        f"{pf_p50:.2f} ms ({rec['readmit_speedup']}x) over "
        f"{n_pages} pages; warm chains per {envelope}-page envelope "
        f"{warm_chains['off']} -> {warm_chains['on']} "
        f"({rec['warm_multiplier']}x); snapshot save {save_ms:.2f} ms "
        f"/ restore {restore_ms:.2f} ms")
    return ctx.record(rec)


def phase_decode_daemon(ctx: SeriesCtx) -> dict:
    """Completion-daemon e2e latency + continuous serving.  Runs LAST:
    this phase (completer e2e) is the only one that ever hung on-chip
    (round-3 watchdog kill); faulthandler leaves a stack if it repeats.
    Env: DECODE_CHUNK (8)."""
    import threading

    import numpy as np

    from libsplinter_tpu import Store
    from libsplinter_tpu.engine import protocol as P
    from libsplinter_tpu.engine.completer import Completer

    chunk = int(os.environ.get("DECODE_CHUNK", "8"))
    quant = os.environ.get("DECODE_QUANT") == "1"
    model, cfg, geometry = _decode_model(quant)
    model.warmup(chunk=chunk)

    name = _bench_store_name("dec")
    Store.unlink(name)
    st = Store.create(name, nslots=256, max_val=4096, vec_dim=8)
    hung = False
    try:
        comp = Completer(st, model=model, max_new_tokens=32,
                         flush_tokens=chunk, template="none")
        comp.attach()
        log("completer e2e ...")
        e2e = []
        probe_err: list[Exception] = []

        def _probe():
            try:
                for i in range(3):
                    key = f"q/{i}"
                    t0 = time.perf_counter()
                    st.set(key, "Say something interesting about TPUs.")
                    st.label_or(key, P.LBL_INFER_REQ)
                    st.bump(key)
                    comp.run_once()
                    e2e.append((time.perf_counter() - t0) * 1000)
                    log(f"completer e2e request {i}: {e2e[-1]:.0f} ms")
            except Exception as exc:       # surfaced on the main thread
                probe_err.append(exc)

        # bounded: the round-3 on-chip hang lived HERE (run_once blocked
        # in a device sync).  A daemon thread + join(timeout) turns a
        # repeat into a failed phase instead of a burned claim window —
        # this is the LAST series phase, so aborting loses nothing else.
        th = threading.Thread(target=_probe, daemon=True)
        th.start()
        th.join(timeout=float(os.environ.get("DECODE_E2E_TIMEOUT",
                                             "300")))
        if th.is_alive():
            import faulthandler
            hung = True                  # finally: must NOT unmap the
            faulthandler.dump_traceback(file=sys.stderr)  # stuck stack
            raise RuntimeError(
                "completer e2e hung past DECODE_E2E_TIMEOUT (round-3 "
                "on-chip mode); aborting the phase — all thread "
                "stacks incl. the stuck one dumped above")
        if probe_err:
            raise probe_err[0]
        e2e_ms = float(np.median(e2e))

        # the block-paged continuous lane: batch_cap at the new 32
        # default, pool capped at 8 windows of pages (the old dense
        # batch=8 cache HBM) — batch width rides live tokens
        comp2 = Completer(st, model=model, max_new_tokens=32,
                          flush_tokens=chunk, template="none",
                          batch_cap=32,
                          pool_pages=8 * (-(-cfg.max_len // 128)))
        comp2.attach()
        comp2.warmup_paged()          # compile outside the timed window
        runner = threading.Thread(
            target=comp2.run_continuous,
            kwargs=dict(idle_timeout_ms=20, stop_after=600.0),
            daemon=True)
        runner.start()
        time.sleep(0.2)
        t0 = time.perf_counter()
        keys = []
        for i in range(12):
            key = f"c/{i}"
            keys.append(key)
            st.set(key, f"Question number {i} about accelerators?")
            st.label_or(key, P.LBL_INFER_REQ)
            st.bump(key)
            if i % 4 == 3:
                time.sleep(0.1)
        deadline = time.perf_counter() + 420
        while time.perf_counter() < deadline:
            if all(st.labels(k) & P.LBL_READY for k in keys):
                break
            time.sleep(0.01)
        cont_s = time.perf_counter() - t0
        comp2.stop()
        runner.join(timeout=5)
        done = sum(1 for k in keys if st.labels(k) & P.LBL_READY)
        cont_tps = comp2.stats.tokens / cont_s if done else 0.0
        log(f"continuous: {done}/12 ready in {cont_s:.2f}s, "
            f"{cont_tps:,.1f} aggregate tok/s")
    finally:
        if hung:
            # the stuck thread still holds pointers into the mapping;
            # closing would unmap under it (use-after-close segfault
            # before the failed phase_status could be recorded).  Only
            # remove the NAME — the mapping lives until process exit.
            Store.unlink(name)
        else:
            st.close()
            Store.unlink(name)

    return ctx.record({
        "metric": "completer_e2e_ms",
        "value": round(e2e_ms, 0), "unit": "ms", "vs_baseline": 0.0,
        "detail": {
            "backend": ctx.backend, "geometry": geometry,
            "quantized": quant,
            "completer_e2e_ms_32tok": round(e2e_ms, 0),
            "continuous_12req_s": round(cont_s, 2),
            "continuous_aggregate_tok_s": round(cont_tps, 1),
            "continuous_ready": done,
        }})


# ---------------------------------------------------------------------------
# the series driver
# ---------------------------------------------------------------------------

def phase_store_ops(ctx: SeriesCtx) -> dict:
    """Raw store throughput + cycles-per-op vs the reference's own
    published numbers: MRSW and 32-writer MRMW ops/s
    from the native stress harnesses (spt_stress/spt_chi_sao --json)
    and the clean single-thread write CPO, ledgered alongside the
    reference contract (/root/reference/README.md:130-133: 3.2M MRSW,
    15.6M MRMW ops/s, CPO~937; splinter.h:553-555).  Host-only — no
    device is touched.  Env: STORE_OPS_MS (duration per tool, default
    3000), STORE_OPS_WRITERS (default 32)."""
    import subprocess

    dur = os.environ.get("STORE_OPS_MS", "3000")
    writers = os.environ.get("STORE_OPS_WRITERS", "32")
    build = os.path.join(REPO, "native", "build")
    # build/refresh the harnesses (make is a fast no-op when current) —
    # native/build is gitignored, so a fresh host has no binaries and a
    # stale pre---json binary would silently ignore the flag
    mk = subprocess.run(["make", "tests"],
                        cwd=os.path.join(REPO, "native"),
                        capture_output=True, text=True, timeout=120)
    if mk.returncode != 0:
        raise RuntimeError(f"make tests failed: {mk.stderr[-400:]}")

    tool_timeout = max(120.0, int(dur) / 1000.0 + 60.0)

    def run_tool(args):
        out = subprocess.run(args, capture_output=True, text=True,
                             timeout=tool_timeout, cwd=REPO)
        if out.returncode != 0:
            raise RuntimeError(
                f"{args[0]} rc={out.returncode}: {out.stderr[-400:]}")
        lines = [ln for ln in out.stdout.splitlines()
                 if ln.startswith("{")]
        if not lines:
            raise RuntimeError(
                f"{args[0]} emitted no JSON line — stale binary "
                f"without --json support? (rebuild: make -C native "
                f"tests)")
        return json.loads(lines[-1])

    mrsw_raw = run_tool([os.path.join(build, "spt_stress"),
                         "--duration-ms", dur, "--raw", "--json"])
    mrsw = run_tool([os.path.join(build, "spt_stress"),
                     "--duration-ms", dur, "--json"])
    mrmw = run_tool([os.path.join(build, "spt_chi_sao"),
                     "--writers", writers, "--duration-ms", dur,
                     "--json"])
    if mrsw_raw["corrupt"] or mrsw["corrupt"] or mrmw["corrupt"]:
        raise RuntimeError("integrity failure under stress")
    ncpu = os.cpu_count() or 1
    ref = {"mrsw_ops_per_sec": 3.2e6, "mrmw_ops_per_sec": 15.6e6,
           "write_cpo": 937.0}
    return ctx.record({
        "metric": "store_ops_per_sec",
        "value": round(mrsw_raw["ops_per_sec"], 0),
        "unit": "ops/s (raw MRSW, 1w+7r)",
        "vs_baseline": round(mrsw_raw["ops_per_sec"]
                             / ref["mrsw_ops_per_sec"], 3),
        "detail": {
            "backend": "host",
            "host_cores": ncpu,
            "mrsw_raw": mrsw_raw,
            "mrsw_structured": mrsw,
            "mrmw": mrmw,
            "write_cpo": mrsw_raw["write_cpo"],
            "cpo_vs_reference": round(
                mrsw_raw["write_cpo"] / ref["write_cpo"], 3),
            "mrmw_vs_reference": round(
                mrmw["ops_per_sec"] / ref["mrmw_ops_per_sec"], 3),
            "reference": ref,
            "note": ("reference numbers were published from a "
                     "many-core box; this host has "
                     f"{ncpu} core(s) — CPO is the core-count-"
                     "independent comparison"),
        },
    })


PHASE_FNS = {
    "embed": phase_embed,
    "embed_sweep": phase_embed_sweep,
    "profile": phase_profile,
    "dispatch": phase_dispatch,
    "kernels": phase_kernels,
    "search": phase_search,
    "restage": phase_restage,
    "decode": phase_decode,
    "decode_quant": phase_decode_quant,
    "multichip": phase_multichip,
    "loadgen": phase_loadgen,
    "prefix": phase_prefix,
    "disagg": phase_disagg,
    "tier": phase_tier,
    "decode_daemon": phase_decode_daemon,
    "store_ops": phase_store_ops,
}


def run_series(phases: tuple[str, ...] | None = None,
               deadline_epoch: float | None = None) -> SeriesCtx:
    """Initialise the backend once (raising if a run meant for the
    chip finds none), then run every requested phase with per-phase
    fencing.  Returns the ctx (ctx.headline = embed record;
    ctx.phase_status says which phases failed — main() turns any
    failure into a non-zero exit)."""
    import faulthandler

    # a hung phase must leave a stack before any external kill (skipped
    # when stderr has no fileno, e.g. under pytest capture)
    try:
        faulthandler.dump_traceback_later(600, repeat=True,
                                          file=sys.stderr)
    except (ValueError, OSError, io.UnsupportedOperation):
        pass

    if phases is None:
        env = os.environ.get("BENCH_PHASES", "")
        phases = tuple(p.strip() for p in env.split(",") if p.strip())
        if not phases:
            # CPU mode is the quick-tracking path: embed only, so
            # `BENCH_CPU=1 python bench.py` stays fast.  A chip run
            # measures the full series by default.
            phases = ("embed",) if cpu_quick_track() else ALL_PHASES
    bad = set(phases) - set(ALL_PHASES)
    if bad:
        raise SystemExit(f"unknown phases: {sorted(bad)}")

    if cpu_quick_track():
        from libsplinter_tpu.utils.jaxplatform import force_cpu
        force_cpu()
    from libsplinter_tpu.utils.jaxplatform import enable_compile_cache
    enable_compile_cache()

    ctx = SeriesCtx(deadline_epoch)

    _stage("client-init")           # first device access opens the chip
    import jax

    ctx.n_devices = len(jax.devices())
    ctx.backend = jax.default_backend()
    require_chip(ctx.backend)
    _stage("client-init-done")
    log(f"[series] backend={ctx.backend} devices={ctx.n_devices} "
        f"window={ctx.remaining():.0f}s phases={','.join(phases)}")

    for name in phases:
        left = ctx.remaining()
        # embed (the headline) always runs once the claim landed; the
        # rest must fit the remaining window
        if name != "embed" and left < PHASE_MIN_S[name]:
            log(f"[series] SKIP {name}: {left:.0f}s left "
                f"< {PHASE_MIN_S[name]}s floor")
            ctx.phase_status[name] = "skipped"
            continue
        _stage(f"phase-{name}")
        t0 = time.perf_counter()
        try:
            PHASE_FNS[name](ctx)
            ctx.phase_status[name] = "ok"
            log(f"[series] phase {name} done in "
                f"{time.perf_counter() - t0:.1f}s")
            _stage(f"phase-{name}-done")
        except Exception:
            ctx.phase_status[name] = "failed"
            log(f"[series] phase {name} FAILED after "
                f"{time.perf_counter() - t0:.1f}s:\n"
                f"{traceback.format_exc()}")
            _stage(f"phase-{name}-failed")
    _stage("series-done")
    faulthandler.cancel_dump_traceback_later()
    return ctx


def _exit_code(ctx: SeriesCtx) -> int:
    """0 only when something was measured and no phase failed."""
    failed = [p for p, s in ctx.phase_status.items() if s == "failed"]
    if failed:
        log(f"[series] FAILED phases: {','.join(failed)}")
    return 1 if failed or not ctx.records else 0


def main() -> int:
    ctx = run_series()
    if ctx.headline is not None:
        out = {k: v for k, v in ctx.headline.items() if k != "ts"}
        # Complete means ALL_PHASES ran ok — a phase-restricted run
        # must not masquerade as the full evidence set.
        out["series_complete"] = all(
            ctx.phase_status.get(p) == "ok" for p in ALL_PHASES)
        out["phase_status"] = ctx.phase_status
        print(json.dumps(out), flush=True)
    return _exit_code(ctx)


def shim_main(*phases: str) -> int:
    """Entry point for the thin standalone wrappers (bench_profile.py,
    bench_decode.py, bench_search.py): run the named phases and print
    the FIRST record — the wrapper's primary metric — as the script's
    ONE stdout JSON line (later phases still ledger their records)."""
    ctx = run_series(phases=phases)
    if ctx.records:
        print(json.dumps({k: v for k, v in ctx.records[0].items()
                          if k != "ts"}), flush=True)
    return _exit_code(ctx)


if __name__ == "__main__":
    raise SystemExit(main())
