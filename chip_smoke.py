#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that splinter-tpu still starts,
compiles and answers on the chip: store -> embed -> search -> complete,
through the daemons' own `main()` entry points, at real size.

    python chip_smoke.py              # one TPU chip; fails anywhere else
    python chip_smoke.py --multichip  # four chips: sharded search + --tp 4
    python chip_smoke.py --rehearse   # CPU control-flow rehearsal, tiny

One process per chip.  This parent NEVER imports jax (asserted at the
end): every phase that needs the device runs in a child that exits
before the next one starts, and the device line is taken from the
children.  Daemon children are this same file re-entered with
`--child daemon LANE -- ARGV`, which reports `jax.devices()` and then
calls `libsplinter_tpu.engine.LANE.main(ARGV)` — exactly what
`python -m libsplinter_tpu.engine.LANE ARGV` runs.

Each phase prints one JSON line when it finishes; the LAST line of
stdout is `{"ok": ..., "device": {"platform", "kind", "count"}}`.
Anything but a passing run on platform "tpu" exits non-zero with
"ok": false.  `--rehearse` is the one exception: tiny model
geometries, the daemons' CPU dispatch, the true platform printed — it
can never print "platform": "tpu".

What "right" means here (the bars, all stated once):
  embed     every key gets a vector; a sample of the daemon's bf16
            vectors vs a plain f32 jnp forward of the same parameters
            with the Pallas path off: min cosine >= EMBED_MIN_COS
  search    every result vs an exact NumPy cosine scan of st.vectors:
            same keys (a swapped key must tie the scan's k-th score
            within SEARCH_TOL), scores within SEARCH_TOL; the fused
            Pallas program ran; no degraded retry
  complete  the ragged paged-attention kernel vs the repo's jnp
            reference on bf16/int8/int4 pools of 1,280 pages: relative
            error <= KERNEL_REL_TOL; then greedy tokens of the paged
            continuous lane, judged TEACHER-FORCED by the dense
            static-cache lane on the same weights: first token equal,
            agreement >= AGREE_BAR[kv dtype] (NOT byte-exact: two
            differently shaped programs do not give bit-equal logits,
            and free-running decodes of random weights are chaotic —
            see child_decode_judge)
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, "chiprun_out", "chip_smoke")

EMBED_MIN_COS = 0.99
SEARCH_TOL = 5e-3
KERNEL_REL_TOL = 2e-2
# teacher-forced agreement with the dense lane — a coarse net under
# the kernel check above.  Measured on a v5e over 256 tokens each
# (PR 21): bf16 0.980, int8 0.965, int4 0.641; the bars sit 0.1-0.2
# below that
AGREE_BAR = {"bf16": 0.9, "int8": 0.85, "int4": 0.45}

# the store's north star (BASELINE.json): a 262,144 x 768 f32 vector
# lane (0.8 GB, all device-resident for search) under the full-width
# Nomic-geometry encoder and the default decoder geometry
REAL = dict(nslots=262_144, dim=768, max_val=4096, reserve=4096,
            n_texts=8192, live_tail=64, sample=64,
            # (count, words lo, words hi): multiples of the daemon's
            # batch cap, so the cold drain compiles one (256, bucket)
            # program per bucket; the longest class lands in the
            # 512 bucket, where the flash kernel runs
            text_classes=((6144, 35, 60), (1536, 80, 110),
                          (512, 300, 420)),
            n_batch_q=32, n_single_q=8, k=10,
            n_completions=8, new_tokens=32, n_ctx=2048, page=128,
            quant_pool_pages=1280, prompt_bytes=(70, 110))
TINY = dict(nslots=4096, dim=64, max_val=4096, reserve=512,
            n_texts=96, live_tail=8, sample=16,
            text_classes=((64, 8, 13), (32, 20, 28)),
            n_batch_q=12, n_single_q=2, k=10,
            n_completions=3, new_tokens=8, n_ctx=128, page=16,
            quant_pool_pages=1280, prompt_bytes=(20, 40))


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


class SmokeFailure(Exception):
    pass


def need(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------- children

def _shrink_for_rehearsal() -> None:
    """--rehearse only: the daemons' mains build EncoderConfig() /
    DecoderConfig() with no size arguments, so the CPU rehearsal
    rebinds the two names to their tiny() geometries in this child.
    Nothing of this runs without --rehearse."""
    import functools

    import jax.numpy as jnp

    import libsplinter_tpu.models as M
    enc, dec = M.EncoderConfig, M.DecoderConfig
    M.EncoderConfig = functools.partial(
        enc, vocab_size=30528, hidden=64, layers=2, heads=4,
        mlp_dim=128)
    # f32: a 64-wide random decoder in bf16 flips its argmax on
    # summation order alone, which would rehearse noise, not control
    # flow
    M.DecoderConfig = functools.partial(
        dec, vocab_size=1024, hidden=64, layers=2, heads=4,
        kv_heads=4, mlp_dim=128, dtype=jnp.float32)


def _device_record() -> dict:
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def _child_setup(rehearse: bool) -> dict:
    sys.path.insert(0, REPO)
    if rehearse:
        _shrink_for_rehearsal()
    dev = _device_record()
    if rehearse and dev["platform"] == "tpu":
        raise SystemExit("--rehearse is the CPU rehearsal; run it "
                         "with JAX_PLATFORMS=cpu")
    return dev


def child_probe(args) -> int:
    emit(_child_setup(args.rehearse))
    return 0


def child_hold(args) -> int:
    """Hold the chip (for --second-process): touch the device, say
    so, sleep until killed."""
    dev = _child_setup(args.rehearse)
    import jax.numpy as jnp
    jnp.zeros(8).block_until_ready()
    emit({"holding": dev})
    time.sleep(600)
    return 0


def child_daemon(args) -> int:
    """`--child daemon LANE --report F -- ARGV`: the lane's real
    main(ARGV) in this process, after writing the device record."""
    dev = _child_setup(args.rehearse)
    with open(args.report, "w") as f:
        json.dump(dev, f)
    import importlib
    mod = importlib.import_module(f"libsplinter_tpu.engine.{args.lane}")
    if args.lane == "completer":
        # The byte tokenizer renders ids outside [3, 259) as b"" — with
        # seeded-random weights over a 32,000-row vocabulary >99% of
        # the generated tokens would leave no trace in the slot value.
        # The smoke's completer children render every id as decimal
        # text instead, so the parent can compare TOKENS through the
        # label protocol; nothing else differs from `python -m`.
        from libsplinter_tpu.models import ByteTokenizer
        ByteTokenizer.token_to_piece = \
            lambda self, tok: b"%d " % int(tok)
    return int(mod.main(args.rest) or 0)


def child_embed_ref(args) -> int:
    """The plain reference: f32 activations, Pallas path off
    (flash_min_seq=0), same seeded parameters the daemon built —
    compared with the vectors the daemon committed for `--keys`."""
    dev = _child_setup(args.rehearse)
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from libsplinter_tpu import Store
    from libsplinter_tpu.models import (EmbeddingModel, EncoderConfig,
                                        default_tokenizer)
    st = Store.open(args.store)
    keys = json.load(open(args.keys))
    cfg = EncoderConfig(out_dim=st.vec_dim, max_len=args.n_ctx)
    served = EmbeddingModel(cfg)          # the daemon's construction
    ref = EmbeddingModel(
        dataclasses.replace(cfg, dtype=jnp.float32, flash_min_seq=0),
        params=served.params)
    tok = default_tokenizer(cfg.vocab_size)
    texts = [st.get(k).rstrip(b"\0").decode() for k in keys]
    lens = np.asarray([len(tok.encode(t, max_len=cfg.max_len))
                       for t in texts])
    buckets = ref.buckets_for(lens)
    t0 = time.perf_counter()
    cos = np.zeros(len(keys))
    for b in sorted(set(buckets.tolist())):
        rows = np.nonzero(buckets == b)[0]
        ids, ln = tok.encode_batch([texts[i] for i in rows], int(b))
        want = ref.encode_ids_async(ids, ln).materialize()
        for j, i in enumerate(rows):
            got = st.vec_get(keys[i])
            cos[i] = float(np.dot(got, want[j]) / max(
                np.linalg.norm(got) * np.linalg.norm(want[j]), 1e-12))
    emit({"min_cos": float(cos.min()), "mean_cos": float(cos.mean()),
          "n": len(keys), "buckets": sorted(set(buckets.tolist())),
          "max_tokens": int(lens.max()),
          "seconds": round(time.perf_counter() - t0, 2),
          "device": dev})
    return 0


class NumpyScan:
    """Exact cosine scan of a copy of st.vectors: the independent
    reference for every search comparison.  Candidates are the rows a
    search may return — a live key that is not a system ("__") row,
    with a non-zero vector."""

    def __init__(self, st):
        import numpy as np
        self.vecs = np.array(st.vectors)
        self.norms = np.linalg.norm(self.vecs, axis=1)
        self.live = np.asarray(
            [not (st.key_at(i) or "__").startswith("__")
             for i in range(st.nslots)])
        self.ok = self.live & (self.norms > 0)

    def check(self, got_rows, got_scores, q, k, tol=SEARCH_TOL):
        """(ok, max score err) for one top-k result: same rows (a
        swapped row must tie the scan's k-th score within tol),
        scores within tol."""
        import numpy as np
        s = (self.vecs @ q) / np.maximum(
            self.norms * np.linalg.norm(q), 1e-12)
        s = np.where(self.ok, s, -np.inf)
        ref_rows = np.argsort(-s)[:k]
        if len(got_rows) != len(ref_rows):
            return False, float("inf")
        err = float(np.max(np.abs(np.asarray(got_scores)
                                  - s[np.asarray(got_rows)])))
        kth = s[ref_rows[-1]]
        swapped = set(map(int, got_rows)) - set(map(int, ref_rows))
        ties_ok = all(s[r] >= kth - tol for r in swapped)
        return bool(err <= tol and ties_ok), err


def child_pod_search(args) -> int:
    """--multichip: PodSearch over every device of the host against
    the one-device top-k and the NumPy scan, on the parent's lane."""
    dev = _child_setup(args.rehearse)
    import jax
    import numpy as np

    from libsplinter_tpu import Store
    from libsplinter_tpu.ops.similarity import cosine_topk
    from libsplinter_tpu.parallel import PodSearch
    from libsplinter_tpu.parallel.mesh import make_mesh

    st = Store.open(args.store)
    queries = np.load(args.queries)
    k = args.k
    mesh = make_mesh()
    n_dev = mesh.shape["dp"]
    ps = PodSearch(st, mesh)
    t0 = time.perf_counter()
    arr = jax.block_until_ready(ps.refresh())
    stage_s = time.perf_counter() - t0
    shards = arr.addressable_shards
    per_dev = sorted(s.data.nbytes for s in shards)
    placement = {
        "devices": len({s.device.id for s in shards}),
        "shard_bytes": per_dev, "total_bytes": int(arr.nbytes)}
    ok_place = (placement["devices"] == n_dev == dev["count"]
                and all(abs(b - arr.nbytes / n_dev)
                        <= 0.01 * arr.nbytes for b in per_dev))

    scan = NumpyScan(st)
    mask = scan.live.astype(np.float32)
    one = jax.device_put(scan.vecs, jax.devices()[0])
    mask_one = jax.device_put(mask, jax.devices()[0])
    ok_all, worst, secs = True, 0.0, []
    for qi, q in enumerate(queries):
        t0 = time.perf_counter()
        hits = ps.search(q, k, mask=mask, refresh=False)
        secs.append(time.perf_counter() - t0)
        ok_p, err_p = scan.check([h["slot"] for h in hits],
                                 [h["similarity"] for h in hits], q, k)
        need(all(h["key"] == st.key_at(h["slot"]) for h in hits),
             f"query {qi}: PodSearch resolved a wrong key")
        s1, i1 = cosine_topk(one, q, k, mask_one)
        ok_1, err_1 = scan.check(i1, s1, q, k)
        ok_all &= ok_p and ok_1
        worst = max(worst, err_p, err_1)
    hlo = None
    if dev["platform"] == "tpu":
        from libsplinter_tpu.parallel.sharded_search import \
            _topk_program
        txt = _topk_program(
            mesh, "dp", ps.tile, st.vec_dim, 1, k, k, True
        ).lower(arr, queries[:1], mask).compile().as_text()
        # the compiler may turn so small an all-gather into an
        # all-reduce over the same devices
        hlo = {"collective": "all-gather" in txt
               or "all-reduce" in txt,
               "tpu_custom_call": "tpu_custom_call" in txt}
        ok_all &= all(hlo.values())
    emit({"ok": bool(ok_all and ok_place), "queries": len(queries),
          "k": k, "max_score_err": worst, "placement": placement,
          "stage_seconds": round(stage_s, 2), "hlo": hlo,
          "first_search_seconds": round(secs[0], 3),
          "median_search_seconds": round(float(np.median(secs[1:]
                                                         or secs)), 4),
          "device": dev})
    return 0


def child_paged_ref(args) -> int:
    """The ragged paged-attention kernel against the repo's own jnp
    reference (ops.paged_attention._paged_ref over dequantize_pool) on
    THIS device, for bf16, int8 and int4-packed pools of --pool-pages
    pages at the decoder's widths — block ids past 1,024 and scales
    that differ 10x between pages, so a wrong scale or page lookup
    cannot hide.  On the chip the kernel is what the default dispatch
    runs; the rehearsal interprets it."""
    dev = _child_setup(args.rehearse)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from libsplinter_tpu.ops.paged_attention import (
        _paged_ref, dequantize_pool, pack_int4, paged_attention)
    B, PP, KH, D, page, nb = 8, 16, 12, 64, args.page, args.pool_pages
    rng = np.random.default_rng(args.seed)
    q = jnp.asarray(rng.standard_normal((B, KH, D)), jnp.bfloat16)
    tables = rng.permutation(np.arange(1, nb))[:B * PP] \
        .reshape(B, PP).astype(np.int32)
    tables[0, 0] = nb - 1
    lengths = rng.integers(1, PP * page + 1, B).astype(np.int32)
    lengths[0] = PP * page
    shape = (nb, KH, page, D)
    out = {}
    for kind in ("bf16", "int8", "int4"):
        ks = vs = None
        if kind == "bf16":
            kp, vp = (jnp.asarray(rng.standard_normal(shape),
                                  jnp.bfloat16) for _ in "kv")
        else:
            qmax = 127 if kind == "int8" else 7
            kp, vp = (jnp.asarray(rng.integers(-qmax, qmax + 1, shape),
                                  jnp.int8) for _ in "kv")
            if kind == "int4":
                kp, vp = pack_int4(kp), pack_int4(vp)
            ks, vs = (jnp.asarray(rng.uniform(0.02, 0.2, (nb, KH))
                                  / qmax, jnp.float32) for _ in "kv")
        t0 = time.perf_counter()
        got = np.asarray(paged_attention(
            q, kp, vp, tables, lengths, k_scales=ks, v_scales=vs,
            interpret=args.rehearse).astype(jnp.float32))
        secs = time.perf_counter() - t0
        with jax.default_matmul_precision("float32"):
            want = np.asarray(_paged_ref(
                q.astype(jnp.float32),
                kp.astype(jnp.float32) if ks is None
                else dequantize_pool(kp, ks),
                vp.astype(jnp.float32) if vs is None
                else dequantize_pool(vp, vs), tables, lengths))
        out[kind] = {
            "rel_err": float(np.abs(got - want).max()
                             / np.abs(want).max()),
            "first_call_seconds": round(secs, 2)}
    emit({"kernels": out, "pool_pages": nb, "page": page,
          "batch": B, "heads": KH, "head_dim": D, "device": dev})
    return 0


def child_decode_judge(args) -> int:
    """The dense static-cache lane as the judge of every paged lane's
    greedy tokens, TEACHER-FORCED: prefill the prompt, then feed the
    lane's own tokens one by one through decode_one, and at each step
    ask whether the lane's next token is the dense lane's argmax.

    Free-running greedy decodes of a seeded-random decoder are chaotic
    — its logits are nearly flat, so one bf16 rounding flips an argmax
    and every later token differs; two runs of the SAME paged lane
    agree with each other position-wise anywhere from 0.2 to 0.7
    depending on which requests shared a batch.  Teacher forcing
    removes the compounding: each step is judged on the same prefix."""
    dev = _child_setup(args.rehearse)
    import numpy as np

    from libsplinter_tpu.models import (ByteTokenizer, CompletionModel,
                                        DecoderConfig)
    job = json.load(open(args.keys))
    model = CompletionModel(DecoderConfig(max_len=args.n_ctx),
                            temp=0.0)      # as completer.main builds it
    tok = ByteTokenizer()
    t0 = time.perf_counter()
    out = {}
    for lane, rows in job["lanes"].items():
        hits, first, gaps = [], True, []
        for prompt, toks in zip(job["prompts"], rows):
            logits = model.prefill(np.asarray(tok.encode(prompt),
                                              np.int32))
            for i, t in enumerate(toks):
                hit = int(np.argmax(logits)) == t
                hits.append(hit)
                first &= hit or i > 0
                gaps.append(float((logits.max() - logits[t])
                                  / max(logits.std(), 1e-9)))
                if i + 1 < len(toks):
                    logits = model.decode_one(t)
            model.reset()
        out[lane] = {"first_token_equal": bool(first),
                     "agreement": round(float(np.mean(hits)), 4),
                     "worst_gap_sigma": round(max(gaps), 4),
                     "tokens": len(hits)}
    emit({"lanes": out, "seconds": round(time.perf_counter() - t0, 2),
          "device": dev})
    return 0


CHILDREN = {"probe": child_probe, "hold": child_hold,
            "daemon": child_daemon, "embed-ref": child_embed_ref,
            "paged-ref": child_paged_ref,
            "decode-judge": child_decode_judge,
            "pod-search": child_pod_search}


# ---------------------------------------------------------------- parent

class Smoke:
    def __init__(self, args):
        self.args = args
        self.cfg = dict(TINY if args.rehearse else REAL)
        self.env = dict(os.environ, PYTHONPATH=REPO)
        if args.rehearse:
            self.env["JAX_PLATFORMS"] = "cpu"
            if args.multichip:
                self.env["XLA_FLAGS"] = (
                    self.env.get("XLA_FLAGS", "")
                    + " --xla_force_host_platform_device_count=4")
        self.store_name = f"/spt-smoke-{os.getpid()}"
        self.st = None
        self.device = None
        self.procs: list[subprocess.Popen] = []
        os.makedirs(WORK, exist_ok=True)

    # -- children ----------------------------------------------------------

    def _argv(self, kind: str, *extra: str) -> list[str]:
        argv = [sys.executable, os.path.abspath(__file__),
                "--child", kind]
        if self.args.rehearse:
            argv.append("--rehearse")
        return argv + list(extra)

    def run_child(self, kind: str, *extra: str,
                  timeout: float = 900) -> dict:
        """Run a compute child to its end; its last stdout line is
        its JSON result."""
        with open(os.path.join(WORK, f"{kind}.err"), "w") as log:
            p = subprocess.run(self._argv(kind, *extra), env=self.env,
                               stdout=subprocess.PIPE, stderr=log,
                               timeout=timeout, text=True)
        lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
        need(p.returncode == 0 and lines,
             f"child {kind} failed rc={p.returncode} "
             f"(see chiprun_out/chip_smoke/{kind}.err)")
        return json.loads(lines[-1])

    def start_daemon(self, lane: str, tag: str, argv: list[str]):
        report = os.path.join(WORK, f"{tag}.device.json")
        if os.path.exists(report):
            os.unlink(report)
        with open(os.path.join(WORK, f"{tag}.log"), "w") as log:
            p = subprocess.Popen(
                self._argv("daemon", "--lane", lane, "--report",
                           report, "--", "--store", self.store_name,
                           *argv),
                env=self.env, stdout=log, stderr=subprocess.STDOUT)
        self.procs.append(p)
        p.tag, p.report = tag, report
        return p

    def stop_daemon(self, p) -> None:
        if p.poll() is None:
            p.send_signal(signal.SIGINT)     # main() returns 0
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        if p in self.procs:
            self.procs.remove(p)

    def daemon_device(self, p, timeout: float = 300) -> dict:
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            if os.path.exists(p.report):
                try:
                    return self.note_device(json.load(open(p.report)))
                except ValueError:
                    pass                    # mid-write
            need(p.poll() is None,
                 f"{p.tag} exited rc={p.returncode} before reaching "
                 f"the device (chiprun_out/chip_smoke/{p.tag}.log)")
            time.sleep(0.2)
        raise SmokeFailure(f"{p.tag}: no device within {timeout}s")

    def note_device(self, dev: dict) -> dict:
        if self.device is None:
            self.device = dev
        need(dev == self.device,
             f"children disagree on the device: {dev} vs {self.device}")
        return dev

    def wait_for(self, what: str, pred, p=None, timeout: float = 600,
                 every: float = 0.25):
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            got = pred()
            if got:
                return got
            if p is not None:
                need(p.poll() is None,
                     f"{p.tag} exited rc={p.returncode} while waiting "
                     f"for {what}")
            time.sleep(every)
        raise SmokeFailure(f"timed out after {timeout}s waiting for "
                           f"{what}")

    def heartbeat(self, key: str) -> dict | None:
        try:
            return json.loads(self.st.get(key).rstrip(b"\0"))
        except (KeyError, OSError, ValueError):
            return None

    def wait_heartbeat(self, key: str, p, field: str | None = None,
                       at_least: int = 0, timeout: float = 600) -> dict:
        """The lane's heartbeat, once it exists and (given `field`)
        counts at least `at_least` there — daemons publish on a
        cadence, so the counters trail the work by a few seconds."""
        def ready():
            hb = self.heartbeat(key)
            if hb and (field is None or hb.get(field, 0) >= at_least):
                return hb
        return self.wait_for(f"{p.tag}'s heartbeat ({field or 'first'})",
                             ready, p, timeout=timeout)

    def compile_seconds(self, lane: str, since: float):
        from libsplinter_tpu.obs.devtime import collect_compile_events
        ev = [e for e in collect_compile_events(self.st)
              if e.get("lane") == lane and e.get("ts", 0) >= since]
        return (round(sum(e["duration_ms"] for e in ev) / 1e3, 2),
                [f'{e["program"]}{e["shapes_key"]}' for e in ev])

    # -- phases ------------------------------------------------------------

    def phase_build(self) -> dict:
        c = self.cfg
        t0 = time.perf_counter()
        # a prebuilt library on disk is not trusted: rebuild from the
        # committed sources.  (The rehearsal only brings it up to
        # date — it runs inside the test suite, where other workers
        # are loading the same file.)
        targets = ["all"] if self.args.rehearse else ["clean", "all"]
        subprocess.run(["make", "-C", os.path.join(REPO, "native"),
                        *targets], check=True,
                       stdout=subprocess.DEVNULL)
        make_s = time.perf_counter() - t0
        from libsplinter_tpu import Store
        t0 = time.perf_counter()
        self.st = Store.create(self.store_name, nslots=c["nslots"],
                               max_val=c["max_val"], vec_dim=c["dim"],
                               overwrite=True)
        need(self.st.vectors.shape == (c["nslots"], c["dim"]),
             "vector lane has the wrong shape")
        return {"make_seconds": round(make_s, 2),
                "create_seconds": round(time.perf_counter() - t0, 2),
                "nslots": c["nslots"], "dim": c["dim"],
                "lane_bytes": c["nslots"] * c["dim"] * 4}

    def _texts(self, rng) -> list[str]:
        words = ["".join(chr(97 + int(x)) for x in
                         rng.integers(0, 26, rng.integers(3, 8)))
                 for _ in range(4096)]
        out = []
        for count, lo, hi in self.cfg["text_classes"]:
            for _ in range(count):
                n = int(rng.integers(lo, hi + 1))
                out.append(" ".join(
                    words[int(i)] for i in rng.integers(0, 4096, n)))
        # shuffled, except that the live tail (the end of the list)
        # stays in the first, shortest class: one more small shape to
        # compile, not one per class
        tail = self.cfg["live_tail"]
        order = rng.permutation(len(out) - tail) + tail
        return [out[int(i)] for i in order] + out[:tail]

    def _request_embed(self, key: str, text: str) -> None:
        from libsplinter_tpu.engine import protocol as P
        st = self.st
        st.set(key, text)                          # set
        st.label_or(key, P.LBL_EMBED_REQ | P.LBL_WAITING)  # label 0x1
        st.bump(key)                               # bump

    def phase_embed(self) -> dict:
        import numpy as np

        from libsplinter_tpu.engine import protocol as P
        c, st = self.cfg, self.st
        rng = np.random.default_rng(self.args.seed)
        texts = self._texts(rng)
        need(len(texts) == c["n_texts"], "text classes != n_texts")
        keys = [f"doc/{i:06d}" for i in range(len(texts))]
        n_cold = len(keys) - c["live_tail"]
        # the bulk is requested before the daemon starts (its attach
        # sweep drains pre-existing requests in full batches, so the
        # set of compiled shapes stays small); the tail is written
        # LIVE, through the wake path, once the bulk has drained
        for k, t in zip(keys[:n_cold], texts[:n_cold]):
            self._request_embed(k, t)
        t_start = time.time()
        t0 = time.perf_counter()
        p = self.start_daemon("embedder", "embedder", [])
        dev = self.daemon_device(p)
        self.wait_heartbeat(P.KEY_EMBED_STATS, p)
        up_s = time.perf_counter() - t0
        st.bump(keys[0])          # re-pulse: the daemon is listening now
        pending = lambda: not st.enumerate_indices(P.LBL_EMBED_REQ)
        self.wait_for("the cold drain", pending, p, every=0.5)
        cold_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        for k, t in zip(keys[n_cold:], texts[n_cold:]):
            self._request_embed(k, t)
        self.wait_for("the live tail", pending, p)
        live_s = time.perf_counter() - t1
        hb = self.wait_heartbeat(P.KEY_EMBED_STATS, p, "embedded",
                                 len(keys), timeout=60)
        compile_s, shapes = self.compile_seconds("embedder", t_start)
        self.stop_daemon(p)

        rows = np.asarray([st.find_index(k) for k in keys])
        missing = int((np.abs(st.vectors[rows]).max(axis=1) == 0).sum())
        need(missing == 0, f"{missing} keys got no vector")
        need(not any(st.labels(k) & (P.LBL_EMBED_REQ | P.LBL_WAITING
                                     | P.LBL_CTX_EXCEEDED)
                     for k in keys), "a request label did not clear")
        faults = {k: hb.get(k, 0) for k in (
            "batch_faults", "embed_failed", "drain_faults",
            "ctx_exceeded")}
        faults["ring_faults"] = hb.get("dispatch", {}).get(
            "ring_faults", 0)
        need(not any(faults.values()), f"embedder faults: {faults}")

        # the sample: every long text's class is represented
        by_len = np.argsort([len(t) for t in texts])
        pick = np.unique(np.concatenate([
            by_len[-c["sample"] // 4:],
            rng.choice(len(keys), c["sample"], replace=False)]))
        kf = os.path.join(WORK, "embed_sample.json")
        json.dump([keys[int(i)] for i in pick], open(kf, "w"))
        ref = self.run_child("embed-ref", "--store", self.store_name,
                             "--keys", kf, "--n-ctx", "2048")
        self.note_device(ref["device"])
        need(ref["min_cos"] >= EMBED_MIN_COS,
             f"min cosine {ref['min_cos']} < {EMBED_MIN_COS}")
        if not self.args.rehearse:
            need(max(ref["buckets"]) >= 512,
                 "no sampled text reached a bucket >= 512 (the flash "
                 f"kernel never ran): {ref['buckets']}")
        self.doc_keys = keys
        return {"texts": len(keys), "live_tail": c["live_tail"],
                "startup_seconds": round(up_s, 2),
                "cold_seconds": round(cold_s, 2),
                "live_seconds": round(live_s, 2),
                "compile_seconds": compile_s, "programs": shapes,
                "compile_count": hb.get("compile_count"),
                "ring_dispatches": hb.get("dispatch", {}).get(
                    "ring_dispatches"),
                "sample": ref["n"], "min_cos": ref["min_cos"],
                "mean_cos": ref["mean_cos"], "bar": EMBED_MIN_COS,
                "buckets_sampled": ref["buckets"],
                "max_tokens_sampled": ref["max_tokens"],
                "ref_seconds": ref["seconds"], "faults": faults,
                "device": dev}

    def _fill_vectors(self, rng, n: int) -> None:
        import numpy as np
        st = self.st
        done = 0
        while done < n:
            m = min(16384, n - done)
            v = rng.standard_normal((m, st.vec_dim), dtype=np.float32)
            v /= np.linalg.norm(v, axis=1, keepdims=True)
            for j in range(m):
                key = f"vec/{done + j:06d}"
                st.set(key, "x")
                st.vec_set(key, v[j])
            done += m

    def _queries(self, rng, n: int):
        """Half random directions, half noisy copies of stored rows
        (a clear top-1), all unit length."""
        import numpy as np
        st = self.st
        q = rng.standard_normal((n, st.vec_dim), dtype=np.float32)
        live = np.nonzero(np.abs(st.vectors).max(axis=1) > 0)[0]
        for i in range(0, n, 2):
            q[i] = st.vectors[int(rng.choice(live))] + 0.05 * q[i]
        return q / np.linalg.norm(q, axis=1, keepdims=True)

    def phase_search(self) -> dict:
        import numpy as np
        from concurrent.futures import ThreadPoolExecutor

        from libsplinter_tpu.engine import protocol as P
        from libsplinter_tpu.engine.searcher import (consume_result,
                                                     submit_search)
        c, st = self.cfg, self.st
        rng = np.random.default_rng(self.args.seed + 1)
        t0 = time.perf_counter()
        n_fill = st.nslots - c["reserve"] - len(st.list())
        self._fill_vectors(rng, n_fill)
        fill_s = time.perf_counter() - t0
        nq = c["n_batch_q"] + c["n_single_q"]
        queries = self._queries(rng, nq)
        qkeys = [f"__sq_smoke_{i}" for i in range(nq)]
        for k, q in zip(qkeys, queries):
            st.set(k, "placeholder")
            st.vec_set(k, q)
        scan = NumpyScan(st)

        t_start = time.time()
        t0 = time.perf_counter()
        p = self.start_daemon("searcher", "searcher", [])
        dev = self.daemon_device(p)
        self.wait_heartbeat(P.KEY_SEARCH_STATS, p)
        up_s = time.perf_counter() - t0

        def ask(k):
            return submit_search(st, k, c["k"], timeout_ms=600_000)

        t0 = time.perf_counter()
        with ThreadPoolExecutor(c["n_batch_q"]) as ex:
            recs = list(ex.map(ask, qkeys[:c["n_batch_q"]]))
        batch_s = time.perf_counter() - t0
        single_s = []
        for k in qkeys[c["n_batch_q"]:]:
            t0 = time.perf_counter()
            recs.append(ask(k))
            single_s.append(time.perf_counter() - t0)
        worst = 0.0
        for qi, (rec, q) in enumerate(zip(recs, queries)):
            need(isinstance(rec, dict) and "i" in rec,
                 f"query {qi}: no result ({rec!r})")
            need(rec["keys"] == [st.key_at(i) for i in rec["i"]],
                 f"query {qi}: keys do not match slots")
            ok, err = scan.check(rec["i"], rec["s"], q, c["k"])
            need(ok, f"query {qi}: differs from the NumPy scan "
                     f"(max score err {err})")
            worst = max(worst, err)
        for k in qkeys:
            consume_result(st, k)
        hb = self.wait_heartbeat(P.KEY_SEARCH_STATS, p, "served", nq,
                                 timeout=60)
        compile_s, shapes = self.compile_seconds("searcher", t_start)
        self.stop_daemon(p)
        faults = {k: hb.get(k, 0) for k in (
            "batch_faults", "retried_unfused", "retried_single",
            "req_failures", "drain_faults", "parse_errors")}
        need(not any(faults.values()), f"searcher faults: {faults}")
        need(hb["coalesced_max"] > 1, "no batch coalesced")
        # which program served: the DEVTIME ledger names every program
        # the lane compiled — the fused Pallas one must be there and
        # the jnp score-matrix one ("topk") must not
        progs = hb.get("devtime", {})
        fused = progs.get("fused_topk", {}).get("compiles", 0)
        if not self.args.rehearse:
            need(fused > 0 and "topk" not in progs,
                 "the fused Pallas top-k did not serve the queries: "
                 f"{progs}")
        return {"lane_rows": st.nslots,
                "live_keys": int(scan.live.sum()),
                "fill_seconds": round(fill_s, 2),
                "startup_seconds": round(up_s, 2),
                "queries": nq, "k": c["k"],
                "batch_seconds": round(batch_s, 3),
                "median_single_seconds": round(
                    float(np.median(single_s)), 4),
                "compile_seconds": compile_s, "programs": shapes,
                "coalesced_max": hb["coalesced_max"],
                "dispatches": hb["dispatches"],
                "fused_topk_programs": fused,
                "max_score_err": worst, "tol": SEARCH_TOL,
                "faults": faults, "device": dev}

    def _complete(self, tag: str, argv: list[str], prompts) -> dict:
        """One completer child over all prompts through the label
        trifecta; returns tokens per prompt + what the daemon said."""
        from concurrent.futures import ThreadPoolExecutor

        from libsplinter_tpu.engine import protocol as P
        from libsplinter_tpu.engine.client import submit_completion
        c, st = self.cfg, self.st
        try:
            st.unset(P.KEY_COMPLETE_STATS)
        except (KeyError, OSError):
            pass
        t_start = time.time()
        t0 = time.perf_counter()
        p = self.start_daemon(
            "completer", tag,
            ["--max-new-tokens", str(c["new_tokens"]), "--temp", "0",
             "--template", "none", "--n-ctx", str(c["n_ctx"]), *argv])
        dev = self.daemon_device(p)
        self.wait_heartbeat(P.KEY_COMPLETE_STATS, p)
        up_s = time.perf_counter() - t0

        def ask(i):
            return submit_completion(st, f"gen/{tag}/{i}", prompts[i],
                                     timeout_ms=900_000)

        t0 = time.perf_counter()
        with ThreadPoolExecutor(len(prompts)) as ex:
            outs = list(ex.map(ask, range(len(prompts))))
        serve_s = time.perf_counter() - t0
        tokens = []
        for i, (out, prompt) in enumerate(zip(outs, prompts)):
            need(isinstance(out, bytes),
                 f"{tag} request {i}: no completion ({out!r})")
            need(out.startswith(prompt.encode()),
                 f"{tag} request {i}: value lost its prompt")
            toks = [int(t) for t in out[len(prompt):].split()]
            need(0 < len(toks) <= c["new_tokens"],
                 f"{tag} request {i}: {len(toks)} tokens")
            tokens.append(toks)
        n_tok = sum(map(len, tokens))
        hb = self.wait_heartbeat(P.KEY_COMPLETE_STATS, p,
                                 "completions", len(prompts),
                                 timeout=60)
        compile_s, shapes = self.compile_seconds("completer", t_start)
        self.stop_daemon(p)
        need(hb.get("tokens") == n_tok,
             f"{tag}: daemon counted {hb.get('tokens')} tokens, the "
             f"values hold {n_tok}")
        faults = {k: hb.get(k, 0) for k in (
            "faults", "truncated", "vanished", "killed_mid_decode",
            "deadline_expired", "shed")}
        need(not any(faults.values()), f"{tag} faults: {faults}")
        return {"tokens": tokens, "hb": hb, "rec": {
            "requests": len(prompts), "tokens": n_tok,
            "startup_seconds": round(up_s, 2),
            "serve_seconds": round(serve_s, 2),
            "compile_seconds": compile_s, "programs": len(shapes),
            "faults": faults, "device": dev}}

    def _judge(self, prompts, lanes: dict) -> dict:
        """Every lane's tokens judged by the dense static-cache lane
        (child_decode_judge): first token equal, then teacher-forced
        agreement >= AGREE_BAR[lane's kv dtype]."""
        jf = os.path.join(WORK, "judge.json")
        json.dump({"prompts": prompts,
                   "lanes": {k: v for k, (v, _) in lanes.items()}},
                  open(jf, "w"))
        rec = self.run_child("decode-judge", "--keys", jf, "--n-ctx",
                             str(self.cfg["n_ctx"]))
        self.note_device(rec.pop("device"))
        for lane, (_, kv) in lanes.items():
            r = rec["lanes"][lane]
            need(r["first_token_equal"],
                 f"{lane}: a first token is not the dense lane's")
            need(r["agreement"] >= AGREE_BAR[kv],
                 f"{lane}: teacher-forced agreement {r['agreement']} "
                 f"< {AGREE_BAR[kv]}")
        return rec

    @staticmethod
    def _free_running(got, want) -> float:
        """Position-wise agreement of two free-running decodes —
        printed, never gated (see child_decode_judge)."""
        same = sum(a == b for g, w in zip(got, want)
                   for a, b in zip(g, w))
        return round(same / max(1, sum(map(len, want))), 4)

    def _prompts(self, rng) -> list[str]:
        lo, hi = self.cfg["prompt_bytes"]
        out = []
        for i in range(self.cfg["n_completions"]):
            n = int(rng.integers(lo, hi + 1))
            body = "".join(chr(97 + int(x)) if x < 26 else " "
                           for x in rng.integers(0, 30, n))
            out.append(f"q{i}: {body}")
        return out

    def phase_complete(self) -> dict:
        import numpy as np
        c = self.cfg
        prompts = self._prompts(
            np.random.default_rng(self.args.seed + 2))
        kern = self.run_child(
            "paged-ref", "--pool-pages", str(c["quant_pool_pages"]),
            "--page", str(c["page"]), "--seed", str(self.args.seed))
        self.note_device(kern.pop("device"))
        for kind, r in kern["kernels"].items():
            need(r["rel_err"] <= KERNEL_REL_TOL,
                 f"paged attention ({kind} pool) differs from the jnp "
                 f"reference: rel err {r['rel_err']}")
        out = {"kernel_vs_jnp": {**kern, "tol": KERNEL_REL_TOL}}
        lanes = {}
        for kv in ("bf16", "int8", "int4"):
            argv = ["--continuous", "--batch-cap",
                    str(len(prompts)), "--page-size", str(c["page"])]
            if kv != "bf16":
                argv += ["--kv-dtype", kv, "--pool-pages",
                         str(c["quant_pool_pages"])]
            run = self._complete(f"paged-{kv}", argv, prompts)
            pool = run["hb"].get("pages_free", 0) \
                + run["hb"].get("pages_used", 0)
            run["rec"]["pool_pages"] = pool
            out[f"paged_{kv}"] = run["rec"]
            lanes[f"paged-{kv}"] = (run["tokens"], kv)
            if kv != "bf16":
                need(run["hb"].get("kv_dtype") == kv and pool >= 1025,
                     f"paged {kv}: pool is {run['hb'].get('kv_dtype')}"
                     f" x {pool} pages, wanted {kv} x >= 1025")
        judged = self._judge(prompts, lanes)
        for lane, r in judged["lanes"].items():
            out[lane.replace("-", "_")].update(r)
        out["judge"] = {"lane": "dense static cache, teacher-forced",
                        "seconds": judged["seconds"],
                        "agree_bar": AGREE_BAR}
        return out

    # -- multichip ---------------------------------------------------------

    def phase_pod_search(self) -> dict:
        import numpy as np
        c, st = self.cfg, self.st
        rng = np.random.default_rng(self.args.seed + 1)
        t0 = time.perf_counter()
        self._fill_vectors(rng, st.nslots - c["reserve"])
        fill_s = time.perf_counter() - t0
        qf = os.path.join(WORK, "pod_queries.npy")
        np.save(qf, self._queries(rng, c["n_single_q"] + 4))
        rec = self.run_child("pod-search", "--store", self.store_name,
                             "--queries", qf, "--k", str(c["k"]))
        self.note_device(rec["device"])
        need(rec["ok"], f"sharded search failed: {rec}")
        return {"lane_rows": st.nslots, "tol": SEARCH_TOL,
                "fill_seconds": round(fill_s, 2), **rec}

    def phase_tp_decode(self) -> dict:
        import numpy as np
        c = self.cfg
        prompts = self._prompts(
            np.random.default_rng(self.args.seed + 2))
        paged = ["--continuous", "--batch-cap", str(len(prompts)),
                 "--page-size", str(c["page"])]
        one = self._complete("paged-1chip", paged, prompts)
        tp = self._complete("paged-tp4", [*paged, "--tp", "4"],
                            prompts)
        shards = tp["hb"].get("pages_shard", {})
        mb = [v.get("shard_mb", 0) for v in shards.values()]
        need(tp["hb"].get("tp") == 4 and len(shards) == 4
             and min(mb) > 0 and max(mb) - min(mb) <= 0.01 * max(mb),
             f"the pools are not split over 4 devices: {shards}")
        judged = self._judge(prompts, {
            "paged-1chip": (one["tokens"], "bf16"),
            "paged-tp4": (tp["tokens"], "bf16")})
        return {"one_device": {**one["rec"],
                               **judged["lanes"]["paged-1chip"]},
                "tp4": {**tp["rec"], **judged["lanes"]["paged-tp4"]},
                "free_running_agreement_tp4_vs_one":
                    self._free_running(tp["tokens"], one["tokens"]),
                "judge": {"lane": "dense static cache on one device, "
                                  "teacher-forced",
                          "seconds": judged["seconds"],
                          "bar": AGREE_BAR["bf16"]},
                "pages_shard": shards}

    def second_process(self) -> dict:
        """What a second JAX process does while the first holds the
        chip — the one run in which two processes meet on purpose."""
        hold = subprocess.Popen(self._argv("hold"), env=self.env,
                                stdout=subprocess.PIPE, text=True)
        self.procs.append(hold)
        line = hold.stdout.readline()
        need("holding" in line, f"holder never reached the chip: "
                                f"{line!r}")
        t0 = time.perf_counter()
        try:
            p = subprocess.run(self._argv("probe"), env=self.env,
                               capture_output=True, text=True,
                               timeout=90)
            rc, err, out = p.returncode, p.stderr, p.stdout
        except subprocess.TimeoutExpired as ex:
            rc, out = "timeout after 90s", ""
            err = (ex.stderr or b"").decode(errors="replace") \
                if isinstance(ex.stderr, bytes) else (ex.stderr or "")
        secs = time.perf_counter() - t0
        hold.kill()
        hold.wait()
        self.procs.remove(hold)
        return {"holder": json.loads(line)["holding"],
                "second_rc": rc, "second_seconds": round(secs, 1),
                "second_stdout": out.strip()[-300:],
                "second_stderr_tail": err.strip()[-1500:]}

    # -- driver ------------------------------------------------------------

    def run(self) -> int:
        a = self.args
        ok, error = True, None
        t_all = time.perf_counter()
        try:
            self.note_device(self.run_child("probe", timeout=300))
            want = 4 if a.multichip else 1
            if not a.rehearse:
                need(self.device["platform"] == "tpu",
                     f"no TPU: JAX found {self.device} (use "
                     "--rehearse for the CPU rehearsal)")
            need(self.device["count"] == want,
                 f"this run needs {want} device(s), JAX sees "
                 f"{self.device['count']}")
            if a.second_process:
                phases = [("second_process", self.second_process)]
            elif a.multichip:
                phases = [("build", self.phase_build),
                          ("pod_search", self.phase_pod_search),
                          ("tp_decode", self.phase_tp_decode)]
            else:
                phases = [("build", self.phase_build),
                          ("embed", self.phase_embed),
                          ("search", self.phase_search),
                          ("complete", self.phase_complete)]
            only = set(a.phases.split(",")) if a.phases else None
            for name, fn in phases:
                if only and name not in only and name != "build":
                    continue
                t0 = time.perf_counter()
                rec = fn()
                emit({"phase": name, "ok": True,
                      "seconds": round(time.perf_counter() - t0, 2),
                      **rec, "device": self.device})
            need("jax" not in sys.modules,
                 "the parent imported jax: two processes held it")
        except (SmokeFailure, subprocess.SubprocessError, OSError,
                KeyError, ValueError) as ex:
            ok, error = False, f"{type(ex).__name__}: {ex}"
        finally:
            for p in list(self.procs):
                p.kill()
                p.wait()
            if self.st is not None:
                from libsplinter_tpu import Store
                self.st.close()
                Store.unlink(self.store_name)
        last = {"ok": ok, "device": self.device}
        if error:
            last["error"] = error
        print(f"total {time.perf_counter() - t_all:.1f}s",
              file=sys.stderr)
        emit(last)
        return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the texts, vectors, queries, prompts")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU control-flow rehearsal at tiny sizes")
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: sharded search + --tp 4 decode "
                         "and their one-device comparisons, only")
    ap.add_argument("--phases", default="",
                    help="comma list: run only these phases (build "
                         "always runs) — for finding a fault cheaply")
    ap.add_argument("--second-process", action="store_true",
                    help="instead of the phases: report what a second "
                         "JAX process does while one holds the chip")
    ap.add_argument("--child", choices=sorted(CHILDREN),
                    help=argparse.SUPPRESS)
    for flag in ("--lane", "--report", "--store", "--keys",
                 "--queries"):
        ap.add_argument(flag, help=argparse.SUPPRESS)
    ap.add_argument("--n-ctx", type=int, default=2048,
                    help=argparse.SUPPRESS)
    ap.add_argument("--k", type=int, default=10,
                    help=argparse.SUPPRESS)
    ap.add_argument("--page", type=int, default=128,
                    help=argparse.SUPPRESS)
    ap.add_argument("--pool-pages", type=int, default=1280,
                    help=argparse.SUPPRESS)
    ap.add_argument("rest", nargs=argparse.REMAINDER,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.rest[:1] == ["--"]:
        args.rest = args.rest[1:]
    if args.child:
        return CHILDREN[args.child](args)
    sys.path.insert(0, REPO)
    return Smoke(args).run()


if __name__ == "__main__":
    raise SystemExit(main())
