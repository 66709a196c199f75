#!/usr/bin/env python3
"""benchmark/run.py — run ONE cell of BENCHMARK.json once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

prints, as the LAST line of stdout, one JSON object
{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"]}:
the cell's end-to-end metrics with --trace 0, its per-layer metrics with
--trace 1.  Earlier lines say what was compared with which limit, the
compile ledger, how late the generator ran.

This process NEVER imports JAX (checked before it prints): the daemon
under test runs in a child (host.py) through its own main(), requests go
through the program's own client calls, and the plain reference runs in
a child of its own once the daemon has gone.  The run exits non-zero and
prints no result when there is no TPU, fewer chips than the cell asks
for, a compile inside the measured window, or a daemon that died.

Everything a cell is lives in files found by name: the cell in
BENCHMARK.json names a configuration (the `file` of its entry) and a
traffic mix (benchmark/traffic/<traffic>.json).  The configuration names
its set-up steps (benchmark/prepare/<name>.py) and its reference
(benchmark/reference/<name>.py); the mix names its payload, client call
and loop (benchmark/payloads, calls, loops: see traffic.py); each
per-layer metric is benchmark/metrics/<name>.json naming a reader
(benchmark/readers/<reader>.py) and its arguments.  No cell's, mix's,
call's or loop's name appears in this file.  `--rehearse` (CPU, tiny
sizes from the files' "rehearse" sections) checks control flow and can
never print "platform": "tpu".
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()         # setup_s counts from here

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

WORK_ROOT = os.path.join(REPO, ".bench_work")
HEARTBEAT_WAIT_S = 40.0


class BenchFailure(Exception):
    pass


def need(cond, msg: str) -> None:
    if not cond:
        raise BenchFailure(msg)


def say(*a) -> None:
    print(*a, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def by_name(entries: list[dict], name: str, what: str) -> dict:
    hit = [e for e in entries if e["name"] == name]
    need(len(hit) == 1, f"BENCHMARK.json has no {what} named {name!r}")
    return hit[0]


def percentile(values, p: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values, float), p))


class Run:
    def __init__(self, args):
        self.args = args
        self.bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
        self.cell = by_name(self.bench["workloads"], args.workload,
                            "workload")
        centry = by_name(self.bench["configs"], self.cell["config"],
                         "config")
        self.cfg = load_json(os.path.join(REPO, centry["file"]))
        self.traffic = load_json(os.path.join(
            HERE, "traffic", f"{self.cell['traffic']}.json"))
        if args.rehearse:
            self.cfg = merged(self.cfg, self.cfg.get("rehearse", {}))
            self.traffic = merged(self.traffic,
                                  self.traffic.get("rehearse", {}))
        self.work = os.path.join(WORK_ROOT, self.cell["name"])
        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.work)
        self.store_name = f"/spt-bench-{os.getpid()}"
        self.st = None
        self.proc = None
        self.device = None
        self.ctx: dict = {"config": self.cfg, "traffic": self.traffic,
                          "peaks": None, "client": {}, "trace": None}
        self.env = dict(os.environ, PYTHONPATH=REPO)
        self.env.pop("SPTPU_JAX_PROFILE", None)
        # a cell's programs have to stay in the persistent cache from
        # one run to the next: a size cap below a cell's working set
        # (the chip tool's machines come with 192 MiB) evicts in LRU
        # order and every run compiles everything again
        self.env["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
        if args.trace:
            self.env["SPTPU_TRACE"] = "1"      # host spans -> heartbeat
        else:
            self.env.pop("SPTPU_TRACE", None)
        if args.rehearse:
            self.env["JAX_PLATFORMS"] = "cpu"
            self.rehearse_file = os.path.join(self.work, "rehearse.json")
            with open(self.rehearse_file, "w") as f:
                json.dump({"sabotage": args.sabotage}, f)

    # ------------------------------------------------------------ children

    def start_daemon(self) -> None:
        cfg = self.cfg
        self.report = os.path.join(self.work, "device.json")
        # {work} and whatever strings the set-up steps prepared
        subst = {k: v for k, v in self.prepared.items()
                 if isinstance(v, str)}
        argv = [a.format(work=self.work, **subst)
                for a in cfg.get("argv", [])]
        if self.args.control:
            # the configuration's own lower-precision path, where the
            # program has one: shows that `correct` catches it
            argv += cfg.get("control", {}).get("argv", [])
        host = [sys.executable, os.path.join(HERE, "host.py"),
                "--chips", str(self.cell["chips"])]
        if self.args.rehearse:
            host += ["--rehearse", self.rehearse_file]
        log = open(os.path.join(self.work, "daemon.log"), "w")
        self.proc = subprocess.Popen(
            host + ["--lane", cfg["lane"], "--report", self.report,
                    "--control", self.work, "--trace", str(self.args.trace),
                    "--", "--store", self.store_name, *argv],
            env=self.env, stdout=log, stderr=subprocess.STDOUT)
        log.close()

    def stop_daemon(self) -> None:
        p, self.proc = self.proc, None
        if p is None:
            return
        if p.poll() is None:
            p.send_signal(signal.SIGINT)       # main() returns 0
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()

    def alive(self, what: str) -> None:
        need(self.proc is not None and self.proc.poll() is None,
             f"the daemon exited (rc={self.proc.returncode if self.proc else None})"
             f" while {what}; see {self.work}/daemon.log")

    def wait_for(self, what: str, pred, timeout: float, every=0.05):
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            got = pred()
            if got:
                return got
            self.alive(f"waiting for {what}")
            time.sleep(every)
        raise BenchFailure(f"timed out after {timeout}s waiting for {what}")

    def heartbeat(self) -> dict | None:
        try:
            return json.loads(
                self.st.get(self.cfg["heartbeat_key"]).rstrip(b"\0"))
        except (KeyError, OSError, ValueError):
            return None

    def fresh_heartbeat(self, after_wall: float, timeout: float) -> dict:
        """The first heartbeat published after `after_wall`."""
        def ready():
            hb = self.heartbeat()
            return hb if hb and hb.get("ts", 0) > after_wall else None
        return self.wait_for("a heartbeat", ready, timeout)

    @staticmethod
    def programs(hb: dict) -> int:
        """How many programs the lane has compiled, as its heartbeat
        counts them."""
        if hb.get("compile_count", -1) >= 0:
            return int(hb["compile_count"])
        return int(sum(v.get("compiles", 0)
                       for v in hb.get("devtime", {}).values()))

    def compile_events(self) -> list[dict]:
        from libsplinter_tpu.obs.devtime import collect_compile_events
        return [e for e in collect_compile_events(self.st)
                if e.get("lane") == self.cfg["lane"]]

    # -------------------------------------------------------------- set-up

    def setup(self) -> None:
        cfg, tr, seed = self.cfg, self.traffic, self.args.seed
        subprocess.run(["make", "-s", "-C", os.path.join(REPO, "native"),
                        "all"], check=True, stdout=subprocess.DEVNULL)
        from libsplinter_tpu import Store
        import traffic as T
        s = cfg["store"]
        self.st = st = Store.create(
            self.store_name, nslots=int(s["nslots"]),
            max_val=int(s["max_val"]), vec_dim=int(s["vec_dim"]),
            overwrite=True)
        # the configuration's set-up steps, then the mix's payload
        # and its client side, all from the seed
        t0 = time.perf_counter()
        self.prepared: dict = {}
        for step in cfg.get("prepare", []):
            self.prepared.update(T.part("prepare", step["name"]).prepare(
                st, cfg, step, seed, self.work))
        t_prep = time.perf_counter() - t0
        self.mix = T.Mix(st, tr, seed, self.prepared)
        t_pay = time.perf_counter() - t0 - t_prep
        t1 = time.perf_counter()
        self.start_daemon()

        def device():
            try:
                return load_json(self.report)
            except (OSError, ValueError):
                return None
        self.device = self.wait_for("the daemon's device", device, 600)
        # host.py has refused anything else already; a rehearsal is
        # the CPU's, a run the TPU's with the chips the cell asks for
        need((self.device["platform"] == "tpu") != bool(self.args.rehearse)
             and (self.args.rehearse
                  or self.device["count"] == self.cell["chips"]),
             f"wrong device for this run: {self.device}")
        self.wait_for("the first heartbeat", self.heartbeat, 900)
        t_up = time.perf_counter() - t1
        t2 = time.perf_counter()
        self.next_req, hb = self.warm_up()
        t_warm = time.perf_counter() - t2
        # the heartbeat before the window: published after the last
        # warm-up request, so that deltas over the window leave the
        # warm-up out and its compiles are in the ledger
        self.hb_start = hb or self.fresh_heartbeat(time.time(),
                                                   HEARTBEAT_WAIT_S)
        self.compiles_before = self.compile_events()
        self.setup_parts = {
            "prepare_s": t_prep, "payload_s": t_pay,
            "daemon_start_s": t_up, "warmup_s": t_warm,
            "heartbeat_wait_s": time.perf_counter() - t2 - t_warm}

    def warm_up(self) -> tuple[int, dict | None]:
        """The call sends the cell's own shapes, and only those, until
        the lane has compiled the number of programs the mix expects.
        Returns the next request ordinal and the heartbeat read after
        the last burst, if one was."""
        expect = int(self.traffic.get("warmup", {}).get(
            "expect_programs", 0))
        base, hb = 0, None
        for attempt in range(3):
            base = self.mix.warm_up(base)
            if not expect:
                break
            hb = self.fresh_heartbeat(time.time(), HEARTBEAT_WAIT_S)
            got = self.programs(hb)
            say(f"warm-up pass {attempt + 1}: {got} programs compiled "
                f"(the mix expects {expect})")
            if got >= expect:
                break
        return base, hb

    # -------------------------------------------------------------- window

    def window(self) -> None:
        tr, a = self.traffic, self.args
        flag = os.path.join(self.work, "trace.start")
        steady = float(tr.get("trace_after_s", 2.0))
        trace_s = min(float(tr.get("trace_seconds", 4.0)),
                      max(a.seconds - steady - 0.5, 0.5))
        fired = []

        def on_tick(t: float) -> None:
            if a.trace and not fired and t >= steady:
                fired.append(t)
                with open(flag + ".tmp", "w") as f:
                    f.write(str(trace_s))
                os.replace(flag + ".tmp", flag)

        self.t0_wall = time.time()
        self.setup_s = time.perf_counter() - T_PROCESS
        self.res = self.mix.run(a.seconds, self.next_req, on_tick)
        self.t1_wall = time.time()
        if a.trace:
            need(fired, "the window closed before the trace was asked for")
            done = os.path.join(self.work, "trace.done")
            self.wait_for("the profiler capture",
                          lambda: os.path.exists(done), 120)
            self.trace_rec = load_json(done)
            need("error" not in self.trace_rec,
                 f"the profiler capture failed: {self.trace_rec}")

    def close_window(self) -> None:
        """Heartbeat and compile ledger at the window's far end; stop
        the daemon; read its peak memory."""
        self.hb_end = self.fresh_heartbeat(self.t1_wall, HEARTBEAT_WAIT_S)
        with open(os.path.join(self.work, "heartbeats.json"), "w") as f:
            json.dump({"start": self.hb_start, "end": self.hb_end}, f)
        def ident(e):
            return e.get("program"), e.get("shapes_key"), e.get("ts")
        seen = {ident(e) for e in self.compiles_before}
        inside = [e for e in self.compile_events() if ident(e) not in seen
                  or self.t0_wall <= e.get("ts", 0) <= self.t1_wall]
        grew = self.programs(self.hb_end) - self.programs(self.hb_start)
        say(f"compile ledger: {len(self.compiles_before)} programs before "
            f"the window, {len(inside)} inside it, program count grew by "
            f"{grew}")
        self.compiled_inside = inside or grew > 0
        if self.compiled_inside:
            say("COMPILED INSIDE THE WINDOW:", json.dumps(inside))
        self.stop_daemon()
        try:
            self.memory = load_json(
                os.path.join(self.work, "memory.json"))["memory_peak_bytes"]
        except (OSError, ValueError, KeyError):
            self.memory = None

    # ---------------------------------------------------------- correctness

    def check(self) -> bool:
        """The comparison that decides `correct`: the timed path's own
        outputs, sampled from the seed once the window has closed,
        against the configuration's plain reference.  Every number
        compared is printed beside its limit."""
        import traffic as T
        ref = T.part("reference", self.cfg["reference"]["name"])
        out = ref.check(self)
        ok = True
        for name, value, limit, how in out["compared"]:
            passed = value <= limit if how == "<=" else value >= limit
            ok &= bool(passed)
            say(f"compared: {name} = {value!r} (limit {how} {limit!r}) "
                f"{'ok' if passed else 'FAILED'}")
        say(f"reference: {out.get('note', '')}")
        return ok

    # --------------------------------------------------------------- result

    def end_to_end(self) -> dict:
        """The end-to-end metrics this cell reports, from the
        generator's own clock."""
        res, out = self.res, {}
        # a failed request is the worst latency
        worst = float(self.traffic.get("timeout_ms", 10_000))
        lat = [r["ms"] if r["ok"] else max(r["ms"], worst)
               for r in res.get("records", [])]
        values = {
            "rate": res["completed"] / res["elapsed_s"],
            "latency_p50_ms": percentile(lat, 50) if lat else None,
            "latency_p95_ms": percentile(lat, 95) if lat else None,
            "setup_s": self.setup_s}
        for m in self.bench["end_to_end"]:
            if self.cell["name"] not in m.get("workloads",
                                              [self.cell["name"]]):
                continue
            src = "setup_s" if m["name"] == "setup_s" \
                else self.traffic["reports"].get(m["name"])
            need(src in values and values[src] is not None,
                 f"the mix does not say how to report {m['name']}")
            out[m["name"]] = {"value": values[src], "unit": m["unit"]}
        return out

    def per_layer(self) -> dict:
        import traffic as T
        out = {}
        for m in self.bench["per_layer"]:
            if self.cell["name"] not in m.get("workloads",
                                              [self.cell["name"]]):
                continue
            spec = load_json(os.path.join(HERE, "metrics",
                                          f"{m['name']}.json"))
            reader = T.part("readers", spec["reader"])
            value = reader.read(self.ctx, **spec.get("args", {}))
            if value is None:
                say(f"per-layer: {m['name']}: nothing to read")
                continue
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    def reduce_trace(self) -> dict:
        # reading the capture needs jax.profiler: in a child held to
        # the CPU, so that this process stays off JAX
        out = os.path.join(self.work, "trace_reduced.json")
        p = subprocess.run(
            [sys.executable, os.path.join(HERE, "tracereduce.py"),
             os.path.join(self.work, "trace"), out],
            env=dict(self.env, JAX_PLATFORMS="cpu"), timeout=300,
            capture_output=True, text=True)
        need(p.returncode == 0, f"reducing the capture failed: "
             f"{p.stderr[-800:]}")
        red = load_json(out)
        need(red["busy_s"] > 0 or self.args.rehearse,
             "no operation ran on the device in the traced window")
        # the capture can start and end in idle time, which no event
        # marks: the window is at least what the profiler's own
        # process slept between start and stop
        red["window_s"] = max(red["window_s"],
                              float(self.trace_rec.get("window_s", 0.0)))
        self.ctx["trace"] = red
        say(f"trace: window {red['window_s']:.3f}s busy {red['busy_s']:.3f}s"
            f" idle share {1 - red['busy_s'] / max(red['window_s'], 1e-9):.3f}; "
            f"host-side capture {self.trace_rec}")
        return red

    def main(self) -> int:
        a = self.args
        self.setup()
        self.window()
        self.close_window()
        res = self.res
        say(f"window: {a.seconds}s, completed {res['completed']} attempted "
            f"{res['attempted']} failed {res['failed']}; set-up "
            f"{self.setup_s:.2f}s = {json.dumps(self.setup_parts)}")
        if "lateness_ms" in res:
            say(f"generator lateness ms: {json.dumps(res['lateness_ms'])}")
        faults = {k: self.hb_end.get(k, 0)
                  for k in self.cfg.get("fault_counters", [])}
        say(f"daemon fault counters: {json.dumps(faults)}")
        failed = int(res["failed"]) + int(sum(faults.values()))
        self.ctx.update(client=client_samples(res), hb_start=self.hb_start,
                        hb_end=self.hb_end, result=res,
                        peaks=load_json(os.path.join(HERE, "peaks.json")),
                        device=self.device)
        device = dict(self.device, memory_peak_bytes=self.memory)
        correct = self.check()
        line = {"correct": bool(correct and failed == 0),
                "attempted": int(res["attempted"]), "failed": failed}
        if a.trace:
            red = self.reduce_trace()
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            line["metrics"] = self.per_layer()
            line["breakdown"] = red["breakdown"]
        else:
            line["metrics"] = self.end_to_end()
        line["device"] = device
        need(not self.compiled_inside,
             "a program compiled inside the measured window")
        need("jax" not in sys.modules, "run.py imported jax")
        say(json.dumps(line))
        return 0

    def cleanup(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        if self.st is not None:
            from libsplinter_tpu import Store
            self.mix = None
            self.ctx.clear()
            try:
                self.st.close()
            except Exception:
                pass
            Store.unlink(self.store_name)
        if not os.environ.get("BENCH_KEEP_TRACE"):
            shutil.rmtree(os.path.join(self.work, "trace"),
                          ignore_errors=True)


def client_samples(res: dict) -> dict:
    """What the generator's own clock saw, for the `client` reader."""
    out = {}
    recs = res.get("records") or []
    if recs:
        out["latency_ms"] = [r["ms"] for r in recs]
        w = [r["write_ns"] / 1e3 for r in recs if "write_ns" in r]
        if w:
            out["write_us"] = w
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the configuration's lower-precision "
                         "control: `correct` has to come out false")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU control-flow rehearsal at tiny sizes; can "
                         "never print \"platform\": \"tpu\"")
    ap.add_argument("--sabotage", default=None,
                    help="--rehearse only, for benchmark/tests: break "
                         "the timed path where host.sabotage says")
    args = ap.parse_args(argv)
    if args.sabotage and not args.rehearse:
        ap.error("--sabotage is for the rehearsal's tests only")
    run = None
    try:
        run = Run(args)
        if args.seconds is None:
            args.seconds = float(run.bench["run_seconds"])
        return run.main()
    except (BenchFailure, subprocess.SubprocessError, OSError, KeyError,
            ValueError, RuntimeError) as ex:
        print(f"benchmark run failed: {type(ex).__name__}: {ex}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        if run is not None:
            run.cleanup()


if __name__ == "__main__":
    raise SystemExit(main())
