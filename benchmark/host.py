#!/usr/bin/env python3
"""benchmark/host.py — the one process of a run that touches JAX.

run.py never imports JAX; whatever needs the device runs here, in a
child that exits before the next one starts (one process per chip).

    host.py --lane <lane> --report F --control D [--rehearse J] -- ARGV
        writes the device record to F, then calls
        libsplinter_tpu.engine.<lane>.main(ARGV) — what `python -m
        libsplinter_tpu.engine.<lane> ARGV` runs.  A side thread waits
        for D/trace.start (written by run.py once the window is steady)
        and takes ONE jax.profiler capture of the seconds it names into
        D/trace, then writes D/trace.done.  When main() returns (SIGINT),
        the device's peak memory goes to D/memory.json.

It fails unless the platform is "tpu" with the chips the cell asks for.
--rehearse alone allows the CPU, at tiny sizes, and refuses a TPU: a
rehearsal can never print "platform": "tpu".  A reference that needs
the device brings a child of its own and calls check_device() there.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def device_record() -> dict:
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def check_device(chips: int, rehearse: bool) -> dict:
    dev = device_record()
    if rehearse:
        if dev["platform"] == "tpu":
            raise SystemExit("--rehearse is the CPU rehearsal; run it "
                             "with JAX_PLATFORMS=cpu")
    elif dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev}")
    elif dev["count"] != chips:
        raise SystemExit(f"this cell needs {chips} chip(s), JAX sees "
                         f"{dev['count']}")
    return dev


def sabotage(kind: str) -> None:
    """--rehearse only, for benchmark/tests: break the timed path
    underneath the harness, where an output is produced, so that a test
    can see `correct` come out false.  benchmark/sabotage/<kind>.py."""
    spec = importlib.util.spec_from_file_location(
        f"bench_sabotage_{kind}", os.path.join(HERE, "sabotage",
                                               f"{kind}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.apply()


def peak_memory_bytes() -> int | None:
    """Peak bytes in use on the fullest device, where the backend
    reports it (the CPU backend does not)."""
    import jax
    peaks = []
    for d in jax.devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def trace_watcher(control: str) -> None:
    """One capture of a steady window.  run.py writes trace.start
    holding the seconds to capture; this thread — the only place that
    can trace the chip — starts and stops the profiler around a sleep
    of that length and says so in trace.done."""
    import jax
    flag = os.path.join(control, "trace.start")
    while not os.path.exists(flag):
        time.sleep(0.05)
    try:
        seconds = float(open(flag).read().strip() or 3.0)
    except ValueError:
        seconds = 3.0
    out = os.path.join(control, "trace")
    rec = {"seconds_asked": seconds}
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # the host is part of what
        opts.host_tracer_level = 2         # is measured: keep it light
        t0 = time.time()
        jax.profiler.start_trace(out, profiler_options=opts)
        t1 = time.time()
        time.sleep(seconds)
        t2 = time.time()
        jax.profiler.stop_trace()
        rec.update(start_call_s=t1 - t0, window_s=t2 - t1,
                   stop_call_s=time.time() - t2, wall_start=t1,
                   wall_stop=t2)
    except Exception as ex:                # a failed capture must not
        rec["error"] = f"{type(ex).__name__}: {ex}"   # stop the daemon
    tmp = os.path.join(control, "trace.done.tmp")
    with open(tmp, "w") as f:
        json.dump(rec, f)
    os.replace(tmp, os.path.join(control, "trace.done"))


def run_daemon(args) -> int:
    sys.path.insert(0, REPO)
    rehearse = json.load(open(args.rehearse)) if args.rehearse else None
    if rehearse is not None and rehearse.get("sabotage"):
        sabotage(rehearse["sabotage"])
    dev = check_device(args.chips, rehearse is not None)
    with open(args.report, "w") as f:
        json.dump(dev, f)
    if args.trace:
        threading.Thread(target=trace_watcher, args=(args.control,),
                         daemon=True).start()
    mod = importlib.import_module(f"libsplinter_tpu.engine.{args.lane}")
    rc = int(mod.main(args.rest) or 0)
    tmp = os.path.join(args.control, "memory.json.tmp")
    with open(tmp, "w") as f:
        json.dump({"memory_peak_bytes": peak_memory_bytes()}, f)
    os.replace(tmp, os.path.join(args.control, "memory.json"))
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--lane", required=True)
    ap.add_argument("--report", required=True)
    ap.add_argument("--control", required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", default=None,
                    help="path of a JSON file with the rehearsal's "
                         "tiny sizes; allows (and demands) the CPU")
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if args.rest[:1] == ["--"]:
        args.rest = args.rest[1:]
    return run_daemon(args)


if __name__ == "__main__":
    raise SystemExit(main())
