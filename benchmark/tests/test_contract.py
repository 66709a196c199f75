"""BENCHMARK.json against the shape the benchmark's contract gives, and
against the files it names.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q"""
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_shape(path=BENCH):
    b = json.load(open(path))
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(path) <= 64 * 1024
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert 1 <= len(b["command"]) <= 32 and all(map(line, b["command"]))
    assert b["paths"] == ["benchmark"]
    cfgs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    assert len(cfgs) == len(b["configs"]) <= 24
    assert len(cells) == len(b["workloads"]) <= 24
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmark/")
        assert os.path.exists(os.path.join(REPO, c["file"]))
        assert len(c["reduced"]) <= 16 and all(map(NAME.match, c["reduced"]))
        assert any(w["config"] == c["name"] for w in b["workloads"])
    assert len({c["file"] for c in b["configs"]}) == len(cfgs)
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4) and line(w["why"])
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "traffic", w["traffic"] + ".json"))
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(cells)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) == len(b["end_to_end"]) <= 16
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m.get("workloads", cells)) <= set(cells)
    names = set(e2e)
    assert 1 <= len(b["per_layer"]) <= 128
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["name"] not in names
        names.add(m["name"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert line(m["layer"]) and m["moves"] in e2e
        spec = json.load(open(os.path.join(
            REPO, "benchmark", "metrics", m["name"] + ".json")))
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "readers", spec["reader"] + ".py"))
        for cell in m.get("workloads", cells):
            # a cell that reports this metric reports what it moves
            assert cell in e2e[m["moves"]].get("workloads", cells)
    for cell in cells:
        mine = [m for m in b["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2, f"{cell}: setup_s and one more"
        assert any(cell in m.get("workloads", cells) for m in b["per_layer"])
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(cells) // 4)


def cells():
    b = json.load(open(BENCH))
    for w in b["workloads"]:
        c = next(c for c in b["configs"] if c["name"] == w["config"])
        yield b, w, json.load(open(os.path.join(REPO, c["file"]))), \
            json.load(open(os.path.join(REPO, "benchmark", "traffic",
                                        w["traffic"] + ".json")))


def test_traffic_reports_name_the_metrics():
    """Every end-to-end metric a cell reports (but setup_s) is named in
    its mix's `reports`."""
    for b, w, _, tr in cells():
        for m in b["end_to_end"]:
            if m["name"] != "setup_s" and w["name"] in m.get(
                    "workloads", [w["name"]]):
                assert m["name"] in tr["reports"], (w["name"], m["name"])


def test_every_part_a_cell_names_is_a_file():
    """Set-up steps, reference, sabotage, payload, call and loop: each
    a file found by the name the data gives."""
    def there(kind, name):
        return os.path.exists(os.path.join(REPO, "benchmark", kind,
                                           name + ".py"))
    for _, w, cfg, tr in cells():
        for step in cfg.get("prepare", []):
            assert there("prepare", step["name"]), step
        assert there("reference", cfg["reference"]["name"])
        assert there("sabotage", cfg["sabotage"])
        assert there("payloads", tr["payload"]["kind"])
        assert there("calls", tr["call"]) and there("loops", tr["loop"])


def test_a_cell_over_the_floor_meets_it_with_rows():
    """What a configuration says lives on the device is data a request
    can reach: rows x row_bytes, not slots."""
    for _, w, cfg, _ in cells():
        if "rows" in cfg:
            assert cfg["rows"] * cfg["row_bytes"] >= 0.25 * 16 * 2**30
            assert cfg["rows"] < cfg["store"]["nslots"] == cfg["slots"]
            assert cfg["store"]["nslots"] % 1024 == 0   # the kernel's tile


def test_harness_knows_no_cell():
    """No cell, configuration, mix, metric, call, loop, payload, set-up
    step or reference name in the harness's own code."""
    b = json.load(open(BENCH))
    names = set()
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        names |= {e["name"] for e in b[k]}
    names |= {w["traffic"] for w in b["workloads"]}
    names -= {"setup_s"}          # the one metric the contract names
    for kind in ("calls", "loops", "payloads", "prepare", "reference",
                 "sabotage"):
        names |= {f[:-3] for f in os.listdir(os.path.join(
            REPO, "benchmark", kind)) if f.endswith(".py")}
    for f in ("run.py", "host.py", "traffic.py", "tracereduce.py"):
        src = open(os.path.join(REPO, "benchmark", f)).read()
        for n in names:
            assert not re.search(rf"(?<![\w.]){re.escape(n)}(?![\w])", src), \
                f"{f} names {n}"
