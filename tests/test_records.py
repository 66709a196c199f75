"""The documents describe the tree that is there.

A back-ticked path in a document names a tracked file or directory;
every `scripts/*.py` is reachable from a `make` recipe or from `docs/`
and every script a recipe names exists; the front page names the
benchmark the driver runs and each of its cells.  A stale mention of a
deleted file fails here instead of misleading the next reader.
"""
from __future__ import annotations

import fnmatch
import functools
import json
import os
import re
import subprocess

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ["README.md", "PERF.md", "docs/performance.md",
        "docs/environment.md", "docs/operations.md", "docs/cli.md",
        "docs/architecture.md", "docs/migration.md",
        "changelogs/README.md", ".claude/skills/verify/SKILL.md"]

# what makes a back-ticked word a claim about this repository's tree
PATH_EXT = (".py", ".json", ".jsonl", ".md")
PATH_PREFIX = ("scripts/", "benchmark/", "libsplinter_tpu/", "tests/",
               "native/")

# paths a document may name although git does not track them
ALLOWED = (
    "native/build/",            # built by `make -C native`
    ".xla_cache",               # the compile cache's default place
    ".bench_work/",             # a benchmark run's work files
    ".archive_check/",          # where two commits are unpacked to compare
    "chiprun_out/",             # what a chip call brings back
    ".claude/scheduled_tasks.json",
    "model.gguf", "small.gguf", "model.safetensors",  # the user's own
    # the reference project's own files, and a published model's
    "changelogs/1.2.0.md", "README.v4.md", "config.json",
)


@functools.lru_cache(maxsize=None)
def _tracked() -> tuple[frozenset, frozenset]:
    """(files, directories) git would commit; in a checkout without
    `.git`, the files that are there (dot-directories and build output
    aside)."""
    files = _tracked_files()
    return (frozenset(files),
            frozenset(p[:i] for p in files
                      for i in range(len(p)) if p[i] == "/"))


def _tracked_files() -> list[str]:
    try:
        out = subprocess.run(
            ["git", "ls-files", "--cached", "--others",
             "--exclude-standard"], cwd=ROOT, capture_output=True,
            text=True, check=True).stdout.split("\n")
        files = [p for p in out if p and
                 os.path.exists(os.path.join(ROOT, p))]
        if files:
            return files
    except (OSError, subprocess.CalledProcessError):
        pass
    files = []
    for base, dirs, names in os.walk(ROOT):
        rel = os.path.relpath(base, ROOT)
        dirs[:] = [d for d in dirs
                   if d not in ("__pycache__", "build", "chiprun_out")
                   and (not d.startswith(".") or d == ".claude")]
        files += [os.path.normpath(os.path.join(rel, n)) for n in names]
    return files


def _names_something(tok: str) -> bool:
    """`tok` is a tracked file or directory, given whole or by its
    tail (`engine/searcher.py`, `store.py`, `readers/`)."""
    files, dirs = _tracked()
    if "*" in tok:
        return any(fnmatch.fnmatch(p, tok) or fnmatch.fnmatch(p, "*/" + tok)
                   for p in files)
    tok = tok.rstrip("/")
    tail = "/" + tok
    return any(p == tok or p.endswith(tail) for p in files | dirs)


def _path_tokens(text: str):
    """Words inside back-ticks that look like a path of this repo,
    without the decorations documents hang on them (`file.py:12-40`,
    `file.py:function`, `tests/x.py::test_y`, a trailing comma)."""
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            word = word.strip("()[],;\"'")
            word = word.split("::")[0]
            word = re.sub(r"(\.(?:py|jsonl?|md)):.*$", r"\1", word)
            word = word.rstrip(".:")
            if not word or "://" in word or word.startswith(("/", "-", "~")):
                continue
            if any(c in word for c in "<>{}$…=|"):
                continue          # a pattern or a placeholder, not a name
            if word.endswith(PATH_EXT) or word.startswith(PATH_PREFIX):
                yield word


@pytest.mark.parametrize("doc", DOCS)
def test_backticked_paths_name_tracked_files(doc):
    text = open(os.path.join(ROOT, doc), encoding="utf-8").read()
    missing = sorted({t for t in _path_tokens(text)
                      if not t.startswith(ALLOWED) and t not in ALLOWED
                      and not _names_something(t)})
    assert not missing, f"{doc} names paths that are not in the tree: " \
                        f"{missing}"


def _recipe_scripts() -> set[str]:
    recipes = [ln for ln in open(os.path.join(ROOT, "Makefile"))
               if ln.startswith("\t")]
    return set(re.findall(r"scripts/\w+\.py", "".join(recipes)))


def test_every_script_is_reachable_and_every_recipe_script_exists():
    files, _ = _tracked()
    scripts = {p for p in files
               if p.startswith("scripts/") and p.endswith(".py")}
    named = _recipe_scripts()
    assert named <= scripts, f"make names scripts that are not there: " \
                             f"{sorted(named - scripts)}"
    docs = "".join(
        open(os.path.join(ROOT, p), encoding="utf-8").read()
        for p in sorted(files)
        if p.startswith("docs/") and p.endswith(".md"))
    orphans = sorted(s for s in scripts - named if s not in docs)
    assert not orphans, f"no make recipe and no page under docs/ " \
                        f"names: {orphans}"


def test_front_page_names_the_benchmark_and_every_cell():
    readme = open(os.path.join(ROOT, "README.md"), encoding="utf-8").read()
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    wanted = ["BENCHMARK.json", "benchmark/run.py", "PERF.md",
              "PERF_LEDGER.jsonl"]
    wanted += [w["name"] for w in bench["workloads"]]
    wanted += [c["name"] for c in bench["configs"]]
    missing = [w for w in wanted if f"`{w}`" not in readme]
    assert not missing, f"README.md does not name {missing}"
