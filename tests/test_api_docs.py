"""docs/api/ is generated from sptpu.h (scripts/gen_api_docs.py) —
these tests keep it complete and in sync."""
from __future__ import annotations

import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEADER = os.path.join(ROOT, "native", "include", "sptpu.h")
DOCS = os.path.join(ROOT, "docs", "api")


def header_functions() -> set[str]:
    """Every function declared in the public header."""
    with open(HEADER) as f:
        src = f.read()
    src = re.sub(r"/\*.*?\*/", "", src, flags=re.S)   # strip comments
    names = set()
    for m in re.finditer(
            r"\b(spt_[A-Za-z0-9_]+)\s*\(", src):
        # a '(' directly after the name inside a declaration line;
        # exclude macro uses (none in the header) and the struct tag
        names.add(m.group(1))
    return names


def test_every_header_function_documented():
    funcs = header_functions()
    assert len(funcs) >= 70, f"expected the ~70-symbol ABI, got {len(funcs)}"
    documented = set()
    for fn in os.listdir(DOCS):
        if not fn.endswith(".md"):
            continue
        with open(os.path.join(DOCS, fn)) as f:
            for m in re.finditer(r"^## `(spt_[A-Za-z0-9_]+)`", f.read(),
                                 re.M):
                documented.add(m.group(1))
    missing = funcs - documented
    assert not missing, f"undocumented ABI functions: {sorted(missing)}"


def test_docs_in_sync_with_header(tmp_path):
    """Regenerating must reproduce the committed pages byte-for-byte."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "gen_api_docs.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    gen = sorted(os.listdir(tmp_path))
    committed = sorted(p for p in os.listdir(DOCS) if p.endswith(".md"))
    assert gen == committed, (
        f"page set drifted: generated {gen} vs committed {committed} "
        f"— run scripts/gen_api_docs.py")
    for name in gen:
        with open(os.path.join(tmp_path, name)) as f:
            want = f.read()
        with open(os.path.join(DOCS, name)) as f:
            have = f.read()
        assert have == want, (
            f"docs/api/{name} is stale — run scripts/gen_api_docs.py")


def test_index_links_resolve():
    with open(os.path.join(DOCS, "index.md")) as f:
        idx = f.read()
    for m in re.finditer(r"\]\(([a-z0-9-]+\.md)\)", idx):
        assert os.path.exists(os.path.join(DOCS, m.group(1))), \
            f"index links to missing page {m.group(1)}"


# --- splint-registry-derived tables (PR 11) ---------------------------
# The label-bit table (bloom-labels appendix) and the operations.md
# fault-point + rule catalogs are GENERATED from the splint registry
# (libsplinter_tpu/analysis).  The byte-sync test above already pins
# docs/api; these pin the operations.md marked regions, which live
# outside the regenerated page set.

def _load_gen_api_docs():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_gen_api_docs_test",
        os.path.join(ROOT, "scripts", "gen_api_docs.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_label_bit_table_derived_from_registry():
    gen = _load_gen_api_docs()
    splint = gen.load_splint()
    table = splint.registry.render_label_table(
        splint.extract_registry())
    with open(os.path.join(DOCS, "bloom-labels.md")) as f:
        page = f.read()
    assert table in page, (
        "bloom-labels label-bit table stale vs protocol.py — run "
        "scripts/gen_api_docs.py")
    # every live LBL_ constant has a row
    for name in splint.extract_registry().labels:
        assert f"`{name}`" in table


def test_operations_fault_catalog_derived_from_sites():
    gen = _load_gen_api_docs()
    splint = gen.load_splint()
    table = splint.registry.render_fault_table(root=ROOT)
    with open(os.path.join(ROOT, "docs", "operations.md")) as f:
        ops = f.read()
    assert splint.registry.OPERATIONS_BEGIN in ops
    assert table in ops, (
        "operations.md fault catalog stale vs the instrumented "
        "sites — run scripts/gen_api_docs.py")
    # every discovered fault() call site has a row
    for site in {s.site for s in splint.fault_sites(ROOT)}:
        assert f"`{site}`" in table


def test_operations_rule_catalog_derived_from_registry():
    gen = _load_gen_api_docs()
    splint = gen.load_splint()
    import sys as _sys
    core = _sys.modules[splint.__name__ + ".core"]
    with open(os.path.join(ROOT, "docs", "operations.md")) as f:
        ops = f.read()
    assert core.RULES_BEGIN in ops
    assert core.render_rule_table() in ops, (
        "operations.md splint rule catalog stale — run "
        "scripts/gen_api_docs.py")
