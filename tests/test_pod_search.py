"""Pod-sharded search end to end.

Single-process tests shard one host's lane over the virtual 8-device
CPU mesh; the multi-process test launches TWO real worker processes
wired by jax.distributed (2 virtual hosts, cross-process collectives)
and asserts the merged global result is identical on both workers and
equal to a dense single-host reference over the concatenated lanes.
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import uuid

import numpy as np
import pytest

from libsplinter_tpu import Store
from libsplinter_tpu.parallel import PodSearch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fill(store, vecs):
    for i in range(len(vecs)):
        store.set(f"doc/{i}", f"text {i}")
        store.vec_set(f"doc/{i}", vecs[i])


def _dense_topk(lane, q, k):
    norms = np.linalg.norm(lane, axis=1) * np.linalg.norm(q)
    with np.errstate(invalid="ignore"):
        scores = np.where(norms > 0, lane @ q / np.maximum(norms, 1e-12),
                          -np.inf)
    order = np.argsort(-scores)[:k]
    return scores[order], order


class TestSingleProcess:
    def test_matches_dense_reference(self, store):
        dim = store.vec_dim
        rng = np.random.default_rng(11)
        vecs = rng.normal(size=(64, dim)).astype(np.float32)
        _fill(store, vecs)
        ps = PodSearch(store)
        q = rng.normal(size=dim).astype(np.float32)
        hits = ps.search(q, k=5)
        lane = np.array(store.vectors)
        want_s, want_i = _dense_topk(lane, q, 5)
        assert [h["slot"] for h in hits] == list(want_i)
        np.testing.assert_allclose([h["similarity"] for h in hits],
                                   want_s, rtol=1e-5)
        assert all(h["host"] == 0 for h in hits)
        # keys resolve through the store
        assert all(h["key"] == store.key_at(h["slot"]) for h in hits)

    def test_non_divisible_nslots_pads(self):
        name = f"/spt-pod-pad-{os.getpid()}"
        Store.unlink(name)
        st = Store.create(name, nslots=100, max_val=128, vec_dim=16)
        try:
            rng = np.random.default_rng(3)
            vecs = rng.normal(size=(50, 16)).astype(np.float32)
            _fill(st, vecs)
            ps = PodSearch(st)
            assert ps.global_n % ps.mesh.shape["dp"] == 0
            q = rng.normal(size=16).astype(np.float32)
            hits = ps.search(q, k=5)
            want_s, want_i = _dense_topk(np.array(st.vectors), q, 5)
            assert [h["slot"] for h in hits] == list(want_i)
            assert all(h["slot"] < 100 for h in hits)
        finally:
            st.close()
            Store.unlink(name)

    def test_mask_prefilters_rows(self, store):
        dim = store.vec_dim
        rng = np.random.default_rng(5)
        vecs = rng.normal(size=(16, dim)).astype(np.float32)
        _fill(store, vecs)
        ps = PodSearch(store)
        q = rng.normal(size=dim).astype(np.float32)
        top = ps.search(q, k=1)[0]
        mask = np.ones(store.nslots, np.float32)
        mask[top["slot"]] = 0.0
        second = ps.search(q, k=1, mask=mask)[0]
        assert second["slot"] != top["slot"]
        assert second["similarity"] <= top["similarity"]

    def test_incremental_staging(self, store):
        dim = store.vec_dim
        _fill(store, np.ones((8, dim), np.float32))
        ps = PodSearch(store)
        q = np.ones(dim, np.float32)
        ps.search(q, k=2)
        assert ps.full_stages == 1 and ps.rows_staged == 0
        ps.search(q, k=2)                     # no writes: no transfer
        assert ps.full_stages == 1 and ps.rows_staged == 0
        store.vec_set("doc/3", np.arange(dim, dtype=np.float32))
        ps.search(q, k=2)
        assert ps.full_stages == 1 and ps.rows_staged == 1

    def test_refresh_sees_new_writes(self, store):
        dim = store.vec_dim
        _fill(store, np.ones((4, dim), np.float32))
        ps = PodSearch(store)
        target = np.zeros(dim, np.float32)
        target[1] = 1.0
        ps.search(target, k=1)
        store.set("late", "late doc")
        store.vec_set("late", target)
        hits = ps.search(target, k=1)
        assert hits[0]["key"] == "late"
        assert hits[0]["similarity"] == pytest.approx(1.0, abs=1e-5)


class TestShardedTopkEdges:
    """sharded_topk edge cases straight on the mesh primitive (no
    store): k_local clamping when a shard's valid rows < k_local, and
    global index translation after the ICI merge — exercised on the
    jnp fallback AND the fused kernel in interpret mode (PR 3), which
    must agree."""

    def _mesh(self):
        from libsplinter_tpu.parallel.mesh import make_mesh
        return make_mesh()

    def _ref(self, vecs, q):
        norms = np.linalg.norm(vecs, axis=1) * np.linalg.norm(q)
        with np.errstate(invalid="ignore"):
            return np.where(norms > 0,
                            vecs @ q / np.maximum(norms, 1e-12),
                            -np.inf)

    @pytest.mark.parametrize("interpret", [False, True])
    def test_k_local_exceeds_shard_valid_rows(self, interpret):
        """3 live rows spread over an 8-shard mesh, k=10: every shard
        clamps k_local to its tile, shards with zero live rows
        contribute only filler, and the merge returns exactly the 3
        real candidates above the score floor."""
        from libsplinter_tpu.parallel.sharded_search import (
            shard_vectors, sharded_topk)
        mesh = self._mesh()
        rng = np.random.default_rng(21)
        vecs = np.zeros((64, 16), np.float32)
        live = [2, 33, 61]                     # shards 0, 4, 7
        vecs[live] = rng.normal(size=(3, 16)).astype(np.float32)
        q = rng.normal(size=16).astype(np.float32)
        s, i = sharded_topk(mesh, shard_vectors(mesh, vecs), q, 10,
                            use_pallas=False, interpret=interpret)
        keep = s > -1e29
        assert keep.sum() == 3
        assert set(i[keep].tolist()) == set(live)
        ref = self._ref(vecs, q)
        np.testing.assert_allclose(np.sort(s[keep]),
                                   np.sort(ref[live]), rtol=1e-5)

    @pytest.mark.parametrize("interpret", [False, True])
    def test_global_index_translation(self, interpret):
        """Winners planted on known shards come back with GLOBAL row
        ids (shard * local_n + local row), in rank order."""
        from libsplinter_tpu.parallel.sharded_search import (
            shard_vectors, sharded_topk)
        mesh = self._mesh()
        m = mesh.shape["dp"]
        local_n = 8
        n, d = m * local_n, 16
        rng = np.random.default_rng(22)
        vecs = rng.normal(size=(n, d)).astype(np.float32)
        q = rng.normal(size=d).astype(np.float32)
        # plant exact hits at the last row of shard 1 and the first
        # row of the last shard — translation errors (off-by-shard,
        # local-vs-global) land exactly on these boundaries
        g1 = 1 * local_n + (local_n - 1)
        g2 = (m - 1) * local_n + 0
        vecs[g1] = q * 2.0
        vecs[g2] = q * 0.5                     # colinear: cosine 1.0 too
        s, i = sharded_topk(mesh, shard_vectors(mesh, vecs), q, 4,
                            use_pallas=False, interpret=interpret)
        assert {int(i[0]), int(i[1])} == {g1, g2}
        np.testing.assert_allclose(s[:2], 1.0, atol=1e-5)
        ref = self._ref(vecs, q)
        order = np.argsort(-ref)[:4]
        assert set(i.tolist()) == set(order.tolist())

    def test_fused_and_jnp_paths_agree(self):
        from libsplinter_tpu.parallel.sharded_search import (
            shard_vectors, sharded_topk)
        mesh = self._mesh()
        rng = np.random.default_rng(23)
        vecs = rng.normal(size=(64, 16)).astype(np.float32)
        vecs[10:20] = 0.0                      # dead rows on one shard
        q = rng.normal(size=16).astype(np.float32)
        arr = shard_vectors(mesh, vecs)
        s_j, i_j = sharded_topk(mesh, arr, q, 5, use_pallas=False)
        s_f, i_f = sharded_topk(mesh, arr, q, 5, use_pallas=False,
                                interpret=True)
        np.testing.assert_allclose(s_f, s_j, rtol=1e-5)
        np.testing.assert_array_equal(i_f, i_j)


WORKER = r"""
import json, os, re, sys
# 2 devices per host -> 4 global; older jax lacks the config option and
# reads the XLA flag instead (must land before backend init).  REPLACE
# any inherited count (pytest's conftest exports =8) — merely skipping
# when present would hand each worker 8 devices
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
               os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count=2").strip()
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
try:
    jax.config.update("jax_num_cpu_devices", 2)
except AttributeError:
    pass
import jax.distributed
pid = int(sys.argv[1]); coord = sys.argv[2]; out_path = sys.argv[3]
jax.distributed.initialize(coordinator_address=coord, num_processes=2,
                           process_id=pid)
sys.path.insert(0, os.environ["SPTPU_ROOT"])
from libsplinter_tpu import Store
from libsplinter_tpu.parallel import PodSearch
from libsplinter_tpu.parallel.mesh import make_mesh

dim, nslots = 16, 32
rng = np.random.default_rng(100 + pid)        # per-host distinct lanes
name = os.environ["SPTPU_POD_STORE"] + str(pid)
Store.unlink(name)
st = Store.create(name, nslots=nslots, max_val=128, vec_dim=dim)
vecs = rng.normal(size=(20, dim)).astype(np.float32)
for i in range(20):
    st.set(f"h{pid}/doc{i}", f"host {pid} text {i}")
    st.vec_set(f"h{pid}/doc{i}", vecs[i])

ps = PodSearch(st)
q = np.arange(dim, dtype=np.float32)          # same query everywhere
hits = ps.search(q, k=6)

# incremental multi-process restage: one write on host 0
# must cost an O(changed) collective scatter, never a full restage
if pid == 0:
    st.vec_set("h0/doc5", q)                  # exact match for the query
hits2 = ps.search(q, k=6)
staged_after_write = ps.rows_staged
hits3 = ps.search(q, k=6)                     # no writes: no transfer

# mismatched per-host geometry must raise, not misattribute results
bad_name = name + "-bad"
Store.unlink(bad_name)
bad = Store.create(bad_name, nslots=32 if pid == 0 else 48,
                   max_val=128, vec_dim=dim)
try:
    PodSearch(bad)
    geometry_guard = "no-error"
except ValueError:
    geometry_guard = "raised"
bad.close()
Store.unlink(bad_name)

json.dump({"hits": hits, "hits2": hits2, "hits3": hits3,
           "full_stages": ps.full_stages,
           "rows_staged_after_write": staged_after_write,
           "rows_staged_final": ps.rows_staged,
           "geometry_guard": geometry_guard},
          open(out_path, "w"))
st.close()
Store.unlink(name)
"""


@pytest.mark.slow
def test_two_process_pod_search(tmp_path):
    port = 12000 + (os.getpid() % 2000)
    # make sure the port is free-ish
    with socket.socket() as s:
        try:
            s.bind(("127.0.0.1", port))
        except OSError:
            port += 1777
    coord = f"127.0.0.1:{port}"
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    env = dict(os.environ, SPTPU_ROOT=ROOT,
               SPTPU_POD_STORE=f"/spt-pod-{uuid.uuid4().hex[:6]}-")
    env.pop("JAX_PLATFORMS", None)
    outs = [tmp_path / "out0.json", tmp_path / "out1.json"]
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(i), coord, str(outs[i])],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        for i in range(2)]
    for p in procs:
        try:
            _, err = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("pod worker timed out")
        assert p.returncode == 0, err.decode()[-2000:]

    r0 = json.load(open(outs[0]))
    r1 = json.load(open(outs[1]))
    h0, h1 = r0["hits"], r1["hits"]
    assert h0 == h1, "workers disagree on the global result"

    # incremental restage: the post-write refresh was a collective
    # O(changed) scatter (1 row on host 0, 0 rows on host 1) — the
    # initial full stage stays the ONLY full stage
    for r, expect_rows in ((r0, 1), (r1, 0)):
        assert r["full_stages"] == 1, r
        assert r["rows_staged_after_write"] == expect_rows, r
        assert r["rows_staged_final"] == expect_rows, r  # idle refresh free
    assert r0["hits2"] == r1["hits2"]
    assert r0["hits3"] == r0["hits2"]
    # the written row won the search on both workers
    assert r0["hits2"][0]["key"] == "h0/doc5"
    assert r0["hits2"][0]["host"] == 0
    assert r0["hits2"][0]["similarity"] == pytest.approx(1.0, abs=1e-5)
    # ADVICE r2 medium: differing nslots across workers is an error
    assert r0["geometry_guard"] == "raised"
    assert r1["geometry_guard"] == "raised"

    # dense reference over the concatenated per-host lanes
    dim, nslots = 16, 32
    lanes = []
    for pid in range(2):
        rng = np.random.default_rng(100 + pid)
        vecs = rng.normal(size=(20, dim)).astype(np.float32)
        # rebuild the store layout host-side to learn slot indices
        name = f"/spt-pod-ref-{pid}"
        Store.unlink(name)
        st = Store.create(name, nslots=nslots, max_val=128, vec_dim=dim)
        for i in range(20):
            st.set(f"h{pid}/doc{i}", f"host {pid} text {i}")
            st.vec_set(f"h{pid}/doc{i}", vecs[i])
        lanes.append(np.array(st.vectors))
        st.close()
        Store.unlink(name)
    lane = np.concatenate(lanes)
    q = np.arange(dim, dtype=np.float32)
    norms = np.linalg.norm(lane, axis=1) * np.linalg.norm(q)
    scores = np.where(norms > 0, lane @ q / np.maximum(norms, 1e-12),
                      -np.inf)
    order = np.argsort(-scores)[:6]
    got_global = [h["host"] * nslots + h["slot"] for h in h0]
    assert got_global == list(order)
    np.testing.assert_allclose([h["similarity"] for h in h0],
                               scores[order], rtol=1e-4)
    # keys resolved across hosts (worker 0 sees worker 1's keys)
    hosts_seen = {h["host"] for h in h0}
    for h in h0:
        assert h["key"].startswith(f"h{h['host']}/")
    assert hosts_seen == {0, 1}, f"expected hits from both hosts: {h0}"
