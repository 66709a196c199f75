"""Embedder wake-path scaling: hot drains are dirty-mask + pending-set
driven, never an O(nslots) label sweep."""
from __future__ import annotations

import time

import numpy as np
import pytest

from libsplinter_tpu import Store, T_VARTEXT
from libsplinter_tpu.engine import protocol as P
from libsplinter_tpu.engine.embedder import Embedder


def fake_encoder(dim):
    def enc(texts):
        out = np.zeros((len(texts), dim), np.float32)
        for i, t in enumerate(texts):
            out[i, 0] = 1.0 + len(t)
        return out
    return enc


def make_embedder(store):
    emb = Embedder(store, encoder_fn=fake_encoder(store.vec_dim))
    emb.attach()
    return emb


def _request(store, key, text="some text"):
    store.set(key, text)
    store.set_type(key, T_VARTEXT)
    store.label_or(key, P.LBL_EMBED_REQ)
    store.bump(key)


def test_hot_drain_never_scans_labels(store, monkeypatch):
    emb = make_embedder(store)
    emb.drain(sweep=True)  # settle cold-start state

    def boom(mask):
        raise AssertionError("hot drain must not enumerate labels")

    monkeypatch.setattr(store, "enumerate_indices", boom)
    _request(store, "a")
    assert emb.drain(sweep=False) == 1          # dirty mask drove it
    assert np.abs(store.vec_get("a")).max() > 0
    assert not store.labels("a") & P.LBL_EMBED_REQ


def test_pending_set_carries_rows_across_drains(store):
    emb = make_embedder(store)
    emb.drain(sweep=True)
    _request(store, "b")
    store.drain_dirty()                          # steal the notification
    # hot drain alone would see nothing...
    idx = store.find_index("b")
    emb._pending.add(idx)                        # ...but pending carries it
    assert emb.drain(sweep=False) == 1
    assert idx not in emb._pending


def test_label_cleared_rows_leave_pending(store):
    emb = make_embedder(store)
    _request(store, "c")
    idx = store.find_index("c")
    store.label_clear("c", P.LBL_EMBED_REQ)      # request withdrawn
    emb._pending.add(idx)
    assert emb.drain(sweep=False) == 0
    assert idx not in emb._pending


def test_cold_start_picks_up_preexisting_requests(store):
    _request(store, "early")                     # labeled BEFORE attach
    emb = make_embedder(store)
    store.drain_dirty()                          # dirty bits long gone
    assert emb.drain(sweep=False) == 1           # pending from attach()
    assert np.abs(store.vec_get("early")).max() > 0


def test_reconciliation_sweep_catches_lost_notifications(store):
    emb = make_embedder(store)
    emb.drain(sweep=True)
    _request(store, "lost")
    store.drain_dirty()                          # notification lost
    assert emb.drain(sweep=False) == 0           # hot path can't see it
    assert emb.drain(sweep=True) == 1            # sweep reconciles


@pytest.mark.slow
def test_idle_wake_cost_independent_of_nslots():
    """Idle hot-drain cost must not scale with store size.  The old
    behavior (label sweep per wake) was O(nslots) and fails the ratio
    bound below by ~100x."""
    def idle_cost(nslots):
        name = f"/spt-wake-{nslots}"
        Store.unlink(name)
        st = Store.create(name, nslots=nslots, max_val=64, vec_dim=8)
        emb = Embedder(st, encoder_fn=fake_encoder(8))
        emb.attach()
        emb.drain(sweep=True)
        n_iter = 200
        t0 = time.perf_counter()
        for _ in range(n_iter):
            emb.drain(sweep=False)
        dt = (time.perf_counter() - t0) / n_iter
        st.close()
        Store.unlink(name)
        return dt

    small = idle_cost(1024)
    big = idle_cost(128 * 1024)                  # 128x the slots
    assert big < small * 20 + 1e-3, (
        f"idle drain scaled with nslots: {small*1e6:.0f}us -> "
        f"{big*1e6:.0f}us")
