"""Continuous batched serving (completer.run_continuous): requests
join the live batch at chunk boundaries, finished rows free their
slots, and outputs stay token-exact.
"""
from __future__ import annotations

import threading
import time

import jax.numpy as jnp

from libsplinter_tpu import Store
from libsplinter_tpu.engine import protocol as P
from libsplinter_tpu.engine.completer import Completer
from libsplinter_tpu.models.decoder import CompletionModel, DecoderConfig


def test_continuous_serves_staggered_arrivals(tmp_path):
    """Keys arriving WHILE the batch decodes are serviced in the same
    window (join path), and every key gets the full label protocol."""
    name = f"/spt-cont-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=128, max_val=4096, vec_dim=8)
    try:
        model = CompletionModel(DecoderConfig.tiny(max_len=128),
                                buckets=(16, 32), temp=0.0)
        comp = Completer(st, model=model, max_new_tokens=24,
                         flush_tokens=4, template="none", batch_cap=4)
        comp.attach()
        runner = threading.Thread(
            target=comp.run_continuous,
            kwargs=dict(idle_timeout_ms=20, stop_after=90.0),
            daemon=True)
        runner.start()
        time.sleep(0.2)
        # first wave starts the batch
        for i in range(2):
            st.set(f"w1/{i}", f"first wave {i}")
            st.label_or(f"w1/{i}", P.LBL_INFER_REQ)
            st.bump(f"w1/{i}")
        time.sleep(1.0)               # batch is (or was) decoding
        # second wave must join without waiting for a full drain
        for i in range(3):
            st.set(f"w2/{i}", f"second wave {i}")
            st.label_or(f"w2/{i}", P.LBL_INFER_REQ)
            st.bump(f"w2/{i}")
        keys = [f"w1/{i}" for i in range(2)] + [f"w2/{i}" for i in range(3)]
        deadline = time.time() + 75
        while time.time() < deadline:
            if all(st.labels(k) & P.LBL_READY for k in keys):
                break
            time.sleep(0.05)
        comp.stop()
        runner.join(timeout=5)
        for k in keys:
            labels = st.labels(k)
            assert labels & P.LBL_READY, (k, comp.stats)
            assert not labels & (P.LBL_INFER_REQ | P.LBL_SERVICING), k
            val = st.get(k).rstrip(b"\0")
            assert len(val) > len(k) + 8, f"{k}: no completion"
        assert comp.stats.completions == 5
    finally:
        st.close()
        Store.unlink(name)


def test_continuous_defers_oversized_joiner(tmp_path):
    """A prompt longer than a dense live batch's position must NOT be
    clipped into the running batch — it completes with its full
    context."""
    name = f"/spt-defer-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=128, max_val=4096, vec_dim=8)
    try:
        # window 128, buckets (16, 64): a dense short batch would sit
        # at pos=16, behind which a ~40-token joiner cannot reach
        model = CompletionModel(DecoderConfig.tiny(max_len=128),
                                buckets=(16, 64), temp=0.0)
        comp = Completer(st, model=model, max_new_tokens=30,
                         flush_tokens=4, template="none", batch_cap=2)
        comp.attach()
        runner = threading.Thread(
            target=comp.run_continuous,
            kwargs=dict(idle_timeout_ms=20, stop_after=120.0),
            daemon=True)
        runner.start()
        time.sleep(0.2)
        st.set("short", b"hi")
        st.label_or("short", P.LBL_INFER_REQ)
        st.bump("short")
        time.sleep(0.8)               # batch live at pos ~16
        long_prompt = ("tok " * 40).encode()     # ~41 tokens > 16
        st.set("long", long_prompt)
        st.label_or("long", P.LBL_INFER_REQ)
        st.bump("long")
        deadline = time.time() + 100
        while time.time() < deadline:
            if all(st.labels(k) & P.LBL_READY for k in ("short", "long")):
                break
            time.sleep(0.05)
        comp.stop()
        runner.join(timeout=5)
        for k in ("short", "long"):
            assert st.labels(k) & P.LBL_READY, (k, comp.stats)
        # the long prompt's value retains its FULL prompt (not clipped)
        val = st.get("long").rstrip(b"\0")
        assert val.startswith(long_prompt.rstrip()), "prompt was clipped"
        assert len(val) > len(long_prompt), "no completion appended"
    finally:
        st.close()
        Store.unlink(name)


def test_continuous_over_quantized_model(tmp_path):
    """Feature lattice: the slot scheduler serves an int8-resident
    model (its joins included) with the full protocol."""
    name = f"/spt-contq-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=64, max_val=2048, vec_dim=8)
    try:
        model = CompletionModel(
            DecoderConfig.tiny(max_len=128, quantized=True),
            buckets=(16, 32), temp=0.0)
        comp = Completer(st, model=model, max_new_tokens=16,
                         flush_tokens=4, template="none", batch_cap=2)
        comp.attach()
        runner = threading.Thread(
            target=comp.run_continuous,
            kwargs=dict(idle_timeout_ms=20, stop_after=90.0),
            daemon=True)
        runner.start()
        time.sleep(0.2)
        st.set("a", b"first question")
        st.label_or("a", P.LBL_INFER_REQ)
        st.bump("a")
        time.sleep(0.8)
        st.set("b", b"late arrival")    # joins the live batch
        st.label_or("b", P.LBL_INFER_REQ)
        st.bump("b")
        deadline = time.time() + 75
        while time.time() < deadline:
            if all(st.labels(k) & P.LBL_READY for k in ("a", "b")):
                break
            time.sleep(0.05)
        comp.stop()
        runner.join(timeout=5)
        for k in ("a", "b"):
            assert st.labels(k) & P.LBL_READY, (k, comp.stats)
    finally:
        st.close()
        Store.unlink(name)


def test_continuous_falls_back_for_serial_models(tmp_path):
    """Models without the paged surface serve through run()."""
    from libsplinter_tpu.models import SpeculativeCompletionModel

    name = f"/spt-contfb-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=64, max_val=2048, vec_dim=8)
    try:
        t = CompletionModel(DecoderConfig.tiny(dtype=jnp.float32),
                            buckets=(16,), temp=0.0, seed=2)
        d = CompletionModel(
            DecoderConfig.tiny(dtype=jnp.float32, layers=1),
            buckets=(16,), temp=0.0, seed=99)
        spec = SpeculativeCompletionModel(t, d, gamma=3)
        comp = Completer(st, model=spec, max_new_tokens=8,
                         flush_tokens=4, template="none", batch_cap=4)
        comp.attach()
        st.set("q", "fallback prompt")
        st.label_or("q", P.LBL_INFER_REQ)
        runner = threading.Thread(
            target=comp.run_continuous,
            kwargs=dict(idle_timeout_ms=20, stop_after=60.0),
            daemon=True)
        runner.start()
        deadline = time.time() + 50
        while time.time() < deadline:
            if st.labels("q") & P.LBL_READY:
                break
            time.sleep(0.05)
        comp.stop()
        runner.join(timeout=5)
        assert st.labels("q") & P.LBL_READY
    finally:
        st.close()
        Store.unlink(name)
