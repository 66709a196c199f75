"""K-quant dequantization (Q2_K..Q8_K, Q5_0/Q5_1).

Ground truth here is an independent SCALAR implementation of each ggml
block format (written element-by-element from the block layout, the way
the C reference loops do) — the vectorized production decoders in
models/gguf.py must agree bit-exactly on random block bytes.  llama.cpp
itself is not installable in this image; agreement between two
independently-written decoders over random data is the strongest
offline check available.
"""
from __future__ import annotations

import struct

import numpy as np
import pytest

from libsplinter_tpu.models import gguf as G

rng = np.random.default_rng(7)


def f16(x: float) -> bytes:
    return struct.pack("<e", x)


def rand_scale() -> float:
    return float(rng.uniform(0.001, 0.1))


# ---------------------------------------------------- scalar references

def ref_q5_0(blob: bytes, n: int) -> np.ndarray:
    out = np.empty(n, np.float32)
    for b in range(n // 32):
        off = b * 22
        d = np.frombuffer(blob, "<f2", 1, off)[0]
        qh = struct.unpack_from("<I", blob, off + 2)[0]
        qs = blob[off + 6: off + 22]
        for j in range(16):
            x0 = (qs[j] & 0x0F) | (((qh >> j) & 1) << 4)
            x1 = (qs[j] >> 4) | (((qh >> (j + 16)) & 1) << 4)
            out[b * 32 + j] = (x0 - 16) * float(d)
            out[b * 32 + j + 16] = (x1 - 16) * float(d)
    return out


def ref_q5_1(blob: bytes, n: int) -> np.ndarray:
    out = np.empty(n, np.float32)
    for b in range(n // 32):
        off = b * 24
        d = float(np.frombuffer(blob, "<f2", 1, off)[0])
        m = float(np.frombuffer(blob, "<f2", 1, off + 2)[0])
        qh = struct.unpack_from("<I", blob, off + 4)[0]
        qs = blob[off + 8: off + 24]
        for j in range(16):
            x0 = (qs[j] & 0x0F) | (((qh >> j) & 1) << 4)
            x1 = (qs[j] >> 4) | (((qh >> (j + 16)) & 1) << 4)
            out[b * 32 + j] = x0 * d + m
            out[b * 32 + j + 16] = x1 * d + m
    return out


def _scale_min_k4_ref(q: bytes, j: int) -> tuple[int, int]:
    if j < 4:
        return q[j] & 63, q[j + 4] & 63
    return ((q[j + 4] & 0x0F) | ((q[j - 4] >> 6) << 4),
            (q[j + 4] >> 4) | ((q[j] >> 6) << 4))


def ref_q4_k(blob: bytes, n: int) -> np.ndarray:
    out = np.empty(n, np.float32)
    for b in range(n // 256):
        off = b * 144
        d = float(np.frombuffer(blob, "<f2", 1, off)[0])
        dmin = float(np.frombuffer(blob, "<f2", 1, off + 2)[0])
        scales = blob[off + 4: off + 16]
        qs = blob[off + 16: off + 144]
        y = b * 256
        is_ = 0
        for j in range(0, 256, 64):
            sc1, m1 = _scale_min_k4_ref(scales, is_)
            sc2, m2 = _scale_min_k4_ref(scales, is_ + 1)
            q = qs[(j // 64) * 32:(j // 64) * 32 + 32]
            for el in range(32):
                out[y] = d * sc1 * (q[el] & 0x0F) - dmin * m1
                y += 1
            for el in range(32):
                out[y] = d * sc2 * (q[el] >> 4) - dmin * m2
                y += 1
            is_ += 2
    return out


def ref_q5_k(blob: bytes, n: int) -> np.ndarray:
    out = np.empty(n, np.float32)
    for b in range(n // 256):
        off = b * 176
        d = float(np.frombuffer(blob, "<f2", 1, off)[0])
        dmin = float(np.frombuffer(blob, "<f2", 1, off + 2)[0])
        scales = blob[off + 4: off + 16]
        qh = blob[off + 16: off + 48]
        qs = blob[off + 48: off + 176]
        y = b * 256
        is_ = 0
        u1, u2 = 1, 2
        for j in range(0, 256, 64):
            sc1, m1 = _scale_min_k4_ref(scales, is_)
            sc2, m2 = _scale_min_k4_ref(scales, is_ + 1)
            q = qs[(j // 64) * 32:(j // 64) * 32 + 32]
            for el in range(32):
                hi = 16 if qh[el] & u1 else 0
                out[y] = d * sc1 * ((q[el] & 0x0F) + hi) - dmin * m1
                y += 1
            for el in range(32):
                hi = 16 if qh[el] & u2 else 0
                out[y] = d * sc2 * ((q[el] >> 4) + hi) - dmin * m2
                y += 1
            is_ += 2
            u1 <<= 2
            u2 <<= 2
    return out


def ref_q6_k(blob: bytes, n: int) -> np.ndarray:
    out = np.empty(n, np.float32)
    for b in range(n // 256):
        off = b * 210
        ql = blob[off: off + 128]
        qh = blob[off + 128: off + 192]
        sc = struct.unpack_from("<16b", blob, off + 192)
        d = float(np.frombuffer(blob, "<f2", 1, off + 208)[0])
        y = b * 256
        for half in range(2):
            qlh = ql[half * 64: half * 64 + 64]
            qhh = qh[half * 32: half * 32 + 32]
            sch = sc[half * 8: half * 8 + 8]
            for el in range(32):
                is_ = el // 16
                q1 = ((qlh[el] & 0x0F) | (((qhh[el] >> 0) & 3) << 4)) - 32
                q2 = ((qlh[el + 32] & 0x0F) |
                      (((qhh[el] >> 2) & 3) << 4)) - 32
                q3 = ((qlh[el] >> 4) | (((qhh[el] >> 4) & 3) << 4)) - 32
                q4 = ((qlh[el + 32] >> 4) |
                      (((qhh[el] >> 6) & 3) << 4)) - 32
                out[y + el] = d * sch[is_ + 0] * q1
                out[y + el + 32] = d * sch[is_ + 2] * q2
                out[y + el + 64] = d * sch[is_ + 4] * q3
                out[y + el + 96] = d * sch[is_ + 6] * q4
            y += 128
    return out


def ref_q2_k(blob: bytes, n: int) -> np.ndarray:
    out = np.empty(n, np.float32)
    for b in range(n // 256):
        off = b * 84
        scales = blob[off: off + 16]
        qs = blob[off + 16: off + 80]
        d = float(np.frombuffer(blob, "<f2", 1, off + 80)[0])
        dmin = float(np.frombuffer(blob, "<f2", 1, off + 82)[0])
        y = b * 256
        is_ = 0
        for half in range(2):
            q = qs[half * 32: half * 32 + 32]
            for j in range(4):
                shift = 2 * j
                sc = scales[is_]
                is_ += 1
                for el in range(16):
                    out[y] = (d * (sc & 0x0F) * ((q[el] >> shift) & 3) -
                              dmin * (sc >> 4))
                    y += 1
                sc = scales[is_]
                is_ += 1
                for el in range(16, 32):
                    out[y] = (d * (sc & 0x0F) * ((q[el] >> shift) & 3) -
                              dmin * (sc >> 4))
                    y += 1
    return out


def ref_q3_k(blob: bytes, n: int) -> np.ndarray:
    out = np.empty(n, np.float32)
    for b in range(n // 256):
        off = b * 110
        hmask = blob[off: off + 32]
        qs = blob[off + 32: off + 96]
        raw_sc = blob[off + 96: off + 108]
        d = float(np.frombuffer(blob, "<f2", 1, off + 108)[0])
        a0, a1, t = struct.unpack("<3I", raw_sc)
        k1, k2 = 0x03030303, 0x0F0F0F0F
        words = [
            (a0 & k2) | (((t >> 0) & k1) << 4),
            (a1 & k2) | (((t >> 2) & k1) << 4),
            ((a0 >> 4) & k2) | (((t >> 4) & k1) << 4),
            ((a1 >> 4) & k2) | (((t >> 6) & k1) << 4),
        ]
        sc16 = [x - 32 if x < 128 else x - 288  # int8 view of each byte
                for w in words for x in struct.pack("<I", w)]
        y = b * 256
        is_ = 0
        m = 1
        for half in range(2):
            q = qs[half * 32: half * 32 + 32]
            for j in range(4):
                shift = 2 * j
                for grp, lo in ((0, 0), (1, 16)):
                    dl = d * sc16[is_]
                    is_ += 1
                    for el in range(lo, lo + 16):
                        hi = 0 if hmask[el] & m else 4
                        out[y] = dl * (((q[el] >> shift) & 3) - hi)
                        y += 1
                m <<= 1
    return out


def ref_q8_k(blob: bytes, n: int) -> np.ndarray:
    out = np.empty(n, np.float32)
    for b in range(n // 256):
        off = b * 292
        d = struct.unpack_from("<f", blob, off)[0]
        qs = struct.unpack_from("<256b", blob, off + 4)
        out[b * 256: b * 256 + 256] = np.array(qs, np.float32) * d
    return out


# ------------------------------------------------------- random blocks

def _rand_block_bytes(fmt: str, nblocks: int) -> bytes:
    """Random-but-sane block bytes: random payload bits, bounded f16/f32
    scales (no inf/nan)."""
    out = b""
    for _ in range(nblocks):
        if fmt == "q5_0":
            out += (f16(rand_scale()) +
                    bytes(rng.integers(0, 256, 20, dtype=np.uint8)))
        elif fmt == "q5_1":
            out += (f16(rand_scale()) + f16(rand_scale() * 3) +
                    bytes(rng.integers(0, 256, 20, dtype=np.uint8)))
        elif fmt == "q4_k":
            out += (f16(rand_scale()) + f16(rand_scale()) +
                    bytes(rng.integers(0, 256, 140, dtype=np.uint8)))
        elif fmt == "q5_k":
            out += (f16(rand_scale()) + f16(rand_scale()) +
                    bytes(rng.integers(0, 256, 172, dtype=np.uint8)))
        elif fmt == "q6_k":
            out += (bytes(rng.integers(0, 256, 208, dtype=np.uint8)) +
                    f16(rand_scale()))
        elif fmt == "q2_k":
            out += (bytes(rng.integers(0, 256, 80, dtype=np.uint8)) +
                    f16(rand_scale()) + f16(rand_scale()))
        elif fmt == "q3_k":
            out += (bytes(rng.integers(0, 256, 108, dtype=np.uint8)) +
                    f16(rand_scale()))
        elif fmt == "q8_k":
            out += (struct.pack("<f", rand_scale()) +
                    bytes(rng.integers(0, 256, 288, dtype=np.uint8)))
        else:
            raise AssertionError(fmt)
    return out


CASES = [
    ("q5_0", 32, G._dequant_q5_0, ref_q5_0),
    ("q5_1", 32, G._dequant_q5_1, ref_q5_1),
    ("q2_k", 256, G._dequant_q2_k, ref_q2_k),
    ("q3_k", 256, G._dequant_q3_k, ref_q3_k),
    ("q4_k", 256, G._dequant_q4_k, ref_q4_k),
    ("q5_k", 256, G._dequant_q5_k, ref_q5_k),
    ("q6_k", 256, G._dequant_q6_k, ref_q6_k),
    ("q8_k", 256, G._dequant_q8_k, ref_q8_k),
]


@pytest.mark.parametrize("fmt,blk,vec_fn,ref_fn", CASES,
                         ids=[c[0] for c in CASES])
def test_vectorized_matches_scalar_reference(fmt, blk, vec_fn, ref_fn):
    nblocks = 7
    n = nblocks * blk
    blob = _rand_block_bytes(fmt, nblocks)
    got = vec_fn(blob, 0, n)
    want = ref_fn(blob, n)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7,
                               err_msg=fmt)


@pytest.mark.parametrize("fmt,blk,vec_fn,ref_fn", CASES,
                         ids=[c[0] for c in CASES])
def test_offset_and_padding(fmt, blk, vec_fn, ref_fn):
    """Decoders must honor a nonzero start offset into the buffer."""
    nblocks = 3
    n = nblocks * blk
    pad = b"\xAA" * 37
    blob = _rand_block_bytes(fmt, nblocks)
    got = vec_fn(pad + blob, len(pad), n)
    np.testing.assert_allclose(got, ref_fn(blob, n), rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("fmt,blk,vec_fn,ref_fn", CASES,
                         ids=[c[0] for c in CASES])
def test_non_multiple_size_is_loud(fmt, blk, vec_fn, ref_fn):
    with pytest.raises(G.GgufError, match="not a multiple"):
        vec_fn(b"\0" * 1024, 0, blk + 1)


def test_container_reads_kquant_tensor(tmp_path):
    """A GGUF carrying a Q6_K tensor dequantizes through the normal
    GgufFile.tensor path (the round-1 gap: K-quants were unreadable,
    gguf.py:44-56)."""
    from tests.test_gguf import _kv, _s
    nblocks = 4
    n = nblocks * 256
    blob = _rand_block_bytes("q6_k", nblocks)
    header = struct.pack("<IIQQ", 0x46554747, 3, 1, 0)
    info = (_s("w") + struct.pack("<I", 1) + struct.pack("<Q", n) +
            struct.pack("<IQ", G.GGML_Q6_K, 0))
    head = header + info
    pad = (-len(head)) % 32
    p = tmp_path / "kq.gguf"
    p.write_bytes(head + b"\0" * pad + blob)
    with G.GgufFile(p) as gf:
        got = gf.tensor("w")
    np.testing.assert_allclose(got, ref_q6_k(blob, n), rtol=1e-6,
                               atol=1e-7)
