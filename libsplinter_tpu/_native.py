"""Loader for the native core library (libsptpu.so).

Builds on demand with make if the shared object is missing or older than its
sources, then binds the full C ABI via ctypes.  The C prototypes mirror
native/include/sptpu.h exactly.

**Threads and the interpreter lock.**  `ctypes.CDLL` drops the
interpreter lock on the way into every call and has to win it back on
the way out; with many client threads awake at once each store call
then costs a round of hand-overs (~30 us a waiter) where the call
itself is a microsecond.  So the shared object is opened twice — the
second handle through `ctypes.PyDLL`, which KEEPS the lock; `dlopen`
gives the same mapping, so the library's state exists once — and the
symbols in `KEEPS_LOCK` are taken from that handle: `spt_vec_set`,
`spt_find_index`, `spt_set`, `spt_get`, `spt_label_or`,
`spt_get_labels`, `spt_bump`.  Every other symbol drops the lock as
before, and every call that waits, sleeps, or is linear in the slots or
in a batch must.  `get_lib().spt_set` IS the lock-keeping function:
there is one path, no option.  A new symbol is classified by the rule
above `KEEPS_LOCK`; `tests/test_native_binding.py` fails until it is.
"""
from __future__ import annotations

import ctypes as C
import os
import subprocess
from pathlib import Path

_NATIVE_DIR = Path(__file__).resolve().parent.parent / "native"
_LIB_PATH = _NATIVE_DIR / "build" / "libsptpu.so"

KEY_MAX = 128
SIGNAL_GROUPS = 64
MAX_BIDS = 32
DIRTY_WORDS = 16
JOURNAL_CAP = 65536
BLOOM_BITS = 64

# open/create flags
BACKEND_SHM = 0
BACKEND_FILE = 1 << 0
CREATE_EXCL = 1 << 1

# slot types
T_VOID, T_BIGINT, T_BIGUINT, T_JSON = 0x00, 0x01, 0x02, 0x04
T_BINARY, T_IMGDATA, T_AUDIO, T_VARTEXT = 0x08, 0x10, 0x20, 0x40
T_MASK = 0xFF
F_SYSTEM = 1 << 16

# integer ops
IOP_AND, IOP_OR, IOP_XOR, IOP_NOT, IOP_INC, IOP_DEC, IOP_ADD, IOP_SUB = range(8)

# advisement intents
ADV_NORMAL, ADV_SEQUENTIAL, ADV_RANDOM, ADV_WILLNEED, ADV_DONTNEED = range(5)

# mop modes
MOP_OFF, MOP_HYBRID, MOP_FULL = 0, 1, 2


class HeaderView(C.Structure):
    _fields_ = [
        ("magic", C.c_uint32), ("version", C.c_uint32),
        ("nslots", C.c_uint32), ("max_val", C.c_uint32),
        ("vec_dim", C.c_uint32), ("mop_mode", C.c_uint32),
        ("map_size", C.c_uint64), ("global_epoch", C.c_uint64),
        ("core_flags", C.c_uint32), ("user_flags", C.c_uint32),
        ("parse_failures", C.c_uint64), ("last_failure_epoch", C.c_uint64),
        ("bus_pid", C.c_int64), ("used_slots", C.c_uint32),
    ]


class SlotView(C.Structure):
    _fields_ = [
        ("epoch", C.c_uint64), ("hash", C.c_uint64),
        ("labels", C.c_uint64), ("watcher_mask", C.c_uint64),
        ("val_len", C.c_uint32), ("flags", C.c_uint32),
        ("ctime", C.c_int64), ("atime", C.c_int64),
        ("index", C.c_int32), ("key", C.c_char * KEY_MAX),
    ]


class BidView(C.Structure):
    _fields_ = [
        ("pid", C.c_int64), ("shard_id", C.c_uint64),
        ("claimed_at", C.c_uint64), ("duration", C.c_uint64),
        ("intent", C.c_uint32), ("priority", C.c_uint32),
        ("live", C.c_int32),
    ]


def _build() -> None:
    subprocess.run(
        ["make", "-s"], cwd=_NATIVE_DIR, check=True,
        env={**os.environ, "CC": os.environ.get("CC", "cc")},
    )


def _needs_build() -> bool:
    if not _LIB_PATH.exists():
        return True
    lib_mtime = _LIB_PATH.stat().st_mtime
    for src in ("src/store.c", "src/coord.c", "src/wptok.c",
                "src/internal.h", "include/sptpu.h"):
        p = _NATIVE_DIR / src
        if p.exists() and p.stat().st_mtime > lib_mtime:
            return True
    return False


# Symbols bound through `ctypes.PyDLL`: the call KEEPS the interpreter
# lock.  The request protocol's calls (what a client thread makes
# between an answer and its next request; PR 35), and no more.
#
# The rule for membership, checked against the C — all four hold:
#   * bounded work: a probe, at most one value or vector copied, a few
#     atomics — nothing linear in the slots or in a batch;
#   * no sleep and no wait: `spt__lock` is a TRY-lock that returns
#     -EAGAIN (store.c); the retry and its `time.sleep(0)` are Python's
#     (`store._retry`), so a contended slot still yields the lock;
#   * no syscall that can block: `spt_bump`'s and every write's fan-out
#     is counter increments and one write to a NON-BLOCKING eventfd
#     (coord.c `spt__fanout` / `bus_notify`; a process's first write
#     after an arming also attaches to the bus: two pidfd syscalls);
#   * no callback into Python.
# Whatever waits (`spt_poll`, `spt_poll_labels`, `spt_signal_wait`,
# `spt_bus_wait`) or walks the slots or a batch (`spt_enumerate*`,
# `spt_list`, `spt_changed_since`, `spt_epochs*`, `spt_vec_gather`,
# `spt_vec_commit_batch`, `spt_purge`, the tokenizer) stays off it: held
# across such a call the lock would stop every other thread.
#
# The worst case, with the lock held.  `spt__probe_find` walks from the
# key's home slot to the first NEVER-USED slot.  At the search cell's
# 91.7% load (1,441,792 keys in 1,572,864 slots, linear probing) a hit
# costs ~6.5 probes and a miss ~73 on average: under a microsecond.  A
# table whose free slots are all TOMBSTONES has no never-used slot and a
# miss walks all `nslots`: 1,572,864 slot headers there, milliseconds,
# once a call — today that stalls one thread, bound here it stalls the
# process.  (`spt_purge` does not reset tombstones; a store that churns
# keys without end wants a rebuild either way.)  A value copy is at most
# `max_val` bytes and a vector `vec_dim` floats: 2,048 B and 3,072 B in
# the search cell; the largest `max_val` a benchmark cell creates is
# 32,768 B (the kimi and trinity stores: prompts of tens of KB), a
# `memcpy` of 1-3 us, and `spt_set` under `MOP_FULL` zeroes `max_val`
# bytes more.
KEEPS_LOCK = frozenset({
    "spt_vec_set", "spt_find_index", "spt_set", "spt_get",
    "spt_label_or", "spt_get_labels", "spt_bump",
})


def load() -> C.CDLL:
    if _needs_build():
        _build()
    lib = C.CDLL(str(_LIB_PATH), use_errno=True)
    # the same mapping a second time (dlopen counts references): one
    # copy of the library's state, two calling conventions
    _declare(lib, C.PyDLL(str(_LIB_PATH), use_errno=True))
    return lib


def _sigs() -> dict[str, tuple]:
    """Every bound symbol: name -> (restype, argtypes)."""
    P = C.c_void_p
    u32, u64, i32, i64 = C.c_uint32, C.c_uint64, C.c_int32, C.c_int64
    cs = C.c_char_p

    return {
        "spt_create": (P, [cs, u32, u32, u32, u32]),
        "spt_open": (P, [cs, u32]),
        "spt_open_numa": (P, [cs, u32, i32, C.POINTER(i32)]),
        "spt_close": (i32, [P]),
        "spt_unlink": (i32, [cs, u32]),
        "spt_nslots": (u32, [P]),
        "spt_max_val": (u32, [P]),
        "spt_vec_dim": (u32, [P]),
        "spt_vec_lane": (P, [P]),
        "spt_values_base": (P, [P]),
        "spt_last_error": (i32, []),
        "spt_set": (i32, [P, cs, C.c_void_p, u32]),
        "spt_get": (i32, [P, cs, C.c_void_p, u32, C.POINTER(u32)]),
        "spt_unset": (i32, [P, cs]),
        "spt_append": (i32, [P, cs, C.c_void_p, u32]),
        "spt_list": (i32, [P, C.c_void_p, u32]),
        "spt_poll": (i32, [P, cs, i32]),
        "spt_poll_labels": (i32, [P, cs, u64, u64, i32]),
        "spt_get_raw": (i32, [P, cs, C.POINTER(C.c_void_p), C.POINTER(u32),
                              C.POINTER(u64)]),
        "spt_find_index": (i32, [P, cs]),
        "spt_key_at": (i32, [P, u32, C.c_void_p]),
        "spt_epoch_at": (u64, [P, u32]),
        "spt_get_at": (i32, [P, u32, C.c_void_p, u32, C.POINTER(u32)]),
        "spt_labels_at": (u64, [P, u32]),
        "spt_flags_at": (u32, [P, u32]),
        "spt_header_snapshot": (i32, [P, C.POINTER(HeaderView)]),
        "spt_slot_snapshot": (i32, [P, cs, C.POINTER(SlotView)]),
        "spt_slot_snapshot_at": (i32, [P, u32, C.POINTER(SlotView)]),
        "spt_set_type": (i32, [P, cs, u32]),
        "spt_get_type": (i32, [P, cs, C.POINTER(u32)]),
        "spt_integer_op": (i32, [P, cs, i32, u64, C.POINTER(u64)]),
        "spt_tandem_set": (i32, [P, cs, u32, C.c_void_p, u32]),
        "spt_tandem_get": (i32, [P, cs, u32, C.c_void_p, u32,
                                 C.POINTER(u32)]),
        "spt_tandem_unset": (i32, [P, cs, u32]),
        "spt_tandem_count": (i32, [P, cs]),
        "spt_label_or": (i32, [P, cs, u64]),
        "spt_label_andnot": (i32, [P, cs, u64]),
        "spt_get_labels": (i32, [P, cs, C.POINTER(u64)]),
        "spt_enumerate": (i32, [P, u64, C.POINTER(u32), u32]),
        "spt_enumerate_prefix": (i32, [P, cs, C.POINTER(u32), u32,
                                       C.POINTER(u32)]),
        "spt_watch_register": (i32, [P, cs, u32]),
        "spt_watch_unregister": (i32, [P, cs, u32]),
        "spt_watch_label_register": (i32, [P, u32, u32]),
        "spt_watch_label_unregister": (i32, [P, u32, u32]),
        "spt_signal_count": (u64, [P, u32]),
        "spt_signal_pulse": (i32, [P, u32]),
        "spt_bump": (i32, [P, cs]),
        "spt_signal_wait": (i32, [P, u32, u64, i32, C.POINTER(u64)]),
        "spt_bus_init": (i32, [P]),
        "spt_bus_open": (i32, [P]),
        "spt_bus_wait": (i32, [P, i32]),
        "spt_bus_close": (i32, [P]),
        "spt_bus_drain": (i32, [P, C.POINTER(u64)]),
        "spt_bus_peek": (i32, [P, C.POINTER(u64)]),
        "spt_shard_claim": (i32, [P, u64, i32, u32, u64]),
        "spt_shard_claim_ex": (i32, [P, u64, i64, i32, u32, u64, u64]),
        "spt_shard_rebid": (i32, [P, i32]),
        "spt_shard_release": (i32, [P, i32]),
        "spt_shard_election": (i32, [P]),
        "spt_bid_info": (i32, [P, i32, C.POINTER(BidView)]),
        "spt_madvise": (i32, [P, i32, u64, u64, i32, i32]),
        "spt_set_mop": (i32, [P, u32]),
        "spt_get_mop": (u32, [P]),
        "spt_purge": (i32, [P]),
        "spt_retrain": (i32, [P, cs]),
        "spt_set_system": (i32, [P, cs]),
        "spt_slot_usr_set": (i32, [P, cs, C.c_uint8]),
        "spt_slot_usr_get": (i32, [P, cs, C.POINTER(C.c_uint8)]),
        "spt_config_set_user": (i32, [P, u32]),
        "spt_config_get_user": (u32, [P]),
        "spt_now": (u64, []),
        "spt_ticks_per_us": (u64, []),
        "spt_stamp": (i32, [P, cs, i32, u64]),
        "spt_vec_set": (i32, [P, cs, C.c_void_p, u32]),
        "spt_vec_get": (i32, [P, cs, C.c_void_p, u32]),
        "spt_vec_set_at": (i32, [P, u32, C.c_void_p, u32]),
        "spt_vec_get_at": (i32, [P, u32, C.c_void_p, u32]),
        "spt_vec_commit_batch": (i32, [P, C.POINTER(u32), C.POINTER(u64),
                                       C.c_void_p, u32, u32, i32,
                                       C.POINTER(i32)]),
        "spt_epochs": (i32, [P, C.POINTER(u64)]),
        "spt_journal_head": (u64, [P]),
        "spt_changed_since": (i32, [P, u64, C.POINTER(u32), u32,
                                    C.POINTER(u64)]),
        "spt_epochs_at": (i32, [P, C.POINTER(u32), u32, C.POINTER(u64)]),
        "spt_vec_gather": (i32, [P, C.POINTER(u32), u32, C.c_void_p,
                                 C.POINTER(u64)]),
        "spt_report_parse_failure": (i32, [P]),
        # host tokenizer (wptok.c)
        "spt_wptok_create": (C.c_void_p,
                             [C.POINTER(C.c_char_p), u32, i32]),
        "spt_wptok_create_hashed": (C.c_void_p, [u32, i32]),
        "spt_wptok_destroy": (None, [C.c_void_p]),
        "spt_wptok_encode": (i32, [C.c_void_p, C.c_char_p,
                                   C.POINTER(u32), u32]),
        "spt_wptok_encode_batch": (i32, [C.c_void_p,
                                         C.POINTER(C.c_char_p), u32,
                                         u32, C.POINTER(u32),
                                         C.POINTER(u32)]),
    }


def _declare(lib: C.CDLL, keeps: C.PyDLL) -> None:
    for name, (res, args) in _sigs().items():
        fn = getattr(keeps if name in KEEPS_LOCK else lib, name)
        setattr(lib, name, fn)       # get_lib().<name> IS this function
        fn.restype = res
        fn.argtypes = args


_lib: C.CDLL | None = None


def get_lib() -> C.CDLL:
    global _lib
    if _lib is None:
        _lib = load()
    return _lib


def build_id() -> str:
    """Native build identity (git describe + build date, stamped by
    native/Makefile).  'unstamped' for ad-hoc compiles.  Resolved as an
    OPTIONAL symbol — a pre-stamp .so must keep loading for every other
    caller, so spt_build_id is not in _declare's mandatory table."""
    try:
        fn = getattr(get_lib(), "spt_build_id", None)
        if fn is None:
            return "unavailable (rebuild native/)"
        fn.restype = C.c_char_p
        fn.argtypes = []
        return fn().decode()
    except OSError:
        return "unavailable"
