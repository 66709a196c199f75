//! Raw FFI bindings to the splinter-tpu native store (`libsptpu`).
//!
//! Hand-maintained against `native/include/sptpu.h` (capability parity with
//! the reference's bindgen-generated libsplinter-sys crate).  Everything is
//! `unsafe extern "C"`; returns follow the library's negative-errno
//! discipline (0 ok, `-EAGAIN` retry, `-ENOENT` missing, ...).
//!
//! ```no_run
//! use libsptpu_sys::*;
//! use std::ffi::CString;
//! unsafe {
//!     let name = CString::new("/demo").unwrap();
//!     let st = spt_create(name.as_ptr(), 1024, 4096, 768, SPT_CREATE_EXCL);
//!     assert!(!st.is_null());
//!     let k = CString::new("greeting").unwrap();
//!     let v = b"hello rust";
//!     spt_set(st, k.as_ptr(), v.as_ptr() as *const _, v.len() as u32);
//!     spt_close(st);
//! }
//! ```
#![allow(non_camel_case_types)]

use std::os::raw::{c_char, c_int, c_void};

pub const SPT_KEY_MAX: usize = 128;
pub const SPT_SIGNAL_GROUPS: u32 = 64;
pub const SPT_MAX_BIDS: u32 = 32;
pub const SPT_DIRTY_WORDS: usize = 16;
pub const SPT_JOURNAL_CAP: u32 = 65536;

pub const SPT_BACKEND_SHM: u32 = 0;
pub const SPT_BACKEND_FILE: u32 = 1 << 0;
pub const SPT_CREATE_EXCL: u32 = 1 << 1;

pub const SPT_T_VOID: u32 = 0x00;
pub const SPT_T_BIGINT: u32 = 0x01;
pub const SPT_T_BIGUINT: u32 = 0x02;
pub const SPT_T_JSON: u32 = 0x04;
pub const SPT_T_BINARY: u32 = 0x08;
pub const SPT_T_IMGDATA: u32 = 0x10;
pub const SPT_T_AUDIO: u32 = 0x20;
pub const SPT_T_VARTEXT: u32 = 0x40;
pub const SPT_F_SYSTEM: u32 = 1 << 16;

pub const SPT_MOP_OFF: u32 = 0;
pub const SPT_MOP_HYBRID: u32 = 1;
pub const SPT_MOP_FULL: u32 = 2;

#[repr(C)]
#[derive(Debug, Clone, Copy, PartialEq, Eq)]
pub enum spt_iop_t {
    AND = 0,
    OR,
    XOR,
    NOT,
    INC,
    DEC,
    ADD,
    SUB,
}

#[repr(C)]
#[derive(Debug, Clone, Copy, PartialEq, Eq)]
pub enum spt_advice_t {
    NORMAL = 0,
    SEQUENTIAL,
    RANDOM,
    WILLNEED,
    DONTNEED,
}

/// Opaque store handle.
#[repr(C)]
pub struct spt_store {
    _priv: [u8; 0],
}

#[repr(C)]
#[derive(Debug, Clone, Copy, Default)]
pub struct spt_header_view {
    pub magic: u32,
    pub version: u32,
    pub nslots: u32,
    pub max_val: u32,
    pub vec_dim: u32,
    pub mop_mode: u32,
    pub map_size: u64,
    pub global_epoch: u64,
    pub core_flags: u32,
    pub user_flags: u32,
    pub parse_failures: u64,
    pub last_failure_epoch: u64,
    pub bus_pid: i64,
    pub used_slots: u32,
}

#[repr(C)]
#[derive(Debug, Clone, Copy)]
pub struct spt_slot_view {
    pub epoch: u64,
    pub hash: u64,
    pub labels: u64,
    pub watcher_mask: u64,
    pub val_len: u32,
    pub flags: u32,
    pub ctime: i64,
    pub atime: i64,
    pub index: i32,
    pub key: [c_char; SPT_KEY_MAX],
}

#[repr(C)]
#[derive(Debug, Clone, Copy, Default)]
pub struct spt_bid_view {
    pub pid: i64,
    pub shard_id: u64,
    pub claimed_at: u64,
    pub duration: u64,
    pub intent: u32,
    pub priority: u32,
    pub live: i32,
}

extern "C" {
    // lifecycle
    pub fn spt_create(name: *const c_char, nslots: u32, max_val: u32,
                      vec_dim: u32, flags: u32) -> *mut spt_store;
    pub fn spt_open(name: *const c_char, flags: u32) -> *mut spt_store;
    pub fn spt_open_numa(name: *const c_char, flags: u32, node: c_int,
                         bind_rc: *mut c_int) -> *mut spt_store;
    pub fn spt_close(st: *mut spt_store) -> c_int;
    pub fn spt_unlink(name: *const c_char, flags: u32) -> c_int;

    // geometry / raw access
    pub fn spt_nslots(st: *const spt_store) -> u32;
    pub fn spt_max_val(st: *const spt_store) -> u32;
    pub fn spt_vec_dim(st: *const spt_store) -> u32;
    pub fn spt_vec_lane(st: *mut spt_store) -> *mut c_void;
    pub fn spt_values_base(st: *mut spt_store) -> *mut c_void;
    pub fn spt_last_error() -> c_int;

    // KV
    pub fn spt_set(st: *mut spt_store, key: *const c_char, val: *const c_void,
                   len: u32) -> c_int;
    pub fn spt_get(st: *mut spt_store, key: *const c_char, buf: *mut c_void,
                   cap: u32, len_out: *mut u32) -> c_int;
    pub fn spt_unset(st: *mut spt_store, key: *const c_char) -> c_int;
    pub fn spt_append(st: *mut spt_store, key: *const c_char,
                      val: *const c_void, len: u32) -> c_int;
    pub fn spt_list(st: *mut spt_store, keys: *mut c_char, max_keys: u32)
                    -> c_int;
    pub fn spt_poll(st: *mut spt_store, key: *const c_char, timeout_ms: c_int)
                    -> c_int;
    pub fn spt_poll_labels(st: *mut spt_store, key: *const c_char, mask: u64,
                           want: u64, timeout_ms: c_int) -> c_int;
    pub fn spt_get_raw(st: *mut spt_store, key: *const c_char,
                       ptr: *mut *const c_void, len_out: *mut u32,
                       epoch_out: *mut u64) -> c_int;

    // index-based access
    pub fn spt_find_index(st: *mut spt_store, key: *const c_char) -> c_int;
    pub fn spt_key_at(st: *mut spt_store, idx: u32, key_out: *mut c_char)
                      -> c_int;
    pub fn spt_epoch_at(st: *mut spt_store, idx: u32) -> u64;
    pub fn spt_get_at(st: *mut spt_store, idx: u32, buf: *mut c_void,
                      cap: u32, len_out: *mut u32) -> c_int;
    pub fn spt_labels_at(st: *mut spt_store, idx: u32) -> u64;
    pub fn spt_flags_at(st: *mut spt_store, idx: u32) -> u32;

    // snapshots
    pub fn spt_header_snapshot(st: *mut spt_store, out: *mut spt_header_view)
                               -> c_int;
    pub fn spt_slot_snapshot(st: *mut spt_store, key: *const c_char,
                             out: *mut spt_slot_view) -> c_int;
    pub fn spt_slot_snapshot_at(st: *mut spt_store, idx: u32,
                                out: *mut spt_slot_view) -> c_int;

    // typed slots / integer ops
    pub fn spt_set_type(st: *mut spt_store, key: *const c_char,
                        type_flag: u32) -> c_int;
    pub fn spt_get_type(st: *mut spt_store, key: *const c_char,
                        type_out: *mut u32) -> c_int;
    pub fn spt_integer_op(st: *mut spt_store, key: *const c_char,
                          op: spt_iop_t, operand: u64, result_out: *mut u64)
                          -> c_int;

    // tandem keys
    pub fn spt_tandem_set(st: *mut spt_store, base: *const c_char, order: u32,
                          val: *const c_void, len: u32) -> c_int;
    pub fn spt_tandem_get(st: *mut spt_store, base: *const c_char, order: u32,
                          buf: *mut c_void, cap: u32, len_out: *mut u32)
                          -> c_int;
    pub fn spt_tandem_unset(st: *mut spt_store, base: *const c_char,
                            max_order: u32) -> c_int;
    pub fn spt_tandem_count(st: *mut spt_store, base: *const c_char) -> c_int;

    // bloom labels
    pub fn spt_label_or(st: *mut spt_store, key: *const c_char, mask: u64)
                        -> c_int;
    pub fn spt_label_andnot(st: *mut spt_store, key: *const c_char, mask: u64)
                            -> c_int;
    pub fn spt_get_labels(st: *mut spt_store, key: *const c_char,
                          out: *mut u64) -> c_int;
    pub fn spt_enumerate(st: *mut spt_store, mask: u64, idx_out: *mut u32,
                         max_out: u32) -> c_int;
    pub fn spt_enumerate_prefix(st: *mut spt_store, prefix: *const c_char,
                                idx_out: *mut u32, max_out: u32,
                                live_out: *mut u32) -> c_int;

    // signal arena
    pub fn spt_watch_register(st: *mut spt_store, key: *const c_char,
                              group: u32) -> c_int;
    pub fn spt_watch_unregister(st: *mut spt_store, key: *const c_char,
                                group: u32) -> c_int;
    pub fn spt_watch_label_register(st: *mut spt_store, bloom_bit: u32,
                                    group: u32) -> c_int;
    pub fn spt_watch_label_unregister(st: *mut spt_store, bloom_bit: u32,
                                      group: u32) -> c_int;
    pub fn spt_signal_count(st: *mut spt_store, group: u32) -> u64;
    pub fn spt_signal_pulse(st: *mut spt_store, group: u32) -> c_int;
    pub fn spt_bump(st: *mut spt_store, key: *const c_char) -> c_int;
    pub fn spt_signal_wait(st: *mut spt_store, group: u32, last: u64,
                           timeout_ms: c_int, count_out: *mut u64) -> c_int;

    // event bus
    pub fn spt_bus_init(st: *mut spt_store) -> c_int;
    pub fn spt_bus_open(st: *mut spt_store) -> c_int;
    pub fn spt_bus_wait(st: *mut spt_store, timeout_ms: c_int) -> c_int;
    pub fn spt_bus_close(st: *mut spt_store) -> c_int;
    pub fn spt_bus_drain(st: *mut spt_store,
                         dirty_out: *mut u64 /* [SPT_DIRTY_WORDS] */) -> c_int;
    pub fn spt_bus_peek(st: *mut spt_store,
                        dirty_out: *mut u64 /* [SPT_DIRTY_WORDS] */) -> c_int;

    // shard bids & advisement
    pub fn spt_shard_claim(st: *mut spt_store, shard_id: u64,
                           intent: spt_advice_t, priority: u32,
                           duration_us: u64) -> c_int;
    pub fn spt_shard_claim_ex(st: *mut spt_store, shard_id: u64, pid: i64,
                              intent: spt_advice_t, priority: u32,
                              duration_us: u64, claimed_at_us: u64) -> c_int;
    pub fn spt_shard_rebid(st: *mut spt_store, bid_idx: c_int) -> c_int;
    pub fn spt_shard_release(st: *mut spt_store, bid_idx: c_int) -> c_int;
    pub fn spt_shard_election(st: *mut spt_store) -> c_int;
    pub fn spt_bid_info(st: *mut spt_store, bid_idx: c_int,
                        out: *mut spt_bid_view) -> c_int;
    pub fn spt_madvise(st: *mut spt_store, bid_idx: c_int, offset: u64,
                       len: u64, advice: spt_advice_t, timeout_ms: c_int)
                       -> c_int;

    // mop / purge / recovery
    pub fn spt_set_mop(st: *mut spt_store, mode: u32) -> c_int;
    pub fn spt_get_mop(st: *mut spt_store) -> u32;
    pub fn spt_purge(st: *mut spt_store) -> c_int;
    pub fn spt_retrain(st: *mut spt_store, key: *const c_char) -> c_int;

    // system keys & flags
    pub fn spt_set_system(st: *mut spt_store, key: *const c_char) -> c_int;
    pub fn spt_slot_usr_set(st: *mut spt_store, key: *const c_char, bits: u8)
                            -> c_int;
    pub fn spt_slot_usr_get(st: *mut spt_store, key: *const c_char,
                            out: *mut u8) -> c_int;
    pub fn spt_config_set_user(st: *mut spt_store, bits: u32) -> c_int;
    pub fn spt_config_get_user(st: *mut spt_store) -> u32;

    // timestamps
    pub fn spt_now() -> u64;
    pub fn spt_ticks_per_us() -> u64;
    pub fn spt_stamp(st: *mut spt_store, key: *const c_char, which: c_int,
                     ticks_ago: u64) -> c_int;

    // embedding vector lane
    pub fn spt_vec_set(st: *mut spt_store, key: *const c_char,
                       vec: *const f32, dim: u32) -> c_int;
    pub fn spt_vec_get(st: *mut spt_store, key: *const c_char, out: *mut f32,
                       dim: u32) -> c_int;
    pub fn spt_vec_set_at(st: *mut spt_store, idx: u32, vec: *const f32,
                          dim: u32) -> c_int;
    pub fn spt_vec_get_at(st: *mut spt_store, idx: u32, out: *mut f32,
                          dim: u32) -> c_int;
    pub fn spt_vec_commit_batch(st: *mut spt_store, rows: *const u32,
                                epochs: *const u64, vecs: *const f32, n: u32,
                                dim: u32, write_once: c_int,
                                results: *mut i32) -> c_int;

    pub fn spt_epochs(st: *mut spt_store, out: *mut u64) -> c_int;
    /* epochs_out[i] == SPT_GATHER_TORN (u64::MAX) => torn row, retry */
    pub fn spt_vec_gather(st: *mut spt_store, rows: *const u32, n: u32,
                          out: *mut f32, epochs_out: *mut u64) -> c_int;

    // change journal (format version 2): rows whose epoch moved since
    // `cursor`; < 0 (-EOVERFLOW lapped, -EAGAIN torn range) => scan
    // spt_epochs AFTER the call and go on from *cursor_out
    pub fn spt_journal_head(st: *mut spt_store) -> u64;
    pub fn spt_changed_since(st: *mut spt_store, cursor: u64,
                             rows_out: *mut u32, max_out: u32,
                             cursor_out: *mut u64) -> c_int;
    pub fn spt_epochs_at(st: *mut spt_store, rows: *const u32, n: u32,
                         out: *mut u64) -> c_int;

    // diagnostics
    pub fn spt_report_parse_failure(st: *mut spt_store) -> c_int;
}

#[cfg(test)]
mod tests {
    use super::*;
    use std::ffi::CString;

    #[test]
    fn round_trip() {
        unsafe {
            let name =
                CString::new(format!("/sptpu-rs-{}", std::process::id()))
                    .unwrap();
            let st = spt_create(name.as_ptr(), 64, 256, 8, SPT_CREATE_EXCL);
            assert!(!st.is_null(), "create failed: {}", spt_last_error());

            let k = CString::new("greeting").unwrap();
            let v = b"hello rust";
            assert_eq!(
                spt_set(st, k.as_ptr(), v.as_ptr() as *const _, v.len() as u32),
                0
            );

            let mut buf = [0u8; 256];
            let mut len = 0u32;
            assert_eq!(
                spt_get(st, k.as_ptr(), buf.as_mut_ptr() as *mut _,
                        buf.len() as u32, &mut len),
                0
            );
            assert_eq!(&buf[..len as usize], v);

            let idx = spt_find_index(st, k.as_ptr());
            assert!(idx >= 0);
            assert_eq!(spt_epoch_at(st, idx as u32), 2);

            spt_close(st);
            spt_unlink(name.as_ptr(), 0);
        }
    }
}
