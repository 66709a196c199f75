/* sptpu.h — public C ABI of the splinter-tpu native core store.
 *
 * A lock-free, seqlock-protected, shared-memory key/value + embedding-vector
 * store designed for a TPU-VM host.  Capability parity with the reference
 * store (splinterhq/libsplinter: splinter.h, splinter.c — see SURVEY.md §2.1),
 * re-designed TPU-first:
 *
 *   - The embedding vectors live in a SEPARATE, CONTIGUOUS float lane
 *     (struct-of-arrays) instead of inline in each slot
 *     (reference keeps them inline: splinter.h:252-254).  A contiguous
 *     (nslots, dim) float32 matrix is what the JAX/Pallas tier stages to HBM
 *     with one DMA; per-slot epochs still govern both value and vector.
 *   - One library, runtime backend selection (shm vs file-backed) instead of
 *     the reference's two compile-time variants (CMakeLists.txt:94-114).
 *   - Negative-errno return discipline (-EAGAIN, -ENOENT, ...) instead of
 *     -1 + errno: FFI callers (ctypes) read the code straight off the return.
 *   - Index-based accessors (slot index <-> key) so the batching engine can
 *     work directly off the event-bus dirty mask without re-hashing keys.
 *   - Tombstoned open addressing: unset leaves a reusable tombstone so probe
 *     chains stay intact and lookup misses stop at the first truly-empty
 *     slot (the reference's probe scans the whole table).
 *
 * Concurrency contract (same protocol as the reference, splinter.h:368-412):
 *   per-slot 64-bit epoch seqlock.  Odd epoch = writer active.  Writers CAS
 *   epoch e -> e+1 (must be even), publish, then store e+2.  Readers load the
 *   epoch before and after a read; odd or changed => retry (-EAGAIN).
 *   -EAGAIN is a SIGNAL, not an error: the caller retries.
 *   A writer that dies mid-write leaves an odd epoch; spt_retrain() is the
 *   sanctioned recovery (drives the epoch backward — "revalidate me").
 *
 * Threads of one process, and a binding with an interpreter lock: no call
 *   here takes a process-wide lock, and the calls fall into two kinds.
 *   BOUNDED ones — a probe, at most one value (max_val bytes) or vector
 *   (vec_dim floats) copied, a few atomics, a write to the non-blocking
 *   event bus; the slot seqlock is a TRY-lock that returns -EAGAIN — and
 *   ones that WAIT (spt_poll, spt_poll_labels, spt_signal_wait,
 *   spt_bus_wait) or are LINEAR in the slots, a batch or a text
 *   (spt_enumerate*, spt_list, spt_changed_since, spt_epochs*,
 *   spt_vec_gather, spt_vec_commit_batch, spt_purge, spt_header_snapshot,
 *   spt_wptok_*).  The Python binding (libsplinter_tpu/_native.py,
 *   KEEPS_LOCK) KEEPS the interpreter lock across the seven bounded calls
 *   of the request protocol — spt_vec_set, spt_find_index, spt_set,
 *   spt_get, spt_label_or, spt_get_labels, spt_bump — and drops it across
 *   every other: dropping and re-taking it costs a round of hand-overs
 *   among the awake threads, ~30 us a waiter, where the call is ~1 us.
 *   The rule a new symbol is classified by: bounded work, no sleep, no
 *   syscall that can block, no callback into the interpreter; the retry on
 *   -EAGAIN stays the caller's, and yields.  The worst case of a bounded
 *   call is its probe: a miss walks to the first NEVER-USED slot (~73
 *   slots at 92% load), and in a table whose free slots are all tombstones
 *   that is every slot, once, with the lock held.  The Rust and TypeScript
 *   bindings have no such lock.
 *
 * Change journal (format version 2): no slot's epoch moves, and no label
 *   is raised, without a record.  A ring of SPT_JOURNAL_CAP slot indices in
 *   the shared mapping with a 64-bit head that only grows; taking a slot's
 *   seqlock appends the slot WHILE IT IS ODD (so do spt_retrain's two
 *   stores), before the new even epoch is published, and spt_label_or
 *   appends the slot AFTER its bits are readable (and before the caller's
 *   bump wakes anyone).  Consumers clear nothing: each keeps its own cursor
 *   and asks spt_changed_since().  A record says "look at this slot", not
 *   what happened: an epoch consumer compares the slot's epoch with the one
 *   it holds, finds nothing to do for a spurious record (an aborted lock
 *   and a label raise journal too), and keeps a slot it saw odd for its
 *   next pass — the writer's record is already behind its cursor; a label
 *   consumer reads the slot's labels and keeps the slot for as long as the
 *   bits it follows are up.  Clearing a label (spt_label_andnot), the bits
 *   a new key or an unset zeroes, and flag operations are not journaled:
 *   whoever holds a row re-reads its labels.
 */
#ifndef SPTPU_H
#define SPTPU_H

#include <stdint.h>
#include <stddef.h>

#ifdef __cplusplus
extern "C" {
#endif

#define SPT_MAGIC           0x53505455u /* "SPTU" */
#define SPT_FORMAT_VERSION  2u   /* 2: the change journal (a region and
                                   three header fields); spt_open refuses
                                   a store of another version */

#define SPT_KEY_MAX         128   /* bytes incl. NUL */
#define SPT_SIGNAL_GROUPS   64
#define SPT_MAX_BIDS        32
#define SPT_DIRTY_WORDS     16    /* 1024 dirty bits: slot_idx % 1024 */
#define SPT_BLOOM_BITS      64
#define SPT_JOURNAL_CAP     65536u /* change-journal entries: a constant of
                                     the format, not an option */

/* --- open/create flags ------------------------------------------------- */
#define SPT_BACKEND_SHM     0u        /* POSIX shm (default) */
#define SPT_BACKEND_FILE    (1u<<0)   /* regular file mapping = persistence */
#define SPT_CREATE_EXCL     (1u<<1)   /* create: fail if store exists      */

/* --- slot type flags (low byte of slot->flags) ------------------------- */
#define SPT_T_VOID      0x00u
#define SPT_T_BIGINT    0x01u
#define SPT_T_BIGUINT   0x02u
#define SPT_T_JSON      0x04u
#define SPT_T_BINARY    0x08u
#define SPT_T_IMGDATA   0x10u
#define SPT_T_AUDIO     0x20u
#define SPT_T_VARTEXT   0x40u
#define SPT_T_MASK      0xFFu
/* bits 8..15: per-slot user flags; bit 16: system scratchpad */
#define SPT_F_USER_SHIFT 8
#define SPT_F_USER_MASK  0xFF00u
#define SPT_F_SYSTEM     (1u<<16)

/* --- atomic integer ops (BIGUINT slots) -------------------------------- */
typedef enum {
  SPT_IOP_AND = 0, SPT_IOP_OR, SPT_IOP_XOR, SPT_IOP_NOT,
  SPT_IOP_INC, SPT_IOP_DEC, SPT_IOP_ADD, SPT_IOP_SUB,
} spt_iop_t;

/* --- cooperative advisement intents (map to posix_madvise) ------------- */
typedef enum {
  SPT_ADV_NORMAL = 0, SPT_ADV_SEQUENTIAL, SPT_ADV_RANDOM,
  SPT_ADV_WILLNEED, SPT_ADV_DONTNEED,
} spt_advice_t;

/* --- mop (scrub) modes -------------------------------------------------- */
#define SPT_MOP_OFF     0u
#define SPT_MOP_HYBRID  1u   /* zero stale tail rounded to 64B slop (default) */
#define SPT_MOP_FULL    2u   /* zero the whole value region on every write    */

typedef struct spt_store spt_store;

/* Snapshot views (plain structs, torn-read-safe copies). */
typedef struct {
  uint32_t magic, version;
  uint32_t nslots, max_val, vec_dim, mop_mode;
  uint64_t map_size, global_epoch;
  uint32_t core_flags, user_flags;
  uint64_t parse_failures, last_failure_epoch;
  int64_t  bus_pid;
  uint32_t used_slots;      /* live keys at snapshot time */
} spt_header_view;

typedef struct {
  uint64_t epoch, hash, labels, watcher_mask;
  uint32_t val_len, flags;
  int64_t  ctime, atime;
  int32_t  index;
  char     key[SPT_KEY_MAX];
} spt_slot_view;

typedef struct {
  int64_t  pid;
  uint64_t shard_id, claimed_at, duration;
  uint32_t intent, priority;
  int32_t  live;            /* 1 if unexpired at snapshot time */
} spt_bid_view;

/* ---- lifecycle --------------------------------------------------------- */
spt_store *spt_create(const char *name, uint32_t nslots, uint32_t max_val,
                      uint32_t vec_dim, uint32_t flags);
spt_store *spt_open(const char *name, uint32_t flags);
/* Open + mbind(MPOL_BIND) the mapping to a NUMA node (reference parity:
 * splinter.c:250-264).  *bind_rc gets 0 or -errno for the bind itself;
 * the open succeeds either way (bind failure is advisory). */
spt_store *spt_open_numa(const char *name, uint32_t flags, int node,
                         int *bind_rc);
int  spt_close(spt_store *st);                    /* unmap; store survives  */
int  spt_unlink(const char *name, uint32_t flags);/* destroy backing object */

/* ---- geometry / raw access (for numpy/JAX zero-copy staging) ----------- */
uint32_t spt_nslots(const spt_store *st);
uint32_t spt_max_val(const spt_store *st);
uint32_t spt_vec_dim(const spt_store *st);
void    *spt_vec_lane(spt_store *st);    /* base of (nslots, dim) f32 matrix */
void    *spt_values_base(spt_store *st);
int      spt_last_error(void);

/* ---- KV ops ------------------------------------------------------------ */
int spt_set(spt_store *st, const char *key, const void *val, uint32_t len);
/* buf==NULL: size query (len_out set, no copy). 0 ok / -EAGAIN / -ENOENT */
int spt_get(spt_store *st, const char *key, void *buf, uint32_t cap,
            uint32_t *len_out);
int spt_unset(spt_store *st, const char *key);
int spt_append(spt_store *st, const char *key, const void *val, uint32_t len);
/* Copy up to max_keys NUL-terminated keys into keys (stride SPT_KEY_MAX).
 * Returns count. */
int spt_list(spt_store *st, char *keys, uint32_t max_keys);
/* Block until the slot's epoch changes from its value at call time.
 * timeout_ms<0: wait forever. 0 ok / -ETIMEDOUT / -ENOENT. */
int spt_poll(spt_store *st, const char *key, int timeout_ms);
/* Block until (labels & mask) == want on the key's slot: at once when that
 * holds on entry (level-triggered, no lost wake), and also when the slot's
 * epoch moves (rewritten or deleted: the caller looks again).  A label
 * flip moves no epoch, so spt_poll sleeps through it; this is the wait for
 * a request the daemons acknowledge by a label.  Same wake cadence as
 * spt_poll: the event bus where it is armed, else a 1 ms sleep.
 * timeout_ms<0: wait forever. 0 ok / -ETIMEDOUT / -ENOENT. */
int spt_poll_labels(spt_store *st, const char *key, uint64_t mask,
                    uint64_t want, int timeout_ms);

/* Zero-copy read protocol: capture a raw pointer + the epoch; compute; then
 * verify the epoch is unchanged (spt_epoch_at) before trusting the bytes. */
int spt_get_raw(spt_store *st, const char *key, const void **ptr,
                uint32_t *len_out, uint64_t *epoch_out);

/* ---- index-based access (engine fast path) ----------------------------- */
int      spt_find_index(spt_store *st, const char *key);  /* idx / -ENOENT */
int      spt_key_at(spt_store *st, uint32_t idx, char *key_out);
uint64_t spt_epoch_at(spt_store *st, uint32_t idx);
int      spt_get_at(spt_store *st, uint32_t idx, void *buf, uint32_t cap,
                    uint32_t *len_out);
uint64_t spt_labels_at(spt_store *st, uint32_t idx);
uint32_t spt_flags_at(spt_store *st, uint32_t idx);

/* ---- snapshots --------------------------------------------------------- */
int spt_header_snapshot(spt_store *st, spt_header_view *out);
int spt_slot_snapshot(spt_store *st, const char *key, spt_slot_view *out);
int spt_slot_snapshot_at(spt_store *st, uint32_t idx, spt_slot_view *out);

/* ---- typed slots ------------------------------------------------------- */
/* Setting SPT_T_BIGUINT on an ASCII-digits slot converts it in place to a
 * host-endian uint64 (val_len becomes 8) — "BIGUINT promotion". */
int spt_set_type(spt_store *st, const char *key, uint32_t type_flag);
int spt_get_type(spt_store *st, const char *key, uint32_t *type_out);
/* -EPROTOTYPE unless the slot is SPT_T_BIGUINT. */
int spt_integer_op(spt_store *st, const char *key, spt_iop_t op,
                   uint64_t operand, uint64_t *result_out);

/* ---- tandem (ordered) keys: base, base.1, base.2, ... ------------------ */
#define SPT_ORDER_SEP "."
int spt_tandem_set(spt_store *st, const char *base, uint32_t order,
                   const void *val, uint32_t len);
int spt_tandem_get(spt_store *st, const char *base, uint32_t order,
                   void *buf, uint32_t cap, uint32_t *len_out);
int spt_tandem_unset(spt_store *st, const char *base, uint32_t max_order);
int spt_tandem_count(spt_store *st, const char *base);

/* ---- bloom labels ------------------------------------------------------ */
/* spt_label_or raises the bits and then appends the slot to the change
 * journal (one record a call, none for a missing key); spt_label_andnot
 * appends nothing. */
int      spt_label_or(spt_store *st, const char *key, uint64_t mask);
int      spt_label_andnot(spt_store *st, const char *key, uint64_t mask);
int      spt_get_labels(spt_store *st, const char *key, uint64_t *out);
/* slot indices whose (labels & mask) == mask; returns count */
int      spt_enumerate(spt_store *st, uint64_t mask, uint32_t *idx_out,
                       uint32_t max_out);
/* slot indices of live keys that start with prefix ("" matches every
 * key); returns count.  One pass over the slots, no key is copied: a key
 * is compared in place, unvalidated, so resolve each index with
 * spt_key_at and check the prefix again.  idx_out NULL counts only.
 * live_out (optional) receives the live keys the pass went over. */
int      spt_enumerate_prefix(spt_store *st, const char *prefix,
                              uint32_t *idx_out, uint32_t max_out,
                              uint32_t *live_out);

/* ---- signal arena (64 cache-line counters, pub/sub) -------------------- */
int      spt_watch_register(spt_store *st, const char *key, uint32_t group);
int      spt_watch_unregister(spt_store *st, const char *key, uint32_t group);
/* Bind a bloom BIT INDEX (0..63) to a signal group: any write to a slot
 * carrying that label bit pulses the group. */
int      spt_watch_label_register(spt_store *st, uint32_t bloom_bit,
                                  uint32_t group);
int      spt_watch_label_unregister(spt_store *st, uint32_t bloom_bit,
                                    uint32_t group);
uint64_t spt_signal_count(spt_store *st, uint32_t group);
int      spt_signal_pulse(spt_store *st, uint32_t group);
/* Pulse a key's watcher groups + label-bound groups WITHOUT writing ("bump"). */
int      spt_bump(spt_store *st, const char *key);
/* Block until group count != last (returns new count via out).
 * Uses the event bus when armed, 1 ms sleep loop otherwise. */
int      spt_signal_wait(spt_store *st, uint32_t group, uint64_t last,
                         int timeout_ms, uint64_t *count_out);

/* ---- event bus (eventfd + dirty mask) ---------------------------------- */
int spt_bus_init(spt_store *st);   /* become bus owner (arm the eventfd)    */
int spt_bus_open(spt_store *st);   /* peer: re-open owner fd via pidfd_getfd;
                                      -ENOTCONN if no owner; -ENOSYS if the
                                      kernel lacks pidfd (callers fall back
                                      to polling spt_bus_drain) */
int spt_bus_wait(spt_store *st, int timeout_ms); /* 0 woke / -ETIMEDOUT */
int spt_bus_close(spt_store *st);
/* Atomically fetch-and-clear the 1024-bit dirty mask (16 words). Returns
 * number of set bits. Bit b = some slot with idx%1024==b was written. */
int spt_bus_drain(spt_store *st, uint64_t dirty_out[SPT_DIRTY_WORDS]);
int spt_bus_peek(spt_store *st, uint64_t dirty_out[SPT_DIRTY_WORDS]);

/* ---- shard bids & cooperative advisement ------------------------------- */
/* Claim a bid slot. duration_us==0 => bid is born expired (test hook).
 * Returns bid index 0..31, or -ENOSPC. */
int spt_shard_claim(spt_store *st, uint64_t shard_id, spt_advice_t intent,
                    uint32_t priority, uint64_t duration_us);
/* Forge a bid for an arbitrary pid/claimed_at — deterministic multi-process
 * election tests without spawning processes (reference: splinter.h:1142-1152). */
int spt_shard_claim_ex(spt_store *st, uint64_t shard_id, int64_t pid,
                       spt_advice_t intent, uint32_t priority,
                       uint64_t duration_us, uint64_t claimed_at_us);
int spt_shard_rebid(spt_store *st, int bid_idx);
int spt_shard_release(spt_store *st, int bid_idx);
/* Deterministic, read-only election: highest priority live bid wins; ties ->
 * earliest claimed_at -> lowest pid.  DONTNEED bids ("soft bumpers") cannot
 * win while any live non-DONTNEED bid exists.  Returns winning bid index or
 * -ENOENT when no live bids. */
int spt_shard_election(spt_store *st);
int spt_bid_info(spt_store *st, int bid_idx, spt_bid_view *out);

/* Cooperative madvise over the arena: only the election sovereign actually
 * issues posix_madvise.  offset/len in bytes relative to the mapping (len==0
 * => whole mapping).  timeout_ms==0 => -EAGAIN if not sovereign (defer);
 * >0 bounded wait; <0 wait forever.  Caller must hold live bid bid_idx. */
int spt_madvise(spt_store *st, int bid_idx, uint64_t offset, uint64_t len,
                spt_advice_t advice, int timeout_ms);

/* ---- mop / purge ------------------------------------------------------- */
int      spt_set_mop(spt_store *st, uint32_t mode);
uint32_t spt_get_mop(spt_store *st);
int      spt_purge(spt_store *st);  /* store-wide stale-tail sweep */

/* ---- recovery ---------------------------------------------------------- */
/* Backward-epoch recovery of a slot stuck odd by a dead writer: forces the
 * epoch to 3 (odd), zeroes the vector, then publishes epoch 4.  A BACKWARD
 * epoch tells observers "revalidate me". */
int spt_retrain(spt_store *st, const char *key);

/* ---- system keys & user flags ------------------------------------------ */
int spt_set_system(spt_store *st, const char *key); /* BINARY scratchpad
                                                       spanning max_val */
int spt_slot_usr_set(spt_store *st, const char *key, uint8_t bits);
int spt_slot_usr_get(spt_store *st, const char *key, uint8_t *out);
int spt_config_set_user(spt_store *st, uint32_t bits);   /* low 4 bits */
uint32_t spt_config_get_user(spt_store *st);

/* ---- timestamps -------------------------------------------------------- */
uint64_t spt_now(void);          /* raw tick counter (rdtsc/cntvct/monotonic) */
uint64_t spt_ticks_per_us(void); /* calibrated once per process */
/* Backfill a slot's ctime/atime to (now - ticks_ago). which: 0 ctime,
 * 1 atime, 2 both. */
int spt_stamp(spt_store *st, const char *key, int which, uint64_t ticks_ago);

/* ---- embedding vector lane --------------------------------------------- */
int spt_vec_set(spt_store *st, const char *key, const float *vec,
                uint32_t dim);
int spt_vec_get(spt_store *st, const char *key, float *out, uint32_t dim);
int spt_vec_set_at(spt_store *st, uint32_t idx, const float *vec,
                   uint32_t dim);
int spt_vec_get_at(spt_store *st, uint32_t idx, float *out, uint32_t dim);
/* Write a batch of vectors, each gated on its captured epoch: vector i is
 * committed iff slot rows[i] still has epoch epochs[i] (and, if write_once,
 * a currently all-zero vector).  Per-row results: 0 committed / -ESTALE
 * raced / -EEXIST write-once skip.  Returns number committed.  This is the
 * TPU micro-batcher's commit path (reference checks epoch per key serially:
 * splinference.cpp:275-287). */
int spt_vec_commit_batch(spt_store *st, const uint32_t *rows,
                         const uint64_t *epochs, const float *vecs,
                         uint32_t n, uint32_t dim, int write_once,
                         int32_t *results);

/* Bulk epoch snapshot: one acquire load per slot into out (nslots u64).
 * Returns nslots.  Consecutive snapshots diffed on the host give the
 * changed-row set — the device-lane cache's dirty detector. */
int spt_epochs(spt_store *st, uint64_t *out);
/* Torn-safe gather of vector rows: per row, epoch-before (odd => skip),
 * memcpy into out[i*dim], epoch-after recheck.  epochs_out[i] = the stable
 * epoch (0 for a stable never-written slot, whose row is zeros), or
 * SPT_GATHER_TORN if the row was mid-write / contended / out of range
 * (caller retries next pass).  Returns the number of stable rows. */
#define SPT_GATHER_TORN UINT64_MAX
int spt_vec_gather(spt_store *st, const uint32_t *rows, uint32_t n,
                   float *out, uint64_t *epochs_out);

/* ---- change journal ------------------------------------------------------ */
/* Entries ever appended: the cursor of a consumer that starts now.  Take it
 * BEFORE the first epoch snapshot or label enumeration, so a write or a
 * raise during the walk is found afterwards. */
uint64_t spt_journal_head(spt_store *st);
/* The slot indices appended at positions [cursor, head), oldest first and
 * with repeats, at most max_out of them; *cursor_out = the position after
 * the last one returned.  Returns the count, or
 *   -EOVERFLOW  the writers lapped the cursor (head - cursor >
 *               SPT_JOURNAL_CAP), or the cursor is not this store's;
 *   -EAGAIN     an entry in the range was claimed and not written within a
 *               short wait (its writer is descheduled, or dead);
 * with *cursor_out = the head as it was when the call began.  Either way
 * the range cannot be trusted: scan every slot (spt_epochs, spt_enumerate)
 * AFTER this call and go on from *cursor_out. */
int spt_changed_since(spt_store *st, uint64_t cursor, uint32_t *rows_out,
                      uint32_t max_out, uint64_t *cursor_out);
/* Epochs of n listed slots, one acquire load each (0 for an index out of
 * range): what a journal consumer compares its own with. */
int spt_epochs_at(spt_store *st, const uint32_t *rows, uint32_t n,
                  uint64_t *out);

/* ---- diagnostics ------------------------------------------------------- */
int spt_report_parse_failure(spt_store *st);

/* Build identity stamped at compile time (git describe + UTC date),
 * surfaced by the CLI `caps` command.  Parity with the reference's
 * generated build hash (scripts/genbuildh -> build.h, surfaced by its
 * caps module). */
const char *spt_build_id(void);

/* ---- host tokenizer (wptok.c) ------------------------------------------
 * Native tokenization for the embedding daemon's hot path (the
 * reference tokenizes natively via llama.cpp, splinference.cpp:209-217).
 * ASCII fast path: inputs with bytes >= 0x80 return -EDOM and the
 * Python caller falls back to its full-Unicode implementation. */
typedef struct spt_wptok spt_wptok;

/* WordPiece over a BERT-family vocab (greedy longest-match, "##"
 * continuations, optional ASCII lowercasing).  Requires [CLS]/[SEP]/
 * [UNK] in the vocab ([PAD] defaults to id 0); returns NULL otherwise. */
spt_wptok *spt_wptok_create(const char *const *tokens, uint32_t n_tokens,
                            int lower);
/* Hashed-vocabulary fallback: word -> 4 + fnv1a64(word) % (vocab-4);
 * ids 0..3 = PAD/CLS/SEP/UNK.  Mirrors models/tokenizer.HashTokenizer. */
spt_wptok *spt_wptok_create_hashed(uint32_t vocab_size, int lower);
void spt_wptok_destroy(spt_wptok *t);

/* Encode one text: out = [CLS] ids... [SEP].  Returns the id count,
 * -EDOM for non-ASCII input (use the host-language fallback), -ERANGE
 * when cap is too small (cap >= strlen(text)+3 always suffices). */
int spt_wptok_encode(const spt_wptok *t, const char *text, uint32_t *out,
                     uint32_t cap);
/* Encode+pad a batch into ids (count x max_len, padded with [PAD]) and
 * lens (count).  Rows the fast path cannot handle (non-ASCII) get
 * lens[i] = UINT32_MAX and an all-PAD row — re-encode those in the
 * caller.  Truncation keeps the trailing [SEP] (tokenizer.py parity). */
int spt_wptok_encode_batch(const spt_wptok *t, const char *const *texts,
                           uint32_t count, uint32_t max_len,
                           uint32_t *ids, uint32_t *lens);

#ifdef __cplusplus
}
#endif
#endif /* SPTPU_H */
