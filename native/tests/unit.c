/* TAP-style unit suite for the native core store.
 *
 * The behavioral spec tier (reference: splinter_test.c:85-533 — ~130
 * TEST() assertions; SURVEY.md §4).  Covers CRUD, seqlock epoch parity,
 * size queries, list, mop modes, snapshots, named types + BIGUINT
 * promotion, integer ops (incl. -EPROTOTYPE discipline), tandem keys,
 * bloom labels + enumeration, the signal arena, bump, append, purge
 * survival, system keys, user flags, timestamps, the vector lane with
 * epoch-gated batch commit, retrain (backward epoch), the full shard
 * election matrix (priority, expiry, claimed_at/pid tie-breaks, DONTNEED
 * bumper, rebid revival, -ENOSPC on the 33rd bid, sovereign /
 * non-sovereign madvise), the event bus (init / dirty bits / wait), and
 * the change journal (every call that can move an epoch or raise a label
 * leaves its slot there; a lapped reader is told so; another format
 * version is refused).
 *
 * Like the reference's claim_ex determinism trick (splinter.h:1142-1152),
 * multi-process elections are tested by forging bids — no processes, no
 * sleeps.  The whole suite runs twice: shm backend, then file backend
 * (the reference builds every test binary twice instead,
 * CMakeLists.txt:269-277).
 */
#define _GNU_SOURCE
#include "sptpu.h"

#include <errno.h>
#include <pthread.h>
#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <time.h>
#include <unistd.h>

static int n_run = 0, n_fail = 0;

#define TEST(cond, name) do {                                            \
    n_run++;                                                             \
    if (cond) printf("ok %d - %s\n", n_run, name);                       \
    else { n_fail++; printf("not ok %d - %s (%s:%d)\n", n_run, name,     \
                            __FILE__, __LINE__); }                       \
  } while (0)

/* what a daemon's commit does to a request key, a little later: a label
 * flip (and a bump), never a write to the slot */
struct flip { spt_store *st; const char *key; uint64_t clear; };

static void *flip_later(void *arg) {
  struct flip *f = arg;
  struct timespec ts = {0, 5000000};  /* 5 ms */
  nanosleep(&ts, NULL);
  spt_label_andnot(f->st, f->key, f->clear);
  spt_bump(f->st, f->key);
  return NULL;
}

static void suite(const char *name, uint32_t flags) {
  char buf[4096];
  uint32_t len = 0;

  spt_unlink(name, flags);
  spt_store *st = spt_create(name, 64, 256, 8, flags);
  TEST(st != NULL, "create");
  TEST(spt_nslots(st) == 64 && spt_max_val(st) == 256 &&
       spt_vec_dim(st) == 8, "geometry");

  /* exclusive create refuses an existing store */
  TEST(spt_create(name, 64, 256, 8, flags | SPT_CREATE_EXCL) == NULL,
       "create EXCL on existing store fails");

  /* ---- CRUD + seqlock epochs ---- */
  TEST(spt_set(st, "k1", "hello", 5) == 0, "set");
  TEST(spt_get(st, "k1", buf, sizeof buf, &len) == 0 && len == 5 &&
       memcmp(buf, "hello", 5) == 0, "get round trip");
  int idx = spt_find_index(st, "k1");
  TEST(idx >= 0, "find_index");
  uint64_t e = spt_epoch_at(st, (uint32_t)idx);
  TEST(e % 2 == 0 && e >= 2, "epoch even after publish");
  TEST(spt_set(st, "k1", "world", 5) == 0 &&
       spt_epoch_at(st, (uint32_t)idx) == e + 2, "rewrite bumps epoch by 2");
  TEST(spt_get(st, "k1", NULL, 0, &len) == 0 && len == 5, "size query");
  TEST(spt_get(st, "nope", buf, sizeof buf, &len) == -ENOENT,
       "get missing -ENOENT");
  TEST(spt_append(st, "k1", "!", 1) == 0, "append");
  TEST(spt_get(st, "k1", buf, sizeof buf, &len) == 0 && len == 6 &&
       buf[5] == '!', "append grew value");
  char big[512]; memset(big, 'x', sizeof big);
  TEST(spt_set(st, "k1", big, sizeof big) == -EMSGSIZE,
       "oversized set -EMSGSIZE");
  TEST(spt_append(st, "k1", big, 251) == -EMSGSIZE,
       "overflowing append -EMSGSIZE");

  /* zero-copy read protocol */
  const void *p; uint64_t re;
  TEST(spt_get_raw(st, "k1", &p, &len, &re) == idx && len == 6 &&
       re == spt_epoch_at(st, (uint32_t)idx), "get_raw epoch capture");

  /* ---- list ---- */
  spt_set(st, "k2", "v2", 2);
  char keys[64 * SPT_KEY_MAX];
  int n = spt_list(st, keys, 64);
  TEST(n == 2, "list count");

  /* ---- unset + tombstone probing ---- */
  TEST(spt_unset(st, "k2") == 0 && spt_find_index(st, "k2") == -ENOENT,
       "unset removes key");
  TEST(spt_unset(st, "k2") == -ENOENT, "double unset -ENOENT");
  TEST(spt_set(st, "k2", "back", 4) == 0, "slot reusable after unset");
  spt_unset(st, "k2");

  /* ---- types + BIGUINT promotion ---- */
  spt_set(st, "num", "41", 2);
  TEST(spt_set_type(st, "num", SPT_T_BIGUINT) == 0, "BIGUINT promotion");
  uint32_t ty;
  TEST(spt_get_type(st, "num", &ty) == 0 && ty == SPT_T_BIGUINT,
       "type readback");
  uint64_t r;
  TEST(spt_integer_op(st, "num", SPT_IOP_INC, 0, &r) == 0 && r == 42,
       "integer inc after promotion (ASCII 41 -> 42)");
  TEST(spt_integer_op(st, "num", SPT_IOP_ADD, 8, &r) == 0 && r == 50,
       "integer add");
  TEST(spt_integer_op(st, "num", SPT_IOP_SUB, 1, &r) == 0 && r == 49,
       "integer sub (borrow path)");
  TEST(spt_integer_op(st, "num", SPT_IOP_XOR, 0xFF, &r) == 0, "integer xor");
  spt_set(st, "txt", "abc", 3);
  TEST(spt_integer_op(st, "txt", SPT_IOP_INC, 0, &r) == -EPROTOTYPE,
       "integer op on non-BIGUINT -EPROTOTYPE");

  /* ---- tandem keys ---- */
  TEST(spt_tandem_set(st, "doc", 0, "p0", 2) == 0 &&
       spt_tandem_set(st, "doc", 1, "p1", 2) == 0 &&
       spt_tandem_set(st, "doc", 2, "p2", 2) == 0, "tandem set x3");
  TEST(spt_tandem_count(st, "doc") == 3, "tandem count");
  TEST(spt_tandem_get(st, "doc", 1, buf, sizeof buf, &len) == 0 &&
       memcmp(buf, "p1", 2) == 0, "tandem get order 1");
  TEST(spt_tandem_unset(st, "doc", 100) == 3 &&
       spt_tandem_count(st, "doc") == 0, "tandem unset removes the set");

  /* ---- bloom labels + enumeration ---- */
  spt_set(st, "lab", "x", 1);
  TEST(spt_label_or(st, "lab", 0x5) == 0, "label or");
  uint64_t lm;
  TEST(spt_get_labels(st, "lab", &lm) == 0 && lm == 0x5, "label readback");
  uint32_t hits[64];
  TEST(spt_enumerate(st, 0x4, hits, 64) == 1 &&
       hits[0] == (uint32_t)spt_find_index(st, "lab"),
       "enumerate by label mask");
  TEST(spt_label_andnot(st, "lab", 0x4) == 0 &&
       spt_enumerate(st, 0x4, hits, 64) == 0, "label clear");

  /* ---- the label wait ---- */
  {
    TEST(spt_poll_labels(st, "nope", 0x1, 0, 5) == -ENOENT,
         "poll_labels: unknown key -ENOENT");
    /* "lab" carries 0x1 here; level-triggered on either sense */
    TEST(spt_poll_labels(st, "lab", 0x4, 0, -1) == 0,
         "poll_labels: a cleared bit already clear returns at once");
    TEST(spt_poll_labels(st, "lab", 0x1, 0x1, -1) == 0,
         "poll_labels: a raised bit already raised returns at once");
    TEST(spt_poll_labels(st, "lab", 0x5, 0x5, 5) == -ETIMEDOUT,
         "poll_labels: every bit of want has to hold (-ETIMEDOUT)");
    TEST(spt_poll_labels(st, "lab", 0x1, 0, 0) == -ETIMEDOUT,
         "poll_labels: timeout 0 looks once");
    int il = spt_find_index(st, "lab");
    uint64_t el = spt_epoch_at(st, (uint32_t)il);
    struct flip f = {st, "lab", 0x1};
    pthread_t th;
    int started = pthread_create(&th, NULL, flip_later, &f) == 0;
    TEST(started && spt_poll_labels(st, "lab", 0x1, 0, 10000) == 0,
         "poll_labels: woken by a label flip");
    if (started) pthread_join(th, NULL);
    TEST(spt_epoch_at(st, (uint32_t)il) == el,
         "poll_labels: ... which moved no epoch");
    TEST(spt_poll(st, "lab", 5) == -ETIMEDOUT,
         "poll: the same flip is invisible to the epoch wait");
    spt_label_or(st, "lab", 0x1);
  }

  /* ---- prefix enumeration ---- */
  uint32_t live = 0, all = (uint32_t)spt_list(st, NULL, 0);
  spt_set(st, "__sr_7", "r", 1);
  spt_set(st, "__sr_70", "r", 1);
  spt_set(st, "__sp_7", "p", 1);
  spt_set(st, "__sr_", "whole", 5);
  all += 4;
  TEST(spt_enumerate_prefix(st, "__sr_", hits, 64, &live) == 3 &&
       live == all, "prefix enumerate: three rows, every live key passed");
  int seen7 = 0;
  for (int i = 0; i < 3; i++)
    seen7 += hits[i] == (uint32_t)spt_find_index(st, "__sr_7");
  TEST(seen7 == 1, "prefix enumerate returns slot indices");
  TEST(spt_enumerate_prefix(st, "__sr_7", NULL, 0, NULL) == 2,
       "prefix enumerate counts without a buffer");
  TEST(spt_enumerate_prefix(st, "__sr_", hits, 2, NULL) == 2,
       "prefix enumerate stops at max_out");
  TEST(spt_enumerate_prefix(st, "", NULL, 0, &live) == (int)all &&
       live == all, "empty prefix matches every live key");
  spt_unset(st, "__sr_7");
  TEST(spt_enumerate_prefix(st, "__sr_7", hits, 64, &live) == 1 &&
       live == all - 1 &&
       hits[0] == (uint32_t)spt_find_index(st, "__sr_70"),
       "prefix enumerate skips a tombstone");
  char toolong[SPT_KEY_MAX + 8];
  memset(toolong, 'k', sizeof toolong - 1);
  toolong[sizeof toolong - 1] = '\0';
  TEST(spt_enumerate_prefix(st, toolong, hits, 64, NULL) == 0 &&
       spt_enumerate_prefix(st, NULL, hits, 64, NULL) == -EINVAL,
       "prefix longer than a key matches nothing; NULL is EINVAL");
  spt_unset(st, "__sr_70"); spt_unset(st, "__sp_7"); spt_unset(st, "__sr_");

  /* ---- signal arena + bump ---- */
  uint64_t c0 = spt_signal_count(st, 7);
  TEST(spt_watch_register(st, "lab", 7) == 0, "watch register");
  spt_set(st, "lab", "y", 1);
  TEST(spt_signal_count(st, 7) == c0 + 1, "write pulses watcher group");
  TEST(spt_bump(st, "lab") == 0 && spt_signal_count(st, 7) == c0 + 2,
       "bump pulses without writing");
  /* label-bound group: bloom bit 3 -> group 9 */
  TEST(spt_watch_label_register(st, 3, 9) == 0, "label watch register");
  spt_label_or(st, "lab", 1ull << 3);
  uint64_t c9 = spt_signal_count(st, 9);
  spt_set(st, "lab", "z", 1);
  TEST(spt_signal_count(st, 9) == c9 + 1, "label-bound group pulsed");
  TEST(spt_watch_label_unregister(st, 3, 9) == 0, "label watch unregister");
  TEST(spt_watch_unregister(st, "lab", 7) == 0, "watch unregister");
  uint64_t cnt;
  TEST(spt_signal_wait(st, 7, spt_signal_count(st, 7), 10, &cnt) ==
       -ETIMEDOUT, "signal_wait times out when quiet");

  /* ---- snapshots ---- */
  spt_header_view hv;
  TEST(spt_header_snapshot(st, &hv) == 0 && hv.magic == SPT_MAGIC &&
       hv.nslots == 64 && hv.used_slots >= 3, "header snapshot");
  spt_slot_view sv;
  TEST(spt_slot_snapshot(st, "lab", &sv) == 0 && sv.val_len == 1 &&
       strcmp(sv.key, "lab") == 0 && sv.epoch % 2 == 0, "slot snapshot");

  /* ---- timestamps ---- */
  TEST(spt_now() != 0 && spt_ticks_per_us() > 0, "tick counter");
  TEST(spt_stamp(st, "lab", 2, 0) == 0, "stamp ctime+atime");
  spt_slot_snapshot(st, "lab", &sv);
  TEST(sv.ctime > 0 && sv.atime > 0, "timestamps recorded");

  /* ---- mop modes + purge ---- */
  TEST(spt_get_mop(st) == SPT_MOP_HYBRID, "default mop hybrid");
  TEST(spt_set_mop(st, SPT_MOP_FULL) == 0 && spt_get_mop(st) == SPT_MOP_FULL,
       "mop full-boil");
  spt_set(st, "mop", "aaaaaaaa", 8);
  spt_set(st, "mop", "b", 1);          /* full-boil zeroes the stale tail */
  spt_get_raw(st, "mop", &p, &len, &re);
  TEST(len == 1 && ((const char *)p)[1] == 0 && ((const char *)p)[7] == 0,
       "full-boil scrubs stale tail");
  spt_set_mop(st, SPT_MOP_OFF);
  spt_set(st, "mop", "cccccccc", 8);
  spt_set(st, "mop", "d", 1);
  spt_get_raw(st, "mop", &p, &len, &re);
  TEST(((const char *)p)[3] == 'c', "mop off leaves stale tail");
  TEST(spt_purge(st) >= 1, "purge sweeps stale tails");
  spt_get_raw(st, "mop", &p, &len, &re);
  TEST(((const char *)p)[3] == 0, "purge scrubbed the tail");
  spt_set_mop(st, SPT_MOP_HYBRID);
  TEST(spt_get(st, "mop", buf, sizeof buf, &len) == 0 && len == 1 &&
       buf[0] == 'd', "value survives purge");

  /* ---- system keys + user flags ---- */
  TEST(spt_set_system(st, "__scratch") == 0, "system key");
  spt_slot_snapshot(st, "__scratch", &sv);
  TEST((sv.flags & SPT_F_SYSTEM) && (sv.flags & SPT_T_BINARY) &&
       sv.val_len == spt_max_val(st), "system scratchpad spans max_val");
  TEST(spt_slot_usr_set(st, "lab", 0xA5) == 0, "slot user flags set");
  uint8_t ub;
  TEST(spt_slot_usr_get(st, "lab", &ub) == 0 && ub == 0xA5,
       "slot user flags get");
  TEST(spt_config_set_user(st, 0x3) == 0 && spt_config_get_user(st) == 0x3,
       "store user flags");

  /* ---- vector lane ---- */
  float v[8] = {1, 2, 3, 4, 5, 6, 7, 8}, vo[8];
  TEST(spt_vec_set(st, "lab", v, 8) == 0 &&
       spt_vec_get(st, "lab", vo, 8) == 0 &&
       memcmp(v, vo, sizeof v) == 0, "vector round trip");
  idx = spt_find_index(st, "lab");
  uint64_t ve = spt_epoch_at(st, (uint32_t)idx);
  uint32_t rows[2] = {(uint32_t)idx, (uint32_t)idx};
  uint64_t eps[2] = {ve, ve - 2};            /* second is stale */
  float vecs[16] = {9, 9, 9, 9, 9, 9, 9, 9, 1, 1, 1, 1, 1, 1, 1, 1};
  int32_t res[2];
  TEST(spt_vec_commit_batch(st, rows, eps, vecs, 2, 8, 0, res) == 1 &&
       res[0] == 0 && res[1] == -ESTALE, "batch commit epoch gating");
  /* write-once gate: vector now non-zero, so write_once commit skips */
  ve = spt_epoch_at(st, (uint32_t)idx);
  TEST(spt_vec_commit_batch(st, rows, &ve, vecs, 1, 8, 1, res) == 0 &&
       res[0] == -EEXIST, "write-once gate -EEXIST");
  TEST(spt_vec_set(st, "nope", v, 8) == -ENOENT, "vec on missing -ENOENT");
  TEST(spt_vec_get(st, "lab", vo, 4) == -EMSGSIZE,
       "vec dim mismatch -EMSGSIZE");

  /* unset zeroes the vector */
  spt_set(st, "vz", "x", 1);
  spt_vec_set(st, "vz", v, 8);
  spt_unset(st, "vz");
  spt_set(st, "vz", "x", 1);
  spt_vec_get(st, "vz", vo, 8);
  int allz = 1; for (int i = 0; i < 8; i++) allz &= vo[i] == 0.0f;
  TEST(allz, "unset scrubs vector");

  /* ---- retrain (backward epoch) ---- */
  spt_set(st, "stuck", "v", 1);
  spt_vec_set(st, "stuck", v, 8);
  TEST(spt_retrain(st, "stuck") == 0, "retrain");
  idx = spt_find_index(st, "stuck");
  TEST(spt_epoch_at(st, (uint32_t)idx) == 4, "retrain publishes epoch 4");
  spt_vec_get(st, "stuck", vo, 8);
  allz = 1; for (int i = 0; i < 8; i++) allz &= vo[i] == 0.0f;
  TEST(allz, "retrain scrubs vector");
  TEST(spt_get(st, "stuck", buf, sizeof buf, &len) == 0 && buf[0] == 'v',
       "retrain keeps value");

  /* ---- shard election matrix (forged bids, deterministic) ----
   * claimed_at is ABSOLUTE microseconds (same clock as spt_now()/
   * spt_ticks_per_us()); forge bids relative to now so they are live. */
  uint64_t now_us = spt_now() / spt_ticks_per_us();
  int b1 = spt_shard_claim_ex(st, 0x100, 1111, SPT_ADV_WILLNEED, 40,
                              60000000, now_us - 3000);
  int b2 = spt_shard_claim_ex(st, 0x200, 2222, SPT_ADV_WILLNEED, 200,
                              60000000, now_us - 2000);
  TEST(b1 >= 0 && b2 >= 0 && b1 != b2, "claim_ex forged bids");
  TEST(spt_shard_election(st) == b2, "highest priority wins");
  /* tie on priority -> earliest claimed_at */
  int b3 = spt_shard_claim_ex(st, 0x300, 3333, SPT_ADV_WILLNEED, 200,
                              60000000, now_us - 3500);
  TEST(spt_shard_election(st) == b3, "tie -> earliest claimed_at");
  /* tie on both -> lowest pid */
  int b4 = spt_shard_claim_ex(st, 0x400, 44, SPT_ADV_WILLNEED, 200,
                              60000000, now_us - 3500);
  TEST(spt_shard_election(st) == b4, "tie -> lowest pid");
  /* DONTNEED bumper cannot win while live non-DONTNEED bids exist */
  int b5 = spt_shard_claim_ex(st, 0x500, 5, SPT_ADV_DONTNEED, 255,
                              60000000, now_us);
  TEST(spt_shard_election(st) == b4, "DONTNEED bumper cannot win");
  spt_shard_release(st, b1); spt_shard_release(st, b2);
  spt_shard_release(st, b3); spt_shard_release(st, b4);
  TEST(spt_shard_election(st) == b5, "bumper wins once alone");
  spt_shard_release(st, b5);
  /* duration 0 = born expired */
  int b6 = spt_shard_claim_ex(st, 0x600, 6, SPT_ADV_WILLNEED, 10, 0,
                              now_us);
  TEST(b6 >= 0 && spt_shard_election(st) == -ENOENT,
       "expired bid never elected");
  spt_bid_view bv;
  TEST(spt_bid_info(st, b6, &bv) == 0 && !bv.live, "bid_info live flag");
  spt_shard_release(st, b6);
  /* rebid refreshes claimed_at, reviving a bid expired BY TIME */
  b6 = spt_shard_claim_ex(st, 0x600, 6, SPT_ADV_WILLNEED, 10, 1000,
                          now_us - 5000000);     /* expired 5 s ago */
  TEST(spt_shard_election(st) == -ENOENT, "time-expired bid not elected");
  TEST(spt_shard_rebid(st, b6) == 0 && spt_shard_election(st) == b6,
       "rebid revives an expired bid");
  /* table capacity: fill to 32, 33rd refused */
  int held[SPT_MAX_BIDS], nheld = 0;
  for (int i = 0; i < SPT_MAX_BIDS; i++) {
    int b = spt_shard_claim_ex(st, 0x1000 + i, 100 + i, SPT_ADV_WILLNEED,
                               1, 60000000, 10);
    if (b >= 0) held[nheld++] = b;
  }
  TEST(nheld == SPT_MAX_BIDS - 1, "table fills to 32 bids");
  TEST(spt_shard_claim_ex(st, 0x9999, 9, SPT_ADV_WILLNEED, 1, 60000000,
                          10) == -ENOSPC, "33rd bid -ENOSPC");
  for (int i = 0; i < nheld; i++) spt_shard_release(st, held[i]);
  /* madvise: sovereign succeeds, non-sovereign defers */
  int lo = spt_shard_claim(st, 0x700, SPT_ADV_WILLNEED, 5, 60000000);
  int hi = spt_shard_claim_ex(st, 0x800, 1, SPT_ADV_WILLNEED, 250,
                              60000000, now_us);
  TEST(spt_madvise(st, lo, 0, 0, SPT_ADV_WILLNEED, 0) == -EAGAIN,
       "non-sovereign madvise defers -EAGAIN");
  TEST(spt_madvise(st, lo, 0, 0, SPT_ADV_WILLNEED, 20) == -ETIMEDOUT,
       "non-sovereign bounded wait -ETIMEDOUT");
  spt_shard_release(st, hi);
  TEST(spt_madvise(st, lo, 0, 0, SPT_ADV_WILLNEED, 0) == 0,
       "sovereign madvise issues");
  TEST(spt_madvise(st, b6, 0, 0, SPT_ADV_WILLNEED, 0) == -EPERM,
       "madvise without live bid -EPERM");
  spt_shard_release(st, lo);
  spt_shard_release(st, b6);

  /* ---- event bus ---- */
  TEST(spt_bus_init(st) == 0, "bus init (owner)");
  uint64_t dirty[SPT_DIRTY_WORDS];
  spt_bus_drain(st, dirty);                  /* clear backlog */
  spt_set(st, "k1", "bus", 3);
  TEST(spt_bus_wait(st, 200) == 0, "bus wakes on write");
  idx = spt_find_index(st, "k1");
  n = spt_bus_drain(st, dirty);
  TEST(n >= 1 &&
       (dirty[((uint32_t)idx % 1024) / 64] >>
        (((uint32_t)idx % 1024) % 64)) & 1, "dirty bit for written slot");
  n = spt_bus_peek(st, dirty);
  TEST(n == 0, "drain cleared the mask");
  TEST(spt_bus_wait(st, 10) == -ETIMEDOUT, "bus wait times out when idle");
  spt_bus_close(st);

  /* ---- diagnostics ---- */
  TEST(spt_report_parse_failure(st) == 0, "parse failure counter");
  spt_header_snapshot(st, &hv);
  TEST(hv.parse_failures == 1, "parse failure visible in header");

  /* ---- NUMA-bound open (advisory bind; mapping valid regardless) ---- */
  {
    int brc = 1;
    spt_store *sn = spt_open_numa(name, flags, 0, &brc);
    TEST(sn != NULL, "numa open maps the store");
    TEST(brc == 0 || brc == -ENOSYS || brc == -EPERM || brc == -EINVAL,
         "numa bind returns 0 or a sane advisory errno");
    uint32_t l2 = 0;
    TEST(spt_get(sn, "k1", buf, sizeof buf, &l2) == 0,
         "numa-opened handle reads data");
    spt_close(sn);
    int brc2 = 0;
    sn = spt_open_numa(name, flags, -1, &brc2);
    TEST(sn != NULL && brc2 == -EINVAL, "numa open rejects bad node");
    spt_close(sn);
  }

  /* ---- persistence across close/reopen ---- */
  spt_close(st);
  st = spt_open(name, flags);
  TEST(st != NULL, "reopen");
  TEST(spt_get(st, "k1", buf, sizeof buf, &len) == 0 && len == 3 &&
       memcmp(buf, "bus", 3) == 0, "data survives reopen");
  spt_vec_get(st, "lab", vo, 8);
  TEST(vo[0] == 9.0f, "vector survives reopen");
  spt_close(st);
  spt_unlink(name, flags);
  TEST(spt_open(name, flags) == NULL, "open after unlink fails");
}

/* ---- change journal ---- */

/* Read the journal from *cursor to its head; 1 if slot idx is in it. */
static int journaled(spt_store *st, uint64_t *cursor, int idx) {
  static uint32_t rows[SPT_JOURNAL_CAP];
  int n = spt_changed_since(st, *cursor, rows, SPT_JOURNAL_CAP, cursor);
  int hit = 0;
  for (int i = 0; i < n; i++)
    if (idx >= 0 && rows[i] == (uint32_t)idx) hit = 1;
  return n < 0 ? n : hit;
}

/* One exported call that can move an epoch: the slot's epoch moved (or
 * the call aborted after taking the lock) and the journal names the
 * slot, in a range that held nothing before the call. */
#define MOVES(call, key, name) do {                                      \
    int ix_ = spt_find_index(st, key);                                   \
    uint64_t e0_ = ix_ >= 0 ? spt_epoch_at(st, (uint32_t)ix_) : 0;       \
    TEST(journaled(st, &cur, -1) == 0 && cur == spt_journal_head(st),    \
         name ": journal read up to its head before the call");          \
    (void)(call);                                                        \
    if (ix_ < 0) ix_ = spt_find_index(st, key);                          \
    TEST(ix_ >= 0 && spt_epoch_at(st, (uint32_t)ix_) != e0_ &&           \
         spt_epoch_at(st, (uint32_t)ix_) % 2 == 0,                       \
         name ": the epoch moved and is even");                          \
    TEST(journaled(st, &cur, ix_) == 1, name ": the slot is journaled"); \
  } while (0)

static void journal_suite(const char *name, uint32_t flags) {
  spt_unlink(name, flags);
  spt_store *st = spt_create(name, 64, 256, 8, flags);
  TEST(st != NULL, "journal: create");
  uint64_t cur = spt_journal_head(st);
  TEST(cur == 0, "journal: a new store's head is 0");
  uint32_t none[1];
  uint64_t c2 = 99;
  TEST(spt_changed_since(st, 0, none, 1, &c2) == 0 && c2 == 0,
       "journal: nothing appended, nothing returned");

  float v[8] = {1, 2, 3, 4, 5, 6, 7, 8};
  MOVES(spt_set(st, "a", "1", 1), "a", "set (new key)");
  MOVES(spt_set(st, "a", "22", 2), "a", "set (rewrite)");
  MOVES(spt_append(st, "a", "3", 1), "a", "append");
  MOVES(spt_append(st, "fresh", "x", 1), "fresh", "append (new key)");
  MOVES(spt_set_type(st, "a", SPT_T_BIGUINT), "a", "set_type");
  uint64_t r = 0;
  MOVES(spt_integer_op(st, "a", SPT_IOP_INC, 0, &r), "a", "integer_op");
  MOVES(spt_tandem_set(st, "t", 0, "p0", 2), "t", "tandem_set (base)");
  MOVES(spt_tandem_set(st, "t", 1, "p1", 2), "t" SPT_ORDER_SEP "1",
        "tandem_set (order 1)");
  MOVES(spt_set_system(st, "a"), "a", "set_system");
  MOVES(spt_set_system(st, "sys"), "sys", "set_system (new key)");
  MOVES(spt_stamp(st, "a", 2, 0), "a", "stamp");
  MOVES(spt_vec_set(st, "a", v, 8), "a", "vec_set");
  int ia = spt_find_index(st, "a");
  MOVES(spt_vec_set_at(st, (uint32_t)ia, v, 8), "a", "vec_set_at");
  {
    uint32_t rows[1] = {(uint32_t)ia};
    uint64_t eps[1] = {spt_epoch_at(st, (uint32_t)ia)};
    int32_t res[1] = {1};
    MOVES(spt_vec_commit_batch(st, rows, eps, v, 1, 8, 0, res), "a",
          "vec_commit_batch");
    TEST(res[0] == 0, "vec_commit_batch committed");
    /* a lock that aborts: the epoch gate refuses, the lock was held */
    eps[0] = 2;
    MOVES(spt_vec_commit_batch(st, rows, eps, v, 1, 8, 0, res), "a",
          "vec_commit_batch (stale: aborted lock)");
    TEST(res[0] == -ESTALE, "stale commit refused");
  }
  spt_set(st, "txt", "not a number", 12);
  journaled(st, &cur, -1);
  MOVES(spt_set_type(st, "txt", SPT_T_BIGUINT), "txt",
        "set_type -EPROTOTYPE (aborted lock)");
  MOVES(spt_integer_op(st, "txt", SPT_IOP_INC, 0, &r), "txt",
        "integer_op -EPROTOTYPE (aborted lock)");
  char big[250]; memset(big, 'y', sizeof big);
  MOVES(spt_append(st, "txt", big, sizeof big), "txt",
        "append -EMSGSIZE (aborted lock)");
  MOVES(spt_purge(st), "a", "purge (live slot)");

  /* retrain drives the epoch BACKWARD, from a slot stuck anywhere */
  for (int i = 0; i < 8; i++) spt_set(st, "a", "x", 1);
  TEST(spt_epoch_at(st, (uint32_t)ia) > 4, "epoch well past 4");
  MOVES(spt_retrain(st, "a"), "a", "retrain (backward)");
  TEST(spt_epoch_at(st, (uint32_t)ia) == 4, "retrain left epoch 4");

  /* unset: the slot is journaled though the key no longer resolves */
  {
    int it = spt_find_index(st, "txt");
    uint64_t e0 = spt_epoch_at(st, (uint32_t)it);
    journaled(st, &cur, -1);
    TEST(spt_unset(st, "txt") == 0 &&
         spt_epoch_at(st, (uint32_t)it) == e0 + 2, "unset moved the epoch");
    TEST(journaled(st, &cur, it) == 1, "unset: the slot is journaled");
    MOVES(spt_purge(st), "a", "purge");   /* sweeps the tombstone too */
    journaled(st, &cur, -1);
    e0 = spt_epoch_at(st, (uint32_t)it);
    spt_purge(st);
    TEST(spt_epoch_at(st, (uint32_t)it) == e0 + 2 &&
         journaled(st, &cur, it) == 1, "purge: a tombstone is journaled");
    int i1 = spt_find_index(st, "t" SPT_ORDER_SEP "1");
    TEST(spt_tandem_unset(st, "t", 1) == 2 &&
         journaled(st, &cur, i1) == 1, "tandem_unset: journaled");
  }

  /* a label RAISE is journaled, after its bits are readable: exactly one
   * record naming the slot, and no epoch moved */
  journaled(st, &cur, -1);
  {
    uint64_t e0 = spt_epoch_at(st, (uint32_t)ia), head0 = cur;
    uint32_t row[2] = {~0u, ~0u};
    TEST(spt_label_or(st, "a", 0x9) == 0 &&
         spt_journal_head(st) == head0 + 1, "label_or: one record");
    TEST(spt_changed_since(st, cur, row, 2, &cur) == 1 &&
         row[0] == (uint32_t)ia && cur == head0 + 1,
         "label_or: the record names the slot");
    TEST((spt_labels_at(st, row[0]) & 0x9) == 0x9,
         "label_or: the bits are readable by whoever read the record");
    TEST(spt_epoch_at(st, (uint32_t)ia) == e0, "label_or moved no epoch");
    TEST(spt_label_or(st, "a", 0x9) == 0 &&
         journaled(st, &cur, ia) == 1 && cur == head0 + 2,
         "label_or of bits already up: still one record a call");
    TEST(spt_label_or(st, "no-such-key", 1) == -ENOENT &&
         spt_journal_head(st) == cur, "label_or on a missing key: no record");
  }

  /* a label CLEAR and what moves no epoch leave no record */
  spt_label_andnot(st, "a", 0x9);
  spt_bump(st, "a"); spt_slot_usr_set(st, "a", 3);
  TEST(spt_labels_at(st, (uint32_t)ia) == 0 && spt_journal_head(st) == cur,
       "label_andnot, bump, user flags: no record");

  /* the listed epochs are the slots' own; out of range reads 0 */
  {
    uint32_t rows[2] = {(uint32_t)ia, 1u << 30};
    uint64_t eps[2] = {7, 7};
    TEST(spt_epochs_at(st, rows, 2, eps) == 2 &&
         eps[0] == spt_epoch_at(st, (uint32_t)ia) && eps[1] == 0,
         "epochs_at");
  }

  /* max_out bounds one read; the cursor says how far it got */
  for (int i = 0; i < 5; i++) spt_set(st, "a", "x", 1);
  {
    uint32_t two[2];
    uint64_t c = cur;
    TEST(spt_changed_since(st, c, two, 2, &c) == 2 && c == cur + 2,
         "a short buffer takes what fits");
    TEST(journaled(st, &c, ia) == 1 && c == cur + 5, "and the rest next");
    cur = c;
  }

  /* a reader the writers lapped is told so, and where to go on from */
  uint64_t old = cur;
  for (uint32_t i = 0; i <= SPT_JOURNAL_CAP; i++) spt_set(st, "a", "x", 1);
  TEST(journaled(st, &cur, ia) == -EOVERFLOW &&
       cur == spt_journal_head(st) && cur == old + SPT_JOURNAL_CAP + 1,
       "lapped: -EOVERFLOW, cursor = head");
  TEST(journaled(st, &cur, ia) == 0, "after the fallback: up to date");
  uint64_t beyond = cur + 5;
  TEST(journaled(st, &beyond, ia) == -EOVERFLOW && beyond == cur,
       "a cursor past the head is not this store's");
  /* exactly a ring behind is still complete */
  old = cur;
  for (uint32_t i = 0; i < SPT_JOURNAL_CAP; i++) spt_set(st, "a", "x", 1);
  TEST(journaled(st, &cur, ia) == 1 && cur == old + SPT_JOURNAL_CAP,
       "one whole ring behind: complete");

  /* a second handle has its own cursor on the same journal */
  spt_store *peer = spt_open(name, flags);
  TEST(peer && spt_journal_head(peer) == cur, "peer sees the same head");
  spt_set(peer, "b", "1", 1);
  TEST(journaled(st, &cur, spt_find_index(st, "b")) == 1,
       "a peer handle's write is journaled");
  spt_close(peer);
  spt_close(st);

  if (flags & SPT_BACKEND_FILE) {
    /* another format version is refused */
    FILE *f = fopen(name, "r+b");
    uint32_t ver = SPT_FORMAT_VERSION - 1;
    int wrote = f && fseek(f, 4, SEEK_SET) == 0 &&
                fwrite(&ver, sizeof ver, 1, f) == 1;
    if (f) fclose(f);
    TEST(wrote && spt_open(name, flags) == NULL &&
         spt_last_error() == EPROTO, "format version 1 refused (-EPROTO)");
  }
  spt_unlink(name, flags);
}

int main(void) {
  char shm_name[64], file_name[128];
  snprintf(shm_name, sizeof shm_name, "/spt-unit-%d", (int)getpid());
  snprintf(file_name, sizeof file_name, "/tmp/spt-unit-%d.store",
           (int)getpid());

  printf("# backend: shm\n");
  suite(shm_name, SPT_BACKEND_SHM);
  journal_suite(shm_name, SPT_BACKEND_SHM);
  printf("# backend: file (persistent)\n");
  suite(file_name, SPT_BACKEND_FILE);
  journal_suite(file_name, SPT_BACKEND_FILE);

  printf("1..%d\n", n_run);
  printf("# %d run, %d failed\n", n_run, n_fail);
  return n_fail ? 1 : 0;
}
