/* stress.c — MRSW integrity stress: writer threads hammer a hot key set
 * while N reader threads validate a structured payload on every read.
 * Any torn read (payload that doesn't parse back to ver|nonce|data) is an
 * integrity failure and a nonzero exit.
 *
 * Parity with the reference's splinter_stress harness (SURVEY.md §4):
 * same contract — readers count EAGAIN retries (expected under load) and
 * corruption (never acceptable); reports ops/sec.
 *
 * Beside them one FOLLOWER thread consumes the change journal the way the
 * device lane does: it keeps the last stable epoch it saw of every slot,
 * fed by spt_changed_since alone (a slot seen odd is carried to the next
 * pass; a full scan only when the journal says it was lapped).  When the
 * writers have stopped, an audit compares every slot's epoch with the
 * follower's: journal + audit = the slots that moved, and the audit's
 * share must be 0 — a slot it finds moved without a record.
 *
 * And two RAISER threads raise a label bit on the hot keys (each its own
 * half, a key again only once its bit is down) against one LABEL FOLLOWER
 * that learns who asks the way the search daemon's gather does: the rows
 * the journal names since its cursor, united with the rows it still holds,
 * their labels read, the bit cleared on the ones it serves (every other
 * pass a row is deferred: it stays held and is served with no new record);
 * a lapped cursor (-EOVERFLOW; with --label-lap the follower lets the
 * writers lap it every 16th pass) walks every slot AFTER the call.  When the raisers have stopped,
 * served == raised and no slot carries the bit: every raise was named.
 *
 * Usage: spt_stress [--writers N] [--readers N] [--keys K]
 *                   [--duration-ms D] [--slots S] [--val-size V]
 *                   [--scrub MODE] [--label-lap]
 */
#define _GNU_SOURCE
#include "sptpu.h"

#include <pthread.h>
#include <sched.h>
#include <stdatomic.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <time.h>
#include <unistd.h>

static _Atomic long g_writes, g_reads, g_eagain, g_miss, g_corrupt;
static _Atomic int g_stop;
static int g_nkeys = 2000;
static int g_valsz = 1024;
static spt_store *g_st;

static void key_name(char *buf, int i) {
  snprintf(buf, SPT_KEY_MAX, "stress-key-%d", i);
}

/* --raw: measure the STORE's ceiling, not the harness's — keys are
 * pre-rendered and the payload is constant, so the loop body is one
 * spt_set per iteration (hash + probe + seqlock + memcpy + fanout).
 * Readers skip payload validation in this mode (the payload carries no
 * per-write nonce to check). */
static int g_raw = 0;

static void *writer_raw(void *arg) {
  (void)arg;
  char *keys = malloc((size_t)g_nkeys * SPT_KEY_MAX);
  char *payload = malloc((size_t)g_valsz + 64);
  for (int i = 0; i < g_nkeys; i++)
    key_name(keys + (size_t)i * SPT_KEY_MAX, i);
  memset(payload, 'x', (size_t)g_valsz);
  long nonce = 0;
  while (!atomic_load_explicit(&g_stop, memory_order_relaxed)) {
    const char *key = keys + (size_t)(nonce % g_nkeys) * SPT_KEY_MAX;
    int rc = spt_set(g_st, key, payload, (uint32_t)g_valsz);
    if (rc == 0)
      atomic_fetch_add_explicit(&g_writes, 1, memory_order_relaxed);
    else if (rc == -11) /* EAGAIN */
      atomic_fetch_add_explicit(&g_eagain, 1, memory_order_relaxed);
    nonce++;
  }
  free(keys);
  free(payload);
  return NULL;
}

static void *writer(void *arg) {
  if (g_raw) return writer_raw(arg);
  char key[SPT_KEY_MAX];
  char *payload = malloc((size_t)g_valsz + 64);
  long nonce = (long)(intptr_t)arg * 7919;  /* writers start apart */
  while (!atomic_load_explicit(&g_stop, memory_order_relaxed)) {
    int i = (int)(nonce % g_nkeys);
    key_name(key, i);
    int head = snprintf(payload, (size_t)g_valsz, "ver:%d|nonce:%ld|data:",
                        i, nonce);
    int fill = (int)(nonce % 64);
    for (int f = 0; f < fill && head + f < g_valsz - 1; f++)
      payload[head + f] = 'x';
    int len = head + (head + fill < g_valsz - 1 ? fill : 0);
    payload[len] = '\0';
    int rc = spt_set(g_st, key, payload, (uint32_t)len + 1);
    if (rc == 0)
      atomic_fetch_add_explicit(&g_writes, 1, memory_order_relaxed);
    else if (rc == -11) /* EAGAIN */
      atomic_fetch_add_explicit(&g_eagain, 1, memory_order_relaxed);
    nonce++;
  }
  free(payload);
  return NULL;
}

static int parse_payload(const char *buf, uint32_t len, int expect_key) {
  /* format: ver:<i>|nonce:<n>|data:x* — returns 1 if intact */
  int ver = -1;
  long nonce = -1;
  if (len < 8) return 0;
  if (sscanf(buf, "ver:%d|nonce:%ld|data:", &ver, &nonce) != 2) return 0;
  if (ver != expect_key || nonce < 0) return 0;
  const char *p = strstr(buf, "data:");
  if (!p) return 0;
  for (p += 5; *p; p++)
    if (*p != 'x') return 0;
  return 1;
}

static void *reader(void *arg) {
  (void)arg;
  char key[SPT_KEY_MAX];
  char *raw_keys = NULL;
  if (g_raw) {        /* pre-render keys: measure the store, not snprintf */
    raw_keys = malloc((size_t)g_nkeys * SPT_KEY_MAX);
    for (int i = 0; i < g_nkeys; i++)
      key_name(raw_keys + (size_t)i * SPT_KEY_MAX, i);
  }
  char *buf = malloc((size_t)g_valsz + 64);
  unsigned seed = (unsigned)(uintptr_t)&buf;
  while (!atomic_load_explicit(&g_stop, memory_order_relaxed)) {
    int i = (int)(rand_r(&seed) % g_nkeys);
    const char *k = key;
    if (raw_keys)
      k = raw_keys + (size_t)i * SPT_KEY_MAX;
    else
      key_name(key, i);
    uint32_t len = 0;
    int rc = spt_get(g_st, k, buf, (uint32_t)g_valsz + 64, &len);
    if (rc == 0) {
      atomic_fetch_add_explicit(&g_reads, 1, memory_order_relaxed);
      if (!g_raw && len > 0 && !parse_payload(buf, len, i)) {
        atomic_fetch_add_explicit(&g_corrupt, 1, memory_order_relaxed);
        fprintf(stderr, "CORRUPT key=%s len=%u buf=%.80s\n", k, len, buf);
      }
    } else if (rc == -11) {
      atomic_fetch_add_explicit(&g_eagain, 1, memory_order_relaxed);
    } else {
      atomic_fetch_add_explicit(&g_miss, 1, memory_order_relaxed);
    }
  }
  free(buf);
  free(raw_keys);
  return NULL;
}

/* ---- the journal follower ---- */

static uint32_t g_slots;
static uint64_t *g_seen;           /* last stable epoch seen, per slot */
static _Atomic int g_follow_stop;
static long g_j_rows, g_j_passes, g_j_fallbacks, g_j_carried;

static void *follower(void *arg) {
  (void)arg;
  uint32_t *rows = malloc(sizeof(uint32_t) * SPT_JOURNAL_CAP);
  uint32_t *carry = malloc(sizeof(uint32_t) * g_slots);
  uint32_t *carried_in = calloc(g_slots, sizeof(uint32_t)); /* pass no. */
  uint64_t *eps = malloc(sizeof(uint64_t) * g_slots);
  uint32_t n_carry = 0, pass = 0;
  uint64_t cursor = 0;             /* a new store's head: before any write */
  for (;;) {
    /* read the flag first: a pass that began after the writers were
     * joined sees everything they appended */
    int last = atomic_load_explicit(&g_follow_stop, memory_order_acquire);
    int n = spt_changed_since(g_st, cursor, rows, SPT_JOURNAL_CAP, &cursor);
    uint32_t kept = 0;
    g_j_passes++;
    pass++;
    if (n < 0) {                    /* lapped: scan, from the new cursor */
      g_j_fallbacks++;
      spt_epochs(g_st, eps);
      for (uint32_t i = 0; i < g_slots; i++) {
        if (eps[i] & 1) carry[kept++] = i;
        else g_seen[i] = eps[i];
      }
    } else {
      g_j_rows += n;
      for (uint32_t j = 0; j < n_carry + (uint32_t)n; j++) {
        uint32_t idx = j < n_carry ? carry[j] : rows[j - n_carry];
        uint64_t e = 0;
        spt_epochs_at(g_st, &idx, 1, &e);
        if (!(e & 1)) {
          g_seen[idx] = e;
        } else if (carried_in[idx] != pass) {  /* rows repeat: once each */
          carried_in[idx] = pass;
          carry[kept++] = idx;
          g_j_carried++;
        }
      }
    }
    n_carry = kept;
    if (last && n == 0 && n_carry == 0) break;
  }
  free(rows); free(carry); free(carried_in); free(eps);
  return NULL;
}

/* ---- label raisers and their follower ---- */

#define ASK_BIT (1ull << 57)
static _Atomic long g_raised;
static _Atomic int g_label_stop;
static long g_l_served, g_l_passes, g_l_fallbacks, g_l_deferred;
static int g_label_lap;

static void *raiser(void *arg) {
  int half = (int)(intptr_t)arg;
  char key[SPT_KEY_MAX];
  while (!atomic_load_explicit(&g_stop, memory_order_relaxed)) {
    for (int i = half; i < g_nkeys; i += 2) {
      uint64_t l = 0;
      key_name(key, i);
      /* -ENOENT until a writer has made the key: nothing raised */
      if (spt_get_labels(g_st, key, &l) == 0 && !(l & ASK_BIT) &&
          spt_label_or(g_st, key, ASK_BIT) == 0)
        atomic_fetch_add_explicit(&g_raised, 1, memory_order_relaxed);
    }
  }
  return NULL;
}

static void *label_follower(void *arg) {
  (void)arg;
  uint32_t *rows = malloc(sizeof(uint32_t) * SPT_JOURNAL_CAP);
  uint32_t *held = malloc(sizeof(uint32_t) * g_slots);
  uint32_t *keep = malloc(sizeof(uint32_t) * g_slots);
  uint32_t *walk = malloc(sizeof(uint32_t) * g_slots);
  uint32_t *seen_in = calloc(g_slots, sizeof(uint32_t));    /* pass no. */
  uint32_t n_held = 0, pass = 0;
  uint64_t cursor = 0;             /* a new store's head: before any raise */
  char key[SPT_KEY_MAX];
  for (;;) {
    int last = atomic_load_explicit(&g_label_stop, memory_order_acquire);
    if (g_label_lap && pass % 16 == 15)        /* fall a lap behind */
      while (spt_journal_head(g_st) - cursor <= SPT_JOURNAL_CAP &&
             !atomic_load_explicit(&g_stop, memory_order_relaxed))
        sched_yield();
    int n = spt_changed_since(g_st, cursor, rows, SPT_JOURNAL_CAP, &cursor);
    const uint32_t *named = rows;
    g_l_passes++;
    pass++;
    if (n < 0) {                    /* lapped: walk, AFTER the call */
      g_l_fallbacks++;
      n = spt_enumerate(g_st, ASK_BIT, walk, g_slots);
      named = walk;
    }
    uint32_t kept = 0, total = n_held + (uint32_t)n;
    for (uint32_t j = 0; j < total; j++) {
      uint32_t idx = j < n_held ? held[j] : named[j - n_held];
      if (seen_in[idx] == pass) continue;      /* rows repeat: once each */
      seen_in[idx] = pass;
      if (!(spt_labels_at(g_st, idx) & ASK_BIT)) continue;   /* not asking */
      if (!last && ((idx ^ pass) & 1)) {       /* deferred: stays held */
        g_l_deferred++;
        keep[kept++] = idx;
        continue;
      }
      if (spt_key_at(g_st, idx, key) == 0 &&
          spt_label_andnot(g_st, key, ASK_BIT) == 0)
        g_l_served++;
      else
        keep[kept++] = idx;
    }
    uint32_t *t = held; held = keep; keep = t;
    n_held = kept;
    if (last && n == 0 && n_held == 0) break;
  }
  free(rows); free(held); free(keep); free(walk); free(seen_in);
  return NULL;
}

/* --json: emit one machine-readable line.  CPO
 * (cycles per op) is measured separately from the contended run: a
 * single-threaded spt_set loop over pre-rendered keys, timed with the
 * store's own tick clock (spt_now = rdtsc/cntvct), so the number is
 * the store's clean per-write cost — the same definition the
 * reference's published CPO uses — not a descheduling artifact of the
 * oversubscribed stress threads. */
static double measure_write_cpo(void) {
  enum { CPO_OPS = 200000 };
  char *keys = malloc((size_t)g_nkeys * SPT_KEY_MAX);
  char *payload = malloc((size_t)g_valsz + 64);
  for (int i = 0; i < g_nkeys; i++)
    key_name(keys + (size_t)i * SPT_KEY_MAX, i);
  memset(payload, 'x', (size_t)g_valsz);
  /* warm the slots so the timed loop measures steady-state updates */
  for (int i = 0; i < g_nkeys; i++)
    spt_set(g_st, keys + (size_t)i * SPT_KEY_MAX, payload,
            (uint32_t)g_valsz);
  uint64_t t0 = spt_now();
  for (long n = 0; n < CPO_OPS; n++)
    spt_set(g_st, keys + (size_t)(n % g_nkeys) * SPT_KEY_MAX, payload,
            (uint32_t)g_valsz);
  uint64_t dt = spt_now() - t0;
  free(keys);
  free(payload);
  return (double)dt / (double)CPO_OPS;
}

static int int_arg(int argc, char **argv, int *i) {
  if (*i + 1 >= argc) {
    fprintf(stderr, "%s needs a value\n", argv[*i]);
    exit(2);
  }
  return atoi(argv[++*i]);
}

int main(int argc, char **argv) {
  int readers = 7, duration_ms = 5000, slots = 50000, json_out = 0;
  int writers = 2;
  uint32_t scrub = 1;
  for (int i = 1; i < argc; i++) {
    if (!strcmp(argv[i], "--readers")) readers = int_arg(argc, argv, &i);
    else if (!strcmp(argv[i], "--writers")) writers = int_arg(argc, argv, &i);
    else if (!strcmp(argv[i], "--keys")) g_nkeys = int_arg(argc, argv, &i);
    else if (!strcmp(argv[i], "--duration-ms"))
      duration_ms = int_arg(argc, argv, &i);
    else if (!strcmp(argv[i], "--slots")) slots = int_arg(argc, argv, &i);
    else if (!strcmp(argv[i], "--val-size")) g_valsz = int_arg(argc, argv, &i);
    else if (!strcmp(argv[i], "--scrub"))
      scrub = (uint32_t)int_arg(argc, argv, &i);
    else if (!strcmp(argv[i], "--label-lap")) g_label_lap = 1;
    else if (!strcmp(argv[i], "--raw")) g_raw = 1;
    else if (!strcmp(argv[i], "--json")) json_out = 1;
  }
  char name[64];
  snprintf(name, sizeof name, "/spt-stress-%d", getpid());
  spt_unlink(name, 0);
  g_st = spt_create(name, (uint32_t)slots, (uint32_t)g_valsz + 64, 0, 0);
  if (!g_st) { perror("create"); return 2; }
  spt_set_mop(g_st, scrub);
  if (writers < 1) writers = 1;
  if (writers > 8) writers = 8;
  g_slots = (uint32_t)slots;
  g_seen = calloc(g_slots, sizeof *g_seen);   /* a new store: all 0 */

  pthread_t ft, lt, at[2], wt[8], rt[64];
  pthread_create(&ft, NULL, follower, NULL);
  pthread_create(&lt, NULL, label_follower, NULL);
  for (int i = 0; i < 2; i++)
    pthread_create(&at[i], NULL, raiser, (void *)(intptr_t)i);
  for (int i = 0; i < writers; i++)
    pthread_create(&wt[i], NULL, writer, (void *)(intptr_t)i);
  for (int i = 0; i < readers && i < 64; i++)
    pthread_create(&rt[i], NULL, reader, NULL);

  struct timespec ts = {duration_ms / 1000, (duration_ms % 1000) * 1000000L};
  nanosleep(&ts, NULL);
  atomic_store(&g_stop, 1);
  for (int i = 0; i < writers; i++) pthread_join(wt[i], NULL);
  for (int i = 0; i < readers && i < 64; i++) pthread_join(rt[i], NULL);
  for (int i = 0; i < 2; i++) pthread_join(at[i], NULL);
  atomic_store_explicit(&g_follow_stop, 1, memory_order_release);
  atomic_store_explicit(&g_label_stop, 1, memory_order_release);
  pthread_join(ft, NULL);
  pthread_join(lt, NULL);
  long raised = g_raised, left = spt_enumerate(g_st, ASK_BIT, NULL, 0);

  /* the audit: every slot's epoch against the follower's */
  long moved = 0, audit = 0;
  uint64_t *now = malloc(sizeof(uint64_t) * g_slots);
  spt_epochs(g_st, now);
  for (uint32_t i = 0; i < g_slots; i++) {
    if (now[i]) moved++;
    if (now[i] != g_seen[i]) audit++;
  }
  free(now);
  free(g_seen);

  long w = g_writes, r = g_reads, e = g_eagain, m = g_miss, c = g_corrupt;
  double secs = duration_ms / 1000.0;
  printf("MRSW: writers=%d readers=%d dur=%.1fs\n", writers, readers, secs);
  printf("  journal: rows=%ld passes=%ld fallbacks=%ld carried=%ld "
         "moved=%ld audit=%ld\n", g_j_rows, g_j_passes, g_j_fallbacks,
         g_j_carried, moved, audit);
  printf("  labels: raised=%ld served=%ld passes=%ld fallbacks=%ld "
         "deferred=%ld left=%ld\n", raised, g_l_served, g_l_passes,
         g_l_fallbacks, g_l_deferred, left);
  printf("  writes=%ld (%.2fM/s)  reads=%ld (%.2fM/s)\n", w, w / secs / 1e6,
         r, r / secs / 1e6);
  printf("  total=%.2fM ops/s  eagain=%ld  miss=%ld  corrupt=%ld\n",
         (w + r) / secs / 1e6, e, m, c);
  if (json_out) {
    double cpo = measure_write_cpo();
    printf("{\"tool\": \"mrsw\", \"writers\": %d, \"readers\": %d, "
           "\"duration_s\": %.2f, \"writes\": %ld, \"reads\": %ld, "
           "\"ops_per_sec\": %.0f, \"write_cpo\": %.1f, "
           "\"ticks_per_us\": %llu, \"eagain\": %ld, \"miss\": %ld, "
           "\"corrupt\": %ld, \"raw\": %d}\n",
           writers, readers, secs, w, r, (w + r) / secs, cpo,
           (unsigned long long)spt_ticks_per_us(), e, m, c, g_raw);
  }
  spt_close(g_st);
  spt_unlink(name, 0);
  if (c) { fprintf(stderr, "INTEGRITY FAILURE\n"); return 1; }
  if (audit || !moved || !g_j_rows) {
    fprintf(stderr, "JOURNAL FAILURE: %ld slots moved without a record "
            "(moved=%ld journal rows=%ld)\n", audit, moved, g_j_rows);
    return 1;
  }
  if (!g_raw && (!raised || raised != g_l_served || left)) {
    fprintf(stderr, "LABEL FAILURE: raised=%ld served=%ld, %ld rows still "
            "labelled: a raise the journal did not name\n", raised,
            g_l_served, left);
    return 1;
  }
  printf("OK\n");
  return 0;
}
