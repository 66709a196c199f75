/* internal.h — in-memory layout of the splinter-tpu store (not installed).
 *
 * Region layout (one mmap, shm or file; format version 2):
 *   [ header 8192B | change journal SPT_JOURNAL_CAP*8B
 *     | slot table nslots*192B | value arena nslots*max_val
 *     | vector lane nslots*vec_dim*4B (256-aligned) ]
 *
 * The vector lane is deliberately last and 256-byte aligned so the Python
 * tier can wrap it as one contiguous (nslots, dim) float32 numpy array and
 * stage dirty row-blocks to TPU HBM without gather-copies.
 *
 * The change journal (sptpu.h, "change journal") is a ring of 64-bit
 * entries at journal_off; entry p lives at ring[p % SPT_JOURNAL_CAP] and
 * holds (lap tag << 32 | slot index), so a reader tells a written entry
 * from a claimed one and from an older lap's by the value alone.  Its
 * head (the number of entries ever claimed) has a cache line of the
 * header's first page to itself.
 */
#ifndef SPTPU_INTERNAL_H
#define SPTPU_INTERNAL_H

#define _GNU_SOURCE
#include "sptpu.h"
#include <stdatomic.h>
#include <stdbool.h>
#include <string.h>
#include <errno.h>

#define SPT_HDR_BYTES   8192u
#define SPT_SLOT_BYTES  192u
#define SPT_TOMBSTONE   1ull    /* hash value marking a deleted slot */

typedef struct {
  _Atomic uint64_t v;
  uint8_t pad[56];
} spt_sigctr;                    /* one counter per cache line */

typedef struct {
  _Atomic int64_t  pid;          /* 0 = free */
  _Atomic uint64_t shard_id;
  _Atomic uint64_t claimed_at;   /* microseconds, CLOCK_MONOTONIC-derived */
  _Atomic uint64_t duration_us;  /* 0 = born expired */
  _Atomic uint32_t intent;
  _Atomic uint32_t priority;
  uint8_t pad[24];
} spt_bid;                       /* 64B */

typedef struct {
  uint32_t magic, version;
  uint64_t map_size;
  uint32_t nslots, max_val, vec_dim;
  _Atomic uint32_t mop_mode;
  uint64_t slots_off, values_off, vectors_off;
  _Atomic uint64_t global_epoch;
  _Atomic uint32_t core_flags;
  _Atomic uint32_t user_flags;
  _Atomic uint64_t parse_failures;
  _Atomic uint64_t last_failure_epoch;
  _Atomic int64_t  bus_pid;      /* event bus owner pid (0 = unarmed) */
  _Atomic int32_t  bus_fd;       /* eventfd number IN THE OWNER PROCESS */
  _Atomic uint32_t bus_gen;      /* bumped each re-arm */
  _Atomic uint64_t dirty[SPT_DIRTY_WORDS];
  /* per bloom bit: 64-bit mask of signal groups pulsed when that label bit
   * is set on a written slot */
  _Atomic uint64_t bloom_groups[SPT_BLOOM_BITS];
  spt_bid bids[SPT_MAX_BIDS];                      /* 2048B */
  /* change journal: where the ring is, how many entries it holds (a
   * constant of the format, kept here for readers in other languages),
   * and — on a cache line of its own, every writer adds to it — how many
   * entries were ever claimed */
  uint64_t journal_off, journal_cap;
  uint8_t pad_to_jhead[8];
  spt_sigctr journal_head;
  /* pad to 4096 then the signal arena fills the second 4K page */
  uint8_t pad_to_sig[4096 - 2048 - (2*8 + 8 + 64)
                     - (2*4 + 8 + 4*4 + 3*8 + 8 + 2*4 + 2*8 + 8 + 4 + 4
                        + SPT_DIRTY_WORDS*8 + SPT_BLOOM_BITS*8)];
  spt_sigctr signals[SPT_SIGNAL_GROUPS];           /* 4096B */
} spt_hdr;

typedef struct {
  _Atomic uint64_t epoch;        /* seqlock: odd = writer active */
  _Atomic uint64_t hash;         /* 0 empty, 1 tombstone; publication point */
  _Atomic uint64_t labels;       /* bloom label bits */
  _Atomic uint64_t watcher_mask; /* signal groups pulsed on write */
  uint32_t val_len;
  _Atomic uint32_t flags;        /* type | user<<8 | system */
  int64_t ctime, atime;          /* spt_now() ticks */
  char key[SPT_KEY_MAX];
} __attribute__((aligned(64))) spt_slot;  /* 184 -> 192B, 64-aligned */

struct spt_store {
  spt_hdr  *h;
  spt_slot *slots;
  uint8_t  *values;
  float    *vectors;             /* NULL if vec_dim == 0 */
  _Atomic uint64_t *journal;     /* SPT_JOURNAL_CAP entries */
  uint8_t  *base;
  uint64_t  map_size;
  int       fd;
  uint32_t  flags;
  int       my_bus_fd;           /* this process's handle on the eventfd */
  uint32_t  my_bus_gen;
  int       bus_owner;           /* this handle armed the bus */
  char      name[256];
};

_Static_assert(sizeof(spt_sigctr) == 64, "sigctr cache line");
_Static_assert(sizeof(spt_bid) == 64, "bid size");
_Static_assert(sizeof(spt_slot) == SPT_SLOT_BYTES, "slot size");
_Static_assert(sizeof(spt_hdr) == SPT_HDR_BYTES, "header size");
_Static_assert(offsetof(spt_hdr, journal_head) % 64 == 0,
               "journal head on its own cache line");
_Static_assert(offsetof(spt_hdr, signals) == 4096, "signal arena page");
_Static_assert((SPT_JOURNAL_CAP & (SPT_JOURNAL_CAP - 1)) == 0,
               "journal capacity is a power of two");

/* FNV-1a 64-bit; 0/1 are reserved sentinels so remap them. */
static inline uint64_t spt_hash_key(const char *k) {
  uint64_t h = 0xcbf29ce484222325ull;
  for (const unsigned char *p = (const unsigned char *)k; *p; ++p) {
    h ^= *p;
    h *= 0x100000001b3ull;
  }
  if (h <= SPT_TOMBSTONE) h += 0x9e3779b97f4a7c15ull;
  return h;
}

static inline uint8_t *slot_val(spt_store *st, uint32_t idx) {
  return st->values + (uint64_t)idx * st->h->max_val;
}
static inline float *slot_vec(spt_store *st, uint32_t idx) {
  return st->vectors ? st->vectors + (uint64_t)idx * st->h->vec_dim : NULL;
}

/* Probe for an existing key.  Returns slot index or -ENOENT.  Stops at the
 * first truly-empty slot (tombstones keep chains intact). */
int spt__probe_find(spt_store *st, const char *key, uint64_t h);
/* Probe for a write target: existing key, else first reusable
 * (tombstone/empty) along the chain.  Returns index or -ENOSPC.
 * *existed set to 1 when the key was already present. */
int spt__probe_claim(spt_store *st, const char *key, uint64_t h, int *existed);

/* Change journal.  The tag of position p is its lap + 1: never that of
 * the zero-filled ring, and the same again only 2^32 laps later. */
static inline uint64_t spt__journal_entry(uint64_t p, uint32_t idx) {
  return ((uint64_t)(uint32_t)(p / SPT_JOURNAL_CAP + 1) << 32) | idx;
}
/* Record that slot idx's epoch moves, or that a label of it was raised:
 * claim a position, then fill it. */
static inline void spt__journal(spt_store *st, uint32_t idx) {
  uint64_t p = atomic_fetch_add_explicit(&st->h->journal_head.v, 1,
                                         memory_order_acq_rel);
  atomic_store_explicit(&st->journal[p & (SPT_JOURNAL_CAP - 1)],
                        spt__journal_entry(p, idx), memory_order_release);
}

/* Seqlock helpers.  Acquire CASes even->odd (else -EAGAIN) and, the slot
 * now odd, journals it: every path that holds the lock has a record, the
 * aborted ones too, and a writer killed at any instruction leaves the
 * slot either odd or journaled.  Release publishes even = acquired+2. */
int  spt__lock(spt_store *st, uint32_t idx, uint64_t *e_out);
void spt__unlock(spt_slot *s, uint64_t e_acquired);
void spt__fanout(spt_store *st, uint32_t idx, spt_slot *s);

uint64_t spt__now_us(void);
int spt__bus_ensure_open(spt_store *st);

#endif
