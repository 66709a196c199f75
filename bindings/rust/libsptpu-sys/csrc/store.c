/* store.c — lifecycle, seqlock KV ops, typed slots, labels, tandem keys,
 * mop/purge, snapshots, recovery, and the embedding vector lane.
 *
 * Capability parity with the reference core (splinterhq/libsplinter
 * splinter.c:103-887, see SURVEY.md §2.1); fresh TPU-first design — see
 * sptpu.h header comment for the deliberate deviations.
 */
#include "internal.h"

#include <fcntl.h>
#include <sched.h>
#include <stdio.h>
#include <stdlib.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <time.h>
#include <unistd.h>

static _Thread_local int spt_errno_tl;

static void set_err(int e) { spt_errno_tl = e; }
int spt_last_error(void) { return spt_errno_tl; }

/* ---------------------------------------------------------------- layout */

static uint64_t layout_size(uint32_t nslots, uint32_t max_val,
                            uint32_t vec_dim, uint64_t off[4]) {
  uint64_t o = SPT_HDR_BYTES;
  off[3] = o;                              /* change journal */
  o += (uint64_t)SPT_JOURNAL_CAP * sizeof(uint64_t);
  off[0] = o;                              /* slots */
  o += (uint64_t)nslots * SPT_SLOT_BYTES;
  o = (o + 63) & ~63ull;
  off[1] = o;                              /* values */
  o += (uint64_t)nslots * max_val;
  o = (o + 255) & ~255ull;
  off[2] = o;                              /* vectors */
  o += (uint64_t)nslots * vec_dim * sizeof(float);
  return (o + 4095) & ~4095ull;
}

static void wire(spt_store *st) {
  st->h = (spt_hdr *)st->base;
  st->slots = (spt_slot *)(st->base + st->h->slots_off);
  st->journal = (_Atomic uint64_t *)(st->base + st->h->journal_off);
  st->values = st->base + st->h->values_off;
  st->vectors = st->h->vec_dim
                    ? (float *)(st->base + st->h->vectors_off)
                    : NULL;
}

/* SPTPU_DEFAULT_UMASK: octal override applied around backing-object create
 * (parity with the reference's SPLINTER_DEFAULT_UMASK, splinter.c:113-146). */
static mode_t env_umask(int *active) {
  const char *s = getenv("SPTPU_DEFAULT_UMASK");
  *active = 0;
  if (!s || !*s) return 0;
  char *end = NULL;
  long v = strtol(s, &end, 8);
  if (end && *end == '\0' && v >= 0 && v <= 0777) {
    *active = 1;
    return (mode_t)v;
  }
  return 0;
}

static int open_backing(const char *name, uint32_t flags, int creating,
                        int *fd_out) {
  /* create is ALWAYS exclusive: truncating a live store out from under
   * its peers would SIGBUS them.  Callers that want replace semantics
   * unlink first. */
  int oflags = creating ? (O_RDWR | O_CREAT | O_EXCL) : O_RDWR;
  int um_active = 0;
  mode_t um = env_umask(&um_active);
  mode_t saved = 0;
  if (creating && um_active) saved = umask(um);
  /* 0666 so the process umask (or SPTPU_DEFAULT_UMASK) decides how widely
   * the store is shared */
  int fd;
  if (flags & SPT_BACKEND_FILE)
    fd = open(name, oflags | O_NOFOLLOW, 0666);
  else
    fd = shm_open(name, oflags, 0666);
  if (creating && um_active) umask(saved);
  if (fd < 0) return -errno;
  *fd_out = fd;
  return 0;
}

spt_store *spt_create(const char *name, uint32_t nslots, uint32_t max_val,
                      uint32_t vec_dim, uint32_t flags) {
  if (!name || !nslots || !max_val) { set_err(EINVAL); return NULL; }
  max_val = (max_val + 63) & ~63u;   /* mop slop granularity */
  uint64_t off[4];
  uint64_t sz = layout_size(nslots, max_val, vec_dim, off);

  int fd = -1, rc = open_backing(name, flags, 1, &fd);
  if (rc < 0) { set_err(-rc); return NULL; }
  if (ftruncate(fd, (off_t)sz) < 0) { set_err(errno); close(fd); return NULL; }

  uint8_t *base = mmap(NULL, sz, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
  if (base == MAP_FAILED) { set_err(errno); close(fd); return NULL; }

  spt_store *st = calloc(1, sizeof *st);
  if (!st) { set_err(ENOMEM); munmap(base, sz); close(fd); return NULL; }
  st->base = base;
  st->map_size = sz;
  st->fd = fd;
  st->flags = flags;
  st->my_bus_fd = -1;
  snprintf(st->name, sizeof st->name, "%s", name);

  spt_hdr *h = (spt_hdr *)base;
  /* fresh mapping is zero-filled; fill geometry then publish magic last */
  h->version = SPT_FORMAT_VERSION;
  h->map_size = sz;
  h->nslots = nslots;
  h->max_val = max_val;
  h->vec_dim = vec_dim;
  h->slots_off = off[0];
  h->values_off = off[1];
  h->vectors_off = off[2];
  h->journal_off = off[3];
  h->journal_cap = SPT_JOURNAL_CAP;
  atomic_store(&h->mop_mode, SPT_MOP_HYBRID);
  atomic_store(&h->bus_fd, -1);
  atomic_thread_fence(memory_order_release);
  h->magic = SPT_MAGIC;
  wire(st);
  return st;
}

spt_store *spt_open(const char *name, uint32_t flags) {
  if (!name) { set_err(EINVAL); return NULL; }
  int fd = -1, rc = open_backing(name, flags, 0, &fd);
  if (rc < 0) { set_err(-rc); return NULL; }

  struct stat sb;
  if (fstat(fd, &sb) < 0 || (uint64_t)sb.st_size < SPT_HDR_BYTES) {
    set_err(EBADF); close(fd); return NULL;
  }
  uint8_t *base = mmap(NULL, (size_t)sb.st_size, PROT_READ | PROT_WRITE,
                       MAP_SHARED, fd, 0);
  if (base == MAP_FAILED) { set_err(errno); close(fd); return NULL; }

  spt_hdr *h = (spt_hdr *)base;
  if (h->magic != SPT_MAGIC || h->version != SPT_FORMAT_VERSION ||
      h->map_size != (uint64_t)sb.st_size ||
      h->journal_cap != SPT_JOURNAL_CAP) {
    set_err(EPROTO);
    munmap(base, (size_t)sb.st_size);
    close(fd);
    return NULL;
  }
  spt_store *st = calloc(1, sizeof *st);
  if (!st) { set_err(ENOMEM); munmap(base, (size_t)sb.st_size); close(fd);
             return NULL; }
  st->base = base;
  st->map_size = h->map_size;
  st->fd = fd;
  st->flags = flags;
  st->my_bus_fd = -1;
  snprintf(st->name, sizeof st->name, "%s", name);
  wire(st);
  return st;
}

/* NUMA-bound open (parity with the reference's SPLINTER_NUMA_AFFINITY
 * variant, splinter.c:250-264): open the store, then mbind(MPOL_BIND) the
 * whole mapping to one node so the arena's pages — and the vector lane the
 * TPU runtime DMAs from — are allocated on the memory controller closest to
 * the accelerator's PCIe root.  Raw syscall: no libnuma dependency.  A
 * kernel without NUMA support returns -ENOSYS from the bind; the mapping
 * itself is still valid, so we surface the error and let the caller decide
 * (the Python tier treats it as advisory). */
#include <sys/syscall.h>
#ifndef SYS_mbind
#  if defined(__x86_64__)
#    define SYS_mbind 237
#  elif defined(__aarch64__)
#    define SYS_mbind 235
#  endif
#endif
#define SPT_MPOL_BIND 2
#define SPT_MPOL_MF_MOVE 2 /* migrate this process's existing pages too;
                              pages other processes pinned need
                              MPOL_MF_MOVE_ALL + CAP_SYS_NICE and stay put */

spt_store *spt_open_numa(const char *name, uint32_t flags, int node,
                         int *bind_rc) {
  spt_store *st = spt_open(name, flags);
  if (!st) return NULL;
  int rc = -ENOSYS;
#ifdef SYS_mbind
  if (node >= 0 && node < 1024) {
    unsigned long mask[1024 / (8 * sizeof(unsigned long))] = {0};
    mask[node / (8 * sizeof(unsigned long))] =
        1ul << (node % (8 * sizeof(unsigned long)));
    long r = syscall(SYS_mbind, st->base, st->map_size, SPT_MPOL_BIND,
                     mask, (unsigned long)(sizeof(mask) * 8 + 1),
                     (unsigned long)SPT_MPOL_MF_MOVE);
    rc = r < 0 ? -errno : 0;
  } else {
    rc = -EINVAL;
  }
#endif
  if (bind_rc) *bind_rc = rc;
  return st;
}

int spt_close(spt_store *st) {
  if (!st) return -EINVAL;
  spt_bus_close(st);
  munmap(st->base, st->map_size);
  close(st->fd);
  free(st);
  return 0;
}

int spt_unlink(const char *name, uint32_t flags) {
  if (!name) return -EINVAL;
  int rc = (flags & SPT_BACKEND_FILE) ? unlink(name) : shm_unlink(name);
  return rc < 0 ? -errno : 0;
}

uint32_t spt_nslots(const spt_store *st) { return st->h->nslots; }
uint32_t spt_max_val(const spt_store *st) { return st->h->max_val; }
uint32_t spt_vec_dim(const spt_store *st) { return st->h->vec_dim; }
void *spt_vec_lane(spt_store *st) { return st->vectors; }
void *spt_values_base(spt_store *st) { return st->values; }

/* ---------------------------------------------------------------- probing */

int spt__probe_find(spt_store *st, const char *key, uint64_t h) {
  uint32_t n = st->h->nslots;
  uint32_t start = (uint32_t)(h % n);
  for (uint32_t d = 0; d < n; d++) {
    uint32_t i = (start + d) % n;
    uint64_t sh = atomic_load_explicit(&st->slots[i].hash,
                                       memory_order_acquire);
    if (sh == 0) return -ENOENT;              /* never-used: end of chain */
    if (sh == h && strncmp(st->slots[i].key, key, SPT_KEY_MAX) == 0)
      return (int)i;
  }
  return -ENOENT;
}

int spt__probe_claim(spt_store *st, const char *key, uint64_t h,
                     int *existed) {
  uint32_t n = st->h->nslots;
  uint32_t start = (uint32_t)(h % n);
  int first_free = -1;
  for (uint32_t d = 0; d < n; d++) {
    uint32_t i = (start + d) % n;
    uint64_t sh = atomic_load_explicit(&st->slots[i].hash,
                                       memory_order_acquire);
    if (sh == 0) {
      *existed = 0;
      return first_free >= 0 ? first_free : (int)i;
    }
    if (sh == SPT_TOMBSTONE) {
      if (first_free < 0) first_free = (int)i;
      continue;
    }
    if (sh == h && strncmp(st->slots[i].key, key, SPT_KEY_MAX) == 0) {
      *existed = 1;
      return (int)i;
    }
  }
  *existed = 0;
  if (first_free >= 0) return first_free;
  return -ENOSPC;
}

/* ---------------------------------------------------------------- seqlock */

int spt__lock(spt_store *st, uint32_t idx, uint64_t *e_out) {
  spt_slot *s = &st->slots[idx];
  uint64_t e = atomic_load_explicit(&s->epoch, memory_order_acquire);
  if (e & 1) return -EAGAIN;                 /* writer active */
  if (!atomic_compare_exchange_strong_explicit(&s->epoch, &e, e + 1,
                                               memory_order_acq_rel,
                                               memory_order_acquire))
    return -EAGAIN;                          /* lost the race */
  spt__journal(st, idx);                     /* odd, not yet published */
  *e_out = e;
  return 0;
}

void spt__unlock(spt_slot *s, uint64_t e_acquired) {
  atomic_store_explicit(&s->epoch, e_acquired + 2, memory_order_release);
}

/* Probe for an existing key, acquire its seqlock, and revalidate the
 * key->slot binding under the lock (the slot may have been unset or
 * reclaimed for a different key between probe and lock).  On success the
 * slot is locked and idx_out/e_out are set. */
static int lock_key(spt_store *st, const char *key, uint32_t *idx_out,
                    uint64_t *e_out) {
  uint64_t h = spt_hash_key(key);
  int idx = spt__probe_find(st, key, h);
  if (idx < 0) return idx;
  spt_slot *s = &st->slots[idx];
  uint64_t e;
  int rc = spt__lock(st, (uint32_t)idx, &e);
  if (rc < 0) return rc;
  uint64_t cur = atomic_load_explicit(&s->hash, memory_order_relaxed);
  if (cur <= SPT_TOMBSTONE) {
    spt__unlock(s, e);
    return -ENOENT;
  }
  if (!(cur == h && strncmp(s->key, key, SPT_KEY_MAX) == 0)) {
    spt__unlock(s, e);
    return -EAGAIN;           /* slot rebound to another key; retry */
  }
  *idx_out = (uint32_t)idx;
  *e_out = e;
  return 0;
}

/* mop scrub: zero the stale tail of the old value beyond the new length.
 * HYBRID rounds the zeroed span up to the 64B slop boundary; FULL always
 * zeroes the entire region. */
static void mop_scrub(spt_store *st, uint32_t idx, uint32_t old_len,
                      uint32_t new_len) {
  uint32_t mode = atomic_load_explicit(&st->h->mop_mode,
                                       memory_order_relaxed);
  uint8_t *v = slot_val(st, idx);
  if (mode == SPT_MOP_FULL) {
    memset(v, 0, st->h->max_val);
  } else if (mode == SPT_MOP_HYBRID && old_len > new_len) {
    uint32_t end = (old_len + 63u) & ~63u;
    if (end > st->h->max_val) end = st->h->max_val;
    memset(v + new_len, 0, end - new_len);
  }
}

/* ------------------------------------------------------------------- set */

int spt_set(spt_store *st, const char *key, const void *val, uint32_t len) {
  if (!st || !key || (!val && len)) return -EINVAL;
  if (strlen(key) >= SPT_KEY_MAX) return -ENAMETOOLONG;
  if (len > st->h->max_val) return -EMSGSIZE;

  uint64_t h = spt_hash_key(key);
  int existed = 0;
  int idx = spt__probe_claim(st, key, h, &existed);
  if (idx < 0) return idx;
  spt_slot *s = &st->slots[idx];

  uint64_t e;
  int rc = spt__lock(st, (uint32_t)idx, &e);
  if (rc < 0) return rc;

  /* the slot may have been claimed for a different key — or our key may
   * have been unset — between probe and lock; re-derive state under the
   * lock (a stale `existed` would publish a ghost slot with no key) */
  uint64_t cur = atomic_load_explicit(&s->hash, memory_order_relaxed);
  if (cur > SPT_TOMBSTONE &&
      !(cur == h && strncmp(s->key, key, SPT_KEY_MAX) == 0)) {
    spt__unlock(s, e);
    return -EAGAIN;
  }
  existed = cur > SPT_TOMBSTONE;

  uint32_t old_len = existed ? s->val_len : 0;
  if (!existed && st->vectors)
    memset(slot_vec(st, (uint32_t)idx), 0,
           (size_t)st->h->vec_dim * sizeof(float));
  mop_scrub(st, (uint32_t)idx, old_len, len);
  if (len) memcpy(slot_val(st, (uint32_t)idx), val, len);
  s->val_len = len;
  if (!existed) {
    atomic_store_explicit(&s->flags, SPT_T_VOID, memory_order_relaxed);
    atomic_store_explicit(&s->labels, 0, memory_order_relaxed);
    atomic_store_explicit(&s->watcher_mask, 0, memory_order_relaxed);
    s->ctime = (int64_t)spt_now();
    memset(s->key, 0, SPT_KEY_MAX);
    memcpy(s->key, key, strlen(key));
  }
  s->atime = (int64_t)spt_now();
  atomic_thread_fence(memory_order_release);
  atomic_store_explicit(&s->hash, h, memory_order_release); /* publication */
  spt__unlock(s, e);
  spt__fanout(st, (uint32_t)idx, s);
  return 0;
}

/* ------------------------------------------------------------------- get */

static int read_slot_val(spt_store *st, uint32_t idx, void *buf,
                         uint32_t cap, uint32_t *len_out) {
  spt_slot *s = &st->slots[idx];
  uint64_t e1 = atomic_load_explicit(&s->epoch, memory_order_acquire);
  if (e1 & 1) return -EAGAIN;
  uint64_t sh = atomic_load_explicit(&s->hash, memory_order_acquire);
  if (sh <= SPT_TOMBSTONE) return -ENOENT;
  uint32_t len = s->val_len;
  if (len > st->h->max_val) return -EAGAIN;  /* torn geometry read */
  if (buf) {
    uint32_t n = len < cap ? len : cap;
    memcpy(buf, slot_val(st, idx), n);
  }
  atomic_thread_fence(memory_order_acquire);
  uint64_t e2 = atomic_load_explicit(&s->epoch, memory_order_acquire);
  if (e1 != e2) return -EAGAIN;
  if (len_out) *len_out = len;
  if (buf && cap < len) return -EMSGSIZE;
  return 0;
}

int spt_get(spt_store *st, const char *key, void *buf, uint32_t cap,
            uint32_t *len_out) {
  if (!st || !key) return -EINVAL;
  int idx = spt__probe_find(st, key, spt_hash_key(key));
  if (idx < 0) return idx;
  return read_slot_val(st, (uint32_t)idx, buf, cap, len_out);
}

int spt_get_at(spt_store *st, uint32_t idx, void *buf, uint32_t cap,
               uint32_t *len_out) {
  if (!st || idx >= st->h->nslots) return -EINVAL;
  return read_slot_val(st, idx, buf, cap, len_out);
}

int spt_get_raw(spt_store *st, const char *key, const void **ptr,
                uint32_t *len_out, uint64_t *epoch_out) {
  if (!st || !key || !ptr) return -EINVAL;
  int idx = spt__probe_find(st, key, spt_hash_key(key));
  if (idx < 0) return idx;
  spt_slot *s = &st->slots[idx];
  uint64_t e = atomic_load_explicit(&s->epoch, memory_order_acquire);
  if (e & 1) return -EAGAIN;
  *ptr = slot_val(st, (uint32_t)idx);
  if (len_out) *len_out = s->val_len;
  if (epoch_out) *epoch_out = e;
  return idx;
}

/* ----------------------------------------------------------------- unset */

int spt_unset(spt_store *st, const char *key) {
  if (!st || !key) return -EINVAL;
  uint32_t idx;
  uint64_t e;
  int rc = lock_key(st, key, &idx, &e);
  if (rc < 0) return rc;
  spt_slot *s = &st->slots[idx];
  memset(slot_val(st, (uint32_t)idx), 0, st->h->max_val);
  if (st->vectors)
    memset(slot_vec(st, (uint32_t)idx), 0,
           (size_t)st->h->vec_dim * sizeof(float));
  memset(s->key, 0, SPT_KEY_MAX);
  s->val_len = 0;
  atomic_store_explicit(&s->flags, SPT_T_VOID, memory_order_relaxed);
  atomic_store_explicit(&s->labels, 0, memory_order_relaxed);
  atomic_store_explicit(&s->watcher_mask, 0, memory_order_relaxed);
  atomic_store_explicit(&s->hash, SPT_TOMBSTONE, memory_order_release);
  spt__unlock(s, e);
  atomic_fetch_add_explicit(&st->h->global_epoch, 1, memory_order_relaxed);
  return 0;
}

/* ---------------------------------------------------------------- append */

int spt_append(spt_store *st, const char *key, const void *val,
               uint32_t len) {
  if (!st || !key || (!val && len)) return -EINVAL;
  uint32_t idx;
  uint64_t e;
  int rc = lock_key(st, key, &idx, &e);
  if (rc == -ENOENT) return spt_set(st, key, val, len); /* append-new = set */
  if (rc < 0) return rc;
  spt_slot *s = &st->slots[idx];
  if ((uint64_t)s->val_len + len > st->h->max_val) {
    spt__unlock(s, e);
    return -EMSGSIZE;
  }
  memcpy(slot_val(st, (uint32_t)idx) + s->val_len, val, len);
  s->val_len += len;
  s->atime = (int64_t)spt_now();
  spt__unlock(s, e);
  spt__fanout(st, (uint32_t)idx, s);
  return 0;
}

/* ------------------------------------------------------------------ list */

int spt_list(spt_store *st, char *keys, uint32_t max_keys) {
  if (!st) return -EINVAL;
  uint32_t n = st->h->nslots, out = 0;
  for (uint32_t i = 0; i < n && (!keys || out < max_keys); i++) {
    uint64_t sh = atomic_load_explicit(&st->slots[i].hash,
                                       memory_order_acquire);
    if (sh <= SPT_TOMBSTONE) continue;
    if (keys) {
      memcpy(keys + (size_t)out * SPT_KEY_MAX, st->slots[i].key,
             SPT_KEY_MAX);
      keys[(size_t)out * SPT_KEY_MAX + SPT_KEY_MAX - 1] = '\0';
    }
    out++;
  }
  return (int)out;
}

/* ------------------------------------------------------------------ poll */

int spt_poll(spt_store *st, const char *key, int timeout_ms) {
  if (!st || !key) return -EINVAL;
  int idx = spt__probe_find(st, key, spt_hash_key(key));
  if (idx < 0) return idx;
  uint64_t e0 = atomic_load_explicit(&st->slots[idx].epoch,
                                     memory_order_acquire);
  uint64_t t_per_us = spt_ticks_per_us();
  uint64_t deadline = timeout_ms < 0
                          ? 0
                          : spt_now() + (uint64_t)timeout_ms * 1000 * t_per_us;
  struct timespec ts = {0, 1000000};  /* 1 ms */
  for (;;) {
    uint64_t e = atomic_load_explicit(&st->slots[idx].epoch,
                                      memory_order_acquire);
    if (e != e0) return 0;
    if (timeout_ms >= 0 && spt_now() >= deadline) return -ETIMEDOUT;
    if (st->my_bus_fd >= 0)
      spt_bus_wait(st, 1);
    else
      nanosleep(&ts, NULL);
  }
}

int spt_poll_labels(spt_store *st, const char *key, uint64_t mask,
                    uint64_t want, int timeout_ms) {
  if (!st || !key) return -EINVAL;
  int idx = spt__probe_find(st, key, spt_hash_key(key));
  if (idx < 0) return idx;
  spt_slot *s = &st->slots[idx];
  uint64_t e0 = atomic_load_explicit(&s->epoch, memory_order_acquire);
  uint64_t deadline =
      timeout_ms < 0 ? 0
                     : spt_now() + (uint64_t)timeout_ms * 1000 *
                                       spt_ticks_per_us();
  struct timespec ts = {0, 1000000};  /* 1 ms */
  for (;;) {
    /* level-triggered: the condition is tested before the first sleep,
     * so a flip between the caller's own read and this call is kept */
    uint64_t l = atomic_load_explicit(&s->labels, memory_order_acquire);
    if ((l & mask) == want) return 0;
    if (atomic_load_explicit(&s->epoch, memory_order_acquire) != e0)
      return 0;                 /* rewritten or deleted: the caller looks */
    if (timeout_ms >= 0 && spt_now() >= deadline) return -ETIMEDOUT;
    if (st->my_bus_fd >= 0)     /* spt_poll's cadence: the commit's bump */
      spt_bus_wait(st, 1);      /* rings the bus */
    else
      nanosleep(&ts, NULL);
  }
}

/* -------------------------------------------------------- index accessors */

int spt_find_index(spt_store *st, const char *key) {
  if (!st || !key) return -EINVAL;
  return spt__probe_find(st, key, spt_hash_key(key));
}

int spt_key_at(spt_store *st, uint32_t idx, char *key_out) {
  if (!st || idx >= st->h->nslots || !key_out) return -EINVAL;
  spt_slot *s = &st->slots[idx];
  for (int tries = 0; tries < 64; tries++) {
    uint64_t e1 = atomic_load_explicit(&s->epoch, memory_order_acquire);
    if (e1 & 1) continue;
    uint64_t sh = atomic_load_explicit(&s->hash, memory_order_acquire);
    if (sh <= SPT_TOMBSTONE) return -ENOENT;
    memcpy(key_out, s->key, SPT_KEY_MAX);
    atomic_thread_fence(memory_order_acquire);
    if (atomic_load_explicit(&s->epoch, memory_order_acquire) == e1) {
      key_out[SPT_KEY_MAX - 1] = '\0';
      return 0;
    }
  }
  return -EAGAIN;
}

uint64_t spt_epoch_at(spt_store *st, uint32_t idx) {
  if (!st || idx >= st->h->nslots) return 0;
  return atomic_load_explicit(&st->slots[idx].epoch, memory_order_acquire);
}

uint64_t spt_labels_at(spt_store *st, uint32_t idx) {
  if (!st || idx >= st->h->nslots) return 0;
  return atomic_load_explicit(&st->slots[idx].labels, memory_order_acquire);
}

uint32_t spt_flags_at(spt_store *st, uint32_t idx) {
  if (!st || idx >= st->h->nslots) return 0;
  return atomic_load_explicit(&st->slots[idx].flags, memory_order_acquire);
}

/* ------------------------------------------------------------- snapshots */

int spt_header_snapshot(spt_store *st, spt_header_view *out) {
  if (!st || !out) return -EINVAL;
  memset(out, 0, sizeof *out);
  out->magic = st->h->magic;
  out->version = st->h->version;
  out->nslots = st->h->nslots;
  out->max_val = st->h->max_val;
  out->vec_dim = st->h->vec_dim;
  out->mop_mode = atomic_load(&st->h->mop_mode);
  out->map_size = st->h->map_size;
  out->global_epoch = atomic_load(&st->h->global_epoch);
  out->core_flags = atomic_load(&st->h->core_flags);
  out->user_flags = atomic_load(&st->h->user_flags);
  out->parse_failures = atomic_load(&st->h->parse_failures);
  out->last_failure_epoch = atomic_load(&st->h->last_failure_epoch);
  out->bus_pid = atomic_load(&st->h->bus_pid);
  uint32_t used = 0;
  for (uint32_t i = 0; i < st->h->nslots; i++)
    if (atomic_load_explicit(&st->slots[i].hash, memory_order_relaxed) >
        SPT_TOMBSTONE)
      used++;
  out->used_slots = used;
  return 0;
}

static int slot_snapshot_idx(spt_store *st, uint32_t idx,
                             spt_slot_view *out) {
  spt_slot *s = &st->slots[idx];
  for (int tries = 0; tries < 1024; tries++) {
    uint64_t e1 = atomic_load_explicit(&s->epoch, memory_order_acquire);
    if (e1 & 1) continue;
    out->hash = atomic_load_explicit(&s->hash, memory_order_acquire);
    out->labels = atomic_load_explicit(&s->labels, memory_order_relaxed);
    out->watcher_mask =
        atomic_load_explicit(&s->watcher_mask, memory_order_relaxed);
    out->val_len = s->val_len;
    out->flags = atomic_load_explicit(&s->flags, memory_order_relaxed);
    out->ctime = s->ctime;
    out->atime = s->atime;
    memcpy(out->key, s->key, SPT_KEY_MAX);
    atomic_thread_fence(memory_order_acquire);
    uint64_t e2 = atomic_load_explicit(&s->epoch, memory_order_acquire);
    if (e1 == e2) {
      out->epoch = e1;
      out->index = (int32_t)idx;
      return 0;
    }
  }
  return -EAGAIN;
}

int spt_slot_snapshot(spt_store *st, const char *key, spt_slot_view *out) {
  if (!st || !key || !out) return -EINVAL;
  int idx = spt__probe_find(st, key, spt_hash_key(key));
  if (idx < 0) return idx;
  return slot_snapshot_idx(st, (uint32_t)idx, out);
}

int spt_slot_snapshot_at(spt_store *st, uint32_t idx, spt_slot_view *out) {
  if (!st || !out || idx >= st->h->nslots) return -EINVAL;
  return slot_snapshot_idx(st, idx, out);
}

/* ----------------------------------------------------------- typed slots */

int spt_set_type(spt_store *st, const char *key, uint32_t type_flag) {
  if (!st || !key || (type_flag & ~SPT_T_MASK)) return -EINVAL;
  uint32_t idx;
  uint64_t e;
  int rc = lock_key(st, key, &idx, &e);
  if (rc < 0) return rc;
  spt_slot *s = &st->slots[idx];
  if (type_flag == SPT_T_BIGUINT) {
    /* BIGUINT promotion: ASCII digits -> host-endian u64 in place */
    uint8_t *v = slot_val(st, (uint32_t)idx);
    uint64_t acc = 0;
    int ok = s->val_len > 0 && s->val_len < 21;
    for (uint32_t i = 0; ok && i < s->val_len; i++) {
      char c = (char)v[i];
      if (c == '\0') break;
      if (c < '0' || c > '9') { ok = 0; break; }
      acc = acc * 10 + (uint64_t)(c - '0');
    }
    if (!ok && s->val_len != 8) { spt__unlock(s, e); return -EPROTOTYPE; }
    if (ok) {
      memset(v, 0, s->val_len);
      memcpy(v, &acc, 8);
      s->val_len = 8;
    }
  }
  uint32_t f = atomic_load_explicit(&s->flags, memory_order_relaxed);
  atomic_store_explicit(&s->flags, (f & ~SPT_T_MASK) | type_flag,
                        memory_order_relaxed);
  spt__unlock(s, e);
  spt__fanout(st, (uint32_t)idx, s);
  return 0;
}

int spt_get_type(spt_store *st, const char *key, uint32_t *type_out) {
  if (!st || !key || !type_out) return -EINVAL;
  int idx = spt__probe_find(st, key, spt_hash_key(key));
  if (idx < 0) return idx;
  *type_out =
      atomic_load_explicit(&st->slots[idx].flags, memory_order_acquire) &
      SPT_T_MASK;
  return 0;
}

int spt_integer_op(spt_store *st, const char *key, spt_iop_t op,
                   uint64_t operand, uint64_t *result_out) {
  if (!st || !key) return -EINVAL;
  uint32_t idx;
  uint64_t e;
  int rc = lock_key(st, key, &idx, &e);
  if (rc < 0) return rc;
  spt_slot *s = &st->slots[idx];
  if ((atomic_load_explicit(&s->flags, memory_order_relaxed) & SPT_T_MASK) !=
          SPT_T_BIGUINT ||
      s->val_len != 8) {
    spt__unlock(s, e);
    return -EPROTOTYPE;
  }
  uint64_t v;
  memcpy(&v, slot_val(st, (uint32_t)idx), 8);
  switch (op) {
    case SPT_IOP_AND: v &= operand; break;
    case SPT_IOP_OR:  v |= operand; break;
    case SPT_IOP_XOR: v ^= operand; break;
    case SPT_IOP_NOT: v = ~v; break;
    case SPT_IOP_INC: v += 1; break;
    case SPT_IOP_DEC: v -= 1; break;
    case SPT_IOP_ADD: v += operand; break;
    case SPT_IOP_SUB: v -= operand; break;
    default: spt__unlock(s, e); return -EINVAL;
  }
  memcpy(slot_val(st, (uint32_t)idx), &v, 8);
  s->atime = (int64_t)spt_now();
  spt__unlock(s, e);
  spt__fanout(st, (uint32_t)idx, s);
  if (result_out) *result_out = v;
  return 0;
}

/* ------------------------------------------------------------ tandem keys */

static int tandem_name(char *buf, const char *base, uint32_t order) {
  int n = order == 0
              ? snprintf(buf, SPT_KEY_MAX, "%s", base)
              : snprintf(buf, SPT_KEY_MAX, "%s" SPT_ORDER_SEP "%u", base,
                         order);
  return (n < 0 || n >= SPT_KEY_MAX) ? -ENAMETOOLONG : 0;
}

int spt_tandem_set(spt_store *st, const char *base, uint32_t order,
                   const void *val, uint32_t len) {
  char k[SPT_KEY_MAX];
  int rc = tandem_name(k, base, order);
  if (rc < 0) return rc;
  rc = spt_set(st, k, val, len);
  if (rc == 0) spt_set_type(st, k, SPT_T_VARTEXT);
  return rc;
}

int spt_tandem_get(spt_store *st, const char *base, uint32_t order,
                   void *buf, uint32_t cap, uint32_t *len_out) {
  char k[SPT_KEY_MAX];
  int rc = tandem_name(k, base, order);
  if (rc < 0) return rc;
  return spt_get(st, k, buf, cap, len_out);
}

int spt_tandem_unset(spt_store *st, const char *base, uint32_t max_order) {
  char k[SPT_KEY_MAX];
  int removed = 0;
  for (uint32_t o = 0; o <= max_order; o++) {
    if (tandem_name(k, base, o) < 0) break;
    if (spt_unset(st, k) == 0) removed++;
  }
  return removed;
}

int spt_tandem_count(spt_store *st, const char *base) {
  char k[SPT_KEY_MAX];
  int n = 0;
  if (spt_find_index(st, base) >= 0) n = 1; else return 0;
  for (uint32_t o = 1;; o++) {
    if (tandem_name(k, base, o) < 0) break;
    if (spt_find_index(st, k) < 0) break;
    n++;
  }
  return n;
}

/* ---------------------------------------------------------- bloom labels */

int spt_label_or(spt_store *st, const char *key, uint64_t mask) {
  if (!st || !key) return -EINVAL;
  int idx = spt__probe_find(st, key, spt_hash_key(key));
  if (idx < 0) return idx;
  atomic_fetch_or_explicit(&st->slots[idx].labels, mask,
                           memory_order_acq_rel);
  /* bits first, record second: a consumer that has read the record sees
   * the bits (sptpu.h, "change journal"); the caller's bump comes after */
  spt__journal(st, (uint32_t)idx);
  return 0;
}

int spt_label_andnot(spt_store *st, const char *key, uint64_t mask) {
  if (!st || !key) return -EINVAL;
  int idx = spt__probe_find(st, key, spt_hash_key(key));
  if (idx < 0) return idx;
  atomic_fetch_and_explicit(&st->slots[idx].labels, ~mask,
                            memory_order_acq_rel);
  return 0;
}

int spt_get_labels(spt_store *st, const char *key, uint64_t *out) {
  if (!st || !key || !out) return -EINVAL;
  int idx = spt__probe_find(st, key, spt_hash_key(key));
  if (idx < 0) return idx;
  *out = atomic_load_explicit(&st->slots[idx].labels, memory_order_acquire);
  return 0;
}

int spt_enumerate(spt_store *st, uint64_t mask, uint32_t *idx_out,
                  uint32_t max_out) {
  if (!st) return -EINVAL;
  uint32_t n = st->h->nslots, out = 0;
  for (uint32_t i = 0; i < n; i++) {
    uint64_t sh = atomic_load_explicit(&st->slots[i].hash,
                                       memory_order_acquire);
    if (sh <= SPT_TOMBSTONE) continue;
    uint64_t l = atomic_load_explicit(&st->slots[i].labels,
                                      memory_order_acquire);
    if ((l & mask) == mask) {
      if (idx_out) {
        if (out >= max_out) break;
        idx_out[out] = i;
      }
      out++;
    }
  }
  return (int)out;
}

int spt_enumerate_prefix(spt_store *st, const char *prefix,
                         uint32_t *idx_out, uint32_t max_out,
                         uint32_t *live_out) {
  if (!st || !prefix) return -EINVAL;
  size_t plen = strlen(prefix);
  uint32_t n = st->h->nslots, out = 0, live = 0;
  for (uint32_t i = 0; i < n; i++) {
    const spt_slot *s = &st->slots[i];
    uint64_t sh = atomic_load_explicit(&s->hash, memory_order_acquire);
    if (sh <= SPT_TOMBSTONE) continue;
    live++;
    /* unvalidated read of a key a writer may be replacing: the caller
     * resolves each index through spt_key_at and re-checks the prefix.
     * The first byte shares the slot's first cache line with hash. */
    if (plen >= SPT_KEY_MAX ||
        (plen && (s->key[0] != prefix[0] || memcmp(s->key, prefix, plen))))
      continue;
    if (idx_out) {
      if (out >= max_out) break;
      idx_out[out] = i;
    }
    out++;
  }
  if (live_out) *live_out = live;
  return (int)out;
}

/* ------------------------------------------------------------ mop / purge */

int spt_set_mop(spt_store *st, uint32_t mode) {
  if (!st || mode > SPT_MOP_FULL) return -EINVAL;
  atomic_store(&st->h->mop_mode, mode);
  return 0;
}

uint32_t spt_get_mop(spt_store *st) { return atomic_load(&st->h->mop_mode); }

int spt_purge(spt_store *st) {
  if (!st) return -EINVAL;
  uint32_t n = st->h->nslots;
  int swept = 0;
  for (uint32_t i = 0; i < n; i++) {
    spt_slot *s = &st->slots[i];
    uint64_t sh = atomic_load_explicit(&s->hash, memory_order_acquire);
    uint64_t e;
    if (sh == SPT_TOMBSTONE) {
      /* compact: a tombstone whose chain-successor region is empty can
       * revert to truly-empty; conservatively just scrub its value */
      if (spt__lock(st, i, &e) == 0) {
        memset(slot_val(st, i), 0, st->h->max_val);
        spt__unlock(s, e);
        swept++;
      }
      continue;
    }
    if (sh == 0) continue;
    if (spt__lock(st, i, &e) == 0) {
      uint32_t len = s->val_len;
      if (len < st->h->max_val)
        memset(slot_val(st, i) + len, 0, st->h->max_val - len);
      spt__unlock(s, e);
      swept++;
    }
  }
  return swept;
}

/* -------------------------------------------------------------- recovery */

int spt_retrain(spt_store *st, const char *key) {
  if (!st || !key) return -EINVAL;
  int idx = spt__probe_find(st, key, spt_hash_key(key));
  if (idx < 0) return idx;
  spt_slot *s = &st->slots[idx];
  /* deliberately NOT CAS-guarded: this works on a slot stuck odd.  The
   * epoch may move BACKWARD here, so the record matters as much as after
   * any write: appended while the slot is odd, like spt__lock's */
  atomic_store_explicit(&s->epoch, 3, memory_order_release);
  spt__journal(st, (uint32_t)idx);
  if (st->vectors)
    memset(slot_vec(st, (uint32_t)idx), 0,
           (size_t)st->h->vec_dim * sizeof(float));
  atomic_thread_fence(memory_order_release);
  atomic_store_explicit(&s->epoch, 4, memory_order_release);
  spt__fanout(st, (uint32_t)idx, s);
  return 0;
}

/* --------------------------------------------------- system keys & flags */

int spt_set_system(spt_store *st, const char *key) {
  if (!st || !key) return -EINVAL;
  if (spt__probe_find(st, key, spt_hash_key(key)) < 0) {
    int rc = spt_set(st, key, NULL, 0);
    if (rc < 0) return rc;
  }
  uint32_t idx;
  uint64_t e;
  int rc = lock_key(st, key, &idx, &e);
  if (rc < 0) return rc;
  spt_slot *s = &st->slots[idx];
  s->val_len = st->h->max_val;     /* scratchpad spans the full region */
  uint32_t f = atomic_load_explicit(&s->flags, memory_order_relaxed);
  atomic_store_explicit(&s->flags,
                        (f & ~SPT_T_MASK) | SPT_T_BINARY | SPT_F_SYSTEM,
                        memory_order_relaxed);
  spt__unlock(s, e);
  return 0;
}

int spt_slot_usr_set(spt_store *st, const char *key, uint8_t bits) {
  if (!st || !key) return -EINVAL;
  int idx = spt__probe_find(st, key, spt_hash_key(key));
  if (idx < 0) return idx;
  spt_slot *s = &st->slots[idx];
  uint32_t f = atomic_load_explicit(&s->flags, memory_order_acquire);
  for (;;) {
    uint32_t nf = (f & ~SPT_F_USER_MASK) | ((uint32_t)bits << SPT_F_USER_SHIFT);
    if (atomic_compare_exchange_weak_explicit(&s->flags, &f, nf,
                                              memory_order_acq_rel,
                                              memory_order_acquire))
      return 0;
  }
}

int spt_slot_usr_get(spt_store *st, const char *key, uint8_t *out) {
  if (!st || !key || !out) return -EINVAL;
  int idx = spt__probe_find(st, key, spt_hash_key(key));
  if (idx < 0) return idx;
  *out = (uint8_t)((atomic_load_explicit(&st->slots[idx].flags,
                                         memory_order_acquire) &
                    SPT_F_USER_MASK) >>
                   SPT_F_USER_SHIFT);
  return 0;
}

int spt_config_set_user(spt_store *st, uint32_t bits) {
  if (!st) return -EINVAL;
  atomic_store(&st->h->user_flags, bits & 0xFu);
  return 0;
}

uint32_t spt_config_get_user(spt_store *st) {
  return atomic_load(&st->h->user_flags) & 0xFu;
}

/* ------------------------------------------------------------ timestamps */

int spt_stamp(spt_store *st, const char *key, int which,
              uint64_t ticks_ago) {
  if (!st || !key || which < 0 || which > 2) return -EINVAL;
  int64_t t = (int64_t)(spt_now() - ticks_ago);
  uint32_t lidx;
  uint64_t e;
  int rc = lock_key(st, key, &lidx, &e);
  if (rc < 0) return rc;
  spt_slot *s = &st->slots[lidx];
  if (which == 0 || which == 2) s->ctime = t;
  if (which == 1 || which == 2) s->atime = t;
  spt__unlock(s, e);
  return 0;
}

/* ------------------------------------------------------------ vector lane */

int spt_vec_set_at(spt_store *st, uint32_t idx, const float *vec,
                   uint32_t dim) {
  if (!st || !vec || idx >= st->h->nslots) return -EINVAL;
  if (!st->vectors) return -ENOTSUP;
  if (dim != st->h->vec_dim) return -EMSGSIZE;
  spt_slot *s = &st->slots[idx];
  uint64_t e;
  int rc = spt__lock(st, idx, &e);
  if (rc < 0) return rc;
  memcpy(slot_vec(st, idx), vec, (size_t)dim * sizeof(float));
  spt__unlock(s, e);
  spt__fanout(st, idx, s);
  return 0;
}

int spt_vec_set(spt_store *st, const char *key, const float *vec,
                uint32_t dim) {
  if (!st || !key || !vec) return -EINVAL;
  if (!st->vectors) return -ENOTSUP;
  if (dim != st->h->vec_dim) return -EMSGSIZE;
  uint32_t idx;
  uint64_t e;
  int rc = lock_key(st, key, &idx, &e);
  if (rc < 0) return rc;
  memcpy(slot_vec(st, idx), vec, (size_t)dim * sizeof(float));
  spt__unlock(&st->slots[idx], e);
  spt__fanout(st, idx, &st->slots[idx]);
  return 0;
}

int spt_vec_get_at(spt_store *st, uint32_t idx, float *out, uint32_t dim) {
  if (!st || !out || idx >= st->h->nslots) return -EINVAL;
  if (!st->vectors) return -ENOTSUP;
  if (dim != st->h->vec_dim) return -EMSGSIZE;
  spt_slot *s = &st->slots[idx];
  uint64_t e1 = atomic_load_explicit(&s->epoch, memory_order_acquire);
  if (e1 & 1) return -EAGAIN;
  memcpy(out, slot_vec(st, idx), (size_t)dim * sizeof(float));
  atomic_thread_fence(memory_order_acquire);
  if (atomic_load_explicit(&s->epoch, memory_order_acquire) != e1)
    return -EAGAIN;
  return 0;
}

int spt_vec_get(spt_store *st, const char *key, float *out, uint32_t dim) {
  if (!st || !key) return -EINVAL;
  int idx = spt__probe_find(st, key, spt_hash_key(key));
  if (idx < 0) return idx;
  return spt_vec_get_at(st, (uint32_t)idx, out, dim);
}

static int vec_is_zero(const float *v, uint32_t dim) {
  for (uint32_t i = 0; i < dim; i++)
    if (v[i] != 0.0f) return 0;
  return 1;
}

int spt_vec_commit_batch(spt_store *st, const uint32_t *rows,
                         const uint64_t *epochs, const float *vecs,
                         uint32_t n, uint32_t dim, int write_once,
                         int32_t *results) {
  if (!st || !rows || !epochs || !vecs) return -EINVAL;
  if (!st->vectors) return -ENOTSUP;
  if (dim != st->h->vec_dim) return -EMSGSIZE;
  int committed = 0;
  for (uint32_t i = 0; i < n; i++) {
    uint32_t idx = rows[i];
    int32_t r;
    if (idx >= st->h->nslots) {
      r = -EINVAL;
    } else {
      spt_slot *s = &st->slots[idx];
      uint64_t e;
      int rc = spt__lock(st, idx, &e);
      if (rc < 0) {
        r = -ESTALE;          /* contended now => text may have changed */
      } else if (e != epochs[i]) {
        spt__unlock(s, e);
        r = -ESTALE;          /* the slot moved since the gather */
      } else if (write_once && !vec_is_zero(slot_vec(st, idx), dim)) {
        spt__unlock(s, e);
        r = -EEXIST;
      } else {
        memcpy(slot_vec(st, idx), vecs + (size_t)i * dim,
               (size_t)dim * sizeof(float));
        spt__unlock(s, e);
        spt__fanout(st, idx, s);
        r = 0;
        committed++;
      }
    }
    if (results) results[i] = r;
  }
  return committed;
}

/* --------------------------------------------------------- change journal */

uint64_t spt_journal_head(spt_store *st) {
  if (!st) return 0;
  return atomic_load_explicit(&st->h->journal_head.v, memory_order_acquire);
}

int spt_changed_since(spt_store *st, uint64_t cursor, uint32_t *rows_out,
                      uint32_t max_out, uint64_t *cursor_out) {
  if (!st || !cursor_out || (!rows_out && max_out)) return -EINVAL;
  _Atomic uint64_t *head_p = &st->h->journal_head.v;
  uint64_t head = atomic_load_explicit(head_p, memory_order_acquire);
  *cursor_out = head;
  if (cursor > head || head - cursor > SPT_JOURNAL_CAP) return -EOVERFLOW;
  uint32_t n = 0;
  for (uint64_t p = cursor; p < head && n < max_out; p++, n++) {
    _Atomic uint64_t *slot = &st->journal[p & (SPT_JOURNAL_CAP - 1)];
    uint64_t want = spt__journal_entry(p, 0) >> 32;
    uint64_t e = atomic_load_explicit(slot, memory_order_acquire);
    /* another tag: a writer claimed p and has not filled it, or one a lap
     * ahead already overwrote it.  The first is two instructions wide in
     * a live writer: wait it out, briefly */
    for (int spin = 0; (e >> 32) != want; spin++) {
      if (atomic_load_explicit(head_p, memory_order_acquire) - cursor >
          SPT_JOURNAL_CAP)
        return -EOVERFLOW;
      if (spin >= 256) return -EAGAIN;
      if (spin >= 16) sched_yield();
      e = atomic_load_explicit(slot, memory_order_acquire);
    }
    rows_out[n] = (uint32_t)e;
  }
  *cursor_out = cursor + n;
  return (int)n;
}

int spt_epochs_at(spt_store *st, const uint32_t *rows, uint32_t n,
                  uint64_t *out) {
  if (!st || (!rows && n) || (!out && n)) return -EINVAL;
  uint32_t nslots = st->h->nslots;
  for (uint32_t i = 0; i < n; i++)
    out[i] = rows[i] < nslots
                 ? atomic_load_explicit(&st->slots[rows[i]].epoch,
                                        memory_order_acquire)
                 : 0;
  return (int)n;
}

int spt_epochs(spt_store *st, uint64_t *out) {
  if (!st || !out) return -EINVAL;
  uint32_t n = st->h->nslots;
  for (uint32_t i = 0; i < n; i++)
    out[i] = atomic_load_explicit(&st->slots[i].epoch, memory_order_acquire);
  return (int)n;
}

int spt_vec_gather(spt_store *st, const uint32_t *rows, uint32_t n,
                   float *out, uint64_t *epochs_out) {
  if (!st || !rows || !out || !epochs_out) return -EINVAL;
  if (!st->vectors) return -ENOTSUP;
  uint32_t dim = st->h->vec_dim;
  int stable = 0;
  for (uint32_t i = 0; i < n; i++) {
    uint32_t idx = rows[i];
    epochs_out[i] = SPT_GATHER_TORN;
    if (idx >= st->h->nslots) continue;
    spt_slot *s = &st->slots[idx];
    uint64_t e1 = atomic_load_explicit(&s->epoch, memory_order_acquire);
    if (e1 & 1) continue;                      /* writer active: torn */
    memcpy(out + (size_t)i * dim, slot_vec(st, idx),
           (size_t)dim * sizeof(float));
    atomic_thread_fence(memory_order_acquire);
    if (atomic_load_explicit(&s->epoch, memory_order_acquire) != e1)
      continue;                                /* raced: retry next pass */
    epochs_out[i] = e1;                        /* 0 = stable empty slot */
    stable++;
  }
  return stable;
}

/* ------------------------------------------------------------ diagnostics */

int spt_report_parse_failure(spt_store *st) {
  if (!st) return -EINVAL;
  atomic_fetch_add(&st->h->parse_failures, 1);
  atomic_store(&st->h->last_failure_epoch,
               atomic_load(&st->h->global_epoch));
  return 0;
}

/* Build identity: the Makefile passes -DSPT_BUILD_ID="git-describe/date"
 * (native/Makefile); a build outside make still links with a sentinel. */
#ifndef SPT_BUILD_ID
#define SPT_BUILD_ID "unstamped"
#endif
const char *spt_build_id(void) { return SPT_BUILD_ID; }
