"""Generate docs/api/ — the per-function API reference for the sptpu.h
C ABI (reference ships ~60 per-function pages,
/root/reference/docs/api/index.md).

The header's comments ARE the documentation source; this script turns
them into browsable markdown so they cannot drift apart:
`tests/test_api_docs.py` regenerates into a temp dir and fails when the
committed pages differ.

Since PR 11 it also renders the splint-registry-derived tables: the
label-bit map (into the bloom-labels appendix, from
`engine/protocol.py` via `libsplinter_tpu/analysis/registry.py`) and
the fault-point catalog + splint rule catalog (into the marked
regions of `docs/operations.md`).  Those tables are DERIVED, never
hand-edited — splint rule SPL106 and the doc-sync tests fail on
drift.

Usage: python scripts/gen_api_docs.py [outdir]   (default docs/api;
the default run also refreshes docs/operations.md's marked regions)
"""
from __future__ import annotations

import importlib.util
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEADER = os.path.join(REPO, "native", "include", "sptpu.h")
OPERATIONS_MD = os.path.join(REPO, "docs", "operations.md")


def load_splint():
    """Load libsplinter_tpu/analysis as a standalone package, WITHOUT
    importing libsplinter_tpu itself (whose __init__ needs the built
    native .so) — the analysis layer is stdlib-only by contract.
    The package-loading trick lives in analysis/_load.py (shared with
    scripts/splint_check.py and tests/test_splint.py)."""
    spec = importlib.util.spec_from_file_location(
        "_splint_load", os.path.join(
            REPO, "libsplinter_tpu", "analysis", "_load.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.load()

_SECTION_RE = re.compile(r"^/\* -{3,}\s*(.+?)\s*-*\s*(?:\*/)?\s*$")
_PROTO_START = re.compile(
    r"^(?:const\s+)?(?:unsigned\s+)?[A-Za-z_][A-Za-z0-9_]*\s*\**\s*"
    r"(spt_[A-Za-z0-9_]+)\s*\(")
_DEFINE_RE = re.compile(r"^#define\s+(SPT_[A-Za-z0-9_]+)")


def _clean_comment(lines: list[str]) -> str:
    """Strip comment markers, preserve paragraph flow."""
    out = []
    for ln in lines:
        ln = ln.strip()
        ln = re.sub(r"^/\*+", "", ln)
        ln = re.sub(r"\*+/$", "", ln)
        ln = re.sub(r"^\*\s?", "", ln)
        out.append(ln.rstrip())
    text = "\n".join(out).strip("\n")
    # collapse runs of blank lines
    text = re.sub(r"\n{3,}", "\n\n", text)
    return text.strip()


def _slug(title: str) -> str:
    s = title.lower()
    s = re.sub(r"\(.*?\)", "", s)          # drop parentheticals
    s = re.sub(r"[^a-z0-9]+", "-", s).strip("-")
    return s


class Section:
    def __init__(self, title: str):
        self.title = title
        self.slug = _slug(title)
        self.intro = ""
        self.funcs: list[tuple[str, str, str]] = []   # (name, sig, doc)
        self.defines: list[tuple[str, str, str]] = []  # (name, line, doc)
        self.types: list[tuple[str, str, str]] = []   # (name, body, doc)


# Hand-maintained appendices merged into generated pages (slug -> md).
# The header documents the C ABI; these document Python-layer surfaces
# that extend a section — kept HERE so the docs stay regenerable and
# tests/test_api_docs.py's sync check covers them too.
_APPENDICES = {
    "bloom-labels": """
## Label-bit map (`libsplinter_tpu/engine/protocol.py`)

The Python engine's bloom-label word, one row per constant — bit
positions, masks, and meanings extracted STATICALLY from
`engine/protocol.py` by the splint registry
(`libsplinter_tpu/analysis/registry.py`), so this table cannot drift
from the code: splint rule SPL101 fails any bit collision, SPL106
fails a stale table, and `make lint-check` gates both.

__SPLINT_LABEL_TABLE__

Bits 48-51 form the tenant-id *field* (`TENANT_MASK`); every other
row is a single-purpose flag.  Raw use of any of these bit values
outside `protocol.py` is splint violation SPL102 — always spell them
via the `protocol.LBL_*` / `BIT_*` constants.

## Paged KV cache + ragged paged attention (`models/decoder.py`, `ops/paged_attention.py`)

The completion lane behind `LBL_INFER_REQ` serves continuous batching
(`spt … --continuous`, `completer.run_continuous`) over a
**block-paged KV pool** instead of the dense per-batch cache:

### `PagedKVCache` (`libsplinter_tpu/models/decoder.py`)

| surface | contents |
|---|---|
| `k_pools` / `v_pools` | per layer `(n_blocks, kv_heads, page, head_dim)` global page pool |
| `tables` | host `(batch, pages_per_row)` int32 block table — entry `(b, p)` holds row b's tokens `[p*page, (p+1)*page)` |
| `lengths` | host `(batch,)` int32 per-row token counts (row b attends `j < lengths[b]`) |
| `ensure(row, tokens)` / `free_row(row)` | page-granular alloc (all-or-nothing; False = backpressure) and per-page refcount release (a page frees only at refcount zero) |
| `refcounts` / `map_shared(row, bids)` | cross-request prefix sharing (PR 14): tables from different rows point at the same full pages; `map_shared` is the refcount-bump table write that replaces a whole prefix prefill |
| `available_pages` | free-list pages + zero-ref prefix-cache pages reclaimable on demand — what admission backpressure gates on |
| `free_pages` / `used_pages` / `live_tokens()` | the pool gauges the completer heartbeat publishes (`sptpu_completer_pages_{free,used}`) |

Block 0 is the reserved **trash block**: unallocated table entries
point at it, so dead rows' appends land harmlessly and gathers of
unused pages read garbage the length mask excludes.  Cache HBM scales
with LIVE TOKENS, not `batch x max_len` — which is why `--batch-cap`
defaults to 32 (was 8) and `--pool-pages` caps the budget (default:
batch full windows).

### `paged_attention` (`libsplinter_tpu/ops/paged_attention.py`)

Pallas decode kernel, grid `(B, kv_heads, pages_per_row)`: the block
table rides scalar prefetch (`PrefetchScalarGridSpec`) so each
program's index map gathers exactly its page; a flash-style online
softmax carried across the page axis computes every row's attention
over its OWN ragged length — no shared `pos`, no window-mask padding,
pages wholly past a row's length skipped.  `interpret=True` runs it on
CPU for parity tests; non-TPU backends serve through the identical
jnp gathered-page math.  Prefill stays on the dense bucket programs
(`causal_flash_attention` for long chunks) and scatters into pages via
one commit program per bucket (`CompletionModel.paged_prefill_row`).

### Scheduler contract (`completer.run_continuous`)

Every admission is a join: the prompt prefills into freshly allocated
pages at any time (no join budget, no oversized-joiner deferral — a
joiner longer than a neighbour's remaining window is fine), finished
rows return pages immediately, and admission reserves the row's worst
case (`prompt + max_new` rounded up to a decode-chunk boundary —
decode appends whole `flush_tokens` chunks — capped at the window) so
decode can never
strand on an exhausted pool — a request the pool cannot cover stays
WAITING and `join_backpressure` counts it.  Stage spans publish under
`CONT_INFER_STAGES` (join / sample / decode / flush) and
client-stamped requests land in the flight recorder (`spt trace
tail`).  `make decode-check` gates the tier.

### Pod-sharded paged serving (`parallel/serve.py`, PR 8)

`ShardedCompletionModel` serves the SAME paged surface tensor-
parallel (`paged_supported` is True): each layer's pool shards on its
kv-head axis over the mesh's `tp` axis
(`parallel/mesh.kv_pool_sharding`; `PagedKVCache(..., sharding=)`
creates the zeros directly into the sharding), block tables / lengths
/ alloc / free stay host-side and replicated, and the ragged
paged-attention + flash-prefill kernels run under `shard_map`
(`paged_attention(..., mesh=)` /
`causal_flash_attention(..., mesh=)`) — each device executes the
same program over its local KH/tp heads, no collective inside the
kernel.  The commit/chunk programs pin `out_shardings` to the pool
sharding so warmup covers the whole serve-time signature (a
join/finish/join cycle never compiles).  `spt … --continuous --tp N`
is the deployment surface; `make pod-check` gates token-exact parity
(sharded-paged == single-chip-paged == serial) on the 8-device CPU
mesh.

### Quantized pool + self-drafting speculation (PR 9)

`PagedKVCache(..., kv_dtype="int8")` (daemon flag `--kv-dtype int8`)
stores the pools as int8 values plus per-page per-kv-head f32 scales
(`k_scales`/`v_scales`, `(n_blocks, kv_heads)` per layer — separate
buffers, layout leaving room for int4-packed values): the prefill
commit scatter quantizes whole pages, decode appends rescale-on-
append (monotone page scales), and `paged_attention(...,
k_scales=, v_scales=)` dequantizes IN REGISTER inside the page loop
— the scales ride scalar prefetch with the block tables.  Cache HBM
per token: 1/2 of bf16, 1/4 of f32 (`device_mb()` measures placed
buffers; heartbeat `pool_mb` + `kv_dtype`, `make quant-check` gates
the parity + byte tiers).  Under `tp` the scales shard with their kv
heads (`parallel/mesh.kv_scale_sharding`).

The kernel also accepts a MULTI-QUERY stack — `q` shaped
`(B, S, H, D)`: token t attends `j < lengths + t` (causal across the
stack).  That is the speculative verifier:
`SpeculativeCompletionModel` (with `self_draft_model(target, k)` — a
draft that is a truncated VIEW of the target's own first k layers,
`--draft-layers k`) implements the full paged surface
(`paged_supported` True): the draft proposes gamma tokens via paged
decode steps, the target scores all gamma+1 positions in ONE
multi-query paged dispatch, acceptance/resample run on device, and a
host FIFO adapts ragged per-row acceptance to the daemon's fixed
chunk cadence.  Draft/verify counters ride the heartbeat
(`sptpu_completer_spec_{draft,accepted,verified}_tokens`); the PR-5
demotion floor still guards the lane (the swap lands at the next
idle point of `run_continuous`).

### Multi-tenant requests: tenant labels + deadline stamps (PR 10)

The request label word carries a **tenant id in bits 48-51**
(`protocol.TENANT_MASK`; `stamp_tenant`/`read_tenant`; ids 1-15, 0 =
untagged) — daemons read every candidate's labels anyway, so tenant
discovery is free, one tenant's waiting rows enumerate with a bloom
prefilter, and the field survives the WAITING→SERVICING→READY
trifecta for post-hoc attribution.  **`LBL_DEADLINE` (bit 52)** flags
an absolute wall-clock deadline in the `__dl_<idx>` companion key
(`stamp_deadline`/`read_deadline` — epoch-gated and self-invalidating
like trace stamps; search requests may carry `{"deadline": ts}` in
their request JSON instead).

Every drain runs the shared admission policy (`engine/qos.py`:
stride-scheduled weighted fairness, persistent across drains) BEFORE
rendering anything: expired deadlines fail fast with a typed
`{"err": "deadline_expired"}` record, saturation orders admission by
tenant weight, and backlog past the queue high-water mark is shed
with `{"err": "overloaded", "retry_after_ms": N}` — backpressure,
never a wedge, and past the mark a typed answer, never silence.
Client side, `engine/client.py::call_with_retries` (under
`submit_search` / `submit_completion`) honors the hint with jittered
backoff inside the caller's deadline.  Runbook:
`docs/operations.md` §Multi-tenant QoS.
""",
    "embedding-vector-lane": """
## Search daemon (`libsplinter_tpu/engine/searcher.py`)

The query-coalescing counterpart of the embedding daemon: scoring
moves server-side so N concurrent clients cost ceil(N / QB) fused
top-k dispatches over the daemon's device-resident lane, not N
private round trips.

### Request contract (one slot per request)

| surface | contents |
|---|---|
| value | JSON `{"k": int, "bloom": int?}` — result count + optional label prefilter |
| vector lane | the query vector in the SAME slot (the embed daemon puts it there in the classic CLI flow, or write it with `spt_vec_set`) |
| labels | `LBL_SEARCH_REQ` (bit 57) + optionally `LBL_WAITING`, then bump |

The daemon drains every pending request per wake
(signal group 4), groups by bloom mask, sends each group as ONE
QB-bucketed batch {8, 128, 256} (a dispatch costs one scan of the
lane, nearly flat in the query count up to the kernel's lane width
`FUSED_Q_LANE` = 128; beyond 256 requests, 256-row batches and one
cover batch for the tail) against pre-compiled programs of the
**fused streaming top-k kernel** (`ops/similarity.topk_program`:
block-local select + merge in VMEM, O(k*Q) off-chip, k <=
`FUSED_K_MAX` = 128), and commits per-request results to the
slot-indexed companion key `__sr_<idx>`:

```json
{"s": [scores...], "i": [slot indices...], "keys": [resolved keys...],
 "fetched": K, "n": valid_candidates}
```

sorted by similarity desc, system keys (`__` prefix — scratch rows,
heartbeats, other requests' slots) already dropped.  The commit is
epoch-gated: a slot rewritten mid-service is retried, never answered
stale.  Clients poll their own request key and read the companion
once `LBL_SEARCH_REQ` clears (`engine.searcher.submit_search` wraps
the dance; `daemon_live` probes the `__searcher_stats` heartbeat).

The CLI `search` command dispatches to a live daemon automatically
(`--local` opts out) and falls back to client-side scoring on
timeout.  Stage quantiles publish under the `SEARCH_STAGES` names
(wake / drain / score / select / commit) in the heartbeat, `spt
metrics`, and `spt trace tail` — see the diagnostics appendix.
""",
    "diagnostics": """
## Observability surface (`libsplinter_tpu/obs/`)

The Python layer above the C ABI: log-bucketed latency histograms,
per-request flight recording, and a Prometheus text exposition.  The
reference's only runtime telemetry is the `__debug` append channel;
this is the structured counterpart the TPU port adds.

### Env vars

| var | effect |
|---|---|
| `SPTPU_TRACE=1` | enable span histograms + flight recording in the daemons, and put the leaf phases of the search daemon's and the continuous completion lane's run loops on the profiler's clock as `jax.profiler.TraceAnnotation`s (off: the hot path pays one dict lookup, and `utils/trace.py` imports no jax) |
| `SPTPU_TRACE_SLOW_MS=<ms>` | explicit slow-log promotion threshold; unset → 5× the recorder's live e2e p50 (arms after 20 samples) |

### The search daemon's run loop, span by span

With `SPTPU_TRACE=1` every pass of `Searcher.run` is one `search.loop`
span, and every second of it belongs to exactly one child
(`protocol.SEARCH_LOOP_PHASES` beside `SEARCH_STAGES`; splint SPL107
reads both tuples):

| span | what it brackets | leaf |
|---|---|---|
| `search.loop` | one pass of the run loop | no |
| `search.idle` | blocked in `signal_wait` | yes |
| `search.drain_cycle` | one drain, serviced or idle (the beat's idle drain too) | no |
| `search.wake` / `search.drain` | signal → drain entry; gather + admit — the gather reads the labels of the rows the change journal names since its last look and of the rows it still holds labelled (`store.LabelCursor`; `gather_slots_scanned` counts them, a first-attach or lapped-cursor walk of every slot counts in `gather_fallbacks`); the histogram counts serviced drains only | `drain` yes |
| `search.score` | host wall of the service outside select and commit; its self time, score − refresh − mask, is batching and dispatch | no |
| `search.refresh` / `search.mask` | `lane.refresh()`: the rows the store's change journal names, compared and re-staged (a pass that does a full upload is the staging of the lane; a lapped cursor falls back to the all-slot comparison, `journal_fallbacks`); bringing the candidate masks up to date — the liveness mask patched for the rows the lane re-examined, the pending request rows hidden for the drain | yes |
| `search.select` / `search.commit` | blocked in `jax.device_get`; result rows + label clears | yes |
| `search.sweep_results` / `search.sweep_stages` | the two heartbeat-cadence sweeps: each finds its `__sr_` / `__sp_` rows with one native prefix scan of the slots (`spt_enumerate_prefix`) and opens only those (`sweep_keys` counts the live keys the scans pass, `sweep_rows` the rows they match, `results_reaped` what the first retires); the second is the span plane's own housekeeping and runs with tracing off too | yes |
| `search.publish` | the beat's two audits — `lane.audit()` (the full epoch comparison: `lane_audit_rows` counts what the journal missed, 0) and the label cursor's (one walk of every slot's labels: `gather_audit_rows` counts the requests no record named, 0; they are adopted and drained) — and `publish_stats`: serialisation, `DEVTIME.flush`, `spans.flush` | yes |

A beat's publish runs at the head of the next pass, so a heartbeat
holds whole passes only: `search.loop` equals its children's sum plus
the loop's own bookkeeping in every snapshot, and the difference of
two heartbeats accounts for the time between them.  Leaf phases are
disjoint in time on the daemon's thread and also open a
`TraceAnnotation`; enclosing spans do not, because a device-idle gap
is named after the single host event that overlaps it longest.

**One capture of a steady window.**  Start the daemon with
`SPTPU_TRACE=1`, let it reach steady state, then from a thread of the
same process (only the process that holds the chip can trace it) call
`jax.profiler.start_trace(dir)`, sleep a few seconds, `stop_trace()` —
`benchmark/host.py:trace_watcher` does exactly this at
`host_tracer_level=2`, `python_tracer_level=0`, and
`benchmark/tracereduce.py` reduces the capture to busy/idle and names
each idle gap after a `search.*` phase.  There is no per-drain
capture: a timeline cut at every drain is not one.

**Start-up.**  Every search heartbeat carries `startup_ms`, one-shot
phases in ms: `process` (exec to `main()`: interpreter, imports, and
whatever a hosting process did first), `jax` (import + device open
inside `main()`), `store_open`, `attach`, `warmup` (with `--warmup`),
`first_refresh` (the first full lane upload, paid by the first
request without `--warmup`), and their sum `total`.

### The continuous completion lane's run loop, span by span

`Completer.run_continuous` (and the disaggregated lanes' loops,
`engine/disagg.py`) is accounted the same way: with `SPTPU_TRACE=1`
every pass of `while self._running` is one `infer.loop` span and every
second of a pass belongs to exactly one leaf
(`protocol.CONT_LOOP_PHASES` beside `CONT_INFER_STAGES`; splint SPL107
reads both under the `infer.` prefix; the phases are spans only and do
not size the per-request flight record):

| span | what it brackets | leaf |
|---|---|---|
| `infer.loop` | one pass of the run loop: an admission round, or one dispatched chunk and the collect of the oldest in flight | no |
| `infer.idle` | blocked in `signal_wait` with no row live and no request waiting | yes |
| `infer.beat` | the 2 s beat at the head of a pass: the speculative-demotion check, the backpressure memo's sweep, `publish_stats`, the tier checkpoint | yes |
| `infer.admit` | one whole admission round (`admit()`); its leaves are the next five rows, `infer.state_restore` / `infer.state_zero` / `infer.state_snapshot`, the first token's `infer.emit`, and a decode lane's `infer.adopt` | no |
| `infer.gather` | finding the waiting rows (`enumerate_indices` over every slot), the QoS order, the backpressure memo, the reservation check: everything of a round that is no other leaf's | yes |
| `infer.prepare` | render + tokenize (`_read_rendered`, `encode`) and the WAITING → SERVICING claim (`_prepare`): two spans a request | yes |
| `infer.prefix_hit` | the prefix cache's and the page allocator's part of a join: the radix walk (one span), then mapping the hit's pages and reserving the row's own (`ensure`; a second span, less the state restore inside it) | yes |
| `infer.join` / `infer.sample` | a join's prefill from its dispatch to its logits (a fully cached prompt: its copy-on-write pass) — where the model's suffix program has a row axis, ONE dispatch for the hits of an admission round, recorded once a ROW at the round's wall over its rows (`n` stays the joined requests, the mean the amortised ms of one); from there to the row's first token — the window group's reserve, the tree's insert, the audit's copy, the draw (a round's: made in graph, nothing left of it here) | yes |
| `infer.state_restore` / `infer.state_zero` / `infer.state_snapshot` | a model with recurrent state: copying a snapshot into the joining row; zeroing the slot of a row that starts from nothing; finding the slot its own snapshot goes to | yes |
| `infer.chunk` | one chunk round of a pass with rows live: deadline kills and the edge scan (its own bookkeeping), then `infer.decode`, `infer.rebid`, and `infer.collect` + `infer.emit` of the oldest chunk in flight | no |
| `infer.emit` | the host work behind sampled tokens — pieces, streaming appends (`infer.flush`, a sum inside it), finalize, pages freed: one span a collected CHUNK and one a join's first token, never one a token | yes |
| `infer.decode` / `infer.collect` | the async dispatch of a chunk; the blocked wait for the oldest chunk in flight (`pend.block()`) | yes |
| `infer.rebid` | the shard re-bid | yes |
| `infer.handoff` / `infer.adopt` | a prefill lane: streaming the first token, exporting the pages, landing the record, the DECODE_READY flip (its `infer.join` spans map + prefill + draw); a decode lane: the claim, the page import, the row's seating | yes |
| `infer.window_release` | a sum read from `WindowPages.release_s`, inside `infer.join` and `infer.decode` | no |

The beat runs at the head of a pass, so a heartbeat holds whole passes
only and the difference of two heartbeats accounts for the time between
them: `infer.loop` less its leaves is the loop's own bookkeeping
(deadline kills, the edge scan), well under a percent.  `infer.admit`
+ `infer.chunk` + `infer.beat` is the loop's busy time; `decode_rows`
over it gives decoded row-steps a second, the continuous reading of a
rate that the benchmark's closed loops quantise, and the benchmark's
shares stand on it too, because its two heartbeats are further apart
than its window and what lies between is idle.  Where a
request's event list wants a phase's number too, the phase is
`tracer.annotation(name)` around the work and the lane's
`span(row, name, ms)` after it, both from one `perf_counter` pair;
elsewhere it is `tracer.span(name, leaf=True)`.  Every `infer.*`
histogram is in `spt metrics` as `sptpu_stage_ms{stage=...}` and in the
heartbeat's `quantiles`.

### Trace-id convention (`engine/protocol.py`)

A client that wants one request's wake→commit journey reconstructed
stamps it **next to the request label** — after `set` + `label_or`,
ideally before the `bump` (a daemon racing the stamp then can't
service the row stampless):

```python
tid = protocol.stamp_trace(store, key)   # returns the trace id
```

The stamp is `"<trace_id>:<wall_ts>:<slot_epoch>"` in the
slot-indexed companion key `__tr_<idx>` (`trace_stamp_key`), plus
`LBL_TRACED` (bit 58) on the request key itself — the daemons'
candidate filters already read every row's label word, so untraced
rows never pay a stamp lookup.  The embedded epoch makes stamps
self-invalidating: a daemon finding a stamp whose epoch doesn't
match the request it gathered consumes it as stale instead of
attributing it (and its seconds-old wall clock) to the wrong
request.  Ids are `(pid << 24) | counter`: unique across concurrent
clients without coordination, originating pid recoverable as
`id >> 24`.  The
servicing daemon consumes the stamp (clears key + label), appends the
request's stage events to its flight recorder under the pinned stage
names (`PIPELINE_STAGES` for the embedder: drain / tokenize /
dispatch / device_wait / commit; `INFER_STAGES` for the completer:
render / generate / commit; `SEARCH_STAGES` for the search daemon:
wake / drain / score / select / commit), and publishes its ring to
`__embedder_trace` / `__completer_trace` / `__searcher_trace`
alongside the heartbeat.

```
$ SPTPU_TRACE=1 ... ; spt trace tail 4
[embedder] id=0x6804000001 pid=26628 key='k' wall=1493.817ms \\
  drain=0.269ms tokenize=0.053ms dispatch=0.087ms \\
  device_wait=0.052ms commit=0.363ms
```

### Heartbeat sections (`publish_heartbeat`)

With tracing on, `__embedder_stats` / `__completer_stats` gain:

- `spans` — per span name `{n, total_ms, max_ms}` (the legacy
  aggregate shape, kept for old consumers);
- `quantiles` — histogram-sourced `{n, total_ms, max_ms, p50_ms,
  p90_ms, p95_ms, p99_ms}` keyed by the pinned stage names (prefix
  stripped) — what `spt metrics` consumes;
- `recorder` — `{recorded, dropped, slow_promoted,
  slow_threshold_ms}`;
- `slow_log` — promoted slow requests, each
  `{id, key, wall_ms, ts, slow_threshold_ms,
  events: [[stage, ms], ...]}` (bounded deque; survives ring wrap).

The search heartbeat's `spans` names every loop phase above; `e2e`
(the sum of a drain's stages) rides `quantiles` only.  The record is
written with compact separators.  Oversized heartbeats degrade
section by section in a FIXED order (`truncated: true`):
`quantiles`, `slow_log`, `recorder`, then the other optional
sections largest first, and last `startup_ms`, `devtime`, `spans` —
the sections deltas are read from never go because they outgrew a
bulkier one nothing reads.  The scalar counters always land.

### Prometheus exposition

`spt metrics` renders exposition-format text: store header gauges
(`sptpu_store_used_slots`, `sptpu_store_parse_failures`, ...),
heartbeat scalars (`sptpu_embedder_*` / `sptpu_completer_*`),
heartbeat ages, per-stage quantile summaries
(`sptpu_stage_ms{daemon=...,stage=...,quantile=...}`), recorder
counters, and StagedLane chunk accounting when a lane is staged.
In-process, `Tracer.render_prom()` serializes the live histograms as
native prometheus histograms (cumulative `le` buckets, edges in ms)
plus any counter groups passed in.  `make obs-check` pins the enabled
record path's overhead < 3% vs disabled.

### Cross-lane span records (`libsplinter_tpu/obs/spans.py`)

Since PR 13 the trace stamp is a full TRACE CONTEXT —
`"<trace_id>:<wall_ts>:<slot_epoch>:<parent_span>:<span_id>"` (legacy
3-field stamps parse as `parent=0, span=trace_id`) — and every lane
commits one **span record** per stamped request into a shared
bounded ring in the store:

| key | contents |
|---|---|
| `__span_<i>` | committed span records; slot claimed by atomically incrementing the `__span_head` BIGUINT, so the ring is multi-writer safe and bounded by construction (`span_ring_size` = nslots/8 clamped to [16, 128]) |
| `__sp_<idx>` | pending-span STAGING row (staged lanes: the pipeliner) — crash recovery: a restarted lane recovers the chain identity, the original queue-enter clock, and the attempt count, so the committed span shows the restart gap.  Orphans (slot epoch moved, TTL) are swept on the heartbeat cadence and by `shed_orphan_stamp`'s discard path |

Each record carries the trace id, span id + parent (the tree edges),
lane, key, tenant, status (`ok` / typed error), the queue-enter /
admit / commit wall clocks, and the **queue-wait vs service-time
split** — with per-stage ms under the pinned `*_STAGES` names when
`SPTPU_TRACE=1`.  Record commits BUFFER in the lane and flush on the
heartbeat cadence, keeping the wake path inside the obs budget
(`make trace-check` gates it).  Propagation: every client verb
(`submit_embed` / `submit_search` / `submit_completion` /
`submit_script`) takes `trace=` (True = new root, a trace id = a hop
of that trace, `(trace_id, parent_span)` = explicit placement), and
the pipeline lane stamps every verb a script dispatches with the
script's own span as parent — ONE trace id spans a whole chain in
both forms.  `spt trace show <id>` renders the assembled tree;
`spt trace export` emits Chrome/Perfetto trace-event JSON.

### Device-time & compile attribution (`libsplinter_tpu/obs/devtime.py`)

Every jitted hot program registers with the process-global `DEVTIME`
registry under a stable `lane.program` name (`embedder.encode`,
`completer.paged_chunk`, `searcher.topk`, ...; splint SPL205 fails an
unregistered one).  Registration wraps the program with two probes,
both piggybacking on work the lane already does — **zero new host
syncs** (SPL201 stays the law; `SPTPU_DEVTIME=0` is the kill
switch, and warmup dispatches never open device windows):

- **the compile ledger** — a jit cache-size growth across a call is a
  compile event: `{program, lane, shapes_key, duration_ms,
  generation, cause: warmup|runtime}`, buffered in-process and
  flushed on the heartbeat cadence into the `__compile_<i>` store
  ring (span-ring slot-claim discipline).  `spt trace export` renders
  the events as instants on their own Perfetto track; the post-warmup
  **no-recompile gate** (`scripts/compile_gate_check.py`, `make
  compile-check`) asserts the runtime-cause count stays ZERO across a
  serve drill and names the guilty program + shapes key when it
  doesn't (`SPTPU_SEED_RECOMPILE=1` seeds the drill for the gate's
  own failure test).
- **device windows** — dispatch→collect wall time per named program,
  closed at the lane's EXISTING collect point (`PendingChunk.block`,
  `materialize_host`, the top-k `device_get`).  Spans gain
  `device_ms` and `dispatch_queue` (= `service_ms - device_ms`)
  beside the queue/service split — "slow because device" vs "slow
  because the lane sat on it" is now readable per request — and each
  lane heartbeat gains a `devtime` section (per program `{n,
  compiles, runtime_compiles, total_ms, p50_ms, p99_ms}`; `n` and
  `total_ms` never reset, so a window's mean is a difference of two
  heartbeats; the search daemon takes the program's mark right after
  each dispatch and closes it at that batch's fetch, so `n` counts
  dispatches; rendered as
  `sptpu_<lane>_devtime_*{program=...}`).

HBM watermarks ride the completer heartbeat beside the live gauges:
`pool_mb_peak` (measured placed-buffer MB high-water) and
`pages_used_peak` (page-occupancy high-water, sampled at
chunk-collect edges so a between-heartbeats spike still shows).

**Tail-based retention**: a request or drain that exceeds the slow
threshold keeps its full `*_STAGES` breakdown even when the client
never stamped a trace id — the lane allocates a trace id at commit
time (`tail: true` on the span), so every slow-log entry resolves
through `spt trace show`.
""",
    "system-keys-user-flags": """
## Supervision heartbeat keys (`libsplinter_tpu/engine/supervisor.py`)

The daemon heartbeats (`__embedder_stats` / `__completer_stats` /
`__searcher_stats`) carry two supervision fields beyond their
counters:

- `pid` — the publishing process.  Liveness probes
  (`protocol.heartbeat_live`, the CLI's `daemon_live`) kill-0 it, so
  a crashed daemon reads dead the instant it dies instead of after
  `max_age_s` of heartbeat decay.
- `generation` — monotonic per-lane start counter (BIGUINT companion
  key `__<heartbeat>_gen`, bumped by `protocol.bump_generation` at
  attach).  Two snapshots with different generations bracket a
  restart even when the OS recycled the pid.

`__supervisor_stats` is the supervisor's own heartbeat
(`spt supervise`): per-lane process state consumed by
`protocol.lane_down` and rendered by `spt metrics`
(`sptpu_supervisor_lane_*`):

| field | meaning |
|---|---|
| `state` | `starting` / `running` / `backoff` / `down` (breaker open) |
| `pid`, `generation` | current child process, spawn count |
| `restarts` | respawns after a crash or hung-heartbeat kill |
| `consecutive_crashes` | backoff ladder position (0 = healthy) |
| `backoff_ms` | the live jittered backoff |
| `breaker_opens`, `hung_kills`, `last_exit` | breaker + exit history |

A lane whose `state` is `down` is skipped by dispatching clients
(`daemon_live` returns False without probing the lane heartbeat) —
a crash-looping lane costs a client zero timeout.  With `SPTPU_FAULT`
armed, heartbeats additionally carry a `faults` section (per-site
hit/fired accounting).  Runbook: `docs/operations.md`.

### Pod-sharded completer keys (PR 8)

A completer serving through `ShardedCompletionModel`
(`--tp N --continuous`) extends `__completer_stats` with:

- `tp` — the tensor-parallel mesh degree
  (`sptpu_completer_tp` in `spt metrics`);
- `pages_shard` — per-tp-shard paged-pool view
  `{"0": {"free": n, "used": m, "shard_mb": x}, ...}`, rendered as
  `sptpu_completer_pages_{free,used}` and
  `sptpu_completer_pool_shard_mb` with a `shard` label.  The pool
  shards on its KV-HEAD axis, so the PAGE counts are host-global
  (every shard backs every page at 1/tp of its bytes); `shard_mb` is
  MEASURED from the placed device buffers per tp position — a broken
  placement collapses the key set (a replicated pool covers the full
  kv-head range → one key) or inflates the MB, so the dashboard
  shows real placement state, not an assumed-uniform number.

### Dispatch-overlap gauges (`libsplinter_tpu/engine/resident.py`)

Every lane heartbeat also carries the PR-7 overlap-window gauges —
the embedder's ring gauges ride a `dispatch` sub-section (dropped
first when a tiny store's `max_val` bites, like every optional
section) and `spt metrics` renders everything flat as
`sptpu_<lane>_<field>`:

| field | lanes | meaning |
|---|---|---|
| `inflight_depth` | all | configured K: un-awaited device dispatches the lane may hold (`--inflight-depth`) |
| `inflight_peak` | all | max un-awaited depth observed; pinned at `inflight_depth` = the overlap window saturates |
| `latent_decode_pages_per_step` | completer, a family with latent (MLA) pages | table pages a grid step of `latent_decode_attention` attends (`ops/latent_attention.pages_per_step`): the kernel's seconds in a trace over rows x ceil(table pages / this) are seconds a grid step |
| `ring_depth` | embedder | configured resident-ring depth (`--ring-depth`; ≤1 = per-call dispatch) |
| `ring_occupancy` / `ring_occupancy_peak` | embedder | occupied slots of the last / fullest resident ring dispatch |
| `ring_dispatches` / `resident_iterations` | embedder | resident programs dispatched / batches serviced inside them — `resident_iterations ÷ ring_dispatches` is the live dispatch-floor amortization factor |
| `ring_faults` | embedder | ring dispatches degraded to the per-call programs |

The searcher's `lane` section additionally counts the StagedLane's
ring staging (`ring_dispatches` / `ring_chunks`: refresh scatter
chunks coalesced into resident dispatches).

### Multi-tenant QoS keys (`libsplinter_tpu/engine/qos.py`)

Every lane heartbeat gains the overload-survival counters
(`deadline_expired` / `shed` / `deferred`, flat `sptpu_<lane>_*`
gauges) plus two optional sections:

- `qos` — the live admission config: `admit_cap` (embedder/searcher;
  0 = unlimited), `queue_high_water` (-1 = shedding disabled),
  `retry_after_ms` (the hint shed responses carry).  Rendered flat as
  `sptpu_<lane>_qos_*`.
- `tenants` — the per-tenant ledger
  `{"<tenant>": {"admitted": n, "shed": n, "deadline_expired": n,
  "served_tokens": n}, ...}` (tenant ids 1-15 from the label word's
  bits 48-51; untagged traffic does not create a section).  Rendered
  as `sptpu_<lane>_tenant_<field>{tenant="..."}` — the incident view
  of WHO is being served and WHO is being shed.

The completer additionally publishes `bp_memo` — occupancy of the
epoch-keyed join-backpressure memo, bounded by the heartbeat-cadence
sweep (entries whose slot epoch moved or whose request label cleared
are evicted; a hard 4096 cap backstops pathological stores).

Deadline stamps ride `__dl_<idx>` companion keys (debug-labeled,
flagged by `LBL_DEADLINE` on the request key, format
`"<deadline_ts>:<slot_epoch>"` — the trace-stamp discipline: epoch
self-invalidating, consumed at service, orphans shed).  Runbook:
`docs/operations.md` §Multi-tenant QoS; harness: `spt loadgen`.

### Telemetry-history keys (`libsplinter_tpu/engine/telemetry.py`)

The telemetry sampler (supervisable lane `telemetry`, jax-free)
scrapes every lane heartbeat on its cadence into fixed-size
time-series rings stored IN the store — the signal plane the
elastic-lane scaling controller reads, rendered by `spt top` and
`spt metrics --history`:

- `__tele_<lane>` — one ring key per scraped lane:
  `{"v": 1, "lane": ..., "interval_s": ..., "n": samples,
  "gauges": {name: [[ts, value], ...]}}`, each gauge bounded to
  `--ring-len` samples (default 64; an oversized snapshot halves its
  history until it fits `max_val`).  Gauges: `queue_depth` (measured
  by label enumeration, never trusted from the heartbeat), `shed` /
  `deferred` / `deadline_expired`, the lane's progress counter,
  `pages_free` / `pool_mb` / `pool_mb_peak` / `pages_used_peak`
  (completer HBM watermarks), `compile_events` (the devtime plane's
  runtime-recompile count — a non-flat ring is the silent-recompile
  alarm), `p99_<stage>_ms` when tracing is on, and
  `tenant<id>_admitted` / `tenant<id>_served_tokens`.
- `__telemetry_stats` — the sampler's own heartbeat (samples,
  lanes_seen, points, shrinks, generation) — supervised exactly like
  the serving lanes, and because the rings live in the store a
  restarted sampler RESUMES them (gauged by the restart test in
  `make trace-check`).

Every lane heartbeat additionally carries a `spans_obs` section
(span-capture accounting: committed / recovered / dropped / pending —
obs/spans.py; size-droppable like every optional section), rendered
flat by `spt metrics` as `sptpu_<lane>_spans_*`.

### Compile-ledger keys (`libsplinter_tpu/obs/devtime.py`)

The device-time plane commits compile events into a bounded store
ring, claimed exactly like the span ring:

- `__compile_<i>` — committed compile-event records: `{"v": 1,
  "program": "lane.name", "lane": ..., "shapes_key": ...,
  "duration_ms": ..., "generation": G, "cause":
  "warmup"|"runtime", "ts": ..., "pid": ...}`.  Ring size =
  `span_ring_size` (nslots/8 in [16, 128]); events buffer in the
  lane and flush on the heartbeat cadence.
- `__compile_head` — the ring's atomically-incremented BIGUINT
  claim counter (multi-writer safe; replicas of an elastic lane
  share the one ring, their events distinguished by `pid` +
  `generation`).

`spt trace export` merges the ring into the Perfetto document as
instant events on a dedicated track; `collect_compile_events(store)`
is the programmatic reader; `scripts/compile_gate_check.py` is the
CI gate that fails on any post-warmup `cause: "runtime"` event.  The
`generation` field is synced from the lane's supervision generation
at attach, so a restart is visible as a generation bump in the ring
— warmup compiles of the NEW process never masquerade as serve-time
recompiles of the old one.

### Prefix-cache keys (`libsplinter_tpu/engine/prefix_cache.py`)

A continuous completer with prefix sharing live (the default; off via
`--no-prefix-cache`) extends `__completer_stats` with flat
`prefix_*` gauges, rendered by `spt metrics` as typed counters
(`sptpu_completer_prefix_*`) and ringed by the telemetry sampler
(`prefix_hits` / `prefix_shared_pages` sparkline in `spt top`):

| field | meaning |
|---|---|
| `prefix_hits` / `prefix_misses` | admissions that mapped ≥ 1 full shared page vs none |
| `prefix_hit_tokens` | prompt tokens served from shared pages instead of prefill |
| `prefix_shared_pages` / `prefix_evictable` | tree residency: total retained pages / the zero-ref subset reclaimable on demand (`available_pages = pages_free + prefix_evictable`) |
| `prefix_evictions` | LRU reclaims back to the free list |
| `prefix_cow_copies` | copy-on-write page copies (≈ one per fully-cached admission) |
| `prefix_bytes_saved` | KV bytes not re-prefilled/committed |

Per-tenant cache residency rides the `tenants` ledger section as
`prefix_pages` (quota pressure: `--prefix-quota T:PAGES,...`), and
traced admissions carry a `prefix_hit` stage span
(`CONT_INFER_STAGES`) so `spt trace show` attributes first-token
latency to the cache hit vs the suffix prefill.  Runbook:
`docs/operations.md` §Prefix cache.

### Elastic-lane keys (`libsplinter_tpu/engine/protocol.py`, `engine/autoscaler.py`)

Striped replica groups + the scaling controller keep their entire
control plane in the store (runbook: `docs/operations.md` §Elastic
lanes):

- `__stripe_<lane>` — the lane's stripe map: `{"v": 1, "epoch": E,
  "width": W, "owners": {"<replica>": [stripe, ...]},
  "closed": [...], "pending": {"<replica>": [...]}}`.  A request's
  stripe is its slot index mod `width`; replicas re-read the map at
  every drain (`protocol.StripeView`), so one epoch-bumped write
  re-stripes the lane with no orphaned requests.  `closed` stripes
  are claimed by NOBODY (a retiring replica's parked share during
  the deadline-bounded scale-down drain); `pending` lists the
  planned shares of spawning replicas — the incumbents keep serving
  those until the first-heartbeat promotion (the two-phase scale-up
  handoff), and being listed there is how a pending replica knows
  it is not retired.  No map = replica 0 owns everything (the
  classic single-process deployment).
- replica-suffixed heartbeats — replica N > 0 publishes
  `__<lane>_stats.rN` / `__<lane>_trace.rN`
  (`protocol.replica_stats_key`); readers discover them via
  `protocol.replica_heartbeat_keys` (`spt top` renders one row per
  replica + a lane aggregate; `spt metrics` exposes replica blocks
  as `sptpu_<lane>_rN_*`; splint SPL105 enforces the discovery).
  Each replica heartbeat carries `replica` + a `stripe` section
  (epoch / width / owned-stripe count).
- `__scale_policy` — supervisor-published bounds + controller knobs:
  `{"lanes": {lane: {"min": m, "max": M, "signal":
  "queue"|"pool"}}, "up_threshold": ..., "down_threshold": ...,
  "cooldown_s": ..., "interval_s": ...}` (`signal` selects each
  lane's pressure source: `queue` = queue depth per live replica —
  every lane's default; `pool` = fleet-worst paged-pool occupancy —
  the decode lane's memory-bound signal).
- `__scale_tgt_<lane>` — one desired-count key per lane: `{"r": N,
  "src": "auto"|"manual", "ts": ...}` (per-lane keys: no shared
  read-modify-write map for concurrent writers to race) — written
  by the autoscaler
  (`src=auto`) or `spt scale set` (`src=manual` = a hold the
  controller respects), applied by the supervisor's poll.
- `__autoscaler_stats` — the controller heartbeat: decision
  counters (ticks / scale_ups / scale_downs / holds), per-lane
  `{target, pressure, reason, up_streak, down_streak}`, and a
  bounded decision `history` (`spt scale status` renders it;
  `spt metrics` exposes `sptpu_autoscaler_lane_*`).
- `__supervisor_stats` lane sections gain `r` (active replicas),
  optional `scale_min`/`scale_max`, per-replica `replicas`
  subsections, and the supervisor totals gain `retired` +
  `scale_events`.

### Disaggregated-handoff keys (`libsplinter_tpu/engine/disagg.py`)

The prefill -> decode page handoff (runbook: `docs/operations.md`
§Disaggregated lanes) keeps its whole wire protocol in the store,
keyed by the request's SLOT INDEX so both sides and the supervisor's
reclaim agree on ownership without a directory:

- `__ho_<idx>` — the handoff record (debug-labeled JSON, `{"v": 1,
  "len": prompt_tokens, "ids": [...], "carry": first_sampled_token,
  "n_tok": 1, "remaining": ..., "disp_left": ..., "plen":
  slot_bytes_at_handoff, "t0": ..., "tenant": ..., "deadline": ...,
  "wire_pages": N, "quant": bool}`).  The record lands LAST — after
  the wire pages, before the `DECODE_READY` flip — so a record's
  existence IS the adoptability contract; `plen` is the truncation
  point crash recovery rolls a dead adopter's slot back to.
- `__ho_<idx>.p<j>` / `__ho_<idx>.s<j>` — the row's exported KV
  pages (and per-page int8 scales when `quant`), one key per page,
  written only when a page fits `max_val`; `wire_pages: 0` means the
  adopter re-prefills from `ids` instead (the `handoff_refill`
  counter).  All `__ho_` keys leave the store with the request —
  finish, typed reject, and both crash-recovery sweeps all clear
  them.
- `__prefill_stats` / `__decode_stats` — the lanes' heartbeats
  (replica-suffixed like every elastic lane).  Prefill: `handoffs`,
  `handoff_failed`, `handoff_wire_mb`, `prefill_wall_ema_ms` (the
  phase-aware QoS slack).  Decode: `adopted`, `readopted`,
  `adopt_backpressure`, `handoff_refill`, plus the pool gauges
  (`pages_free`/`pages_used`) the telemetry sampler turns into the
  `pool_occ` ring — the decode autoscaler's `pool` signal.
""",
}


def parse_header(path: str = HEADER):
    with open(path) as f:
        raw = f.read()
    lines = raw.splitlines()

    # the leading block comment is the ABI overview
    m = re.match(r"/\*(.*?)\*/", raw, re.S)
    preamble = _clean_comment(m.group(0).splitlines()) if m else ""

    sections: list[Section] = []
    cur = Section("core constants")   # pre-marker #defines land here
    sections.append(cur)
    pending: list[str] = []        # comment lines awaiting an owner
    i = 0
    n = len(lines)
    # skip the preamble comment
    while i < n and not lines[i].startswith("#ifndef"):
        i += 1
    while i < n:
        ln = lines[i]
        sm = _SECTION_RE.match(ln)
        if sm:
            cur = Section(sm.group(1))
            sections.append(cur)
            pending = []
            # a section marker may open a multi-line comment whose body
            # documents the whole section (e.g. the tokenizer block)
            if "*/" not in ln:
                # the marker opens a multi-line comment: its body is
                # the section's own introduction
                body = []
                i += 1
                while i < n and "*/" not in lines[i]:
                    body.append(lines[i])
                    i += 1
                if i < n:
                    body.append(lines[i])
                cur.intro = _clean_comment(body)
            i += 1
            continue
        stripped = ln.strip()
        if stripped.startswith("/*"):
            block = [ln]
            while "*/" not in lines[i] and i + 1 < n:
                i += 1
                block.append(lines[i])
            pending = block
            i += 1
            continue
        dm = _DEFINE_RE.match(stripped)
        if dm:
            # a define takes only its INLINE comment; a block comment
            # above it stays pending — in this header those blocks
            # document the function that follows the define (e.g. the
            # spt_vec_gather contract above SPT_GATHER_TORN)
            inline = re.search(r"/\*(.*?)\*/", stripped)
            doc = inline.group(1).strip() if inline else ""
            cur.defines.append(
                (dm.group(1), re.sub(r"\s*/\*.*?\*/", "", stripped), doc))
            i += 1
            continue
        if stripped.startswith("typedef enum") or \
                stripped.startswith("typedef struct {"):
            block = [stripped]
            while not re.search(r"}\s*\w+\s*;", block[-1]) and i + 1 < n:
                i += 1
                block.append(lines[i].strip())
            tm = re.search(r"}\s*(\w+)\s*;", block[-1])
            tname = tm.group(1) if tm else "?"
            doc = _clean_comment(pending) if pending else ""
            cur.types.append((tname, "\n".join(block), doc))
            pending = []
            i += 1
            continue
        pm = _PROTO_START.match(stripped)
        if pm:
            sig_lines = [stripped]

            def _unclosed(txt: str) -> bool:
                return txt.count("/*") > txt.count("*/")

            # collect until the statement's ';' lands OUTSIDE a comment
            # (trailing block comments can run past the prototype line)
            while i + 1 < n:
                joined = " ".join(sig_lines)
                bare = re.sub(r"/\*.*?\*/", "", joined, flags=re.S)
                if ";" in bare and not _unclosed(joined):
                    break
                i += 1
                sig_lines.append(lines[i].strip())
            sig = " ".join(sig_lines)
            inline = re.findall(r"/\*(.*?)\*/", sig, re.S)
            sig = re.sub(r"\s*/\*.*?\*/", "", sig, flags=re.S).rstrip()
            sig = re.sub(r"\s+", " ", sig)
            if ";" in sig:
                sig = sig[:sig.index(";") + 1]
            doc = _clean_comment(pending) if pending else ""
            if inline:
                extra = " ".join(
                    re.sub(r"\s+", " ", t.strip()) for t in inline)
                doc = (doc + "\n" + extra).strip()
            cur.funcs.append((pm.group(1), sig, doc))
            pending = []
            i += 1
            continue
        if stripped == "":
            pending = []           # a blank line orphans the comment
        i += 1
    return preamble, sections


def render(outdir: str) -> list[str]:
    preamble, sections = parse_header()
    os.makedirs(outdir, exist_ok=True)
    written = []

    idx = ["# sptpu.h — C ABI reference",
           "",
           "Generated from `native/include/sptpu.h` by "
           "`scripts/gen_api_docs.py`; do not edit by hand "
           "(`tests/test_api_docs.py` enforces sync).",
           "",
           "```",
           preamble,
           "```",
           "",
           "| Section | Functions |",
           "|---|---|"]
    for sec in sections:
        if not sec.funcs and not sec.defines and not sec.types:
            continue
        names = ", ".join(f"`{nm}`" for nm, _, _ in sec.funcs) or "—"
        idx.append(f"| [{sec.title}]({sec.slug}.md) | {names} |")
    idx.append("")

    for sec in sections:
        if not sec.funcs and not sec.defines and not sec.types:
            continue
        page = [f"# {sec.title}",
                "",
                f"Part of the [sptpu.h C ABI](index.md); declarations "
                f"in `native/include/sptpu.h`.",
                ""]
        if sec.intro:
            page.append(sec.intro)
            page.append("")
        if sec.defines:
            page.append("## Constants")
            page.append("")
            for name, line, doc in sec.defines:
                page.append(f"- `{line}`" + (f" — {doc.splitlines()[0]}"
                                             if doc else ""))
            page.append("")
        for name, body, doc in sec.types:
            page.append(f"## `{name}`")
            page.append("")
            page.append("```c")
            page.append(body)
            page.append("```")
            page.append("")
            if doc:
                page.append(doc)
                page.append("")
        for name, sig, doc in sec.funcs:
            page.append(f"## `{name}`")
            page.append("")
            page.append("```c")
            page.append(sig)
            page.append("```")
            page.append("")
            if doc:
                page.append(doc)
                page.append("")
        extra = _APPENDICES.get(sec.slug)
        if extra:
            if "__SPLINT_LABEL_TABLE__" in extra:
                splint = load_splint()
                extra = extra.replace(
                    "__SPLINT_LABEL_TABLE__",
                    splint.registry.render_label_table(
                        splint.extract_registry()))
            page.append(extra.strip())
            page.append("")
        path = os.path.join(outdir, f"{sec.slug}.md")
        with open(path, "w") as f:
            f.write("\n".join(page))
        written.append(path)

    with open(os.path.join(outdir, "index.md"), "w") as f:
        f.write("\n".join(idx))
    written.append(os.path.join(outdir, "index.md"))
    return written


def sync_operations(path: str = OPERATIONS_MD) -> None:
    """Refresh docs/operations.md's generated regions in place: the
    fault-point catalog (from the discovered `fault()` sites +
    FAULT_SITE_DOCS) and the splint rule catalog (from the rule
    registry).  Markers missing -> loud failure, never a silent
    stop."""
    splint = load_splint()
    R, core = splint.registry, sys.modules[splint.__name__ + ".core"]
    with open(path) as f:
        text = f.read()
    text = R.replace_marked_region(
        text, R.OPERATIONS_BEGIN, R.OPERATIONS_END,
        R.render_fault_table())
    text = R.replace_marked_region(
        text, core.RULES_BEGIN, core.RULES_END,
        core.render_rule_table())
    with open(path, "w") as f:
        f.write(text)


if __name__ == "__main__":
    out = sys.argv[1] if len(sys.argv) > 1 else None
    files = render(out or os.path.join(REPO, "docs", "api"))
    print(f"wrote {len(files)} pages to {out or 'docs/api'}")
    if out is None:
        # the default run also refreshes the generated operations.md
        # regions; an explicit outdir (the doc-sync test's tmp dir)
        # must never touch the committed runbook
        sync_operations()
        print("refreshed docs/operations.md generated regions")
