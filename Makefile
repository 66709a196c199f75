# libsplinter-tpu — top-level bootstrap.
#
# One command from a clean checkout to a green suite:
#
#   make all        native lib + tools, TAP unit tier, full pytest
#   make quick      native lib + TAP tier + pytest smoke subset (~2 min)
#   make check      the native check tier (TAP + MRSW stress + MRMW
#                   chi-sao) + full pytest
#   make memcheck   valgrind (if installed) or ASan/UBSan native tier
#   make obs-check  observability tier: tracing-overhead budget
#                   (scripts/obs_overhead_check.py, <3% vs disabled)
#                   + the `-m obs` pytest group
#   make search-check  fused top-k tier: interpret-mode kernel parity
#                   vs the lax.top_k reference + the search daemon's
#                   coalescing smoke (N clients « N dispatches)
#   make decode-check  paged decode tier: interpret-mode ragged
#                   paged-attention parity vs dense flash, pool
#                   alloc/free leak checks, the paged continuous-
#                   batching smoke (token-exact vs dense, joiner
#                   past the dense window), and spec-demotion (CPU)
#   make chaos-check   fault-injection tier: SPTPU_FAULT unit tests,
#                   supervisor backoff/breaker, and the CPU-only
#                   crash-at-every-stage recovery matrix (child
#                   daemons crashed mid-drain via crash@k, restarted,
#                   convergence asserted; `pytest -m chaos`)
#   make dispatch-check  dispatch-floor tier: resident-ring /
#                   K-overlap parity vs the per-call paths (byte-
#                   identical vectors, search results, decode tokens)
#   make pod-check  pod-sharded paged decode tier (fast, CPU
#                   8-device mesh): sharded-paged vs single-chip-
#                   paged vs serial token-exact parity, the
#                   shard_map'd ragged/flash kernels in interpret
#                   mode, mid-flight joiner, pool backpressure,
#                   shard-labeled heartbeat gauges, and sharded-
#                   dispatch fault containment
#   make qos-check  multi-tenant QoS tier (fast, CPU): weighted
#                   fairness within 2x under 10:1 offered-load skew,
#                   typed overloaded shedding + retry_after_ms at the
#                   queue high-water mark, deadline fast-fail on a
#                   real searcher (scripts/qos_fairness_check.py) +
#                   the `tests/test_qos.py` fast tier (admission
#                   policy units, all three lanes, loadgen smoke)
#   make pipeline-check  pipeline-lane tier (fast, CPU): sandbox
#                   containment (hostile scripts die typed while
#                   siblings complete), scripted-chain end-to-end
#                   parity, and the round-trip gate (the client-
#                   side rag-churn chain costs one client round trip
#                   per hop, the stored script ONE request, zero
#                   admitted loss either way;
#                   scripts/pipeline_roundtrip_check.py)
#   make trace-check  cross-lane tracing + telemetry tier (fast,
#                   CPU): trace-context stamp round-trips, span-ring
#                   wire protocol (staging, crash recovery with
#                   restart-gap attribution, bounded multi-writer
#                   ring), orphan sweeps (raced rewrites cannot leak
#                   staging rows), span-tree assembly parity for both
#                   chain forms, the Chrome/Perfetto export schema
#                   check, telemetry-ring persistence across sampler
#                   restarts, and the EXTENDED obs-overhead gate
#                   (span stamping + a concurrently-scraping sampler
#                   must stay under the same <3% budget)
#   make lint-check  splint static-analysis tier (pure stdlib ast,
#                   no jax, no native build needed): protocol-
#                   registry sync rules (label-bit collisions, raw
#                   bit literals, fault-site catalog + chaos
#                   reachability, metrics/heartbeat sync, generated
#                   doc tables) + JAX dispatch-hazard rules (host
#                   syncs in drain loops, donated-buffer reuse,
#                   missing out_shardings pins, unseeded fault-path
#                   randomness), then the splint test tier.
#                   Non-zero exit on any unsuppressed finding.
#   make prefix-check  cross-request prefix-sharing tier (fast,
#                   CPU): refcount churn drill (zero leaks / double
#                   frees, refcount-0 <=> free XOR tree-retained),
#                   COW-vs-private byte-exact greedy decode (f32 +
#                   int8, single-chip + tp=2), >= 4x rows per page
#                   budget, LRU eviction + tenant quotas, mid-flight
#                   joiner parity, loadgen --shared-prefix, and the
#                   hot-admission gate (scripts/prefix_hit_check.py:
#                   greedy bytes identical with and without the
#                   cache, every hot admission maps all of its
#                   prompt pages and dispatches no prefill)
#   make disagg-check  disaggregated prefill/decode tier (fast,
#                   CPU): PrefillLane + DecodeLane on one store,
#                   driven through loadgen's prefill-burst scenario
#                   with a 10x prefill rate step — zero admitted
#                   loss, and the page handoff runs the real wire
#                   export/import path (handoffs exported and
#                   adopted, none refilled;
#                   scripts/disagg_check.py) + the test_disagg.py
#                   fast tier (byte-exactness vs the unified
#                   completer, handoff crash drills both directions)
#   make warm-check  tiered-KV warm-restart tier (fast, CPU): one
#                   supervised completer lane with the host-DRAM
#                   spill tier + persistent radix index armed,
#                   SIGKILLed mid-loadgen — the respawn must attach
#                   WARM (index restored, hot set readmitted from the
#                   tier instead of re-prefilled, greedy bytes
#                   identical across the restart), with zero admitted
#                   loss (scripts/warm_restart_check.py) + the
#                   test_kv_tier.py fast tier (write-through spill /
#                   readmit byte-exactness, torn-snapshot taxonomy,
#                   capacity-drop pruning)
#   make scale-check  elastic-lane tier (fast, CPU): stripe-map
#                   protocol + striped replica groups (R=2 byte-
#                   identical to R=1, no double-claims, no orphans
#                   across a re-stripe), supervisor replica sets +
#                   scale-down drain/reclaim, autoscaler hysteresis
#                   (no flapping on oscillating input), loadgen rate
#                   profiles, then the in-process 1x->4x->1x rate-
#                   step gate (scripts/scale_step_check.py: replicas
#                   follow the step, zero admitted-request loss
#                   through scale-up AND scale-down)
#   make quant-check  quantized-KV tier (fast, CPU): int8-vs-f32
#                   ragged paged-attention parity (interpret mode),
#                   multi-query verify stack, quantize-on-commit /
#                   rescale-on-append error budgets, spec-paged
#                   greedy exactness, compile-count pinning, and the
#                   pool-bytes gate (int8 == 1/2 bf16 == 1/4 f32,
#                   measured from placed buffers;
#                   scripts/quant_pool_bytes_check.py)
#   make compile-check  device-time/compile-attribution tier (fast,
#                   CPU): devtime registry + compile-ring unit tests,
#                   then the post-warmup no-recompile gate over the
#                   pod-sharded paged drill in both directions —
#                   clean passes, a seeded out_shardings drop is
#                   caught by program name + shapes key
#                   (scripts/compile_gate_check.py)
#   make clean
#
# Parity: the reference's `configure` + shim Makefile + bigbang.sh
# (/root/reference/configure:1-60) — here there are no external deps to
# install (jax & friends are baked into the image; the native tier
# needs only cc + make), so bootstrap is just build + test.  The build
# hash the reference stamps via scripts/genbuildh lands in
# native/build/libsptpu.so as spt_build_id(), surfaced by `caps`.

PY ?= python

all: native
	native/build/spt_unit
	$(PY) -m pytest tests/ -x -q

native:
	$(MAKE) -C native all tests

quick: native
	native/build/spt_unit
	$(PY) -m pytest tests/test_store.py tests/test_embedder.py \
		tests/test_cli.py -q

# the full sweep excludes the chaos tier, which runs once on its own
# line (it needs JAX_PLATFORMS=cpu for the crash-matrix children and
# would otherwise run twice); search-check/decode-check/chaos-check/
# pod-check stay standalone fast gates, same pattern as obs-check's
# `-m obs` group — the full pytest sweep below collects their tiers too
check: native
	$(MAKE) -C native check
	$(PY) scripts/splint_check.py
	$(PY) scripts/obs_overhead_check.py
	JAX_PLATFORMS=cpu $(PY) scripts/quant_pool_bytes_check.py
	JAX_PLATFORMS=cpu $(PY) scripts/qos_fairness_check.py
	JAX_PLATFORMS=cpu $(PY) scripts/pipeline_roundtrip_check.py
	JAX_PLATFORMS=cpu $(PY) scripts/prefix_hit_check.py
	JAX_PLATFORMS=cpu $(PY) scripts/scale_step_check.py
	JAX_PLATFORMS=cpu $(PY) scripts/disagg_check.py
	JAX_PLATFORMS=cpu $(PY) scripts/warm_restart_check.py
	JAX_PLATFORMS=cpu $(PY) scripts/compile_gate_check.py
	JAX_PLATFORMS=cpu $(PY) scripts/compile_gate_check.py --seed-recompile
	$(PY) -m pytest tests/ -q -m "not chaos"
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m chaos

obs-check: native
	$(PY) scripts/obs_overhead_check.py
	$(PY) -m pytest tests/ -q -m obs

search-check: native
	$(PY) -m pytest tests/test_fused_topk.py tests/test_searcher.py -q

decode-check: native
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_paged_attention.py \
		tests/test_paged_continuous.py -q

chaos-check: native
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m chaos

dispatch-check: native
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_resident.py -q \
		-m "not chaos"

pod-check: native
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_sharded_paged.py \
		tests/test_sharded_decode.py -q -m "not slow"

scale-check: native
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_elastic.py -q \
		-m "not slow and not chaos"
	JAX_PLATFORMS=cpu $(PY) scripts/scale_step_check.py

disagg-check: native
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_disagg.py -q \
		-m "not slow and not chaos"
	JAX_PLATFORMS=cpu $(PY) scripts/disagg_check.py

warm-check: native
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_kv_tier.py -q \
		-m "not slow and not chaos"
	JAX_PLATFORMS=cpu $(PY) scripts/warm_restart_check.py

quant-check: native
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_quant_kv.py \
		tests/test_quant_int4.py -q -m "not slow"
	JAX_PLATFORMS=cpu $(PY) scripts/quant_pool_bytes_check.py

prefix-check: native
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_prefix_cache.py -q \
		-m "not slow and not chaos"
	JAX_PLATFORMS=cpu $(PY) scripts/prefix_hit_check.py

# no `native` dep: splint is stdlib-ast only and must be runnable
# before (or without) any build step — the cheapest pre-commit gate
lint-check:
	$(PY) scripts/splint_check.py
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_splint.py -q

qos-check: native
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_qos.py -q \
		-m "not slow and not chaos"
	JAX_PLATFORMS=cpu $(PY) scripts/qos_fairness_check.py

trace-check: native
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_spans.py \
		tests/test_telemetry.py -q -m "not slow and not chaos"
	$(PY) scripts/obs_overhead_check.py

# the post-warmup no-recompile gate (obs/devtime.py compile ledger)
# over the pod-sharded paged drill, both directions: clean must pass,
# the seeded out_shardings drop must be CAUGHT by name + shapes key
compile-check: native
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_devtime.py -q \
		-m "not slow and not chaos"
	JAX_PLATFORMS=cpu $(PY) scripts/compile_gate_check.py
	JAX_PLATFORMS=cpu $(PY) scripts/compile_gate_check.py --seed-recompile

pipeline-check: native
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_pipeliner.py -q \
		-m "not slow and not chaos"
	JAX_PLATFORMS=cpu $(PY) scripts/pipeline_roundtrip_check.py

memcheck: native
	$(MAKE) -C native memcheck

clean:
	$(MAKE) -C native clean

.PHONY: all native quick check obs-check search-check decode-check \
	chaos-check dispatch-check pod-check quant-check prefix-check \
	qos-check pipeline-check trace-check lint-check scale-check \
	disagg-check warm-check compile-check memcheck clean
