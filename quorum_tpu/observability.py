"""Observability: request tracing, latency histograms, log channels, profiling.

Parity with the reference's two-channel logging (SURVEY.md §5.5):
the ``aggregation`` logger records individual backend responses, aggregator
prompts, and final combined output; :func:`setup_aggregation_log` attaches the
``logs/aggregation.log`` file handler the reference configured at import time
(/root/reference/src/quorum/oai_proxy.py:17-37) — here it is explicit and
lazy, so importing the package has no filesystem side effects.

Beyond parity (the reference had static ``chatcmpl-parallel*`` ids, no timing,
and no metrics at all), this module is the instrumentation spine every layer
records into:

  - :class:`Histogram` / :class:`MetricsRegistry` — Prometheus histogram
    families (``_bucket``/``_sum``/``_count`` exposition) exported on
    ``/metrics``: request duration, TTFT, inter-token gap, queue wait,
    prefill, decode-chunk. Pure stdlib, thread-safe, O(buckets) memory.
  - :class:`RequestTrace` — the request-scoped span recorder: every request
    gets one trace (id surfaced in ``X-Request-Id``) that the server,
    strategies, backends, and the engine scheduler append spans to
    (queue-wait → prefill → decode → aggregate → sse-flush) that form a tree
    under the root ``request`` span (``id`` / ``parent``), plus wire-level
    TTFT, per-token flush timings, and the first token's path: one clock
    read at each hand-over from the engine's submit to the wire.
  - :class:`TraceStore` — bounded ring buffer of completed traces plus the
    in-flight set, served as JSON from ``GET /debug/traces``.
  - :func:`validate_exposition` — a promtool-style pure-Python checker for
    the full ``/metrics`` text (``make metrics-check``).

TPU profiling: when ``QUORUM_TPU_PROFILE_DIR`` is set, :func:`maybe_profile`
wraps a request in ``jax.profiler.trace`` so device timelines land in
TensorBoard-readable traces — the TPU-native analog of a CPU profiler.
:func:`profile_process` is the on-demand variant behind
``POST /debug/profile?seconds=N`` (single-flight — the jax profiler is
process-global and cannot nest; concurrent requests get 409). The host
plane of either capture holds the engine's ``engine.*`` annotations.

The Prometheus primitive types (Histogram/Counter/Gauge/MetricsRegistry)
and :func:`validate_exposition` moved to ``quorum_tpu.telemetry.metrics``
when the telemetry package grew the flight recorder / latency-model / SLO
subsystems (ISSUE 12) — re-exported here so every existing import keeps
working; the REGISTERED families stay in this module.
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
import os
import threading
import time
from collections import deque
from pathlib import Path
from typing import Any, Iterator

from quorum_tpu.telemetry.metrics import (  # noqa: F401  (re-exports)
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    validate_exposition,
)
from quorum_tpu.telemetry.recorder import RECORDER

logger = logging.getLogger(__name__)
aggregation_logger = logging.getLogger("aggregation")

_configured_paths: set[Path] = set()


def setup_aggregation_log(log_dir: str | os.PathLike = "logs") -> Path:
    """Attach the ``logs/aggregation.log`` file handler (idempotent per path —
    a later call with a *different* directory attaches an additional handler
    rather than silently keeping only the first location).

    Mirrors the reference's channel: dir auto-created, a test write performed
    so misconfiguration fails loudly at startup, INFO level, not propagated to
    the root logger's console output.
    """
    path = (Path(log_dir) / "aggregation.log").resolve()
    if path in _configured_paths:
        return path
    path.parent.mkdir(parents=True, exist_ok=True)
    handler = logging.FileHandler(path)
    handler.setFormatter(
        logging.Formatter("%(asctime)s - %(name)s - %(levelname)s - %(message)s")
    )
    aggregation_logger.addHandler(handler)
    aggregation_logger.setLevel(logging.INFO)
    aggregation_logger.propagate = False
    aggregation_logger.info("Aggregation logging initialized")  # test write
    _configured_paths.add(path)
    return path


# ---- histogram metrics -----------------------------------------------------
# (Primitive types live in quorum_tpu/telemetry/metrics.py; this module
# registers the serving families on the process-wide registry below.)


METRICS = MetricsRegistry()

# The canonical serving-latency families (ISSUE 1 acceptance set + the
# engine-phase pair the scheduler records). All in seconds.
REQUEST_DURATION = METRICS.histogram(
    "quorum_tpu_request_duration_seconds",
    "End-to-end request wall time (headers in to last byte out).")
TTFT = METRICS.histogram(
    "quorum_tpu_ttft_seconds",
    "Time to first content byte on the SSE wire.")
INTER_TOKEN = METRICS.histogram(
    "quorum_tpu_inter_token_seconds",
    "Gap between consecutive content flushes on the SSE wire.",
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
             0.25, 0.5, 1.0, 2.5, 5.0))
QUEUE_WAIT = METRICS.histogram(
    "quorum_tpu_queue_wait_seconds",
    "Engine admission-queue wait (submit to slot claim).")
# The first token's path (docs/observability.md "Where a first token's time
# went"): one family per stage, observed where the stage ends. With
# quorum_tpu_queue_wait_seconds (submit to admit) they partition a request's
# TTFT from the engine submit on. A family per stage and not a ``stage``
# label: scrapers that sum a family over its label sets would add stages up.
FIRST_TOKEN_PREFILL = METRICS.histogram(
    "quorum_tpu_first_token_prefill_seconds",
    "Slot claim to the engine's first emitted token, per engine submission "
    "(prefill work plus, for a chunked admission, the decode chunks it "
    "waited behind and the chunk that sampled the token).")
FIRST_TOKEN_BACKEND = METRICS.histogram(
    "quorum_tpu_first_token_backend_seconds",
    "Engine's first emitted token to the tpu:// backend's first non-empty "
    "content delta, per engine submission (consumer thread wake-up, "
    "detokenizer, stop matcher, the hop onto the event loop).")
FIRST_TOKEN_STRATEGY = METRICS.histogram(
    "quorum_tpu_first_token_strategy_seconds",
    "Earliest member's first backend delta to the first content frame the "
    "strategy hands to the SSE writer, per request (merge queue, thinking "
    "filter, chunk encoding).")
FIRST_TOKEN_WIRE = METRICS.histogram(
    "quorum_tpu_first_token_wire_seconds",
    "First content frame handed to the SSE writer to the first content "
    "write on the wire, per request (write coalescing).")
PREFILL = METRICS.histogram(
    "quorum_tpu_prefill_seconds",
    "Prompt prefill wall time (admission start to cache-complete; chunked "
    "admissions include interleaved decode turns).")
DECODE_CHUNK = METRICS.histogram(
    "quorum_tpu_decode_chunk_seconds",
    "One blocking decode-chunk reap (fetch + delivery) of the scheduler "
    "loop; pipelined chunks' in-flight wait is excluded.",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0, 10.0))
# Depth of the decode-dispatch ring right now (engine/engine.py: chunks
# dispatched but not yet read; 0 when the pipeline is drained). Last-writer-
# wins across engines sharing the process.
PIPELINE_DEPTH = METRICS.gauge(
    "quorum_tpu_decode_pipeline_inflight",
    "Decode chunks currently in flight on the device (dispatch ring depth).")
# Megachunk decode (decode_loop=C, engine/engine.py): chunk segments ONE
# dispatch actually produced tokens for — 1 per dispatch when unfused, up
# to C when the device rolled chunk-to-chunk inside one program, 0 when a
# dispatch's rows had all finished on device before it ran. The C× win is
# this histogram's mean against decode_chunks_total staying ~flat.
DECODE_LOOP_CHUNKS = METRICS.histogram(
    "quorum_tpu_decode_loop_chunks",
    "Decode chunk segments covered by one device dispatch (decode_loop "
    "megachunk fusion; per-chunk n_valid counts the segments that "
    "produced tokens).",
    buckets=(1, 2, 4, 8, 16, 32, 64))

# Disaggregated prefill/decode serving (tpu://…&disagg=P+D — docs/
# tpu_backends.md): admission prefill runs on its own device group and a
# completed admission's KV prefix hands off device→device into the claimed
# decode-group slot (quorum_tpu/cache/kv_transfer.py). The handoff pair
# counts every KV byte that crosses the group boundary; the per-group
# occupancy gauges are the split view of the old single-mesh busy_slots.
KV_HANDOFF_BYTES = METRICS.counter(
    "quorum_tpu_kv_handoff_bytes_total",
    "KV cache bytes handed off between device groups (prefill-group "
    "staging -> decode-group slot), labelled route= direct (same-layout "
    "device->device put), reshard (either side partitioned: per-group tp= "
    "or an sp-sharded staging cache, re-laid-out on the fly), host-bounce "
    "(the explicit d2h+h2d fallback), or resident (zero-drain same-mesh "
    "injection: 0 bytes cross any boundary).")
KV_HANDOFF_SECONDS = METRICS.histogram(
    "quorum_tpu_kv_handoff_seconds",
    "One chunk-granular KV handoff between device groups (slice dispatch "
    "to landed-on-target), blocking on the prefill scheduler thread; "
    "route= labels as on quorum_tpu_kv_handoff_bytes_total.",
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
             0.25, 0.5, 1.0, 2.5, 5.0))
# Paged KV slot memory (tpu://…&kv_pages=1, docs/tpu_backends.md): the
# dense [n_slots, max_seq] rectangle becomes a refcounted page pool + a
# per-row page table. Pool occupancy is the capacity story (rows admit
# while pages remain, not while worst-case rectangles remain); the alias/
# COW pair is the prefix-reuse economics — a tier-0 hit installs page
# REFERENCES (zero KV bytes moved), and only a partially-reused boundary
# page pays a one-page copy-on-write.
KV_PAGES_ALLOCATED = METRICS.gauge(
    "quorum_tpu_kv_pages_allocated",
    "KV pool pages currently referenced by a live or retained chain "
    "(kv_pages=1 engines; 0/absent on dense layouts). Last-writer-wins "
    "across engines sharing the process, like the other engine gauges.")
KV_PAGES_FREE = METRICS.gauge(
    "quorum_tpu_kv_pages_free",
    "KV pool pages on the free list (kv_pages=1 engines). "
    "free + allocated == kv_pool_pages.")
KV_PAGE_ALIAS_HITS = METRICS.counter(
    "quorum_tpu_kv_page_alias_hits_total",
    "Tier-0 prefix hits served by page ALIASING under kv_pages=1: the "
    "admission installed refcounted references to the donor's pages "
    "instead of copying KV bytes (kv_handoff_bytes stays 0 for these).")
KV_PAGE_COW_COPIES = METRICS.counter(
    "quorum_tpu_kv_page_cow_copies_total",
    "Copy-on-write boundary-page copies under kv_pages=1: a reused "
    "prefix ended mid-page, so the partially-shared page was copied "
    "(one page) before the new tenant's suffix writes. Full pages "
    "alias by reference and never pay this.")
PREFILL_GROUP_ACTIVE = METRICS.gauge(
    "quorum_tpu_prefill_group_active",
    "In-flight chunked admissions occupying the prefill device group "
    "right now (last-writer-wins across engines sharing the process).")
DECODE_GROUP_ACTIVE = METRICS.gauge(
    "quorum_tpu_decode_group_active",
    "Busy decode-group slots right now (last-writer-wins across engines "
    "sharing the process).")

# Zero-drain continuous batching (tpu://…&zero_drain=1 — docs/
# tpu_backends.md): staged in-flight row injection on colocated engines.
# Admissions prefill into a same-mesh staging cache and the new row's KV
# injects into its claimed slot at a reap boundary while the
# decode_pipeline=K × decode_loop=C ring holds the other rows' in-flight
# state — the structural admission-pressure clamp (C=1/K=1) is retired.
ADMISSION_OVERLAP = METRICS.counter(
    "quorum_tpu_admission_overlap_total",
    "Staged-injection admissions that registered onto a live ring "
    "(in-flight dispatches or active resident rows the admission never "
    "drained or clamped). Structurally 0 on drain-based colocated "
    "engines, whose admissions never ride the injection queue.")
ADMISSION_STALL_SECONDS = METRICS.counter(
    "quorum_tpu_admission_stall_seconds_total",
    "Wall time the decode dispatch ring spent clamped to depth 1 for an "
    "admission (the drain-based coupling). Structurally 0 under "
    "zero_drain=1 and under disagg=P+D, where admission pressure never "
    "clamps the ring.")

# Tiered KV prefix store (quorum_tpu/cache/prefix_store.py + the engine's
# snapshot/restore hooks, docs/prefix_cache.md): host-RAM retention of
# decoded KV prefixes beyond the resident slots. Process-wide families —
# the per-engine split is in the quorum_tpu_engine_prefix_store_* block.
PREFIX_STORE_HITS = METRICS.counter(
    "quorum_tpu_prefix_store_hits_total",
    "Admissions whose prompt prefix was restored from the host prefix "
    "store (the store's match beat the slot-resident LCP).")
PREFIX_STORE_RESTORED_TOKENS = METRICS.counter(
    "quorum_tpu_prefix_store_restored_tokens_total",
    "Prompt tokens restored host->device instead of being re-prefilled.")
PREFIX_STORE_EVICTIONS = METRICS.counter(
    "quorum_tpu_prefix_store_evictions_total",
    "KV chunks evicted from the host prefix store (byte-budget LRU).")
PREFIX_STORE_BYTES = METRICS.gauge(
    "quorum_tpu_prefix_store_bytes",
    "Bytes of KV prefix data held in the host store right now "
    "(last-writer-wins across engines sharing the process).")
PREFIX_STORE_RESTORE = METRICS.histogram(
    "quorum_tpu_prefix_store_restore_seconds",
    "Host->device restore of a matched KV prefix into a claimed slot "
    "(transfer + cache write, blocking on the scheduler thread).",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0, 10.0))

# Fault-contained serving (docs/robustness.md): request deadlines, HTTP
# backend retry, and the engine failure breaker. Per-engine breakdowns
# (rebuilds_total, breaker_state, deadline_exceeded_total) live in the
# quorum_tpu_engine_* block each engine's metrics() feeds.
# Constrained decoding (quorum_tpu/constrain/ + the engine's on-device
# DFA threading — docs/structured_output.md).
CONSTRAINED_REQUESTS = METRICS.counter(
    "quorum_tpu_constrained_requests_total",
    "Requests served under a response_format grammar (json_object / "
    "json_schema / regex).")
CONSTRAIN_MASKED_TOKENS = METRICS.counter(
    "quorum_tpu_constrain_masked_tokens_total",
    "Vocabulary entries masked to -inf by the on-device grammar DFA, "
    "summed over every decode step of every constrained row.")
CONSTRAIN_CACHE_HITS = METRICS.counter(
    "quorum_tpu_constrain_cache_hits_total",
    "Grammar compilations served from the (grammar, tokenizer) cache.")
CONSTRAIN_CACHE_MISSES = METRICS.counter(
    "quorum_tpu_constrain_cache_misses_total",
    "Grammar compilations that had to run (cache miss).")
CONSTRAIN_COMPILE = METRICS.histogram(
    "quorum_tpu_constrain_compile_seconds",
    "Grammar -> token-DFA compile time (regex/schema lowering, byte-DFA "
    "construction, token lifting) on a cache miss.",
    buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
             1.0, 2.5, 5.0, 10.0))

# Recompile sentinel (quorum_tpu/analysis/compile_watch.py, docs/
# static_analysis.md): XLA compiles observed AFTER the process served its
# first completed request. First-of-shape traffic still legitimately ticks
# it (the first constrained request, a new history bucket, a second
# engine); what indicates program-key drift — a shape-family leak, an
# unhashable key component — is SUSTAINED growth under steady traffic,
# which is what to alert on. The runtime half of the qlint recompile-budget
# rules and the compile_budget.json contract.
RECOMPILES = METRICS.counter(
    "quorum_tpu_recompiles_total",
    "XLA compilations observed after the first served request. Expected "
    "to tick on first-of-shape traffic; sustained growth under steady "
    "traffic indicates program-key drift (docs/static_analysis.md).")

DEADLINE_EXCEEDED = METRICS.counter(
    "quorum_tpu_deadline_exceeded_total",
    "Requests that ran past their deadline, by stage: queue = shed before "
    "admission (503 + Retry-After), prefill/decode = cancelled after "
    "admission (504), backend = an HTTP/device hop outlived its wait.")
BACKEND_RETRIES = METRICS.counter(
    "quorum_tpu_backend_retries_total",
    "HTTP backend attempts retried after a connect error or 5xx "
    "(opt-in per-backend retries= config knob), by backend.")

# Multi-replica router tier (quorum_tpu/router/, docs/scaling.md): the
# standalone prefix-affinity router process records its placement,
# failover, and prefix-migration accounting on these families; they expose
# on the ROUTER's /metrics (the same process-wide registry — on a serving
# replica they simply read 0).
ROUTER_REQUESTS = METRICS.counter(
    "quorum_tpu_router_requests_total",
    "Requests the router placed, by replica and outcome (ok = a 2xx/4xx "
    "relay, failover = this replica failed pre-stream and the request "
    "moved on, error = the relayed terminal failure).")
ROUTER_AFFINITY_HITS = METRICS.counter(
    "quorum_tpu_router_affinity_hits_total",
    "Requests served by the replica their conversation key hashes to "
    "(the bounded-load consistent-hash primary) — where the KV prefix "
    "from earlier turns lives.")
ROUTER_AFFINITY_MISSES = METRICS.counter(
    "quorum_tpu_router_affinity_misses_total",
    "Requests served AWAY from their affinity primary: bounded-load "
    "spill, failover, the primary out of the ring, or policy=random.")
ROUTER_FAILOVERS = METRICS.counter(
    "quorum_tpu_router_failovers_total",
    "Pre-first-byte upstream failures that moved a request to the next "
    "ring candidate, by the replica that failed.")
ROUTER_MIGRATED_BYTES = METRICS.counter(
    "quorum_tpu_router_migrated_bytes_total",
    "Serialized KV prefix-chunk bytes moved between replicas by the "
    "router's rotation migration (GET/PUT /debug/prefix/chunks).")
ROUTER_MIGRATED_CHAINS = METRICS.counter(
    "quorum_tpu_router_migrated_chains_total",
    "Prefix chunk chains moved between replicas by rotation migration.")
ROUTER_STREAM_RESUMES = METRICS.counter(
    "quorum_tpu_router_stream_resumes_total",
    "Mid-stream resume outcomes (docs/robustness.md 'Zero-loss streams'): "
    "resumed = the journaled stream spliced onto a sibling replica "
    "token-exactly; divergence = the sibling's replay byte-check failed "
    "and the stream degraded to the error-chunk contract; failed = a "
    "resume attempt died pre-commit and the next candidate was tried; "
    "exhausted = no candidate/deadline remained; unresumable = the "
    "journal could not cover the stream (no token-id metadata, bound "
    "overflow, or the finish chunk already relayed).")

# Native quorum serving (quorum_tpu/quorum/, docs/quorum.md — ISSUE 20):
# shared-prefix member dedup on stacked engines, the in-engine aggregation
# hop, and the router's cross-cell quorum fan-out with member-kill
# degradation.
QUORUM_DEDUP_TOKENS = METRICS.counter(
    "quorum_tpu_quorum_dedup_tokens_total",
    "Prefill tokens NOT recomputed by shared-prefix member dedup "
    "(quorum_dedup=1 on a members=M engine): a member-complete admission "
    "group with identical prompts prefills ONCE and broadcasts into the "
    "[M, ...] stacked cache, saving (M-1) x n_prompt tokens per group.")
QUORUM_DEGRADED = METRICS.counter(
    "quorum_tpu_quorum_degraded_total",
    "Quorum members dropped mid-request while the quorum was SERVED from "
    "the survivors (never failed), by reason: member_failed = a member "
    "leg died pre-first-byte on every candidate; stream_broken = a "
    "member's live stream died and token-exact resume was exhausted; "
    "resume_diverged = the replay guard refused the member's resume; "
    "no_content = a member completed empty.")
QUORUM_REQUESTS = METRICS.counter(
    "quorum_tpu_quorum_requests_total",
    "Router-tier quorum fan-outs (the quorum= body knob), by outcome: "
    "full = every member contributed, degraded = served from a strict "
    "subset of members, failed = no member produced content.")
AGGREGATE_DEGRADED = METRICS.counter(
    "quorum_tpu_aggregate_degraded_total",
    "Aggregate-strategy combines that fell back to the separator-join of "
    "the member outputs instead of a real LLM aggregation, by reason: "
    "no_aggregator = none configured, no_credentials = the aggregator "
    "required auth no header provided, error = the aggregator call "
    "failed or returned non-2xx, empty = it returned no content. The "
    "first underlying error rides the X-Quorum-Aggregate-Error response "
    "header (docs/quorum.md).")

# Fleet observability plane (ISSUE 16, docs/observability.md "Fleet
# plane"): cross-tier trace propagation, per-replica telemetry absorption,
# and burn-aware placement. Registered process-wide like the other router
# families — a serving replica reads them at zero.
ROUTER_REPLICA_BURN = METRICS.gauge(
    "quorum_tpu_router_replica_burn",
    "Last absorbed SLO burn rate per replica and class (the router's "
    "/ready poller pulls each replica's GET /debug/telemetry; stale "
    "telemetry keeps the last reading but stops driving demotion).")
ROUTER_BURN_DEMOTIONS = METRICS.counter(
    "quorum_tpu_router_burn_demotions_total",
    "Placements in which a replica was demoted to the candidate tail "
    "because its interactive-class burn rate exceeded the router's "
    "threshold (per-request reorder like bounded-load spill — membership "
    "untouched, fail-open when telemetry is stale).")
TELEMETRY_POLL_SECONDS = METRICS.histogram(
    "quorum_tpu_telemetry_poll_seconds",
    "One replica telemetry pull (GET /debug/telemetry inside the router's "
    "/ready poll sweep), request to parsed snapshot.",
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
             0.25, 0.5, 1.0, 2.5, 5.0))
TRACE_PROPAGATED = METRICS.counter(
    "quorum_tpu_trace_propagated_total",
    "Requests stamped with a W3C trace-id, by source: client = an "
    "incoming traceparent was honored, router/server = this tier minted "
    "one (none arrived), engine = an engine-direct submission self-minted "
    "its flight-recorder correlation id.")

# Engine flight recorder + per-family device-time attribution + SLO
# accounting (quorum_tpu/telemetry/, docs/observability.md — ISSUE 12).
# Every program an engine dispatches is observed with the seconds its
# device ledger (telemetry/device_ledger.py) booked to it, landing to
# landing, under its compile_budget.json program family (admission-path
# programs under seg/register/hslice/hput/...). Buckets reach below the
# serving ladder: one tiny-chunk dispatch is sub-millisecond on a warm TPU.
DISPATCH_DEVICE_SECONDS = METRICS.histogram(
    "quorum_tpu_dispatch_device_seconds",
    "Per-program device time by compile_budget.json program family, as "
    "the engine's device ledger booked it: the interval between the "
    "landing before the program's and its own.",
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
             0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0))
# SLO accounting (quorum_tpu/telemetry/slo.py): requests classify by
# deadline headroom into interactive/batch and score one good-or-breached
# observation per stage (ttft / inter_token / deadline) at teardown. The
# burn rate (breached/observed over a sliding window) rides /health.
SLO_GOOD = METRICS.counter(
    "quorum_tpu_slo_good_total",
    "Requests that met the stage's objective for their SLO class "
    "(class=interactive|batch, stage=ttft|inter_token|deadline).")
SLO_BREACHED = METRICS.counter(
    "quorum_tpu_slo_breached_total",
    "Requests that breached the stage's objective for their SLO class "
    "(class=interactive|batch, stage=ttft|inter_token|deadline).")
# QoS scheduler (quorum_tpu/sched/, docs/scheduling.md): mid-decode
# preemptions by VICTIM class, the generated tokens parked at preemption
# (regenerated deterministically on resume), and the pending-queue depth
# per priority class (refreshed each scheduler turn).
PREEMPTIONS = METRICS.counter(
    "quorum_tpu_preemptions_total",
    "Mid-decode preemptions by victim class (class=batch|background): a "
    "lower-class row parked at a reap boundary so a higher-class "
    "admission could take its slot (qos=1 engines only).")
PREEMPTED_TOKENS = METRICS.counter(
    "quorum_tpu_preempted_tokens_total",
    "Generated tokens parked at preemption — already delivered to their "
    "consumers, regenerated token-for-token on resume (the replay the "
    "engine byte-checks against the delivered stream).")
SCHED_QUEUE_DEPTH = METRICS.gauge(
    "quorum_tpu_sched_queue_depth",
    "Pending admissions by priority class "
    "(class=interactive|batch|background).")
# Flight-recorder self-accounting: current ring depth (refreshed on
# /metrics scrapes) and events overwritten by the bounded ring.
FLIGHT_RECORDER_EVENTS = METRICS.gauge(
    "quorum_tpu_flight_recorder_events",
    "Events currently held in the engine flight recorder's bounded ring "
    "(GET /debug/engine/timeline; QUORUM_TPU_FLIGHT_EVENTS caps it).")
FLIGHT_RECORDER_DROPPED = METRICS.counter(
    "quorum_tpu_flight_recorder_dropped_total",
    "Flight-recorder events overwritten by the bounded ring (the oldest "
    "event falls off when a new one lands on a full ring).")
# On-demand/per-request jax profiling: requests that proceeded UNTRACED
# because the process-global profiler was already busy (maybe_profile's
# silent skip, made visible — ISSUE 12 satellite).
PROFILE_SKIPPED = METRICS.counter(
    "quorum_tpu_profile_skipped_total",
    "Requests that ran unprofiled because the process-global jax "
    "profiler was busy with another trace (QUORUM_TPU_PROFILE_DIR "
    "per-request tracing, or a POST /debug/profile in flight).")

# The bounded ring's overwrite hook (the recorder itself imports nothing
# from this module — the wiring lives on this side of the boundary).
RECORDER.on_drop = FLIGHT_RECORDER_DROPPED.inc


# ---- request-scoped tracing ------------------------------------------------

# Span budget per trace: a pathological 100k-token generation must not grow
# an unbounded span list; past the cap only the drop counter advances.
MAX_SPANS = 512
# Wire flush-timing budget per trace (ttft + the first N inter-token gaps).
MAX_TOKEN_TIMES = 2048
# add_span(parent=) default: the innermost open span of the calling context.
_INHERIT: Any = object()


class Span:
    """One timed phase inside a request. ``start``/``end`` are seconds
    relative to the trace's origin; ``meta`` carries small tags (backend,
    bucket, occupancy...). ``id`` is unique in its trace and ``parent`` is
    the id of the span that caused this one (None for the root)."""

    __slots__ = ("name", "start", "end", "meta", "id", "parent")

    def __init__(self, name: str, start: float, end: float | None = None,
                 meta: dict | None = None, id: int = 0,
                 parent: int | None = None):
        self.name = name
        self.start = start
        self.end = end
        self.meta = meta or {}
        self.id = id
        self.parent = parent

    @property
    def duration(self) -> float | None:
        return None if self.end is None else self.end - self.start

    def to_dict(self) -> dict:
        out = {
            "id": self.id,
            "parent": self.parent,
            "name": self.name,
            "start_s": round(self.start, 6),
            "end_s": None if self.end is None else round(self.end, 6),
            "duration_ms": (None if self.end is None
                            else round((self.end - self.start) * 1000, 3)),
        }
        if self.meta:
            out["meta"] = self.meta
        return out


class RequestTrace:
    """Span recorder for ONE request, appended to from any thread.

    The server creates it per request and opens the root ``request`` span;
    the engine scheduler, strategies, and the SSE wire wrapper record into
    it through :func:`current_trace` / direct references. ``phases`` (name →
    accumulated seconds), ``total`` and ``log()`` feed the one summary line
    per request."""

    def __init__(self, request_id: str, mode: str = "",
                 trace_id: str = "", span_id: str = ""):
        self.request_id = request_id
        # W3C trace-context identity (telemetry/tracecontext.py): the
        # 32-hex trace-id names this request across router, replica, and
        # engine tiers (the flight-recorder rid), the 16-hex span-id names
        # THIS server hop. Empty on untraced callers (engine-direct tests,
        # non-chat endpoints) — the engine then self-mints.
        self.trace_id = trace_id
        self.span_id = span_id
        self._t0 = time.perf_counter()
        self.started_at = time.time()
        self._lock = threading.Lock()
        self.spans: list[Span] = []
        self._next_span_id = 0
        self.root_id: int | None = None
        self.dropped_spans = 0
        # The first token's path: one row of instants per engine submission
        # (stamped by the engine and the tpu:// backend through the row the
        # submission holds), and the strategy's first content frame.
        self.members: list[dict] = []
        self.strategy_first_delta: float | None = None
        self.meta: dict = {"mode": mode} if mode else {}
        self.ttft: float | None = None
        self.token_times: list[float] = []  # wire flush times, rel. seconds
        self.n_tokens = 0        # content flushes, NOT capped like the list
        # Worst gap between consecutive content flushes, tracked UNCAPPED
        # (the token_times list stops at MAX_TOKEN_TIMES — a stall past
        # the cap must still be visible to the SLO inter_token scorer).
        self.max_token_gap: float | None = None
        self._last_token_t: float | None = None
        self.n_flushes = 0
        self.status: int | None = None
        self.duration: float | None = None  # set by finish()

    # -- clocks --------------------------------------------------------------

    def now(self) -> float:
        """Seconds since this trace began (the span timebase)."""
        return time.perf_counter() - self._t0

    def rel(self, perf_t: float) -> float:
        """A ``time.perf_counter()`` stamp → this trace's timebase."""
        return perf_t - self._t0

    # -- spans ---------------------------------------------------------------

    def add_span(self, name: str, start: float, end: float | None = None,
                 parent: Any = _INHERIT, **meta: Any) -> Span:
        """Record a span with trace-relative times (see :meth:`rel`).

        ``parent`` is the id of the span that caused this one. Left out, it
        is the innermost span this trace has open in the calling context
        (:meth:`span`), else the root: a span recorded from a task or thread
        that inherited no context still hangs under ``request``. Threads that
        outlive the submitting context (the engine scheduler) pass the id
        their submission was handed (:meth:`open_member`'s ``span``).

        Completed traces are immutable: a timed-out request's still-running
        device loop keeps calling in for minutes after the trace was
        published to /debug/traces — those late spans are counted in
        ``dropped_spans``, never appended (the returned detached span keeps
        callers' ``span.end = ...`` stamping harmless)."""
        if parent is _INHERIT:
            parent = self.context_parent()
        with self._lock:
            span = Span(name, start, end, meta or None,
                        self._next_span_id, parent)
            self._next_span_id += 1
            if self.duration is not None or len(self.spans) >= MAX_SPANS:
                self.dropped_spans += 1
            else:
                self.spans.append(span)
        return span

    def add_span_abs(self, name: str, start_perf: float, end_perf: float,
                     parent: Any = _INHERIT, **meta: Any) -> Span:
        """Record a span from two ``time.perf_counter()`` stamps."""
        return self.add_span(name, self.rel(start_perf), self.rel(end_perf),
                             parent, **meta)

    @contextlib.contextmanager
    def span(self, name: str, **meta: Any) -> Iterator[Span]:
        """An open span: until the block ends it is the default parent of
        every span this context (and the tasks it creates) records."""
        s = self.add_span(name, self.now(), **meta)
        token = _current_span.set((self, s.id))
        try:
            yield s
        finally:
            s.end = self.now()
            # An async generator closed from another task cannot reset a
            # token of the context it was opened in; nothing reads it then.
            with contextlib.suppress(ValueError):
                _current_span.reset(token)

    def open_root(self, name: str = "request") -> Span:
        """The span every other span of this trace descends from; open from
        the trace's origin until :meth:`finish`."""
        root = self.add_span(name, 0.0, parent=None)
        self.root_id = root.id
        return root

    def context_parent(self) -> int | None:
        cur = _current_span.get()
        return cur[1] if cur is not None and cur[0] is self else self.root_id

    # -- the first token's path ----------------------------------------------

    def open_member(self, member: int, submit_perf: float) -> dict:
        """One engine submission's row of first-token instants, opened in
        the submitting context. The engine holds the row and stamps
        ``admit_s`` and ``engine_first_token_s`` into it, the tpu:// backend
        ``backend_first_delta_s``; ``span`` is the hop span open at the
        submission, under which the submission's engine spans hang."""
        row = {"member": member, "span": self.context_parent(),
               "submit_s": self.rel(submit_perf), "admit_s": None,
               "engine_first_token_s": None, "backend_first_delta_s": None}
        with self._lock:
            self.members.append(row)
        return row

    def _first_member(self) -> dict | None:
        """The submission whose backend delta came first, if any has."""
        done = [m for m in self.members
                if m["backend_first_delta_s"] is not None]
        return min(done, key=lambda m: m["backend_first_delta_s"],
                   default=None)

    def mark_strategy_delta(self) -> None:
        """The strategy hands its first content frame to the SSE writer."""
        if self.strategy_first_delta is not None:
            return
        t = self.strategy_first_delta = self.now()
        first = self._first_member()
        if first is not None:
            FIRST_TOKEN_STRATEGY.observe(
                max(0.0, t - first["backend_first_delta_s"]))

    def first_token_path(self) -> dict:
        """The instants of the first token's path as exported on
        ``/debug/traces/<id>``, with the stages of the member whose delta
        came first: they sum to ``wire_first_content_s``."""
        def r(t):
            return None if t is None else round(t, 6)

        members = [{k: (v if k in ("member", "span") else r(v))
                    for k, v in m.items()} for m in self.members]
        out = {"members": members,
               "strategy_first_delta_s": r(self.strategy_first_delta),
               "wire_first_content_s": r(self.ttft), "stages_ms": None}
        m = self._first_member()
        if m is not None:
            marks = [0.0, m["submit_s"], m["admit_s"],
                     m["engine_first_token_s"], m["backend_first_delta_s"],
                     self.strategy_first_delta, self.ttft]
            if None not in marks:
                out["stages_ms"] = {
                    name: round((b - a) * 1000, 3) for name, a, b in zip(
                        ("submit", "queue_wait", "prefill", "backend",
                         "strategy", "wire"), marks, marks[1:])}
        return out

    # -- wire timing ---------------------------------------------------------

    def mark_flush(self, content: "bool | int") -> None:
        """One SSE write hit the wire; ``content`` counts the token-bearing
        frames it carried (role chunks and [DONE] don't set TTFT; a
        coalesced write ships several content frames in one flush — bools
        are accepted for the uncoalesced single-frame case)."""
        t = self.now()
        count = int(content)
        with self._lock:
            if self.duration is not None:
                return  # completed traces are immutable (see add_span)
            self.n_flushes += 1
            if count <= 0:
                return
            if self.ttft is None:
                self.ttft = t
                TTFT.observe(t)
                if self.strategy_first_delta is not None:
                    FIRST_TOKEN_WIRE.observe(
                        max(0.0, t - self.strategy_first_delta))
            else:
                # Gap from the LAST content flush, tracked independently of
                # the capped token_times list — past the cap each gap must
                # still measure one flush, not the distance back to entry
                # MAX_TOKEN_TIMES. One observation per FLUSH: frames inside
                # a coalesced write arrived together, a zero gap per extra
                # frame would fake wire latency the client never saw.
                gap = t - self._last_token_t
                INTER_TOKEN.observe(gap)
                if self.max_token_gap is None or gap > self.max_token_gap:
                    self.max_token_gap = gap
            self._last_token_t = t
            self.n_tokens += count
            # All of a coalesced flush's tokens hit the wire at t.
            for _ in range(count):
                if len(self.token_times) >= MAX_TOKEN_TIMES:
                    break
                self.token_times.append(t)

    # -- lifecycle -----------------------------------------------------------

    def finish(self, status: int | None = None) -> None:
        """Close the trace: stamp status + total duration, observe the
        request-duration histogram, close any still-open spans (a client
        disconnect can abandon one mid-phase). Idempotent."""
        with self._lock:
            if self.duration is not None:
                return
            self.duration = self.now()
            if status is not None:
                self.status = status
            for s in self.spans:
                if s.end is None:
                    s.end = self.duration
        # Status-class label: a flood of fast-failing 4xxs must not read as
        # serving latency collapsing on a dashboard's unlabeled p50.
        klass = (f"{self.status // 100}xx" if self.status is not None
                 else "unknown")
        REQUEST_DURATION.observe(self.duration, status=klass)

    # -- serialization -------------------------------------------------------

    def to_dict(self) -> dict:
        with self._lock:
            spans = sorted(self.spans, key=lambda s: s.start)
            out = {
                "request_id": self.request_id,
                "started_at": self.started_at,
                "in_flight": self.duration is None,
                "status": self.status,
                "duration_ms": (None if self.duration is None
                                else round(self.duration * 1000, 3)),
                "ttft_ms": (None if self.ttft is None
                            else round(self.ttft * 1000, 3)),
                "tokens": self.n_tokens,
                "sse_flushes": self.n_flushes,
                "token_times_ms": [round(t * 1000, 3)
                                   for t in self.token_times],
                "spans": [s.to_dict() for s in spans],
                "dropped_spans": self.dropped_spans,
                "first_token_path": self.first_token_path(),
            }
            if self.trace_id:
                out["trace_id"] = self.trace_id
                out["span_id"] = self.span_id
            if self.meta:
                out["meta"] = dict(self.meta)
        return out

    def summary(self) -> dict:
        """The /debug/traces list row: the scalar fields only — built
        directly, NOT via to_dict(), so listing a full ring never
        materializes (and discards) thousands of span/timing dicts under
        live traces' locks."""
        with self._lock:
            return {
                "request_id": self.request_id,
                "started_at": self.started_at,
                "in_flight": self.duration is None,
                "status": self.status,
                "duration_ms": (None if self.duration is None
                                else round(self.duration * 1000, 3)),
                "ttft_ms": (None if self.ttft is None
                            else round(self.ttft * 1000, 3)),
                "tokens": self.n_tokens,
                "sse_flushes": self.n_flushes,
                "dropped_spans": self.dropped_spans,
                **({"trace_id": self.trace_id} if self.trace_id else {}),
                **({"meta": dict(self.meta)} if self.meta else {}),
            }

    # -- the per-request summary line ------------------------------------------

    @property
    def phases(self) -> dict[str, float]:
        """Accumulated seconds per span name (closed spans only; the root
        is ``total``)."""
        with self._lock:
            out: dict[str, float] = {}
            for s in self.spans:
                if s.end is not None and s.id != self.root_id:
                    out[s.name] = out.get(s.name, 0.0) + (s.end - s.start)
        return out

    @property
    def total(self) -> float:
        return self.duration if self.duration is not None else self.now()

    def log(self, mode: str, **extra: Any) -> None:
        """One structured summary line per request: phases, ttft, tokens."""
        detail = " ".join(f"{k}={v}" for k, v in extra.items())
        phases = " ".join(f"{k}={v * 1000:.1f}ms"
                          for k, v in self.phases.items())
        wire = ""
        if self.ttft is not None:
            wire = f"ttft={self.ttft * 1000:.1f}ms tokens={self.n_tokens}"
        logger.info(
            "request %s mode=%s total=%.1fms %s %s %s",
            self.request_id, mode, self.total * 1000, phases, wire, detail,
        )


class TraceStore:
    """In-flight traces plus a bounded ring of completed ones."""

    def __init__(self, capacity: int | None = None):
        if capacity is None:
            capacity = int(os.environ.get("QUORUM_TPU_TRACE_CAPACITY", "256"))
        self.capacity = max(1, capacity)
        self._lock = threading.Lock()
        self._inflight: dict[str, RequestTrace] = {}
        self._completed: deque[RequestTrace] = deque(maxlen=self.capacity)

    def start(self, trace: RequestTrace) -> RequestTrace:
        with self._lock:
            self._inflight[trace.request_id] = trace
        return trace

    def complete(self, trace: RequestTrace) -> None:
        with self._lock:
            self._inflight.pop(trace.request_id, None)
            self._completed.append(trace)

    def get(self, request_id: str) -> RequestTrace | None:
        with self._lock:
            t = self._inflight.get(request_id)
            if t is not None:
                return t
            for t in self._completed:
                if t.request_id == request_id:
                    return t
        return None

    def snapshot(self, limit: int | None = None) -> dict:
        """Summaries of every in-flight trace plus completed ones newest
        first — the whole ring by default (it is already bounded by
        ``capacity``); ``limit`` trims the listing further."""
        with self._lock:
            inflight = list(self._inflight.values())
            completed = list(self._completed)
        completed.reverse()  # newest first
        rows = inflight + completed
        if limit is not None:
            rows = rows[:limit]
        return {
            "capacity": self.capacity,
            "in_flight": len(inflight),
            "completed": len(completed),
            "traces": [t.summary() for t in rows],
        }

    def reset(self) -> None:
        with self._lock:
            self._inflight.clear()
            self._completed.clear()


TRACES = TraceStore()

_current_trace: contextvars.ContextVar[RequestTrace | None] = \
    contextvars.ContextVar("quorum_tpu_trace", default=None)


# (trace, id) of the innermost span open in this context: RequestTrace.span.
_current_span: contextvars.ContextVar[tuple[RequestTrace, int] | None] = \
    contextvars.ContextVar("quorum_tpu_span", default=None)


def current_trace() -> RequestTrace | None:
    """The trace of the request this task/thread is serving, if any."""
    return _current_trace.get()


@contextlib.contextmanager
def use_trace(trace: RequestTrace | None) -> Iterator[RequestTrace | None]:
    """Bind ``trace`` as the current trace for this context (None is a
    no-op bind, so callers can pass through an optional trace)."""
    token = _current_trace.set(trace)
    try:
        yield trace
    finally:
        _current_trace.reset(token)


@contextlib.contextmanager
def trace_span(trace: RequestTrace | None, name: str, **meta: Any):
    """``trace.span(...)`` tolerant of ``trace is None``."""
    if trace is None:
        yield None
        return
    with trace.span(name, **meta) as s:
        yield s


def finish_request_trace(trace: RequestTrace, status: int | None = None,
                         mode: str = "") -> None:
    """Request teardown: close the trace, move it to the completed ring,
    score its SLO class (when the server tagged one — telemetry/slo.py),
    and emit the one structured per-request summary line."""
    trace.finish(status=status)
    TRACES.complete(trace)
    if trace.meta.get("slo"):
        from quorum_tpu.telemetry.slo import SLO

        try:
            SLO.score_trace(trace)
        except Exception:
            logger.exception("SLO scoring failed for %s", trace.request_id)
    trace.log(mode or trace.meta.get("mode", ""), status=trace.status)


_profile_lock = threading.Lock()


@contextlib.contextmanager
def maybe_profile(request_id: str):
    """jax.profiler device trace for this request when QUORUM_TPU_PROFILE_DIR
    is set; no-op (and no jax import) otherwise.

    The jax profiler is process-global and cannot nest: when another request
    is already being traced, this one proceeds untraced — visibly: the skip
    ticks ``quorum_tpu_profile_skipped_total`` and records a
    ``profile-skipped`` flight-recorder event, so dropped profiles no longer
    vanish into a DEBUG line (ISSUE 12 satellite)."""
    profile_dir = os.environ.get("QUORUM_TPU_PROFILE_DIR", "")
    if not profile_dir:
        yield
        return
    if not _profile_lock.acquire(blocking=False):
        logger.debug("profiler busy — request %s runs untraced", request_id)
        PROFILE_SKIPPED.inc()
        RECORDER.record("profile-skipped", rid=request_id, loop="server")
        yield
        return
    try:
        import jax

        with jax.profiler.trace(os.path.join(profile_dir, request_id)):
            yield
    finally:
        _profile_lock.release()


class ProfilerBusy(RuntimeError):
    """The process-global jax profiler is already tracing (surface as 409)."""


def profile_process(seconds: float, profile_dir: str | None = None) -> str:
    """On-demand whole-process device profile (``POST /debug/profile``):
    run ``jax.profiler.trace`` over everything the process dispatches for
    ``seconds``, blocking the calling thread (the route runs it in an
    executor). Returns the trace directory.

    Single-flight behind the same lock as :func:`maybe_profile` — the jax
    profiler cannot nest — raising :class:`ProfilerBusy` instead of
    queueing: a profile of "the next N seconds, later" is not the profile
    the operator asked for."""
    if not _profile_lock.acquire(blocking=False):
        PROFILE_SKIPPED.inc()
        RECORDER.record("profile-skipped", rid="on-demand", loop="server")
        raise ProfilerBusy("jax profiler busy with another trace")
    try:
        import jax

        base = (profile_dir or os.environ.get("QUORUM_TPU_PROFILE_DIR", "")
                or os.path.join("profiles", "ondemand"))
        out = os.path.join(base, time.strftime("%Y%m%d-%H%M%S"))
        RECORDER.record("profile-start", rid="on-demand", loop="server",
                        seconds=seconds, dir=out)
        with jax.profiler.trace(out):
            time.sleep(max(0.0, float(seconds)))
        return out
    finally:
        _profile_lock.release()
