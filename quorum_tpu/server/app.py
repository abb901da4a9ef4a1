"""The OpenAI-compatible application: routes, auth, validation, dispatch.

Endpoint parity with /root/reference/src/quorum/oai_proxy.py:959-1414:

  POST /chat/completions   (and /v1/chat/completions — the reference had no
                            /v1 alias, quirk 10; both are served here)
  GET  /health             → {"status": "healthy"}

Request handling parity:
  - all request headers forwarded minus ``host`` (:973);
  - missing Authorization → fall back to $OPENAI_API_KEY, else 401
    ``auth_error`` with the reference's exact message (:976-998); header
    casing normalized to ``Authorization`` (:1000-1004);
  - no valid backends → 500 ``configuration_error`` (:1010-1024);
  - no model in request and none in config → 400 ``invalid_request_error``
    (:1026-1040);
  - parallel mode iff strategy config present AND >1 valid backend (:1043-1044);
  - non-streaming non-parallel: all backends still called concurrently, first
    success returned verbatim (:1356-1380);
  - all backends failed → 500 "All backends failed. First error: …" (:1140-1162).

Difference: malformed request JSON returns 400 (the reference's blanket
handler turned it into a 500).
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import logging
import os
import time
import uuid
from typing import Any, AsyncIterator

from quorum_tpu import oai, sse
from quorum_tpu.observability import (
    FLIGHT_RECORDER_EVENTS,
    METRICS,
    TRACE_PROPAGATED,
    TRACES,
    ProfilerBusy,
    RequestTrace,
    finish_request_trace,
    maybe_profile,
    profile_process,
    use_trace,
)
from quorum_tpu.telemetry import slo as slo_mod
from quorum_tpu.telemetry import tracecontext
from quorum_tpu.telemetry.recorder import RECORDER
from quorum_tpu.backends.base import Backend, BackendError
from quorum_tpu.backends.registry import BackendRegistry, build_registry
from quorum_tpu.config import Config, load_config
from quorum_tpu.devices import device_memory
from quorum_tpu.server.asgi import (
    App,
    JSONResponse,
    Request,
    Response,
    StreamingResponse,
)
from quorum_tpu.strategies.combine import combine_outcomes, degraded_headers
from quorum_tpu.strategies.fanout import fanout_complete
from quorum_tpu.strategies.streaming import StreamPlan, parallel_stream

logger = logging.getLogger(__name__)

# content-encoding must be dropped too: httpx decompresses upstream bodies, so
# forwarding the upstream's "gzip" label over our identity-encoded JSON would
# corrupt the response for compression-aware clients.
_PASSTHROUGH_SKIP = {"content-length", "content-type", "transfer-encoding", "content-encoding", "connection"}


def _auth_error() -> JSONResponse:
    return JSONResponse(
        {
            "error": {
                "message": (
                    "Authorization header is required and OPENAI_API_KEY "
                    "environment variable is not set"
                ),
                "type": "auth_error",
            }
        },
        status_code=401,
    )


def _resolve_headers(request_headers: dict[str, str]) -> dict[str, str] | None:
    """Forward headers minus host; normalize/inject Authorization.

    Returns None when no credential is available (→ 401).
    """
    headers = {k: v for k, v in request_headers.items() if k.lower() != "host"}
    lower_to_orig = {k.lower(): k for k in headers}
    if "authorization" not in lower_to_orig:
        api_key = os.environ.get("OPENAI_API_KEY", "")
        if not api_key:
            return None
        headers["Authorization"] = f"Bearer {api_key}"
    elif "Authorization" not in headers:
        orig = lower_to_orig["authorization"]
        headers["Authorization"] = headers.pop(orig)
    if "content-type" not in lower_to_orig:
        headers["Content-Type"] = "application/json"
    return headers


class _SSECoalescer:
    """The MoreChunk buffer-and-flush contract, shared by both stream
    generators: frames of chunks marked ``oai.MoreChunk`` (the backend saw
    further events already queued — one decode chunk's k tokens) buffer and
    ship with the next unmarked chunk's flush — k events, ONE socket write.
    ``add`` returns the bytes to write now (b"" while buffering); ``drain``
    returns whatever is still buffered and must be called before emitting
    an error frame or [DONE] so a stream never strands marked frames."""

    def __init__(self) -> None:
        self._buf: list[bytes] = []

    def add(self, chunk: dict[str, Any], frame: bytes | None) -> bytes:
        if frame is not None:
            self._buf.append(frame)
        if not oai.has_more(chunk) and self._buf:
            return self.drain()
        return b""

    def drain(self) -> bytes:
        out = b"".join(self._buf)
        self._buf.clear()
        return out


async def _stream_with_role(
    first_chunk: dict[str, Any] | None,
    rest: AsyncIterator[dict[str, Any]],
    model: str,
    trace: RequestTrace | None = None,
) -> AsyncIterator[bytes]:
    """Single-backend SSE normalization (oai_proxy.py:888-956 parity):
    synthetic role chunk first, duplicate upstream role-only chunk skipped,
    trailing [DONE] guaranteed, MoreChunk runs coalesced per flush. The
    first content frame entering the coalescer is the passthrough's
    ``strategy_first_delta_s`` on ``trace``."""
    yield sse.encode_event(oai.chunk(id="chatcmpl-role", model=model, delta={"role": "assistant"}))
    co = _SSECoalescer()

    def add(chunk: dict[str, Any]) -> bytes:
        if (trace is not None and trace.strategy_first_delta is None
                and oai.extract_delta_content(chunk)):
            trace.mark_strategy_delta()
        return co.add(chunk, sse.encode_event(chunk))

    try:
        if first_chunk is not None:
            delta = (first_chunk.get("choices") or [{}])[0].get("delta") or {}
            is_dup_role = bool(delta.get("role")) and not delta.get("content")
            if not is_dup_role:
                if out := add(first_chunk):
                    yield out
        async for chunk in rest:
            if out := add(chunk):
                yield out
    except BackendError as e:
        # Mid-stream failure: flush anything buffered, then surface as an
        # SSE error chunk and terminate.
        if out := co.drain():
            yield out
        yield sse.encode_event(oai.error_chunk(
            f"Backend failed: {e}", model=model,
            code=getattr(e, "code", None)))
    if out := co.drain():
        yield out
    yield sse.encode_done()


def create_app(
    config: Config | None = None,
    registry: BackendRegistry | None = None,
    watch_config: bool | None = None,
    **backend_overrides: Backend,
) -> App:
    """Build the ASGI application.

    Tests inject deterministic backends via ``backend_overrides`` (name →
    Backend) or a fully custom ``registry``.

    ``watch_config`` enables dev-mode hot reload (default: the
    ``QUORUM_TPU_CONFIG_WATCH`` env toggle): on each request the config
    file's mtime is checked (rate-limited) and edits swap in a rebuilt
    registry without dropping untouched live backends — see
    ``quorum_tpu.server.reload``. Requires a file-backed config.
    """
    cfg = config if config is not None else load_config()
    reg = registry if registry is not None else build_registry(cfg, **backend_overrides)
    if cfg.strategy_name == "aggregate":
        _ = cfg.aggregate  # parsed so that an invalid block fails at boot

    from quorum_tpu.server.reload import ConfigWatcher, Runtime

    rt = Runtime(cfg, reg)
    if watch_config is None:
        watch_config = os.environ.get("QUORUM_TPU_CONFIG_WATCH", "") == "1"
    watcher = (ConfigWatcher(cfg.source_path, rt, backend_overrides)
               if watch_config and cfg.source_path is not None
               and registry is None else None)

    app = App()
    app.state["runtime"] = rt
    app.state["config"] = cfg
    app.state["registry"] = reg

    async def current() -> tuple[Config, BackendRegistry]:
        """The live (config, registry) pair — post-reload when watching."""
        if watcher is not None:
            await watcher.poll()
            app.state["config"], app.state["registry"] = rt.cfg, rt.reg
        return rt.cfg, rt.reg

    def _distinct_engines(reg: BackendRegistry, need: str):
        """(backend name, engine) per DISTINCT engine exposing ``need`` —
        backends sharing a cached engine must not double-count it. The one
        iteration /metrics and /health both build on (HTTP relay backends
        hold no local state and contribute nothing)."""
        seen: set[int] = set()
        for backend in reg.backends:
            engine = getattr(backend, "engine", None)
            if engine is None or not hasattr(engine, need):
                continue
            if id(engine) in seen:
                continue
            seen.add(id(engine))
            yield backend.name, engine

    def _engine_health() -> tuple[str, list[dict]]:
        """Aggregate health from real signals (docs/robustness.md): one
        check row per distinct tpu:// engine — scheduler / snapshot-worker
        thread liveness, breaker state, queue depth vs capacity.
        ``unhealthy``: a serving thread is dead (only a restart recovers).
        ``degraded``: the failure breaker is open/half-open or the
        admission queue is saturated — alive, but shedding.

        Group-aware under disaggregated serving (``disagg=P+D``): the
        engine runs TWO cooperating scheduler loops, and a dead
        decode-group loop must not report healthy because the prefill loop
        is still alive (or vice versa) — /ready then sheds whenever either
        group would.

        A configured backend that failed to CONSTRUCT (registry.failed) is
        ``degraded`` too, with its name and error in its own check row:
        requests degrade to the survivors, but a quorum missing a member
        must not report whole (and /ready stays 503)."""
        checks: list[dict] = []
        for name, engine in _distinct_engines(rt.reg, "health"):
            row = engine.health()
            row["backend"] = name
            checks.append(row)
        failed = [{"backend": name, "constructed": False, "error": err}
                  for name, err in rt.reg.failed.items()]
        status = "degraded" if failed else "healthy"
        for row in checks:
            if (not row["scheduler_alive"]
                    or not row.get("prefill_scheduler_alive", True)
                    or not row["snapshot_worker_alive"]):
                return "unhealthy", checks + failed
            if (row["breaker"] != "closed"
                    or row["pending"] >= row["queue_limit"]
                    or row.get("draining")
                    or row.get("programs_preparing")):
                # Draining: admissions are gated shut (POST /admin/drain)
                # but residents still finish — degraded sheds /ready so
                # the fleet rotates the replica out while they do.
                # Preparing: the engine's stored programs are still being
                # loaded (engine/prepare.py); a request would be served,
                # waiting for its own program, but /ready turns 200 only
                # once nothing loads behind the traffic.
                status = "degraded"
        # SLO burn-rate degradation (telemetry/slo.py): opt-in via
        # QUORUM_TPU_SLO_READY_BURN — while a class burns objectives past
        # the threshold the process reports degraded (and /ready sheds),
        # so a load balancer rotates the replica before more clients eat
        # the breaches. Only meaningful for engine-backed processes.
        if status == "healthy" and checks \
                and slo_mod.burning_class() is not None:
            status = "degraded"
        return status, checks + failed

    @app.route("GET", "/health", "/v1/health")
    async def health(request: Request) -> Response:
        """Truthful liveness: ``healthy`` / ``degraded`` (200 — the process
        still serves, possibly shedding) / ``unhealthy`` (503 — rotate it
        out). With no engine-backed backends the body stays the reference's
        exact ``{"status": "healthy"}``."""
        await current()
        status, checks = _engine_health()
        body: dict = {"status": status}
        if checks:
            # Allocator readings ride /health only: /ready and the fleet's
            # telemetry poll share _engine_health and stay host-side.
            memory = {name: device_memory(engine.mesh) for name, engine
                      in _distinct_engines(rt.reg, "health")}
            for row in checks:
                if memory.get(row["backend"]):
                    row["device_memory"] = memory[row["backend"]]
            body["checks"] = checks
            # Per-class SLO accounting (good/breached by stage + burn
            # rate over the sliding window) — the degradation signal's
            # raw numbers, only for engine-backed processes (the bare
            # reference body stays exact without them).
            body["slo"] = slo_mod.SLO.snapshot()
        if status == "unhealthy":
            return JSONResponse(body, status_code=503,
                                headers={"Retry-After": "5"})
        return JSONResponse(body)

    @app.route("GET", "/ready", "/v1/ready")
    async def ready(request: Request) -> Response:
        """Readiness: 200 only while NEW work would be admitted — a dead
        serving thread, an open/half-open breaker, or a saturated queue all
        503 so load balancers stop routing here before clients eat the
        rejections."""
        await current()
        status, checks = _engine_health()
        if status == "healthy":
            return JSONResponse({"status": "ready"})
        return JSONResponse(
            {"status": "unready", "reason": status,
             **({"checks": checks} if checks else {})},
            status_code=503, headers={"Retry-After": "5"})

    started = time.monotonic()

    @app.route("GET", "/models", "/v1/models")
    async def models(request: Request) -> Response:
        """OpenAI model-discovery surface: one entry per distinct configured
        model id (SDKs and UIs probe this before chatting). The reference
        exposes no discovery endpoint — clients had to know the model name
        out of band; a local serving framework can simply list what it
        loaded. ``owned_by`` carries the backend name(s) serving the id."""
        _, reg = await current()
        owners: dict[str, list[str]] = {}
        for backend in reg.backends:
            mid = getattr(backend, "model", "") or getattr(
                backend, "model_id", "")
            if mid:
                owners.setdefault(mid, []).append(backend.name)
        data = [{"id": mid, "object": "model", "created": 0,
                 "owned_by": ",".join(names)}
                for mid, names in sorted(owners.items())]
        return JSONResponse({"object": "list", "data": data})

    @app.route("GET", "/metrics", "/v1/metrics")
    async def metrics(request: Request) -> Response:
        """Prometheus text exposition of engine/scheduler state — the
        metrics-export gap the reference leaves open (SURVEY.md §5.5: two
        log channels, no metrics). One line set per tpu:// backend; HTTP
        backends have no local state to export."""
        _, reg = await current()
        lines = [
            "# TYPE quorum_tpu_uptime_seconds gauge",
            f"quorum_tpu_uptime_seconds {time.monotonic() - started:.3f}",
        ]
        gauges = ("slots", "members", "busy_slots", "admitting", "pending",
                  "queue_limit", "decode_pipeline", "decode_loop",
                  "inflight_chunks",
                  "prefix_store_bytes", "prefix_store_entries",
                  "disagg", "prefill_sp",
                  "prefill_group_devices", "decode_group_devices",
                  "prefill_group_active", "decode_group_active",
                  "zero_drain", "breaker_state",
                  "kv_pages", "kv_page_size",
                  "kv_pages_allocated", "kv_pages_free",
                  "qos", "draining", "moe_experts_held",
                  "kv_cache_full_bytes", "kv_cache_window_bytes",
                  "kv_cache_index_bytes", "kv_cache_state_bytes",
                  "prepare_seconds")
        # One snapshot per distinct engine (_distinct_engines). Each
        # family's TYPE line appears exactly once, with all its samples
        # grouped — the Prometheus text format rejects repeated TYPE lines.
        snapshots = [(name, engine.metrics())
                     for name, engine in _distinct_engines(reg, "metrics")]
        if snapshots:
            for key in snapshots[0][1]:
                kind = "gauge" if key in gauges else "counter"
                lines.append(f"# TYPE quorum_tpu_engine_{key} {kind}")
                for name, m in snapshots:
                    # a dict is a family with labels of its own: {labels: n}
                    samples = (m[key] if isinstance(m[key], dict)
                               else {"": m[key]})
                    lines.extend(
                        f'quorum_tpu_engine_{key}{{backend="{name}"'
                        f'{"," if labels else ""}{labels}}} {value}'
                        for labels, value in samples.items())
        # Latency histogram families (request duration, TTFT, inter-token,
        # queue wait, prefill, decode chunk) — recorded by the tracing spine
        # across server/strategy/engine layers (observability.METRICS).
        FLIGHT_RECORDER_EVENTS.set(RECORDER.depth())  # scrape-time truth
        lines.extend(METRICS.expose())
        return Response(
            ("\n".join(lines) + "\n").encode(),
            media_type="text/plain; version=0.0.4",
        )

    @app.route("GET", "/debug/traces", "/v1/debug/traces")
    async def debug_traces(request: Request) -> Response:
        """Ring buffer of completed request traces plus the in-flight set:
        per-request span timelines (queue-wait → prefill → decode →
        aggregate → sse-flush), TTFT, and per-token wire timings — the
        drill-down surface behind the aggregate histograms on /metrics."""
        return JSONResponse(TRACES.snapshot())

    @app.route("GET", "/debug/traces/{request_id}",
               "/v1/debug/traces/{request_id}")
    async def debug_trace_one(request: Request) -> Response:
        trace = TRACES.get(request.path_params["request_id"])
        if trace is None:
            return JSONResponse(
                {"error": {"message": "trace not found (expired from the "
                           "ring buffer, or the id was never traced)",
                           "type": "invalid_request_error"}},
                status_code=404,
            )
        return JSONResponse(trace.to_dict())

    @app.route("GET", "/debug/engine/timeline", "/v1/debug/engine/timeline")
    async def debug_timeline(request: Request) -> Response:
        """The engine flight recorder (quorum_tpu/telemetry/recorder.py):
        the bounded ring of structured engine events — dispatches tagged
        with their compile-budget program family, admissions/injections/
        handoffs/registers, clamp transitions, deadline expiries, breaker
        and containment events — correlated across the prefill and decode
        loops by request id. ``?format=perfetto`` returns Chrome
        trace-event JSON (save it and open in ui.perfetto.dev); the
        default JSON form additionally carries each engine's per-family
        device-time statistics and the SLO accounting snapshot."""
        _, reg = await current()
        fmt = request.query_params.get("format", "json")
        if fmt in ("perfetto", "trace", "chrome"):
            return JSONResponse({"displayTimeUnit": "ms",
                                 "traceEvents": RECORDER.to_trace_events()})
        if fmt != "json":
            return JSONResponse(
                {"error": {"message": f"unknown format {fmt!r} "
                           "(json or perfetto)",
                           "type": "invalid_request_error"}},
                status_code=400)
        device_time = {
            name: engine.latency.snapshot()
            for name, engine in _distinct_engines(reg, "latency")}
        return JSONResponse({
            "clock": "perf_counter",
            "capacity": RECORDER.capacity,
            "recorded_total": RECORDER.total(),
            "events": RECORDER.snapshot(),
            "device_time": device_time,
            "slo": slo_mod.SLO.snapshot(),
        })

    @app.route("GET", "/debug/telemetry", "/v1/debug/telemetry")
    async def debug_telemetry(request: Request) -> Response:
        """Compact telemetry snapshot for the fleet plane
        (docs/observability.md): per-class SLO burn, queue depth, breaker
        state, per-family latency models, prefix-store footprint, and a
        sample of this process's monotonic clock. The router's /ready
        poller absorbs one of these per replica per poll into its
        ``TelemetryView`` — burn-aware placement and fleet-timeline clock
        alignment both read from it — so this must stay CHEAP (no jax,
        no device sync; everything here is host-side counters)."""
        _, reg = await current()
        status, checks = _engine_health()
        queue_depth = sum(int(row.get("pending", 0) or 0)
                          for row in checks)
        breakers = {row["backend"]: row["breaker"]
                    for row in checks if "breaker" in row}
        latency = {name: engine.latency.snapshot()
                   for name, engine in _distinct_engines(reg, "latency")}
        prefix_store_bytes = 0
        for _name, engine in _distinct_engines(reg, "prefix_store"):
            store = getattr(engine, "prefix_store", None)
            if store is not None:
                prefix_store_bytes += int(store.bytes_held or 0)
        # QoS scheduler plane (docs/scheduling.md): cost-model EWMAs and
        # shed counters per distinct engine, plus the per-class pending
        # breakdown — all host-side counters, same cost rule as above.
        sched = {}
        for name, engine in _distinct_engines(reg, "cost_model"):
            cm = getattr(engine, "cost_model", None)
            if cm is None:
                continue
            entry = dict(cm.snapshot())
            entry["qos"] = bool(getattr(engine, "qos", False))
            policy = getattr(engine, "_policy", None)
            if policy is not None:
                with engine._cond:
                    entry["queue_depths"] = policy.queue_depths(
                        engine._pending)
            sched[name] = entry
        return JSONResponse({
            # perf_counter sample: the fleet-timeline merger estimates
            # this process's clock offset from (poll request, response,
            # this sample) — same timebase as every flight-recorder "t".
            "clock": time.perf_counter(),
            "time": time.time(),
            "status": status,
            "slo": slo_mod.SLO.snapshot(),
            "queue_depth": queue_depth,
            "breaker": breakers,
            "latency": latency,
            "prefix_store_bytes": prefix_store_bytes,
            "sched": sched,
        })

    @app.route("POST", "/debug/profile", "/v1/debug/profile")
    async def debug_profile(request: Request) -> Response:
        """On-demand whole-process jax device profile
        (``?seconds=N``, default 1, capped at 60): runs
        ``jax.profiler.trace`` over everything the process dispatches for
        N seconds and returns the trace directory (TensorBoard/XProf-
        readable). Single-flight — the jax profiler is process-global and
        cannot nest, so a second request while one runs gets 409
        ``conflict_error`` (the same guard per-request
        QUORUM_TPU_PROFILE_DIR tracing shares; its losers are counted in
        ``quorum_tpu_profile_skipped_total``)."""
        raw = request.query_params.get("seconds", "1")
        try:
            seconds = float(raw)
        except ValueError:
            seconds = -1.0
        if not 0.0 < seconds <= 60.0:
            return JSONResponse(
                {"error": {"message": f"'seconds' must be a number in "
                           f"(0, 60], got {raw!r}",
                           "type": "invalid_request_error"}},
                status_code=400)
        try:
            out_dir = await asyncio.to_thread(profile_process, seconds)
        except ProfilerBusy:
            return JSONResponse(
                {"error": {"message": "profiler busy: another profile "
                           "(on-demand or per-request) is in flight",
                           "type": "conflict_error"}},
                status_code=409, headers={"Retry-After": "5"})
        except Exception as e:
            return JSONResponse(
                {"error": {"message": f"profiling failed: {e}",
                           "type": "proxy_error"}},
                status_code=500)
        return JSONResponse({"profile_dir": out_dir, "seconds": seconds})

    def _prefix_store_engine(reg: BackendRegistry, name: str | None):
        """The engine whose host prefix store /debug/prefix/chunks serves:
        ``?backend=`` selects by backend name; default is the first
        store-backed engine in config order. None when no engine carries a
        store."""
        rows = [(n, e) for n, e in _distinct_engines(reg, "prefix_store")
                if getattr(e, "prefix_store", None) is not None]
        if name:
            rows = [(n, e) for n, e in rows if n == name]
        return rows[0] if rows else (None, None)

    @app.route("GET", "/debug/prefix/chunks", "/v1/debug/prefix/chunks")
    async def prefix_chunks_export(request: Request) -> Response:
        """Serialize the host prefix store's restorable chunk chains (the
        migration wire format, quorum_tpu/cache/prefix_wire.py) — the
        router tier fetches this from a replica rotating out of the ring
        and seeds its ring successors, so spilled conversations restore a
        warm tier-1 prefix instead of paying cold prefill. ``?backend=``
        selects among engines; ``?max_bytes=`` bounds the export."""
        _, reg = await current()
        name, engine = _prefix_store_engine(
            reg, request.query_params.get("backend"))
        if engine is None:
            return JSONResponse(
                {"error": {"message": "no engine with a host prefix store "
                           "(prefix_store=host) is configured",
                           "type": "invalid_request_error"}},
                status_code=404)
        raw_max = request.query_params.get("max_bytes")
        max_bytes = None
        if raw_max is not None:
            # A caller who asked for a bound must GET a bound: an
            # unparseable or non-positive value is a 400, never a silent
            # full-store export (the whole point of the knob is capping
            # payload size).
            try:
                max_bytes = int(raw_max)
            except ValueError:
                max_bytes = -1
            if max_bytes < 1:
                return JSONResponse(
                    {"error": {"message": f"'max_bytes' must be a "
                               f"positive integer, got {raw_max!r}",
                               "type": "invalid_request_error"}},
                    status_code=400)
        blob = await asyncio.to_thread(engine.export_prefix_chunks,
                                       max_bytes)
        return Response(
            blob, media_type="application/octet-stream",
            headers={"X-Prefix-Chunk-Tokens":
                     str(engine.prefix_store.chunk_tokens),
                     "X-Prefix-Backend": name})

    @app.route("PUT", "/debug/prefix/chunks", "/v1/debug/prefix/chunks")
    async def prefix_chunks_import(request: Request) -> Response:
        """Seed the host prefix store from a peer replica's export. The
        engine validates the blob against its own cache layout (chunk
        granularity, leaf count, per-leaf dtype/shape) — a mismatched blob
        is a 400, never a poisoned store."""
        _, reg = await current()
        name, engine = _prefix_store_engine(
            reg, request.query_params.get("backend"))
        if engine is None:
            return JSONResponse(
                {"error": {"message": "no engine with a host prefix store "
                           "(prefix_store=host) is configured",
                           "type": "invalid_request_error"}},
                status_code=404)
        blob = await request.body()
        try:
            stats = await asyncio.to_thread(engine.import_prefix_chunks,
                                            blob)
        except ValueError as e:
            return JSONResponse(
                {"error": {"message": f"prefix-chunk import rejected: {e}",
                           "type": "invalid_request_error"}},
                status_code=400)
        stats["backend"] = name
        return JSONResponse(stats)

    @app.route("POST", "/admin/drain", "/v1/admin/drain")
    async def admin_drain(request: Request) -> Response:
        """Begin a graceful drain of every engine-backed backend
        (docs/robustness.md "Zero-loss streams"): admissions shed with a
        retryable 503 (the router fails the shed requests over
        pre-first-byte) and /ready goes unready so the fleet rotates the
        replica out. Default lets residents finish; ``?park=1``
        additionally parks them — each active stream ends with a
        ``parked`` finish the router proactively resumes on a sibling,
        and a parked NON-streaming request sheds as a retryable 503
        (no resume journal — truncated text must never ship as a 200).
        Idempotent; returns per-engine drain status."""
        _, reg = await current()
        park = request.query_params.get("park", "0") not in ("0", "", None)
        rows = []
        for name, engine in _distinct_engines(reg, "drain"):
            row = await asyncio.to_thread(engine.drain, park)
            row["backend"] = name
            rows.append(row)
        if not rows:
            return JSONResponse(
                {"error": {"message": "no engine-backed backend to drain",
                           "type": "invalid_request_error"}},
                status_code=404)
        return JSONResponse({"draining": True, "engines": rows})

    @app.route("GET", "/admin/drain", "/v1/admin/drain")
    async def admin_drain_status(request: Request) -> Response:
        """Drain progress: ``resident`` per engine counts every stream
        still attached (active + admitting + queued) — all zeros means
        the process holds no client state and is safe to take down."""
        _, reg = await current()
        rows = []
        for name, engine in _distinct_engines(reg, "drain_status"):
            row = engine.drain_status()
            row["backend"] = name
            rows.append(row)
        return JSONResponse({
            "draining": any(r["draining"] for r in rows),
            "resident": sum(r["resident"] for r in rows),
            "engines": rows,
        })

    @app.route("POST", "/admin/undrain", "/v1/admin/undrain")
    async def admin_undrain(request: Request) -> Response:
        """Reopen admissions after a drain (the rollback knob for an
        aborted rotation); idempotent."""
        _, reg = await current()
        rows = []
        for name, engine in _distinct_engines(reg, "undrain"):
            row = engine.undrain()
            row["backend"] = name
            rows.append(row)
        return JSONResponse({"draining": False, "engines": rows})

    @app.route("POST", "/chat/completions", "/v1/chat/completions")
    async def chat_completions(request: Request) -> Response:
        """Request-id + tracing + profiling wrapper around the dispatch
        logic. Every request gets a :class:`RequestTrace` (id echoed in
        X-Request-Id; spans land on /debug/traces; latencies land on the
        /metrics histograms). For SSE the trace/profiler scope must cover
        the *stream* — the device work happens while the ASGI server drives
        the iterator, after this handler returns — so the scope is closed
        from the iterator's finally, not here.

        Cross-tier trace propagation (docs/observability.md "Fleet
        plane"): a W3C ``traceparent`` from the caller (header, or body
        knob for header-less clients — ``Request.body()`` caches, so the
        peek costs nothing extra) is honored — its trace-id becomes the
        flight-recorder correlation key for every engine event this
        request causes, and the router's route/failover events carry the
        same id. No (valid) traceparent → this tier mints one. Either
        way the response echoes ``traceparent`` so callers can join
        their logs to the fleet timeline."""
        rid = f"req-{uuid.uuid4().hex[:16]}"
        parsed = tracecontext.parse_traceparent(
            request.headers.get("traceparent"))
        if parsed is None:
            with contextlib.suppress(Exception):
                raw = await request.json()
                if isinstance(raw, dict):
                    parsed = tracecontext.parse_traceparent(
                        raw.get("traceparent"))
        if parsed is not None:
            trace_id = parsed[0]
            TRACE_PROPAGATED.inc(source="client")
        else:
            trace_id = tracecontext.new_trace_id()
            TRACE_PROPAGATED.inc(source="server")
        span_id = tracecontext.new_span_id()
        trace = TRACES.start(RequestTrace(rid, trace_id=trace_id,
                                          span_id=span_id))
        trace.open_root()
        scope = contextlib.ExitStack()
        scope.enter_context(maybe_profile(rid))
        try:
            with use_trace(trace):
                response = await _chat_impl(request, trace)
        except (asyncio.CancelledError, GeneratorExit):
            # Client disconnect, not a server error: 499 (the nginx
            # client-closed-request convention) keeps impatient clients out
            # of the 5xx request-duration series on dashboards.
            scope.close()
            finish_request_trace(trace, status=499)
            raise
        except BaseException:
            scope.close()
            finish_request_trace(trace, status=500)
            raise
        response.headers.setdefault("X-Request-Id", rid)
        response.headers.setdefault(
            "traceparent", tracecontext.format_traceparent(trace_id,
                                                           span_id))
        if isinstance(response, StreamingResponse):
            response.iterator = _finish_scope_after(
                sse.instrument_stream(response.iterator, trace),
                scope, trace, response.status_code,
            )
        else:
            scope.close()
            finish_request_trace(trace, status=response.status_code,
                                 mode="complete")
        return response

    async def _finish_scope_after(
        iterator: AsyncIterator[bytes],
        scope: contextlib.ExitStack,
        trace: RequestTrace,
        status: int,
    ) -> AsyncIterator[bytes]:
        try:
            async for chunk in iterator:
                yield chunk
        except (GeneratorExit, asyncio.CancelledError):
            status = 499  # client left mid-stream (see chat_completions)
            raise
        finally:
            scope.close()
            finish_request_trace(trace, status=status, mode="stream")

    async def _chat_impl(request: Request, trace: RequestTrace) -> Response:
        cfg, reg = await current()
        try:
            body = await request.json()
            if not isinstance(body, dict):
                raise ValueError("request body must be a JSON object")
        except Exception as e:
            return JSONResponse(
                {"error": {"message": f"Invalid JSON body: {e}", "type": "invalid_request_error"}},
                status_code=400,
            )
        # Internal plan field (the /completions raw-prompt path) — never
        # accepted from the wire: it would bypass chat templating.
        body.pop("_raw_prompt_ids", None)

        headers = _resolve_headers(request.headers)
        if headers is None:
            return _auth_error()

        # After auth (reference ordering, oai_proxy.py:976 then :1026): a
        # malformed knob is one 400 up front, not N backend failures → 500.
        invalid = oai.validate_request_body(body)
        if invalid is not None:
            return JSONResponse(
                {"error": {"message": invalid, "type": "invalid_request_error"}},
                status_code=400,
            )

        if len(reg) == 0:
            return JSONResponse(
                {"error": {"message": "No valid backends configured", "type": "configuration_error"}},
                status_code=500,
            )

        # Trace identity rides the RequestTrace (stamped by the wrapper);
        # the body knob was only a carrier for header-less clients — never
        # forwarded (upstreams would reject an unknown field).
        body.pop("traceparent", None)

        # Cross-cell quorum is the ROUTER's job (docs/quorum.md): this
        # server is one cell. quorum=1 is a no-op (stripped); quorum>1
        # reaching a cell directly is a topology error, not something to
        # silently serve at 1/M strength.
        if (body.pop("quorum", None) or 1) > 1:
            return JSONResponse(
                {"error": {"message": "'quorum' requires the router tier "
                           "(python -m quorum_tpu.router): this server is "
                           "a single cell and cannot fan out across "
                           "replicas", "type": "invalid_request_error"}},
                status_code=400,
            )

        is_streaming = bool(body.get("stream", False))
        is_parallel = cfg.parallel_enabled(len(reg))
        # Per-request deadline override (validated above): a client that
        # knows its own budget caps the whole request — engine deadline AND
        # every HTTP backend hop inherit it; the knob is consumed here, not
        # forwarded (upstreams would reject an unknown field). ``deadline``
        # anchors the budget so SEQUENTIAL hops (fan-out then aggregator)
        # split one allowance instead of each getting a fresh full one.
        timeout = float(body.pop("timeout", None) or cfg.timeout)
        deadline = time.monotonic() + timeout
        # SLO class from deadline headroom (telemetry/slo.py): tagged on
        # the trace now, scored once against the class's TTFT/inter-token/
        # deadline objectives at teardown (finish_request_trace).
        trace.meta["slo"] = slo_mod.classify(timeout)

        # Resolve the actual fan-out targets first: in aggregate strategy only
        # the configured source_backends are called (fix of quirk 4), and both
        # the model check and the empty-selection guard must look at *them*,
        # not the whole registry.
        if is_parallel and cfg.strategy_name == "aggregate":
            targets = reg.select(cfg.aggregate.source_backends)
            if not targets:
                return JSONResponse(
                    {
                        "error": {
                            "message": "source_backends matches no configured backend",
                            "type": "configuration_error",
                        }
                    },
                    status_code=500,
                )
        else:
            targets = reg.backends

        # 400 only when every target call would fail the model check; with a
        # mixed config (some backends carry a model) partial success applies.
        if "model" not in body and not any(b.model for b in targets):
            return JSONResponse(
                {
                    "error": {
                        "message": "Model must be specified when config.yaml model is blank",
                        "type": "invalid_request_error",
                    }
                },
                status_code=400,
            )

        trace.meta["mode"] = (
            ("parallel-" if is_parallel else "single-")
            + ("stream" if is_streaming else "complete"))
        trace.meta["backends"] = [b.name for b in targets]

        if is_streaming:
            if is_parallel:
                plan = StreamPlan.from_config(cfg, reg, body)
                return StreamingResponse(
                    parallel_stream(plan, body, headers, timeout,
                                    trace=trace)
                )
            return await _single_stream(targets[0], body, headers, timeout,
                                        trace)

        # Non-streaming. Parity: every backend is called even in non-parallel
        # mode (oai_proxy.py:1132-1137).
        with trace.span("fanout", backends=len(targets)):
            outcomes = await fanout_complete(targets, body, headers, timeout)
        successes = [o for o in outcomes if o.ok]
        if not successes:
            # When EVERY backend rejected the request as a client error
            # (e.g. 'tools' on a tpu:// backend, docs/api.md knob table) or
            # reported overload (503 queue-full), the status is meaningful to
            # the client: relay the first error verbatim instead of
            # collapsing it into a 500 proxy_error (which breaks retry logic
            # keyed on 4xx-vs-503).
            def relayable(o):
                return o.error is not None and (
                    400 <= o.error.status_code < 500
                    or o.error.status_code in (503, 504)
                )

            if all(relayable(o) for o in outcomes):
                first_err = outcomes[0].error
                return JSONResponse(first_err.body,
                                    status_code=first_err.status_code,
                                    headers=first_err.headers)
            return JSONResponse(
                {
                    "error": {
                        "message": f"All backends failed. First error: {outcomes[0].error_message}",
                        "type": "proxy_error",
                    }
                },
                status_code=500,
            )

        if is_parallel:
            with trace.span("aggregate", strategy=cfg.strategy_name):
                combined, agg_outcome = await combine_outcomes(
                    cfg, reg, outcomes, body, headers,
                    # The aggregator hop runs AFTER the fan-out: it gets the
                    # remaining budget, not a second full one, so the
                    # request's declared deadline bounds the whole chain.
                    aggregator_timeout=max(
                        0.001, deadline - time.monotonic()),
                )
            # A degraded aggregate (separator-join fallback) is marked in
            # response headers so clients can tell it from a real synthesis
            # (docs/quorum.md). Streaming can't do this — headers are gone
            # by the time the final hop runs — so it relies on the counter
            # + recorder event instead.
            return JSONResponse(combined, headers=degraded_headers(agg_outcome))

        # Non-parallel: first successful response verbatim (oai_proxy.py:1356-1380).
        first = successes[0]
        resp_headers = {
            k: v
            for k, v in first.result.headers.items()
            if k.lower() not in _PASSTHROUGH_SKIP
        }
        return JSONResponse(first.result.body, status_code=first.result.status_code, headers=resp_headers)

    def _relay_backend_error(e: BackendError) -> Response:
        """Typed client errors keep their body verbatim; everything else
        normalizes to proxy_error (the chat error contract — docs/api.md).
        Either way the error's response headers ride along — 503/504s carry
        Retry-After (docs/robustness.md)."""
        err = e.body.get("error")
        if isinstance(err, dict) and err.get("type") not in (None, "proxy_error"):
            return JSONResponse(e.body, status_code=e.status_code,
                                headers=e.headers)
        msg = err.get("message", str(e)) if isinstance(err, dict) else str(e)
        return JSONResponse(
            {"error": {"message": f"Backend failed: {msg}",
                       "type": "proxy_error"}},
            status_code=e.status_code,
            headers=e.headers,
        )

    async def _single_backend_request(
        request: Request, capability: str, what: str
    ):
        """Shared preamble for the no-fan-out endpoints (/embeddings,
        /completions): parse + auth, strip internal-only fields, pick the
        single target — the backend whose configured model matches the
        request model; with no model in the request, the first capable one
        in config order. A requested model no capable backend is pinned to
        falls to a blank-model backend (it forwards/serves whatever the
        request names) or — with every candidate pinned elsewhere — 404s
        with OpenAI's ``model_not_found``. Returns
        ``(cfg, body, headers, target)`` or an error Response."""
        cfg, reg = await current()
        try:
            body = await request.json()
            if not isinstance(body, dict):
                raise ValueError("request body must be a JSON object")
        except Exception as e:
            return JSONResponse(
                {"error": {"message": f"Invalid JSON body: {e}",
                           "type": "invalid_request_error"}},
                status_code=400,
            )
        # Internal plan field (raw-prompt path) — never accepted from the
        # wire, or a client could bypass chat templating with it.
        body.pop("_raw_prompt_ids", None)
        headers = _resolve_headers(request.headers)
        if headers is None:
            return _auth_error()
        candidates = [b for b in reg.backends if hasattr(b, capability)]
        if not candidates:
            return JSONResponse(
                {"error": {"message": f"No backend supports {what}",
                           "type": "configuration_error"}},
                status_code=500,
            )
        req_model = body.get("model")
        target = next(
            (b for b in candidates if req_model and b.model == req_model),
            None)
        if target is None and req_model:
            # A typo'd or unserved model must NOT silently fall to a
            # different model's backend — eval harnesses key results on
            # `model`, and OpenAI answers model_not_found here. A backend
            # with a blank configured model is the exception: only
            # http(s):// relays can be blank (TpuBackend.model falls back
            # to its model_id — pinned by test_embeddings), and a relay
            # forwards the requested name for the UPSTREAM to validate.
            target = next((b for b in candidates if not b.model), None)
            if target is None:
                return JSONResponse(
                    {"error": {
                        "message": f"The model '{req_model}' does not "
                                   "exist or is not served by any "
                                   f"backend with {what} support",
                        "type": "invalid_request_error",
                        "param": "model",
                        "code": "model_not_found"}},
                    status_code=404)
        if target is None:
            target = candidates[0]
        return (cfg, body, headers, target)

    @app.route("POST", "/embeddings", "/v1/embeddings")
    async def embeddings(request: Request) -> Response:
        """OpenAI embeddings surface, served from the chat models' resident
        weights (quorum_tpu/engine/embed.py) or relayed to an ``http(s)://``
        upstream. NOT a fan-out: one embedding space per response is the
        only coherent contract. (Beyond reference: it serves only
        /chat/completions and /health.)"""
        got = await _single_backend_request(request, "embed", "embeddings")
        if isinstance(got, Response):
            return got
        cfg, body, headers, target = got
        try:
            result = await target.embed(body, headers, cfg.timeout)
        except BackendError as e:
            return _relay_backend_error(e)
        return JSONResponse(result.body, status_code=result.status_code)

    @app.route("POST", "/completions", "/v1/completions")
    async def completions(request: Request) -> Response:
        """Legacy OpenAI text completions (beyond reference): raw-prompt
        generation plus the ``echo+logprobs`` teacher-forced scoring mode
        eval harnesses use. Routes like /embeddings — one backend, no
        fan-out. Streaming is supported on ``tpu://`` backends for a single
        prompt without echo/logprobs; ``http(s)://`` backends relay
        non-streaming only."""
        got = await _single_backend_request(
            request, "text_complete", "/completions")
        if isinstance(got, Response):
            return got
        cfg, body, headers, target = got

        if body.get("stream"):
            if not hasattr(target, "plan_text_stream"):
                return JSONResponse(
                    {"error": {"message": "streaming /completions is only "
                               "served by tpu:// backends",
                               "type": "invalid_request_error"}},
                    status_code=400,
                )
            # Validation lives with the backend (shared with the flat
            # path) — the route only converts chunk shapes.
            try:
                sbody, model = target.plan_text_stream(body)
            except BackendError as e:
                return _relay_backend_error(e)
            stream = target.stream(sbody, headers, cfg.timeout)
            try:
                first_chunk = await stream.__anext__()
            except StopAsyncIteration:
                first_chunk = None
            except BackendError as e:
                return _relay_backend_error(e)
            return StreamingResponse(
                _completions_stream(first_chunk, stream, model))

        try:
            result = await target.text_complete(body, headers, cfg.timeout)
        except BackendError as e:
            return _relay_backend_error(e)
        return JSONResponse(result.body, status_code=result.status_code)

    async def _completions_stream(
        first_chunk: dict[str, Any] | None,
        rest: AsyncIterator[dict[str, Any]],
        model: str,
    ) -> AsyncIterator[bytes]:
        """chat.completion.chunk frames → text_completion SSE frames (the
        legacy wire shape: choices[].text, no role/delta), [DONE]-terminated."""
        cid = f"cmpl-{uuid.uuid4().hex[:24]}"
        created = int(time.time())

        def convert(chunk: dict[str, Any]) -> dict[str, Any] | None:
            choice = (chunk.get("choices") or [{}])[0]
            delta = choice.get("delta") or {}
            content = delta.get("content")
            finish = choice.get("finish_reason")
            if content or finish:
                return {"id": cid, "object": "text_completion",
                        "created": created, "model": model,
                        "choices": [{"index": 0, "text": content or "",
                                     "logprobs": None,
                                     "finish_reason": finish}]}
            if chunk.get("usage") is not None and not chunk.get("choices"):
                return {"id": cid, "object": "text_completion",
                        "created": created, "model": model,
                        "choices": [], "usage": chunk["usage"]}
            return None  # role-only chunks have no legacy-wire analog

        def encode(chunk: dict[str, Any]) -> bytes | None:
            out = convert(chunk)
            return sse.encode_event(out) if out is not None else None

        co = _SSECoalescer()
        try:
            if first_chunk is not None:
                if flushed := co.add(first_chunk, encode(first_chunk)):
                    yield flushed
            async for chunk in rest:
                if flushed := co.add(chunk, encode(chunk)):
                    yield flushed
        except BackendError as e:
            if flushed := co.drain():
                yield flushed
            yield sse.encode_event(
                {"id": cid, "object": "text_completion", "created": created,
                 "model": model,
                 "choices": [{"index": 0, "text": f"Backend failed: {e}",
                              "logprobs": None, "finish_reason": "error"}]})
        if flushed := co.drain():
            yield flushed
        yield sse.encode_done()

    async def _single_stream(
        backend: Backend, body: dict[str, Any], headers: dict[str, str],
        timeout: float, trace: RequestTrace,
    ) -> Response:
        model = body.get("model") or backend.model or "unknown"
        stream = backend.stream(body, headers, timeout)
        try:
            first_chunk = await stream.__anext__()
        except StopAsyncIteration:
            first_chunk = None
        except BackendError as e:
            # Failure before any token: JSON error with upstream status
            # (oai_proxy.py:1107-1128 parity); typed errors keep their body
            # verbatim — stream and non-stream must present the same error
            # contract (docs/api.md error table).
            return _relay_backend_error(e)
        return StreamingResponse(
            _stream_with_role(first_chunk, stream, model, trace))

    return app
