"""Prometheus exposition conformance: the pure-Python promtool-style
validator (observability.validate_exposition) plus live /metrics checks —
histogram bucket monotonicity, _sum/_count consistency, and exactly one
# TYPE line per metric family. Run standalone via ``make metrics-check``."""

from quorum_tpu.observability import (
    DEFAULT_BUCKETS,
    Histogram,
    validate_exposition,
)
from tests.conftest import make_client

# ---- validator unit tests (no server, no jax) ------------------------------


def test_validator_accepts_reference_shapes():
    text = "\n".join([
        "# HELP demo_seconds a demo",
        "# TYPE demo_seconds histogram",
        'demo_seconds_bucket{le="0.1"} 1',
        'demo_seconds_bucket{le="1.0"} 3',
        'demo_seconds_bucket{le="+Inf"} 4',
        "demo_seconds_sum 2.5",
        "demo_seconds_count 4",
        "# TYPE demo_total counter",
        'demo_total{backend="LLM1",mode="a,b"} 7',
        "# TYPE demo_gauge gauge",
        "demo_gauge 3.14",
    ]) + "\n"
    assert validate_exposition(text) == []


def test_validator_flags_malformed_lines():
    bad = "\n".join([
        "# TYPE demo_total counter",
        "demo_total seven",           # non-numeric value
        'demo_total{unclosed="x" 1',  # unterminated label set
        "# TYPE demo_total counter",  # duplicate TYPE
    ]) + "\n"
    errors = validate_exposition(bad)
    assert any("non-numeric" in e for e in errors)
    assert any("malformed sample" in e for e in errors)
    assert any("duplicate TYPE" in e for e in errors)


def test_validator_flags_histogram_inconsistencies():
    text = "\n".join([
        "# TYPE h_seconds histogram",
        'h_seconds_bucket{le="0.1"} 5',
        'h_seconds_bucket{le="1.0"} 3',    # non-monotonic counts
        'h_seconds_bucket{le="+Inf"} 6',
        "h_seconds_sum 1.0",
        "h_seconds_count 7",               # != +Inf bucket
        "# TYPE g_seconds histogram",
        'g_seconds_bucket{le="0.5"} 2',    # no +Inf bucket
        "g_seconds_sum 0.5",
        "g_seconds_count 2",
    ]) + "\n"
    errors = validate_exposition(text)
    assert any("not monotonic" in e for e in errors)
    assert any("_count" in e and "+Inf" in e for e in errors)
    assert any("missing +Inf" in e for e in errors)


def test_validator_flags_type_after_samples():
    text = "\n".join([
        "late_total 1",
        "# TYPE late_total counter",
    ]) + "\n"
    assert any("after its samples" in e for e in validate_exposition(text))


def test_histogram_expose_is_valid_and_cumulative():
    h = Histogram("t_seconds", "t")
    for v in (0.002, 0.002, 0.3, 7.0, 1000.0):
        h.observe(v)
    h.observe(0.05, backend="A")
    text = "\n".join(h.expose()) + "\n"
    assert validate_exposition(text) == []
    snap = h.snapshot()
    unlabeled = snap[()]
    assert unlabeled["count"] == 5
    assert unlabeled["buckets"][-1] == 5          # +Inf holds everything
    assert abs(unlabeled["sum"] - 1007.304) < 1e-6
    # cumulative counts never decrease
    assert unlabeled["buckets"] == sorted(unlabeled["buckets"])
    labeled = snap[(("backend", "A"),)]
    assert labeled["count"] == 1


def test_labeled_gauge_and_route_series_expose_valid():
    """Gauge labels (ISSUE 14: per-stage decode occupancy) — each label
    set is its own last-writer-wins series under one TYPE line, the bare
    series survives for unlabeled writers, and the whole family (plus a
    route-labeled counter like kv_handoff_bytes) validates."""
    from quorum_tpu.telemetry.metrics import Counter, Gauge

    g = Gauge("demo_occupancy", "per-stage occupancy")
    g.set(3, stage="0")
    g.set(1, stage="1")
    g.set(2, stage="1")  # last writer wins per series
    lines = g.expose()
    assert 'demo_occupancy{stage="0"} 3.0' in lines
    assert 'demo_occupancy{stage="1"} 2.0' in lines
    assert "demo_occupancy 0.0" in lines  # bare series retained
    assert g.value_of(stage="0") == 3.0
    assert g.value == 0.0
    c = Counter("demo_bytes_total", "bytes by route")
    c.inc(10, route="reshard")
    c.inc(5, route="host-bounce")
    assert c.value == 15.0
    assert c.value_of(route="reshard") == 10.0
    text = "\n".join(g.expose() + c.expose()) + "\n"
    assert validate_exposition(text) == []


def test_default_buckets_strictly_increase():
    assert list(DEFAULT_BUCKETS) == sorted(set(DEFAULT_BUCKETS))


# ---- live /metrics conformance ---------------------------------------------


def _config():
    return {
        "settings": {"timeout": 60},
        "primary_backends": [
            # prefix_store=host so the quorum_tpu_prefix_store_* families
            # (and the engine-block store gauges/counters) are live on the
            # exposition this test validates.
            # decode_loop=2 so the megachunk knob rides the config path
            # the exposition's engine block reports.
            {"name": "LLM1",
             # kv_pages=1 so the paged-pool gauge/counter families
             # (ISSUE 17) ride the same live exposition; qos=1 so the
             # scheduler families (ISSUE 18) do too.
             "url": "tpu://llama-tiny?seed=3&slots=2&prefix_store=host"
                    "&decode_loop=2&kv_pages=1&qos=1",
             "model": "t"},
        ],
    }


async def test_live_metrics_exposition_validates():
    """The FULL /metrics output — engine gauges/counters plus every
    histogram family — passes the validator after real traffic, with one
    TYPE line per family and consistent histogram series."""
    async with make_client(_config()) as client:
        resp = await client.post(
            "/chat/completions",
            json={"model": "t", "max_tokens": 5,
                  "messages": [{"role": "user", "content": "hi"}]},
            headers={"Authorization": "Bearer x"},
        )
        assert resp.status_code == 200
        stream = await client.post(
            "/chat/completions",
            json={"model": "t", "max_tokens": 5, "stream": True,
                  "messages": [{"role": "user", "content": "hi"}]},
            headers={"Authorization": "Bearer x"},
        )
        assert stream.status_code == 200
        text = (await client.get("/metrics")).text

    assert validate_exposition(text) == [], validate_exposition(text)

    # exactly one TYPE line per family across the whole exposition
    type_lines = [ln for ln in text.splitlines() if ln.startswith("# TYPE ")]
    families = [ln.split()[2] for ln in type_lines]
    assert len(families) == len(set(families)), families

    # the acceptance histogram families, each with samples after traffic
    for fam in ("quorum_tpu_ttft_seconds",
                "quorum_tpu_inter_token_seconds",
                "quorum_tpu_queue_wait_seconds",
                "quorum_tpu_prefill_seconds",
                "quorum_tpu_decode_chunk_seconds"):
        assert f"# TYPE {fam} histogram" in text, fam
        assert f'{fam}_bucket{{le="+Inf"}}' in text, fam
        assert f"{fam}_sum" in text and f"{fam}_count" in text, fam

    # paged-KV pool observability (ISSUE 17): occupancy gauges + the
    # alias/COW counters, and the engine block's paged config/pool keys
    # mapped as gauges (a counter-typed pool level could never go down)
    for fam, typ in (("quorum_tpu_kv_pages_allocated", "gauge"),
                     ("quorum_tpu_kv_pages_free", "gauge"),
                     ("quorum_tpu_kv_page_alias_hits_total", "counter"),
                     ("quorum_tpu_kv_page_cow_copies_total", "counter"),
                     ("quorum_tpu_engine_kv_pages", "gauge"),
                     ("quorum_tpu_engine_kv_pages_free", "gauge")):
        assert f"# TYPE {fam} {typ}" in text, fam
    assert 'quorum_tpu_engine_kv_pages{backend="LLM1"} 1' in text
    # request duration is labeled by status class (2xx here)
    assert "# TYPE quorum_tpu_request_duration_seconds histogram" in text
    assert ('quorum_tpu_request_duration_seconds_bucket'
            '{status="2xx",le="+Inf"}') in text
    assert 'quorum_tpu_request_duration_seconds_count{status="2xx"}' in text

    # the tiered-prefix-store families (ISSUE 3): the restore histogram
    # exposes its full _bucket/_sum/_count triplet even before any hit,
    # and the counter/gauge families carry the counter/gauge TYPEs
    fam = "quorum_tpu_prefix_store_restore_seconds"
    assert f"# TYPE {fam} histogram" in text
    assert f'{fam}_bucket{{le="+Inf"}}' in text
    assert f"{fam}_sum" in text and f"{fam}_count" in text
    for counter in ("quorum_tpu_prefix_store_hits_total",
                    "quorum_tpu_prefix_store_restored_tokens_total",
                    "quorum_tpu_prefix_store_evictions_total"):
        assert f"# TYPE {counter} counter" in text, counter
    assert "# TYPE quorum_tpu_prefix_store_bytes gauge" in text
    # per-engine split: the store keys ride the engine block with the
    # right kinds (bytes/entries are gauges, the rest counters)
    assert ("# TYPE quorum_tpu_engine_prefix_store_bytes gauge") in text
    assert ("# TYPE quorum_tpu_engine_prefix_store_hits_total counter"
            ) in text

    # constrained-decoding families (ISSUE 5, docs/structured_output.md):
    # the compile histogram exposes its full triplet even before any
    # constrained traffic, the counters carry counter TYPEs, and the
    # per-engine split rides the engine block
    fam = "quorum_tpu_constrain_compile_seconds"
    assert f"# TYPE {fam} histogram" in text
    assert f'{fam}_bucket{{le="+Inf"}}' in text
    assert f"{fam}_sum" in text and f"{fam}_count" in text
    for counter in ("quorum_tpu_constrained_requests_total",
                    "quorum_tpu_constrain_masked_tokens_total",
                    "quorum_tpu_constrain_cache_hits_total",
                    "quorum_tpu_constrain_cache_misses_total"):
        assert f"# TYPE {counter} counter" in text, counter
    assert ("# TYPE quorum_tpu_engine_constrained_requests_total counter"
            in text)
    assert ("# TYPE quorum_tpu_engine_constrain_masked_tokens_total "
            "counter" in text)

    # QoS scheduler families (ISSUE 18, docs/scheduling.md): the
    # preemption counters and the per-class queue-depth gauge expose even
    # at zero (no preemption happened for this traffic), and the engine
    # block carries the qos flag plus the per-engine preempt/replay/shed
    # split — qos is a gauge (a flag), the rest counters
    for counter in ("quorum_tpu_preemptions_total",
                    "quorum_tpu_preempted_tokens_total"):
        assert f"# TYPE {counter} counter" in text, counter
    assert "# TYPE quorum_tpu_sched_queue_depth gauge" in text
    assert "# TYPE quorum_tpu_engine_qos gauge" in text
    assert 'quorum_tpu_engine_qos{backend="LLM1"} 1' in text
    for counter in ("quorum_tpu_engine_preemptions_total",
                    "quorum_tpu_engine_preempted_tokens_total",
                    "quorum_tpu_engine_replayed_tokens_total",
                    "quorum_tpu_engine_predictive_sheds_total"):
        assert f"# TYPE {counter} counter" in text, counter

    # drain lifecycle (ISSUE 19, docs/robustness.md "Zero-loss streams"):
    # the draining flag is a gauge (0 on a serving engine), the parked-
    # stream tally a counter — both expose even when no drain ever ran
    assert "# TYPE quorum_tpu_engine_draining gauge" in text
    assert 'quorum_tpu_engine_draining{backend="LLM1"} 0' in text
    assert ("# TYPE quorum_tpu_engine_drain_parked_total counter"
            in text)

    # recompile sentinel (ISSUE 9, docs/static_analysis.md): the counter
    # fed by the analysis/compile_watch.py log-compiles hook exposes a
    # sample even at zero — post-warmup compiles are a serving bug an
    # operator must be able to alert on
    assert "# TYPE quorum_tpu_recompiles_total counter" in text
    assert "quorum_tpu_recompiles_total " in text

    # megachunk-decode families (ISSUE 6): chunk segments per dispatch as
    # a histogram (samples after any decode traffic — unfused dispatches
    # observe 1), the configured decode_loop as an engine gauge, and the
    # executed-segment/drain-gap accounting as engine counters
    fam = "quorum_tpu_decode_loop_chunks"
    assert f"# TYPE {fam} histogram" in text
    assert f'{fam}_bucket{{le="+Inf"}}' in text
    assert f"{fam}_sum" in text and f"{fam}_count" in text
    assert "# TYPE quorum_tpu_engine_decode_loop gauge" in text
    assert ("# TYPE quorum_tpu_engine_decode_loop_chunks_total counter"
            in text)
    assert ("# TYPE quorum_tpu_engine_drain_gap_seconds_total counter"
            in text)
    assert 'quorum_tpu_engine_decode_loop{backend="LLM1"} 2' in text

    # disaggregated-serving families (ISSUE 8, docs/tpu_backends.md): the
    # KV-handoff histogram exposes its full triplet even on a colocated
    # engine (no handoff traffic), the byte counter carries a counter
    # TYPE, the per-group occupancy gauges are registered, and the
    # per-engine split (handoff totals + group sizes/occupancy) rides the
    # engine block with the right kinds
    fam = "quorum_tpu_kv_handoff_seconds"
    assert f"# TYPE {fam} histogram" in text
    # route= label (ISSUE 14): a process whose engines moved KV exposes
    # per-route series (direct/reshard/host-bounce/resident); a cold
    # family exposes the bare triplet — either way one +Inf bucket per
    # series, under the one TYPE line the validator already enforced
    import re

    assert re.search(
        fam + r'_bucket\{(?:route="[a-z-]+",)?le="\+Inf"\}', text)
    assert f"{fam}_sum" in text and f"{fam}_count" in text
    assert "# TYPE quorum_tpu_kv_handoff_bytes_total counter" in text
    assert "# TYPE quorum_tpu_prefill_group_active gauge" in text
    assert "# TYPE quorum_tpu_decode_group_active gauge" in text
    assert "# TYPE quorum_tpu_engine_disagg gauge" in text
    assert "# TYPE quorum_tpu_engine_prefill_group_devices gauge" in text
    assert "# TYPE quorum_tpu_engine_decode_group_devices gauge" in text
    assert "# TYPE quorum_tpu_engine_prefill_group_active gauge" in text
    assert "# TYPE quorum_tpu_engine_decode_group_active gauge" in text
    assert "# TYPE quorum_tpu_engine_kv_handoffs_total counter" in text
    assert "# TYPE quorum_tpu_engine_kv_handoff_bytes_total counter" in text
    assert ("# TYPE quorum_tpu_engine_kv_handoff_seconds_total counter"
            in text)
    # colocated engine: the knob gauge reads 0 (the disagg leg's nonzero
    # bytes are pinned by tests/test_disagg.py against a live handoff)
    assert 'quorum_tpu_engine_disagg{backend="LLM1"} 0' in text

    # zero-drain continuous batching (ISSUE 11, docs/tpu_backends.md):
    # the injection-overlap counter and the admission-stall counter expose
    # even at zero (this app serves a drain-based engine — overlap is
    # structurally 0 there and the stall only accumulates when a burst
    # actually clamps the ring), and the engine block carries the
    # per-engine split plus the knob gauge
    assert "# TYPE quorum_tpu_admission_overlap_total counter" in text
    assert ("# TYPE quorum_tpu_admission_stall_seconds_total counter"
            in text)
    assert "# TYPE quorum_tpu_engine_zero_drain gauge" in text
    assert ("# TYPE quorum_tpu_engine_admission_overlap_total counter"
            in text)
    assert ("# TYPE quorum_tpu_engine_admission_stall_seconds_total "
            "counter" in text)
    assert 'quorum_tpu_engine_zero_drain{backend="LLM1"} 0' in text
    assert 'quorum_tpu_engine_admission_overlap_total{backend="LLM1"} 0' \
        in text

    # telemetry families (ISSUE 12, docs/observability.md): the
    # per-program-family device-time histogram carries real samples after
    # any traffic (every dispatch attributes), labeled by family; the SLO
    # counters expose (the chat requests above were classified and scored
    # at teardown); the flight-recorder depth gauge and drop counter
    # expose; and the profiler-skip counter exposes even at zero
    fam = "quorum_tpu_dispatch_device_seconds"
    assert f"# TYPE {fam} histogram" in text
    assert f'{fam}_bucket{{family="' in text
    assert f"{fam}_sum" in text and f"{fam}_count" in text
    for counter in ("quorum_tpu_slo_good_total",
                    "quorum_tpu_slo_breached_total"):
        assert f"# TYPE {counter} counter" in text, counter
    # the served requests above carried a class and scored the deadline
    # stage (status 200 => good)
    assert 'quorum_tpu_slo_good_total{class="' in text
    assert "# TYPE quorum_tpu_flight_recorder_events gauge" in text
    assert ("# TYPE quorum_tpu_flight_recorder_dropped_total counter"
            in text)
    assert "# TYPE quorum_tpu_profile_skipped_total counter" in text
    assert "quorum_tpu_profile_skipped_total " in text

    # robustness families (docs/robustness.md): deadline sheds by stage,
    # HTTP retry attempts, and the per-engine rebuild/breaker block
    assert "# TYPE quorum_tpu_deadline_exceeded_total counter" in text
    assert "# TYPE quorum_tpu_backend_retries_total counter" in text
    assert "# TYPE quorum_tpu_engine_rebuilds_total counter" in text
    assert ("# TYPE quorum_tpu_engine_deadline_exceeded_total counter"
            in text)
    assert "# TYPE quorum_tpu_engine_breaker_state gauge" in text

    # router-tier families (ISSUE 13, quorum_tpu/router/ — registered
    # process-wide so `make metrics-check` covers them; on a serving
    # replica they expose at zero, on the router process they carry the
    # placement/failover/migration accounting)
    for counter in ("quorum_tpu_router_requests_total",
                    "quorum_tpu_router_affinity_hits_total",
                    "quorum_tpu_router_affinity_misses_total",
                    "quorum_tpu_router_failovers_total",
                    "quorum_tpu_router_migrated_bytes_total",
                    "quorum_tpu_router_migrated_chains_total",
                    "quorum_tpu_router_burn_demotions_total",
                    "quorum_tpu_router_stream_resumes_total",
                    "quorum_tpu_trace_propagated_total"):
        assert f"# TYPE {counter} counter" in text, counter

    # native quorum serving families (docs/quorum.md): shared-prefix
    # dedup savings, member-kill degradation + request outcomes, and the
    # aggregation hop's fallback visibility — process-wide counters, so
    # they expose (at zero here) on every tier
    for counter in ("quorum_tpu_quorum_dedup_tokens_total",
                    "quorum_tpu_quorum_degraded_total",
                    "quorum_tpu_quorum_requests_total",
                    "quorum_tpu_aggregate_degraded_total"):
        assert f"# TYPE {counter} counter" in text, counter

    # fleet-plane families (ISSUE 16): burn gauge absorbed from replica
    # telemetry and the telemetry-poll latency histogram
    assert "# TYPE quorum_tpu_router_replica_burn gauge" in text
    assert ("# TYPE quorum_tpu_telemetry_poll_seconds histogram"
            in text)

    # _count == +Inf bucket and bucket monotonicity for one family, by hand
    # (belt to the validator's braces)
    inf = count = None
    prev = -1
    for ln in text.splitlines():
        if ln.startswith("quorum_tpu_queue_wait_seconds_bucket"):
            v = int(float(ln.rsplit(" ", 1)[1]))
            assert v >= prev
            prev = v
            if 'le="+Inf"' in ln:
                inf = v
        elif ln.startswith("quorum_tpu_queue_wait_seconds_count"):
            count = int(float(ln.rsplit(" ", 1)[1]))
    assert inf is not None and count is not None and inf == count
    assert count >= 1  # the requests above really were observed
