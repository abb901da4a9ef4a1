"""Request-scoped tracing: /debug/traces span structure for streamed
multi-backend requests, wire-level TTFT / per-token timings, and the
trace-store ring-buffer bound (ISSUE 1 tentpole)."""

import pytest

from tests.conftest import make_client


def _two_tpu_config():
    return {
        "settings": {"timeout": 60},
        "primary_backends": [
            {"name": "LLM1", "url": "tpu://llama-tiny?seed=1&slots=2",
             "model": "t"},
            {"name": "LLM2", "url": "tpu://llama-tiny?seed=2&slots=2",
             "model": "t"},
        ],
        "iterations": {"aggregation": {"strategy": "concatenate"}},
        "strategy": {
            "concatenate": {"separator": "\n---\n"},
            "aggregate": {"source_backends": "all",
                          "aggregator_backend": ""},
        },
    }


async def test_streamed_multibackend_trace_spans():
    """A completed streaming parallel request exposes ordered spans —
    queue-wait, prefill, decode, aggregate, sse-flush — with TTFT and
    per-token wire timings populated (the ISSUE 1 acceptance shape)."""
    async with make_client(_two_tpu_config()) as client:
        resp = await client.post(
            "/v1/chat/completions",
            json={"model": "t", "stream": True, "max_tokens": 6,
                  "messages": [{"role": "user", "content": "hi"}]},
            headers={"Authorization": "Bearer x"},
        )
        assert resp.status_code == 200
        rid = resp.headers["x-request-id"]
        assert "data: [DONE]" in resp.text

        got = await client.get(f"/debug/traces/{rid}")
        assert got.status_code == 200
        trace = got.json()
    assert trace["request_id"] == rid
    assert trace["in_flight"] is False
    assert trace["status"] == 200
    assert trace["duration_ms"] > 0

    names = [s["name"] for s in trace["spans"]]
    for required in ("queue-wait", "prefill", "decode", "aggregate",
                     "sse-flush"):
        assert required in names, f"missing span {required} in {names}"
    # Both backends' engine paths were traced (fan-out = 2 submissions).
    assert names.count("queue-wait") == 2
    assert names.count("prefill") == 2
    assert names.count("fanout-stream") == 2

    # Ordered by start time, every span closed, durations consistent.
    starts = [s["start_s"] for s in trace["spans"]]
    assert starts == sorted(starts)
    for s in trace["spans"]:
        assert s["end_s"] is not None and s["end_s"] >= s["start_s"]

    # Span tags: the fan-out hops carry backend names; decode spans carry
    # step counts and batch occupancy (the step-loop visibility this PR adds).
    fanout_backends = {s["meta"]["backend"] for s in trace["spans"]
                      if s["name"] == "fanout-stream"}
    assert fanout_backends == {"LLM1", "LLM2"}
    decode = next(s for s in trace["spans"] if s["name"] == "decode")
    assert decode["meta"]["steps"] >= 1
    assert decode["meta"]["occupancy"] >= 1

    # Wire-level timings: TTFT set, one entry per content flush, monotone.
    assert trace["ttft_ms"] is not None and trace["ttft_ms"] > 0
    assert trace["tokens"] >= 1
    times = trace["token_times_ms"]
    assert len(times) == trace["tokens"]
    assert times == sorted(times)
    assert times[0] == trace["ttft_ms"]


async def test_trace_listing_and_miss():
    async with make_client(_two_tpu_config()) as client:
        resp = await client.post(
            "/chat/completions",
            json={"model": "t", "max_tokens": 4,
                  "messages": [{"role": "user", "content": "yo"}]},
            headers={"Authorization": "Bearer x"},
        )
        assert resp.status_code == 200
        rid = resp.headers["x-request-id"]

        listing = (await client.get("/debug/traces")).json()
        assert listing["in_flight"] == 0
        assert listing["completed"] >= 1
        rows = {t["request_id"]: t for t in listing["traces"]}
        assert rid in rows
        # summaries stay light: spans/token arrays only on the detail view
        assert "spans" not in rows[rid]
        assert rows[rid]["status"] == 200

        # non-streaming parallel requests trace the fanout + aggregate hops
        detail = (await client.get(f"/v1/debug/traces/{rid}")).json()
        names = [s["name"] for s in detail["spans"]]
        assert "fanout" in names and "aggregate" in names
        assert "queue-wait" in names and "prefill" in names

        missing = await client.get("/debug/traces/req-does-not-exist")
        assert missing.status_code == 404
        assert missing.json()["error"]["type"] == "invalid_request_error"


def test_trace_store_ring_bound():
    from quorum_tpu.observability import RequestTrace, TraceStore

    store = TraceStore(capacity=4)
    for i in range(10):
        t = RequestTrace(f"req-{i}")
        store.start(t)
        t.finish(status=200)
        store.complete(t)
    snap = store.snapshot()
    assert snap["completed"] == 4
    assert [t["request_id"] for t in snap["traces"]] == [
        "req-9", "req-8", "req-7", "req-6"]  # newest first
    assert store.get("req-0") is None  # aged out
    assert store.get("req-9") is not None


def test_trace_span_cap():
    from quorum_tpu.observability import MAX_SPANS, RequestTrace

    t = RequestTrace("req-cap")
    for i in range(MAX_SPANS + 25):
        t.add_span("decode", 0.0, 0.001)
    t.finish(status=200)
    d = t.to_dict()
    assert len(d["spans"]) == MAX_SPANS
    assert d["dropped_spans"] == 25


def test_token_times_cap_keeps_counting():
    """Past MAX_TOKEN_TIMES the stored wire timings stop growing but the
    token count keeps counting every content flush (and inter-token gaps
    keep measuring one flush, not the distance back to the cap entry)."""
    from quorum_tpu.observability import MAX_TOKEN_TIMES, RequestTrace

    t = RequestTrace("req-flood")
    for _ in range(MAX_TOKEN_TIMES + 10):
        t.mark_flush(True)
    t.finish(status=200)
    d = t.to_dict()
    assert len(d["token_times_ms"]) == MAX_TOKEN_TIMES
    assert d["tokens"] == MAX_TOKEN_TIMES + 10


async def test_param_route_method_mismatch_is_405():
    """POST to a /{param} route must 405 like any other known path, not
    404 (the exact-route table's behavior)."""
    async with make_client(_two_tpu_config()) as client:
        resp = await client.post("/debug/traces/req-whatever", json={})
        assert resp.status_code == 405


def test_long_generation_coalesces_decode_spans():
    """A multi-thousand-token generation must not flood the span budget
    with per-chunk decode entries: past the engine's TURN_SPAN_CAP the
    last decode span extends instead (summing steps, counting turns), so
    end-of-stream spans (aggregate, sse-flush) always have room."""
    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.models.model_config import resolve_spec
    from quorum_tpu.observability import RequestTrace, use_trace

    eng = InferenceEngine(resolve_spec("llama-tiny", {"max_seq": "1024"}),
                          decode_chunk=2, n_slots=1)
    trace = RequestTrace("req-long")
    with use_trace(trace):
        req = eng.submit([5, 6, 7], max_new_tokens=200)
    tokens = list(eng.stream_results(req))
    assert len(tokens) == 200
    decode_spans = [s for s in trace.spans if s.name == "decode"]
    assert 1 <= len(decode_spans) <= eng.TURN_SPAN_CAP
    # every chunk's steps are accounted for, appended or coalesced
    total_steps = sum(s.meta.get("steps", 0) for s in decode_spans)
    assert total_steps >= 200 - 1  # first token comes from the admit
    if len(decode_spans) == eng.TURN_SPAN_CAP:
        assert decode_spans[-1].meta.get("coalesced_turns", 0) >= 1
    eng.shutdown()


@pytest.mark.parametrize("path", ["/debug/traces", "/v1/debug/traces"])
async def test_debug_traces_served_on_both_prefixes(path):
    async with make_client(_two_tpu_config()) as client:
        resp = await client.get(path)
        assert resp.status_code == 200
        assert set(resp.json()) == {"capacity", "in_flight", "completed",
                                    "traces"}
