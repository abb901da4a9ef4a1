"""TpuBackend tests: Backend-protocol conformance and end-to-end serving
through the ASGI app with a real (tiny) in-process model."""

import asyncio
import json

import pytest

from tests.conftest import StubRequest, make_client

from quorum_tpu.backends.tpu_backend import TpuBackend, _StopMatcher
from quorum_tpu.config import BackendSpec

# Engine-scale / compile-heavy / multi-process: slow tier (make test skips,
# make test-all and CI run everything — VERDICT r3 item 6).
pytestmark = pytest.mark.slow


def tiny_backend(name="TPU1", seed=0, model=""):
    return TpuBackend.from_spec(
        BackendSpec(
            name=name,
            url=f"tpu://llama-tiny?seed={seed}&max_tokens=8&decode_chunk=4",
            model=model,
        )
    )


# ---- stop matcher ---------------------------------------------------------

def test_stop_matcher_boundary_split():
    m = _StopMatcher(["END"])
    assert m.feed("abcE") == "abc"     # "E" withheld (possible stop prefix)
    assert m.feed("ND junk") == ""     # stop completes → everything after dropped
    assert m.hit


def test_stop_matcher_false_alarm():
    m = _StopMatcher(["END"])
    assert m.feed("abcE") == "abc"
    assert m.feed("xyz") == "Exyz"     # withheld prefix released
    assert m.flush() == ""


def test_stop_matcher_no_stops_passthrough():
    m = _StopMatcher([])
    assert m.feed("anything") == "anything"


def test_stop_matcher_earliest_occurrence_wins():
    m = _StopMatcher(["world", "hello"])
    assert m.feed("say hello world") == "say "
    assert m.hit


# ---- protocol conformance -------------------------------------------------

async def test_complete_returns_tagged_openai_body():
    b = tiny_backend()
    res = await b.complete({"messages": [{"role": "user", "content": "hi"}]}, {}, 30.0)
    assert res.ok
    assert res.body["backend"] == "TPU1"
    assert res.body["object"] == "chat.completion"
    assert res.body["model"] == "llama-tiny"
    u = res.body["usage"]
    assert u["prompt_tokens"] > 0
    assert u["completion_tokens"] > 0
    assert u["total_tokens"] == u["prompt_tokens"] + u["completion_tokens"]


async def test_complete_model_override_precedence():
    b = tiny_backend(model="my-override")
    res = await b.complete(
        {"model": "req-model", "messages": [{"role": "user", "content": "x"}]}, {}, 30.0
    )
    assert res.body["model"] == "my-override"


async def test_max_tokens_respected():
    b = tiny_backend()
    res = await b.complete(
        {"messages": [{"role": "user", "content": "x"}], "max_tokens": 3}, {}, 30.0
    )
    assert res.body["usage"]["completion_tokens"] <= 3


async def test_deterministic_at_temperature_zero():
    b = tiny_backend()
    body = {"messages": [{"role": "user", "content": "x"}], "temperature": 0}
    r1 = await b.complete(body, {}, 30.0)
    r2 = await b.complete(body, {}, 30.0)
    assert r1.content == r2.content


async def test_stream_chunks_concatenate_to_complete():
    b = tiny_backend()
    body = {"messages": [{"role": "user", "content": "x"}], "temperature": 0}
    full = (await b.complete(body, {}, 30.0)).content
    pieces, finish = [], None
    async for ch in b.stream(dict(body), {}, 30.0):
        d = ch["choices"][0]["delta"]
        if "content" in d and d["content"]:
            pieces.append(d["content"])
        if ch["choices"][0]["finish_reason"]:
            finish = ch["choices"][0]["finish_reason"]
    assert "".join(pieces) == full
    assert finish in ("stop", "length")


async def test_stream_first_chunk_is_role():
    b = tiny_backend()
    chunks = [c async for c in b.stream({"messages": [{"role": "user", "content": "x"}]}, {}, 30.0)]
    assert chunks[0]["choices"][0]["delta"] == {"role": "assistant"}


async def test_stop_sequence_truncates_completion():
    b = tiny_backend()
    body = {"messages": [{"role": "user", "content": "x"}], "temperature": 0}
    full = (await b.complete(body, {}, 30.0)).content
    if len(full) < 2:
        pytest.skip("tiny model generated too little text to split a stop from")
    stop = full[1:3]
    res = await b.complete({**body, "stop": stop}, {}, 30.0)
    assert res.content == full[: full.index(stop)]
    assert res.body["choices"][0]["finish_reason"] == "stop"


def test_sampler_quantization_bounds_programs():
    from quorum_tpu.backends.tpu_backend import _request_sampler

    a = _request_sampler({"temperature": 0.70123})
    b = _request_sampler({"temperature": 0.70456})
    assert a == b  # quantized to the same compiled program


async def test_stream_timeout_aborts_quickly():
    import time

    b = tiny_backend()
    body = {"messages": [{"role": "user", "content": "x"}], "max_tokens": 64}
    t0 = time.monotonic()
    from quorum_tpu.backends.base import BackendError

    with pytest.raises(BackendError):
        async for _ in b.stream(body, {}, 0.000001):
            await asyncio.sleep(0)  # consume until the timeout fires
    # generation (64 tokens) must NOT run to completion after the timeout:
    # the cancel event aborts within one decode chunk.
    assert time.monotonic() - t0 < 20


async def test_engines_shared_across_backends():
    a = tiny_backend("A")
    b = tiny_backend("B")
    c = tiny_backend("C", seed=7)
    assert a.engine is b.engine           # same spec+seed → shared weights
    assert a.engine is not c.engine       # different seed → distinct member


# ---- end-to-end through the server ---------------------------------------

def tpu_parallel_config():
    return {
        "settings": {"timeout": 60},
        "primary_backends": [
            {"name": "M0", "url": "tpu://llama-tiny?seed=0&max_tokens=6", "model": ""},
            {"name": "M1", "url": "tpu://llama-tiny?seed=1&max_tokens=6", "model": ""},
        ],
        "iterations": {"aggregation": {"strategy": "concatenate"}},
        "strategy": {
            "concatenate": {"separator": "\n---\n", "thinking_tags": ["think"]},
            "aggregate": {"source_backends": "all", "aggregator_backend": ""},
        },
    }


async def test_e2e_non_streaming_parallel_tpu():
    async with make_client(tpu_parallel_config()) as client:
        r = await client.post(
            "/chat/completions",
            json={"messages": [{"role": "user", "content": "hi"}], "temperature": 0},
            headers={"Authorization": "Bearer k"},
        )
    assert r.status_code == 200
    body = r.json()
    content = body["choices"][0]["message"]["content"]
    assert "\n---\n" in content   # two members concatenated
    assert body["usage"]["total_tokens"] > 0


async def test_e2e_streaming_parallel_tpu():
    async with make_client(tpu_parallel_config()) as client:
        async with client.stream(
            "POST",
            "/chat/completions",
            json={
                "messages": [{"role": "user", "content": "hi"}],
                "stream": True,
                "temperature": 0,
            },
            headers={"Authorization": "Bearer k"},
        ) as r:
            assert r.status_code == 200
            events = []
            async for line in r.aiter_lines():
                if line.startswith("data: "):
                    events.append(line[6:])
    assert events[-1] == "[DONE]"
    parsed = [json.loads(e) for e in events[:-1]]
    ids = {p["id"] for p in parsed}
    assert any(i.startswith("chatcmpl-parallel-") for i in ids)
    final = [p for p in parsed if p["id"] == "chatcmpl-parallel-final"]
    assert final and final[0]["choices"][0]["finish_reason"] == "stop"


# ---- request validation / usage reporting ---------------------------------

async def test_bad_temperature_is_400_not_500():
    from quorum_tpu.backends.base import BackendError

    b = tiny_backend()
    with pytest.raises(BackendError) as ei:
        await b.complete(
            {"messages": [{"role": "user", "content": "hi"}], "temperature": "abc"},
            {}, 30.0,
        )
    assert ei.value.status_code == 400
    assert ei.value.body["error"]["type"] == "invalid_request_error"


async def test_stream_include_usage_appends_usage_chunk():
    b = tiny_backend()
    chunks = []
    async for c in b.stream(
        {
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 5,
            "stream_options": {"include_usage": True},
        },
        {}, 30.0,
    ):
        chunks.append(c)
    last = chunks[-1]
    assert last["choices"] == []
    assert last["usage"]["completion_tokens"] >= 1
    assert last["usage"]["total_tokens"] == (
        last["usage"]["prompt_tokens"] + last["usage"]["completion_tokens"]
    )
    # the finish_reason chunk still precedes it
    assert chunks[-2]["choices"][0]["finish_reason"] in ("stop", "length")


def test_first_user_message_skips_null_content():
    from quorum_tpu import oai

    body = {
        "messages": [
            {"role": "user", "content": None},
            {"role": "user", "content": "real question"},
        ]
    }
    assert oai.first_user_message(body) == "real question"


async def test_engines_shared_despite_decode_chunk_difference():
    """decode_chunk is a dispatch knob, not weight identity: two backends that
    differ only in decode_chunk share one engine (one copy of weights)."""
    a = TpuBackend.from_spec(
        BackendSpec(name="A", url="tpu://llama-tiny?seed=7&decode_chunk=2")
    )
    b = TpuBackend.from_spec(
        BackendSpec(name="B", url="tpu://llama-tiny?seed=7&decode_chunk=8")
    )
    assert a.engine is b.engine
    assert a.decode_chunk == 2 and b.decode_chunk == 8


# ---- ADVICE round-1 regressions ------------------------------------------

class _ScriptedEngine:
    """Stub engine: yields a fixed token script (ids into a 512-vocab byte
    tokenizer). Lets tests stage exact detokenizer/stop-matcher interactions
    that a real model can't produce deterministically."""

    def __init__(self, tokens, delay=0.0):
        from quorum_tpu.models.model_config import MODEL_PRESETS

        self.spec = MODEL_PRESETS["llama-tiny"]
        self._tokens = list(tokens)
        self._delay = delay

    def generate_stream(self, prompt_ids, *, cancel=None, **kw):
        import time as _time

        for t in self._tokens:
            if cancel is not None and cancel.is_set():
                return
            if self._delay:
                _time.sleep(self._delay)
            yield t

    # New engine API (submit-then-stream, so backends can 503 a full queue
    # before the first SSE byte): the stub has no queue, so submit just
    # captures the args and stream_results replays the script.
    def submit(self, prompt_ids, *, cancel=None, **kw):
        return StubRequest(prompt_ids, cancel)

    def stream_results(self, req):
        yield from self.generate_stream(req.script, cancel=req.cancel)


def _byte_token(b: int) -> int:
    return 3 + b  # ByteTokenizer: id = _OFFSET + byte


async def test_stop_hit_in_flushed_tail_sets_finish_reason_stop():
    """A stop string that only completes in the detokenizer's flush() tail
    (dangling partial UTF-8 -> replacement char) must still report
    finish_reason="stop" — in both complete() and stream()."""
    # "X" then the first byte of a 2-byte UTF-8 char: flush() emits "X" + U+FFFD
    tokens = [_byte_token(ord("X")), _byte_token(0xC3)]
    body = {
        "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 8,
        "stop": ["X�"],
    }

    b = TpuBackend("S", _ScriptedEngine(tokens), model="m")
    res = await b.complete(body, {}, 30.0)
    assert res.body["choices"][0]["finish_reason"] == "stop"
    assert res.body["choices"][0]["message"]["content"] == ""

    b2 = TpuBackend("S2", _ScriptedEngine(tokens), model="m")
    finish = None
    async for chunk in b2.stream(body, {}, 30.0):
        for choice in chunk.get("choices", []):
            if choice.get("finish_reason"):
                finish = choice["finish_reason"]
    assert finish == "stop"


async def test_stream_timeout_is_end_to_end_not_per_delta():
    """A generation that keeps emitting deltas must still be bounded by the
    configured timeout (complete() parity), not granted a fresh timeout per
    delta."""
    import time

    from quorum_tpu.backends.base import BackendError

    # 200 tokens, 20ms apart: per-delta waits always succeed, but the
    # end-to-end deadline (0.5s) must fire long before the ~4s total.
    tokens = [_byte_token(ord("a"))] * 200
    b = TpuBackend("T", _ScriptedEngine(tokens, delay=0.02), model="m")
    body = {"messages": [{"role": "user", "content": "x"}], "max_tokens": 200}
    t0 = time.monotonic()
    with pytest.raises(BackendError):
        async for _ in b.stream(body, {}, 0.5):
            pass
    assert time.monotonic() - t0 < 3.0


async def test_an_engine_with_more_rows_than_the_default_pool_drains_every_stream():
    """A stream holds a thread for its whole life. asyncio's default pool has
    min(32, cores + 4) threads: a backend of 32 rows, which that pool holds
    on no host, gets a pool of the engine's own, shared by the backends that
    share the engine; a smaller one keeps the default pool, whatever the
    host's cores. As many streams at once as there are rows all deliver."""
    from quorum_tpu.backends.tpu_backend import ASYNCIO_DEFAULT_POOL_MAX

    few = TpuBackend.from_spec(
        BackendSpec(name="F", url="tpu://llama-tiny?slots=2&seed=41", model="t"))
    assert few._stream_pool() is None
    rows = ASYNCIO_DEFAULT_POOL_MAX
    url = f"tpu://llama-tiny?slots={rows}&seed=42"
    a = TpuBackend.from_spec(BackendSpec(name="A", url=url, model="t"))
    b = TpuBackend.from_spec(BackendSpec(name="B", url=url, model="t"))
    assert a.engine is b.engine
    pool = a._stream_pool()
    assert pool is not None and pool is b._stream_pool()
    assert pool._max_workers == 2 * rows

    async def one(i: int) -> int:
        body = {"model": "t", "max_tokens": 6, "temperature": 0,
                "messages": [{"role": "user", "content": f"{i:03d} hello"}]}
        n = 0
        async for chunk in a.stream(body, {}, timeout=120):
            n += bool(chunk["choices"][0]["delta"].get("content"))
        return n

    assert all(await asyncio.gather(*(one(i) for i in range(rows))))
    a.engine.shutdown()
    assert a.engine.stream_pool is None
