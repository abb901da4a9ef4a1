"""The OpenAI request-knob contract on tpu:// backends (docs/api.md table;
VERDICT r2 missing item 1 — the round-2 backend silently ignored these).

Every knob has an accept test (it changes/structures the output as
documented) and a reject test (out-of-range or unsupported values are a 400,
not a silent ignore or a 500).
"""

import asyncio

import numpy as np
import pytest

from tests.conftest import StubRequest
from quorum_tpu.backends.base import BackendError
from quorum_tpu.backends.tpu_backend import TpuBackend
from quorum_tpu.config import BackendSpec

BASE = {"model": "m", "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 5}


@pytest.fixture(scope="module")
def backend():
    return TpuBackend.from_spec(BackendSpec(
        name="knobs", url="tpu://llama-tiny?seed=1", model="m"))


def run(coro):
    return asyncio.run(coro)


# ---- n ---------------------------------------------------------------------

def test_n_returns_distinct_choices(backend):
    body = {**BASE, "n": 3, "temperature": 0.9, "seed": 4}
    res = run(backend.complete(body, {}, 60))
    choices = res.body["choices"]
    assert [c["index"] for c in choices] == [0, 1, 2]
    texts = {c["message"]["content"] for c in choices}
    assert len(texts) >= 2  # distinct sampling streams per choice
    assert res.body["usage"]["completion_tokens"] == 15  # summed across choices


def test_n_streaming_tags_choice_indices(backend):
    async def go():
        idxs, finishes = set(), []
        async for ch in backend.stream({**BASE, "n": 2, "stream": True}, {}, 60):
            for c in ch.get("choices") or []:
                idxs.add(c["index"])
                if c.get("finish_reason"):
                    finishes.append(c["index"])
        return idxs, finishes

    idxs, finishes = run(go())
    assert idxs == {0, 1}
    assert sorted(finishes) == [0, 1]  # one finish chunk per choice


@pytest.mark.parametrize("bad", [0, 9, -1, "3", 2.5, True])
def test_n_rejects_bad_values(backend, bad):
    with pytest.raises(BackendError) as e:
        run(backend.complete({**BASE, "n": bad}, {}, 60))
    assert e.value.status_code == 400


# ---- logprobs --------------------------------------------------------------

def test_logprobs_structure_and_consistency(backend):
    body = {**BASE, "logprobs": True, "top_logprobs": 2, "temperature": 0.0}
    res = run(backend.complete(body, {}, 60))
    choice = res.body["choices"][0]
    content = choice["logprobs"]["content"]
    assert len(content) == 5  # one entry per generated token
    for entry in content:
        assert set(entry) == {"token", "logprob", "bytes", "top_logprobs"}
        assert entry["logprob"] <= 0.0
        assert len(entry["top_logprobs"]) == 2
        assert isinstance(entry["bytes"], list)
    # greedy sampling: the sampled token IS the top-1 alternative
    e0 = content[0]
    assert e0["token"] == e0["top_logprobs"][0]["token"]
    assert e0["logprob"] == pytest.approx(e0["top_logprobs"][0]["logprob"])


def test_logprobs_absent_by_default(backend):
    res = run(backend.complete(dict(BASE), {}, 60))
    assert "logprobs" not in res.body["choices"][0]


@pytest.mark.parametrize("bad", [
    {"logprobs": "yes"},
    {"logprobs": True, "top_logprobs": 21},
    {"logprobs": True, "top_logprobs": -1},
    {"top_logprobs": 5},  # requires logprobs: true
])
def test_logprobs_rejects_bad_values(backend, bad):
    with pytest.raises(BackendError) as e:
        run(backend.complete({**BASE, **bad}, {}, 60))
    assert e.value.status_code == 400


def test_logprobs_align_with_content_under_stop(backend):
    """logprobs.content must track EMITTED content: tokens swallowed by the
    stop matcher (the stop string itself) get no entries (OpenAI 1:1
    content/logprobs alignment)."""
    # Find what the model greedily emits, pick its 3rd token's text as stop.
    probe = run(backend.complete(
        {**BASE, "max_tokens": 8, "temperature": 0.0, "logprobs": True}, {}, 60))
    entries = probe.body["choices"][0]["logprobs"]["content"]
    assert len(entries) == 8
    stop_tok = entries[3]["token"]
    if not stop_tok:
        pytest.skip("3rd token has empty text (detokenizer buffering)")

    res = run(backend.complete(
        {**BASE, "max_tokens": 8, "temperature": 0.0, "logprobs": True,
         "stop": [stop_tok]}, {}, 60))
    choice = res.body["choices"][0]
    content = choice["message"]["content"]
    lp = choice["logprobs"]["content"]
    assert stop_tok not in content  # stop string excluded from content
    # entries correspond to the emitted prefix only — joining their token
    # texts reproduces the content exactly
    assert "".join(e["token"] for e in lp) == content


def test_streaming_logprobs_align_with_streamed_content(backend):
    """Streamed logprob entries ride inside content chunks and, joined,
    reproduce exactly the streamed content (stop-swallowed text drops its
    entries)."""
    probe = run(backend.complete(
        {**BASE, "max_tokens": 8, "temperature": 0.0, "logprobs": True}, {}, 60))
    stop_tok = probe.body["choices"][0]["logprobs"]["content"][3]["token"]
    if not stop_tok:
        pytest.skip("3rd token has empty text")

    async def go():
        text, toks = [], []
        async for ch in backend.stream(
            {**BASE, "max_tokens": 8, "temperature": 0.0, "logprobs": True,
             "stop": [stop_tok], "stream": True}, {}, 60):
            for c in ch.get("choices") or []:
                delta = c.get("delta") or {}
                if delta.get("content"):
                    text.append(delta["content"])
                for e in ((c.get("logprobs") or {}).get("content") or []):
                    toks.append(e["token"])
        return "".join(text), "".join(toks)

    streamed, lp_joined = run(go())
    assert stop_tok not in streamed
    assert lp_joined == streamed


# ---- penalties -------------------------------------------------------------

def test_frequency_penalty_discourages_repeats(backend):
    base = {**BASE, "max_tokens": 12, "temperature": 0.0, "seed": 0}
    plain = run(backend.complete(base, {}, 60))
    pen = run(backend.complete({**base, "frequency_penalty": 2.0}, {}, 60))
    t_plain = plain.body["choices"][0]["message"]["content"]
    t_pen = pen.body["choices"][0]["message"]["content"]
    assert t_plain != t_pen  # the knob visibly acts on the distribution


@pytest.mark.parametrize("knob", ["presence_penalty", "frequency_penalty"])
@pytest.mark.parametrize("bad", [2.5, -2.5, "x"])
def test_penalties_reject_out_of_range(backend, knob, bad):
    with pytest.raises(BackendError) as e:
        run(backend.complete({**BASE, knob: bad}, {}, 60))
    assert e.value.status_code == 400


# ---- logit_bias ------------------------------------------------------------

def test_logit_bias_forces_token(backend):
    # +100 bias on one token makes greedy sampling emit it every step
    body = {**BASE, "max_tokens": 3, "temperature": 0.0,
            "logit_bias": {"42": 100}}
    res = run(backend.complete(body, {}, 60))
    text = res.body["choices"][0]["message"]["content"]
    assert text == backend.tokenizer.decode([42, 42, 42])


@pytest.mark.parametrize("bad", [
    {"999999": 1},        # out-of-vocab id
    {"5": 500},           # bias outside [-100, 100]
    {"x": 1},             # non-integer id
    "notadict",
])
def test_logit_bias_rejects_bad_values(backend, bad):
    with pytest.raises(BackendError) as e:
        run(backend.complete({**BASE, "logit_bias": bad}, {}, 60))
    assert e.value.status_code == 400


# ---- unsupported fields → documented 400 -----------------------------------

@pytest.mark.parametrize("field,value", [
    ("tools", [{"type": "function", "function": {"name": "f"}}]),
    ("tool_choice", "auto"),
    ("functions", [{"name": "f"}]),
    ("function_call", "auto"),
    # response_format types are now IMPLEMENTED (docs/structured_output.md,
    # tests/test_constrained_decoding.py); malformed shapes and schemas
    # outside the supported subset stay 400s:
    ("response_format", {"type": "json_schema", "json_schema": {}}),
    ("response_format", {"type": "json_schema",
                         "json_schema": {"schema": {"$ref": "#/x"}}}),
    ("response_format", {"type": "regex", "pattern": "("}),
    ("response_format", {"type": "xml"}),
])
def test_unsupported_fields_rejected(backend, field, value):
    with pytest.raises(BackendError) as e:
        run(backend.complete({**BASE, field: value}, {}, 60))
    assert e.value.status_code == 400
    assert e.value.body["error"]["type"] == "invalid_request_error"


def test_response_format_regex_constrains_output(backend):
    """Structured output's fast-tier smoke: a regex response_format is
    enforced on device (the full json_schema/pipeline matrix lives in
    tests/test_constrained_decoding.py)."""
    res = run(backend.complete(
        {**BASE, "max_tokens": 8, "temperature": 0.9, "seed": 2,
         "response_format": {"type": "regex", "pattern": "yes|no|maybe"}},
        {}, 60))
    choice = res.body["choices"][0]
    assert choice["message"]["content"] in ("yes", "no", "maybe")
    assert choice["finish_reason"] == "stop"


def test_response_format_text_accepted(backend):
    res = run(backend.complete(
        {**BASE, "response_format": {"type": "text"}}, {}, 60))
    assert res.status_code == 200


@pytest.mark.parametrize("field", ["user", "store", "metadata", "service_tier"])
def test_metadata_fields_accepted_and_ignored(backend, field):
    res = run(backend.complete({**BASE, field: "anything"}, {}, 60))
    assert res.status_code == 200


# ---- n>1 isolation: one choice finishing must not truncate siblings --------

class _MultiScriptEngine:
    """Stub engine where each submitted choice gets its own token script,
    replayed with the real engine's contract: stream_results sets the
    request's cancel event in its finally (slot release)."""

    def __init__(self, scripts):
        from quorum_tpu.models.model_config import MODEL_PRESETS

        self.spec = MODEL_PRESETS["llama-tiny"]
        self.scripts = list(scripts)
        self._i = 0

    def submit(self, prompt_ids, *, cancel=None, **kw):
        script = self.scripts[self._i]
        self._i += 1
        return StubRequest(script, cancel)

    def stream_results(self, req):
        import time

        script, cancel = req.script, req.cancel
        try:
            for t in script:
                if cancel is not None and cancel.is_set():
                    return
                time.sleep(0.005)
                yield t
        finally:
            if cancel is not None:
                cancel.set()


def test_one_choice_finishing_does_not_truncate_siblings():
    """Choice 0 hits EOS after 1 token; choice 1 must still produce its full
    8 tokens (per-choice cancel events — a shared event let the first
    finisher's slot release abort every sibling)."""
    eng = None

    def build():
        nonlocal eng
        b = TpuBackend.from_spec(BackendSpec(
            name="iso", url="tpu://llama-tiny?seed=3", model="m"))
        eos = b.tokenizer.eos_id
        eng = _MultiScriptEngine([[7, eos], [11] * 8])
        b.engine = eng
        return b

    b = build()
    res = run(b.complete({**BASE, "n": 2, "max_tokens": 8}, {}, 60))
    choices = res.body["choices"]
    assert choices[0]["finish_reason"] == "stop"
    assert choices[1]["finish_reason"] == "length"
    assert choices[1]["message"]["content"] == b.tokenizer.decode([11] * 8)


# ---- drain park: non-streaming must shed, never return truncated text ------

class _ParkingEngine:
    """Stub engine honoring the drain-park contract: a few tokens, then
    ``req.parked = True`` set BEFORE the stream ends (engine
    _sweep_drain_parks semantics)."""

    def __init__(self, tokens):
        from quorum_tpu.models.model_config import MODEL_PRESETS

        self.spec = MODEL_PRESETS["llama-tiny"]
        self.tokens = list(tokens)

    def submit(self, prompt_ids, *, cancel=None, **kw):
        return StubRequest(cancel=cancel)

    def stream_results(self, req):
        yield from self.tokens
        req.parked = True


def test_drain_park_non_streaming_is_retryable_503():
    """A drain-parked request on the NON-streaming path has no resume
    journal: the partial text must become a retryable 503 overload (the
    router re-places the whole request on a sibling), never a truncated
    200 with finish_reason "length"."""
    b = TpuBackend.from_spec(BackendSpec(
        name="park", url="tpu://llama-tiny?seed=5", model="m"))
    b.engine = _ParkingEngine([7, 8, 9])
    with pytest.raises(BackendError) as ei:
        run(b.complete({**BASE, "max_tokens": 8}, {}, 60))
    assert ei.value.status_code == 503
    assert ei.value.body["error"]["type"] == "overloaded_error"
    assert "draining" in str(ei.value)
    assert "Retry-After" in ei.value.headers


# ---- proxy-level validation & status relay (app layer) ---------------------

async def _app_post(config, body, **fakes):
    from tests.conftest import make_client

    async with make_client(config, **fakes) as client:
        return await client.post(
            "/v1/chat/completions", json=body,
            headers={"Authorization": "Bearer x"})


def _two_fake_config():
    return {
        "settings": {"timeout": 30},
        "primary_backends": [
            {"name": "A", "url": "http://a.test", "model": "m"},
            {"name": "B", "url": "http://b.test", "model": "m"},
        ],
        "iterations": {"aggregation": {"strategy": "concatenate"}},
        "strategy": {"concatenate": {"separator": "+"},
                     "aggregate": {"source_backends": "all",
                                   "aggregator_backend": ""}},
    }


@pytest.mark.parametrize("bad", [
    {"n": 0}, {"n": "x"}, {"logprobs": "yes"}, {"top_logprobs": 21},
    {"presence_penalty": 5}, {"frequency_penalty": -3},
    {"logit_bias": {"x": 1}}, {"logit_bias": {"5": 500}},
])
async def test_malformed_knobs_rejected_before_fanout(bad):
    """docs/api.md: malformed knob values are ONE 400 before fan-out — no
    backend sees the request (not N failures, not a 200 from a permissive
    backend)."""
    from quorum_tpu.backends.fake import FakeBackend

    fakes = dict(A=FakeBackend("A", text="a"), B=FakeBackend("B", text="b"))
    resp = await _app_post(
        _two_fake_config(),
        {"model": "m", "messages": [{"role": "user", "content": "q"}], **bad},
        **fakes)
    assert resp.status_code == 400, resp.text
    assert resp.json()["error"]["type"] == "invalid_request_error"
    assert fakes["A"].calls == [] and fakes["B"].calls == []


async def test_backend_503_relayed_not_collapsed():
    """A tpu:// backend's 503 overloaded_error must reach the client as a
    503, not be collapsed into the all-failed 500 proxy_error
    (docs/api.md error table)."""
    from quorum_tpu.backends.fake import FakeBackend
    from quorum_tpu import oai

    overloaded = BackendError(
        "queue full", status_code=503,
        body=oai.error_body("queue full", type_="overloaded_error", code=503))
    config = {
        "settings": {"timeout": 30},
        "primary_backends": [{"name": "A", "url": "http://a.test", "model": "m"}],
    }
    resp = await _app_post(
        config,
        {"model": "m", "messages": [{"role": "user", "content": "q"}]},
        A=FakeBackend("A", fail_with=overloaded))
    assert resp.status_code == 503
    assert resp.json()["error"]["type"] == "overloaded_error"
