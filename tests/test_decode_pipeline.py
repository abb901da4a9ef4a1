"""Depth-K decode-dispatch pipeline: K>1 must be token-for-token identical
to K=1 across every finish mode, with on-device finish accounting keeping
overrun at zero for EOS/budget finishes (ISSUE: deep decode-dispatch
pipeline).

The K=1 engine is the oracle: same programs, ring capped at one chunk (the
host blocks on every dispatch). Everything here runs the tiny preset on the
CPU backend — the same compiled code paths as TPU."""

import threading

import pytest

from quorum_tpu.engine.engine import InferenceEngine
from quorum_tpu.models.model_config import MODEL_PRESETS, resolve_spec
from quorum_tpu.ops.sampling import SamplerConfig


TINY = MODEL_PRESETS["llama-tiny"]
GREEDY = SamplerConfig(temperature=0.0)


def _pair(**kw):
    """(K=1 oracle, K=4 pipelined) engines over identical weights."""
    return (InferenceEngine(TINY, decode_chunk=4, decode_pipeline=1, **kw),
            InferenceEngine(TINY, decode_chunk=4, decode_pipeline=4, **kw))


def test_greedy_token_for_token():
    e1, e4 = _pair()
    a = e1.generate([5, 6, 7], max_new_tokens=32, sampler=GREEDY)
    b = e4.generate([5, 6, 7], max_new_tokens=32, sampler=GREEDY)
    assert a.token_ids == b.token_ids
    assert len(b.token_ids) == 32
    assert e4.n_overrun == 0  # budget finish is detected on device


@pytest.mark.slow
def test_sampled_token_for_token():
    e1, e4 = _pair()
    s = SamplerConfig(temperature=0.9, top_p=0.95)
    for seed in (7, 42):
        a = e1.generate([5, 6, 7], max_new_tokens=24, sampler=s, seed=seed)
        b = e4.generate([5, 6, 7], max_new_tokens=24, sampler=s, seed=seed)
        assert a.token_ids == b.token_ids, f"seed {seed} diverged"
    assert e4.n_overrun == 0


def test_eos_mid_chunk_token_for_token():
    """EOS landing mid-chunk with 3 further chunks in flight: the row stops
    on device — identical output, zero overrun, no K extra chunks of
    garbage."""
    e1, e4 = _pair()
    probe = e1.generate([9, 8], max_new_tokens=32, sampler=GREEDY)
    eos = probe.token_ids[9]  # stop at a position inside chunk 3
    a = e1.generate([9, 8], max_new_tokens=32, sampler=GREEDY, eos_id=eos)
    b = e4.generate([9, 8], max_new_tokens=32, sampler=GREEDY, eos_id=eos)
    assert a.token_ids == b.token_ids
    assert a.finish_reason == b.finish_reason == "stop"
    assert e4.n_overrun == 0


@pytest.mark.slow
def test_stop_sequence_parity_via_backend():
    """Host-side stop-string hits cancel the row by masking it out of
    not-yet-dispatched chunks; the delivered text must match K=1 exactly
    (the discarded in-flight tail is overrun, not output)."""
    import asyncio

    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec
    from quorum_tpu.engine.engine import release_engine

    def backend(k):
        return TpuBackend.from_spec(BackendSpec(
            name=f"p{k}",
            url=f"tpu://llama-tiny?seed=5&decode_pipeline={k}", model="m"))

    b1 = backend(1)
    base = {"model": "m", "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 24, "temperature": 0.0}
    probe = asyncio.run(b1.complete(base, {}, 60))
    text = probe.body["choices"][0]["message"]["content"]
    stop = text[3:5] if len(text) >= 5 else text[-1]
    r1 = asyncio.run(b1.complete({**base, "stop": [stop]}, {}, 60))
    # get_engine keys engines on weight identity only (decode_pipeline is
    # structural, first-construction-wins), so b4 built now would silently
    # reuse b1's K=1 engine: evict it first — the same seed rebuilds
    # identical weights on a genuinely depth-4 ring.
    release_engine(b1.engine)
    b4 = backend(4)
    assert b4.engine.decode_pipeline == 4
    r4 = asyncio.run(b4.complete({**base, "stop": [stop]}, {}, 60))
    c1 = r1.body["choices"][0]
    c4 = r4.body["choices"][0]
    assert c4["message"]["content"] == c1["message"]["content"]
    assert c4["finish_reason"] == c1["finish_reason"]


@pytest.mark.slow
def test_cancel_does_not_corrupt_later_requests():
    """Abandoning a stream mid-generation (cancel at a chunk boundary with
    chunks in flight) must leave the engine producing exactly the K=1
    stream for the next request."""
    e1, e4 = _pair()
    cancel = threading.Event()
    it = e4.generate_stream([5, 6, 7], max_new_tokens=40, sampler=GREEDY,
                            cancel=cancel)
    for _, tok in zip(range(5), it):
        pass
    it.close()  # abandons the iterator -> cancel fires, slot drains
    after1 = e1.generate([3, 4], max_new_tokens=16, sampler=GREEDY)
    after4 = e4.generate([3, 4], max_new_tokens=16, sampler=GREEDY)
    assert after4.token_ids == after1.token_ids


def test_admission_pressure_drains_and_matches():
    """More requests than slots at K=4: the ring must shrink for waiting
    admissions (no K-chunk admission delay) and every stream must still be
    its K=1 self."""
    spec = resolve_spec("llama-tiny", {})
    e1 = InferenceEngine(spec, decode_chunk=4, decode_pipeline=1, n_slots=2)
    e4 = InferenceEngine(spec, decode_chunk=4, decode_pipeline=4, n_slots=2)
    prompts = [[5, 6, 7], [9, 8], [3, 4, 5], [11, 12]]

    def run_all(eng):
        reqs = [eng.submit(p, max_new_tokens=12, sampler=GREEDY, seed=0)
                for p in prompts]
        return [list(eng.stream_results(r)) for r in reqs]

    assert run_all(e4) == run_all(e1)


@pytest.mark.slow
def test_dispatch_accounting_counters():
    """The acceptance counters: a >=8-chunk generation at K=4 must block
    the host on strictly fewer dispatches than K=1 (n_decode_chunks -
    overlapped_chunks_total), with zero overrun when the row finishes on
    device."""
    e1, e4 = _pair()
    e1.generate([5, 6, 7], max_new_tokens=40, sampler=GREEDY)  # 10 chunks
    e4.generate([5, 6, 7], max_new_tokens=40, sampler=GREEDY)
    m1, m4 = e1.metrics(), e4.metrics()
    assert m1["decode_chunks_total"] >= 8
    syncs1 = m1["decode_chunks_total"] - m1["overlapped_chunks_total"]
    syncs4 = m4["decode_chunks_total"] - m4["overlapped_chunks_total"]
    assert m1["overlapped_chunks_total"] == 0  # K=1 never dispatches ahead
    assert syncs4 < syncs1
    assert m4["overrun_tokens_total"] == 0
    assert m4["decode_pipeline"] == 4 and m1["decode_pipeline"] == 1


# ---- megachunk decode loop (decode_loop=C, ISSUE 6) ------------------------
#
# decode_loop=C fuses up to C chunk bodies into ONE dispatch
# (transformer.decode_loop). The C=1 engine is the oracle at BOTH ring
# depths: the fused program replays the identical per-chunk body, so every
# leg must be token-for-token.

def _loop_pair(k: int, **kw):
    """(decode_loop=1 oracle, decode_loop=4 megachunk) engines at ring
    depth ``k`` over identical weights."""
    return (InferenceEngine(TINY, decode_chunk=4, decode_pipeline=k,
                            decode_loop=1, **kw),
            InferenceEngine(TINY, decode_chunk=4, decode_pipeline=k,
                            decode_loop=4, **kw))


@pytest.mark.slow
@pytest.mark.parametrize("k", [1, 4])
def test_loop_greedy_and_sampled_token_for_token(k):
    e1, e4 = _loop_pair(k)
    a = e1.generate([5, 6, 7], max_new_tokens=32, sampler=GREEDY)
    b = e4.generate([5, 6, 7], max_new_tokens=32, sampler=GREEDY)
    assert a.token_ids == b.token_ids and len(b.token_ids) == 32
    s = SamplerConfig(temperature=0.9, top_p=0.95)
    for seed in (7, 42):
        a = e1.generate([5, 6, 7], max_new_tokens=24, sampler=s, seed=seed)
        b = e4.generate([5, 6, 7], max_new_tokens=24, sampler=s, seed=seed)
        assert a.token_ids == b.token_ids, f"seed {seed} diverged at K={k}"
    assert e4.n_overrun == 0  # budget finishes stay on device under fusion


@pytest.mark.slow
@pytest.mark.parametrize("k", [1, 4])
def test_loop_eos_mid_chunk_token_for_token(k):
    """EOS landing mid-chunk inside a megachunk: the on-device early exit
    must skip the remaining fused chunks — identical output, zero overrun,
    no C extra chunks of garbage."""
    e1, e4 = _loop_pair(k)
    probe = e1.generate([9, 8], max_new_tokens=32, sampler=GREEDY)
    eos = probe.token_ids[9]  # a position inside fused chunk 3
    a = e1.generate([9, 8], max_new_tokens=32, sampler=GREEDY, eos_id=eos)
    b = e4.generate([9, 8], max_new_tokens=32, sampler=GREEDY, eos_id=eos)
    assert a.token_ids == b.token_ids
    assert a.finish_reason == b.finish_reason == "stop"
    assert e4.n_overrun == 0


@pytest.mark.slow
def test_loop_stop_sequence_parity_via_backend():
    """Host-side stop-string finishes under megachunks: the delivered text
    must match decode_loop=1 exactly; the already-dispatched fused tail is
    bounded overrun (≤ C−1 chunks), never output."""
    import asyncio

    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec
    from quorum_tpu.engine.engine import release_engine

    def backend(c):
        return TpuBackend.from_spec(BackendSpec(
            name=f"l{c}",
            url=f"tpu://llama-tiny?seed=5&decode_pipeline=4&decode_loop={c}",
            model="m"))

    b1 = backend(1)
    base = {"model": "m", "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 24, "temperature": 0.0}
    probe = asyncio.run(b1.complete(base, {}, 60))
    text = probe.body["choices"][0]["message"]["content"]
    stop = text[3:5] if len(text) >= 5 else text[-1]
    r1 = asyncio.run(b1.complete({**base, "stop": [stop]}, {}, 60))
    # decode_loop is structural (first-construction-wins on the shared
    # engine): evict the C=1 engine so the C=4 URL really builds one.
    release_engine(b1.engine)
    b4 = backend(4)
    assert b4.engine.decode_loop == 4
    r4 = asyncio.run(b4.complete({**base, "stop": [stop]}, {}, 60))
    c1, c4 = r1.body["choices"][0], r4.body["choices"][0]
    assert c4["message"]["content"] == c1["message"]["content"]
    assert c4["finish_reason"] == c1["finish_reason"]


@pytest.mark.slow
def test_loop_cancel_does_not_corrupt_later_requests():
    """Abandoning a stream mid-megachunk: the wasted fused tail is
    bounded (counted as overrun), and the engine must produce exactly the
    decode_loop=1 stream for the next request."""
    e1, e4 = _loop_pair(4)
    cancel = threading.Event()
    it = e4.generate_stream([5, 6, 7], max_new_tokens=40, sampler=GREEDY,
                            cancel=cancel)
    for _, tok in zip(range(5), it):
        pass
    it.close()  # abandons the iterator -> cancel fires, slot drains
    after1 = e1.generate([3, 4], max_new_tokens=16, sampler=GREEDY)
    after4 = e4.generate([3, 4], max_new_tokens=16, sampler=GREEDY)
    assert after4.token_ids == after1.token_ids


@pytest.mark.slow
@pytest.mark.parametrize("k", [1, 4])
def test_loop_constrained_token_for_token(k):
    """A schema-constrained stream under megachunks: the DFA state rides
    the fused carry (zero extra host syncs), and the stream equals the
    decode_loop=1 constrained stream token for token."""
    import json as _json

    from quorum_tpu.constrain import compile_response_format
    from quorum_tpu.engine.tokenizer import ByteTokenizer

    tok = ByteTokenizer(TINY.vocab_size)
    schema = {"type": "object", "properties": {
        "ok": {"type": "boolean"}, "n": {"type": "integer"}}}
    rf = {"type": "json_schema", "json_schema": {"schema": schema}}
    e1, e4 = _loop_pair(k)

    def run(eng):
        g = compile_response_format(rf, tok, TINY.vocab_size)
        req = eng.submit(tok.encode("go"), max_new_tokens=64,
                         sampler=SamplerConfig(temperature=0.8), seed=11,
                         eos_id=tok.eos_id, grammar=g)
        return list(eng.stream_results(req))

    a, b = run(e1), run(e4)
    assert a == b
    body = tok.decode([t for t in b if t != tok.eos_id])
    obj = _json.loads(body)
    assert isinstance(obj, dict)
    assert e4.n_overrun == 0


@pytest.mark.slow
def test_loop_members_token_for_token():
    """Stacked members under megachunks: every member's stream equals its
    decode_loop=1 self (the fused loop advances all members per chunk
    body, exactly as the unfused dispatch did)."""
    e1 = InferenceEngine(TINY, decode_chunk=4, decode_pipeline=1,
                         decode_loop=1, members=2)
    e4 = InferenceEngine(TINY, decode_chunk=4, decode_pipeline=1,
                         decode_loop=4, members=2)
    for m in (0, 1):
        a = e1.generate([5, 6, 7], max_new_tokens=16, sampler=GREEDY,
                        member=m)
        b = e4.generate([5, 6, 7], max_new_tokens=16, sampler=GREEDY,
                        member=m)
        assert a.token_ids == b.token_ids, f"member {m} diverged"


@pytest.mark.slow
def test_loop_dispatch_counter_acceptance():
    """The ISSUE acceptance: dispatches per 64-token request drop ~C× at
    decode_loop=C (64 tokens / chunk 4 = 16 chunks → ≤ 5 dispatches at
    C=4 vs 16 unfused), chunk-segment accounting stays exact, and blocking
    syncs stay ≤ the unfused count."""
    e1, e4 = _loop_pair(2)
    e1.generate([5, 6, 7], max_new_tokens=64, sampler=GREEDY)
    e4.generate([5, 6, 7], max_new_tokens=64, sampler=GREEDY)
    m1, m4 = e1.metrics(), e4.metrics()
    assert m1["decode_chunks_total"] >= 16
    assert m4["decode_chunks_total"] <= m1["decode_chunks_total"] // 3
    # every fused dispatch's segments are accounted: 16 chunks either way
    assert m4["decode_loop_chunks_total"] == m1["decode_loop_chunks_total"]
    assert m4["decode_loop"] == 4 and m1["decode_loop"] == 1
    assert m4["overrun_tokens_total"] == 0
