"""On-device constrained decoding, end to end (ISSUE 5 acceptance):

- a ``response_format`` json_schema STREAMING request at decode_pipeline=4
  yields output that json.loads-parses and validates, with the hostpath
  counters pinning zero additional blocking syncs per chunk vs an
  unconstrained request (the DFA never forces a host round-trip);
- unconstrained batches compile and dispatch the exact pre-constrain
  decode program variant (cache-key pin, mirroring the logprobs-gating
  contract);
- the constrained-vs-unconstrained determinism pin: a grammar the
  unconstrained stream already satisfies masks nothing, so the token
  streams are identical — at K=1 and K=4;
- members=M stacking: per-member rows carry independent DFA states.

Everything runs the tiny preset on CPU — the same compiled code paths as
TPU (engine-scale: slow tier)."""

import json
import threading

import pytest

from quorum_tpu.analysis import budget
from quorum_tpu.constrain import compile_response_format
from quorum_tpu.engine.engine import InferenceEngine
from quorum_tpu.engine.tokenizer import ByteTokenizer
from quorum_tpu.models.model_config import MODEL_PRESETS
from quorum_tpu.ops.sampling import SamplerConfig

pytestmark = pytest.mark.slow

TINY = MODEL_PRESETS["llama-tiny"]
TOK = ByteTokenizer(TINY.vocab_size)
GREEDY = SamplerConfig(temperature=0.0)
SCHEMA = {"type": "object", "properties": {
    "ok": {"type": "boolean"},
    "dir": {"enum": ["N", "S", "E", "W"]},
    "n": {"type": "integer"}}}


def _grammar(rf=None):
    rf = rf or {"type": "json_schema", "json_schema": {"schema": SCHEMA}}
    return compile_response_format(rf, TOK, TINY.vocab_size)


def _run(eng, grammar, *, max_new=64, temp=0.8, seed=3, prompt="go"):
    req = eng.submit(
        TOK.encode(prompt), max_new_tokens=max_new,
        sampler=SamplerConfig(temperature=temp), seed=seed,
        eos_id=TOK.eos_id, grammar=grammar)
    return list(eng.stream_results(req))


def _text(toks):
    return TOK.decode([t for t in toks if t != TOK.eos_id])


def test_constrained_stream_at_k4_parses_with_no_extra_syncs():
    """The headline acceptance: a schema-constrained generation on a
    depth-4 ring parses and validates, and the dispatch accounting shows
    the SAME blocking-sync profile as an equal-length unconstrained run —
    the DFA is inside the chunk program, so it can never add a host
    round-trip (hostpath-bench counter contract)."""
    eng = InferenceEngine(TINY, decode_chunk=4, decode_pipeline=4)
    try:
        g = _grammar()
        toks = _run(eng, g, seed=11)
        obj = json.loads(_text(toks))
        assert isinstance(obj["ok"], bool) and obj["dir"] in "NSEW"
        assert isinstance(obj["n"], int)
        assert toks[-1] == TOK.eos_id  # grammar sink forced EOS → "stop"
        assert eng.n_overrun == 0

        # Sync accounting on an apples-to-apples pair: a wildcard grammar
        # (every byte allowed — the constrained VARIANT runs, with table
        # gathers and state advances, but masks nothing) against the
        # plain variant, same seed and budget. The streams are identical
        # (no-op masking), so the scheduler makes identical decisions and
        # any dispatch/sync difference would be the DFA's doing.
        wild = compile_response_format(
            {"type": "regex", "pattern": "[\\x00-\\xff]*"},
            TOK, TINY.vocab_size)
        n = 32  # a decode_chunk multiple: both admission paths need n/4
        _run(eng, wild, max_new=n, seed=12)          # warm constrained
        _run(eng, None, max_new=n, seed=12)          # warm plain
        c0, o0 = eng.n_decode_chunks, eng.n_overlapped
        toks_c = _run(eng, wild, max_new=n, seed=13)
        c1, o1 = eng.n_decode_chunks, eng.n_overlapped
        toks_u = _run(eng, None, max_new=n, seed=13)
        c2, o2 = eng.n_decode_chunks, eng.n_overlapped
        assert toks_u == toks_c  # no-op masking: identical stream
        assert (c1 - c0) == (c2 - c1), "chunk counts must match"
        syncs_con = (c1 - c0) - (o1 - o0)
        syncs_un = (c2 - c1) - (o2 - o1)
        assert syncs_con == syncs_un, (
            f"constrained decoding added blocking syncs: {syncs_con} vs "
            f"{syncs_un}")
        assert (o1 - o0) > 0  # the ring really pipelined under the DFA
    finally:
        eng.shutdown()


def test_unconstrained_batches_run_the_pre_constrain_program_variant():
    """The gating pin (mirrors the logprobs contract): plain decode
    programs are cached under the pre-constrain 3-tuple key with no
    mask/table operands; the constrained variant lives under its own
    tagged key; and unconstrained traffic AFTER constrained traffic adds
    no constrained-variant compiles."""
    eng = InferenceEngine(TINY, decode_chunk=4, decode_pipeline=1)
    try:
        eng.generate(TOK.encode("hi"), max_new_tokens=8, sampler=GREEDY)
        # families against the shared budget (classification also pins the
        # exact key shapes — analysis/compile_budget.json)
        assert budget.decode_families(eng._decode_cache) == {"plain"}

        _run(eng, _grammar(), max_new=32, temp=0.0)
        fams = budget.decode_families(eng._decode_cache)
        assert "dfa" in fams, "constrained traffic must use the tagged variant"
        # one literal end-to-end sentinel this file keeps: the plain key
        # stays the bare pre-constrain 3-tuple with no tag component
        assert any(isinstance(k, tuple) and len(k) == 3
                   and isinstance(k[0], int) for k in eng._decode_cache)

        before = set(eng._decode_cache)
        eng.generate(TOK.encode("hi"), max_new_tokens=8, sampler=GREEDY)
        after = set(eng._decode_cache)
        # the unconstrained request re-used plain keys; anything new is a
        # plain variant (a fresh history bucket), never a "dfa" one
        assert budget.decode_families(after - before) <= {"plain"}
    finally:
        eng.shutdown()


def test_noop_masking_is_token_identical_at_k1_and_k4():
    """Determinism pin: a grammar the unconstrained generation already
    satisfies must produce the IDENTICAL token stream — masking a token
    that would be sampled anyway is a no-op (Gumbel-argmax sampling:
    the restricted winner equals the unrestricted one whenever the
    unrestricted winner is allowed) — at K=1 and K=4."""
    e1 = InferenceEngine(TINY, decode_chunk=4, decode_pipeline=1)
    e4 = InferenceEngine(TINY, decode_chunk=4, decode_pipeline=4)
    try:
        for temp, seed in ((0.0, 3), (0.9, 7)):
            base = _run(e1, None, max_new=24, temp=temp, seed=seed)
            assert TOK.eos_id not in base  # budget finish: exact prefix
            # a pattern accepting exactly this byte stream, then anything
            pattern = "".join("\\x%02x" % b
                              for t in base for b in TOK.token_byte(t))
            pattern += "[\\x00-\\xff]*"
            g = compile_response_format(
                {"type": "regex", "pattern": pattern}, TOK,
                TINY.vocab_size)
            for eng in (e1, e4):
                got = _run(eng, g, max_new=24, temp=temp, seed=seed)
                assert got == base, (
                    f"K={eng.decode_pipeline} temp={temp}: constrained "
                    "stream diverged from its unconstrained self")
    finally:
        e1.shutdown()
        e4.shutdown()


def test_mixed_batch_constrains_only_grammar_rows():
    """A constrained and an unconstrained request co-batched in one chunk:
    the unconstrained row rides the constrained program variant in the
    FREE state and must produce exactly the stream it produces alone."""
    eng = InferenceEngine(TINY, decode_chunk=4, decode_pipeline=1)
    try:
        solo = eng.generate(TOK.encode("solo"), max_new_tokens=24,
                            sampler=GREEDY).token_ids
        g = _grammar()
        cancel = threading.Event()
        r_con = eng.submit(TOK.encode("go"), max_new_tokens=64,
                           sampler=SamplerConfig(temperature=0.8), seed=5,
                           eos_id=TOK.eos_id, grammar=g, cancel=cancel)
        r_un = eng.submit(TOK.encode("solo"), max_new_tokens=24,
                          sampler=GREEDY, eos_id=None)
        con = list(eng.stream_results(r_con))
        un = list(eng.stream_results(r_un))
        assert un == solo
        json.loads(_text(con))
    finally:
        eng.shutdown()


def test_members_rows_carry_independent_states():
    """members=2 stacking: each member's constrained request advances its
    own DFA state; both streams must be grammar-valid."""
    eng = InferenceEngine(TINY, decode_chunk=4, members=2)
    try:
        g = _grammar()
        reqs = [eng.submit(TOK.encode("go"), max_new_tokens=64,
                           sampler=SamplerConfig(temperature=0.8),
                           seed=20 + m, eos_id=TOK.eos_id, grammar=g,
                           member=m)
                for m in range(2)]
        outs = [list(eng.stream_results(r)) for r in reqs]
        texts = [_text(t) for t in outs]
        for text in texts:
            obj = json.loads(text)
            assert obj["dir"] in "NSEW"
    finally:
        eng.shutdown()


def test_grammar_reuse_and_arena_stability_across_requests():
    """Same grammar across sequential requests reuses the arena offset
    (no re-upload, bucket unchanged); a second grammar extends it while
    the first's offsets stay valid."""
    eng = InferenceEngine(TINY, decode_chunk=4)
    try:
        g1 = _grammar()
        _run(eng, g1, seed=1)
        bucket1 = eng._g_bucket
        states1 = eng._g_states
        _run(eng, g1, seed=2)
        assert eng._g_states == states1 and eng._g_bucket == bucket1
        g2 = _grammar({"type": "regex", "pattern": "yes|no"})
        out = _run(eng, g2, seed=3, temp=0.0)
        assert _text(out) in ("yes", "no")
        assert eng._g_states > states1
        # and g1 still decodes correctly against the grown arena
        json.loads(_text(_run(eng, g1, seed=4)))
    finally:
        eng.shutdown()


def test_constrained_metrics_and_span_attr():
    eng = InferenceEngine(TINY, decode_chunk=4)
    try:
        _run(eng, _grammar(), seed=6)
        m = eng.metrics()
        assert m["constrained_requests_total"] == 1
        assert m["constrain_masked_tokens_total"] > 0
    finally:
        eng.shutdown()


def test_submit_rejections():
    eng = InferenceEngine(TINY, decode_chunk=4, prefill_chunk=0)
    try:
        g = _grammar()
        with pytest.raises(ValueError, match="chunked prefill"):
            eng.submit(TOK.encode("x"), max_new_tokens=8,
                       eos_id=TOK.eos_id, grammar=g)
        with pytest.raises(ValueError, match="EOS"):
            eng.submit(TOK.encode("x"), max_new_tokens=8, grammar=g)
    finally:
        eng.shutdown()


def test_arena_cap_contains_to_one_request():
    """A grammar that would grow the device arena past CONSTRAIN_ARENA_MAX
    fails ALONE (GrammarArenaFull — the backend maps it to a retryable
    503); resident grammars and unconstrained traffic keep serving."""
    import quorum_tpu.engine.engine as em

    eng = InferenceEngine(TINY, decode_chunk=4)
    old = em.CONSTRAIN_ARENA_MAX
    em.CONSTRAIN_ARENA_MAX = 8
    try:
        small = _grammar({"type": "regex", "pattern": "ab"})
        assert small.n_states <= 7
        out = _run(eng, small, max_new=8, temp=0.0)
        assert _text(out) == "ab"
        big = _grammar()  # the schema grammar: far more than 8 states
        req = eng.submit(TOK.encode("x"), max_new_tokens=8,
                         sampler=GREEDY, eos_id=TOK.eos_id, grammar=big)
        with pytest.raises(em.GrammarArenaFull):
            list(eng.stream_results(req))
        # contained: the resident grammar and plain traffic still serve
        assert _text(_run(eng, small, max_new=8, temp=0.0)) == "ab"
        assert len(eng.generate(TOK.encode("y"), max_new_tokens=4,
                                sampler=GREEDY).token_ids) == 4
    finally:
        em.CONSTRAIN_ARENA_MAX = old
        eng.shutdown()


def test_constrained_logprobs_are_json_safe():
    """Masked alternatives must never surface as -Infinity in the wire
    body (RFC 8259 has no Infinity literal): a near-sink grammar state
    allows fewer tokens than top_logprobs, and the response must still be
    strict-JSON round-trippable with finite logprobs throughout."""
    import asyncio
    import math

    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    b = TpuBackend.from_spec(BackendSpec(
        name="lp", url="tpu://llama-tiny?seed=4", model="m"))
    res = asyncio.run(b.complete(
        {"model": "m", "messages": [{"role": "user", "content": "go"}],
         "max_tokens": 8, "temperature": 0.7, "seed": 3,
         "logprobs": True, "top_logprobs": 5,
         "response_format": {"type": "regex", "pattern": "yes|no"}},
        {}, 60))
    body = json.dumps(res.body, allow_nan=False)  # raises on inf/nan
    content = res.body["choices"][0]
    assert content["message"]["content"] in ("yes", "no")
    for e in content["logprobs"]["content"]:
        assert math.isfinite(e["logprob"])
        for t in e["top_logprobs"]:
            assert math.isfinite(t["logprob"])
    assert body


def test_backend_stream_and_finish_reason_via_api():
    """Backend-level wire contract: streaming a json_schema request at
    K=4 yields deltas whose concatenation parses and validates, with
    finish_reason "stop" (grammar completion forces EOS)."""
    import asyncio

    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    b = TpuBackend.from_spec(BackendSpec(
        name="con", url="tpu://llama-tiny?seed=3&decode_pipeline=4",
        model="m"))
    base = {"model": "m", "messages": [{"role": "user", "content": "go"}],
            "max_tokens": 64, "temperature": 0.8, "seed": 21,
            "response_format": {"type": "json_schema",
                                "json_schema": {"schema": SCHEMA}}}

    async def collect():
        finish, parts = None, []
        async for ch in b.stream(dict(base), {}, 60):
            for choice in ch.get("choices", []):
                parts.append(choice.get("delta", {}).get("content") or "")
                if choice.get("finish_reason"):
                    finish = choice["finish_reason"]
        return "".join(parts), finish

    text, finish = asyncio.run(collect())
    obj = json.loads(text)
    assert isinstance(obj["ok"], bool) and obj["dir"] in "NSEW"
    assert finish == "stop"

    # non-streaming parity + json_object mode
    r = asyncio.run(b.complete(
        {**base, "response_format": {"type": "json_object"}}, {}, 60))
    body = r.body["choices"][0]
    assert isinstance(json.loads(body["message"]["content"]), dict)
    assert body["finish_reason"] == "stop"
