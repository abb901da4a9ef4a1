"""Draft-MODEL speculative decoding (``spec_model=…``, engine._DraftRuntime).

A second, small model proposes each verify turn's draft instead of the
prompt-lookup 2-gram heuristic. The acceptance rule is unchanged — a draft
token is accepted iff it equals the target's own greedy token — so output
content NEVER depends on the draft model. These tests pin:

  - exactness: draft-model engines reproduce the plain engine's greedy
    output token-for-token, for a perfect draft (same weights — the
    oracle) and for a useless one (different seed);
  - the oracle actually accelerates: near-full acceptance, strictly fewer
    verify turns than tokens emitted;
  - composition guards (members, vocab/window mismatches) fail at
    construction, not per-request.
"""

import pytest

from quorum_tpu.engine.engine import InferenceEngine
from quorum_tpu.models.model_config import resolve_spec
from quorum_tpu.ops.sampling import SamplerConfig

# Engine-scale / compile-heavy / multi-process: slow tier (make test skips,
# make test-all and CI run everything — VERDICT r3 item 6).
pytestmark = pytest.mark.slow

GREEDY = SamplerConfig(temperature=0.0, top_p=1.0)
SPEC = {"n_kv_heads": "4", "max_seq": "256"}
PROMPT = [3, 4, 5, 6, 7, 8]


def _serve(engine, n=24, prompt=PROMPT, seed=7):
    out = engine.generate(prompt, max_new_tokens=n, sampler=GREEDY,
                          seed=seed).token_ids
    return out


def test_oracle_draft_matches_and_accelerates():
    spec = resolve_spec("llama-tiny", SPEC)
    base = InferenceEngine(spec, decode_chunk=4, n_slots=2)
    ref = _serve(base)
    base.shutdown()

    # Same spec, same seed: the draft IS the target, so every drafted token
    # matches the target's greedy chain — maximal acceptance.
    drafted = InferenceEngine(spec, decode_chunk=4, n_slots=2,
                              spec_decode=4, draft_spec=spec, draft_seed=0)
    got = _serve(drafted)
    m = drafted.metrics()
    drafted.shutdown()
    assert got == ref, "draft-model engine changed greedy content"
    assert m["spec_turns_total"] > 0
    # 24 tokens in ≤ ceil(24/5)+1 verify dispatches at g=4 full acceptance.
    assert m["spec_turns_total"] < 24
    assert m["spec_accepted_total"] >= 2 * m["spec_turns_total"], (
        f"oracle draft barely accepted: {m}")


def test_useless_draft_is_harmless():
    spec = resolve_spec("llama-tiny", SPEC)
    base = InferenceEngine(spec, decode_chunk=4, n_slots=2)
    ref = _serve(base, n=12)
    base.shutdown()

    # Different weights: acceptance ~0, content must be identical anyway.
    drafted = InferenceEngine(spec, decode_chunk=4, n_slots=2,
                              spec_decode=4, draft_spec=spec, draft_seed=99)
    got = _serve(drafted, n=12)
    drafted.shutdown()
    assert got == ref


def test_cobatched_drafted_requests_match_serial():
    from concurrent.futures import ThreadPoolExecutor

    spec = resolve_spec("llama-tiny", SPEC)
    prompts = [PROMPT, [9, 10, 11], list(range(3, 40))]
    base = InferenceEngine(spec, decode_chunk=4, n_slots=3)
    ref = [_serve(base, n=10, prompt=p) for p in prompts]
    base.shutdown()

    drafted = InferenceEngine(spec, decode_chunk=4, n_slots=3,
                              spec_decode=4, draft_spec=spec, draft_seed=0)
    with ThreadPoolExecutor(max_workers=3) as ex:
        got = list(ex.map(lambda p: _serve(drafted, n=10, prompt=p), prompts))
    drafted.shutdown()
    assert got == ref


def test_guards_fail_at_construction():
    spec = resolve_spec("llama-tiny", SPEC)
    small_window = resolve_spec("llama-tiny", dict(SPEC, max_seq="128"))
    other_vocab = resolve_spec("gpt2-tiny", {"max_seq": "256",
                                             "vocab_size": "1024"})
    with pytest.raises(ValueError, match="max_seq"):
        InferenceEngine(spec, spec_decode=4, draft_spec=small_window)
    with pytest.raises(ValueError, match="vocab"):
        InferenceEngine(spec, spec_decode=4, draft_spec=other_vocab)
    with pytest.raises(ValueError, match="members"):
        InferenceEngine(spec, members=2, spec_decode=4, draft_spec=spec)


def test_backend_url_knob():
    import asyncio

    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    be = TpuBackend.from_spec(BackendSpec(
        name="D",
        url="tpu://llama-tiny?n_kv_heads=4&max_seq=256&slots=2"
            "&spec_model=llama-tiny&spec_decode=4&max_tokens=8",
        model="m"))
    body = {"model": "m", "temperature": 0.0, "max_tokens": 8,
            "messages": [{"role": "user", "content": "hello there"}]}
    result = asyncio.run(be.complete(body, {}, 60.0))
    assert result.ok and result.usage["completion_tokens"] >= 1
    assert be.engine.metrics()["spec_turns_total"] > 0
    assert be.engine._draft_rt is not None


def test_backend_propagates_target_window_to_draft():
    """ADVICE r3: the draft must inherit the target's sliding_window (not
    keep its preset) — the docs promise the draft runs the target's
    vocab/window, and a mismatched span only lowers acceptance silently."""
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    be = TpuBackend.from_spec(BackendSpec(
        name="DW",
        url="tpu://llama-tiny?n_kv_heads=4&max_seq=256&sliding_window=64"
            "&slots=1&spec_model=llama-tiny&spec_decode=4&max_tokens=4",
        model="m"))
    draft = be.engine._draft_rt.spec
    target = be.engine.spec
    assert draft.sliding_window == target.sliding_window == 64
    assert draft.max_seq == target.max_seq
    assert draft.vocab_size == target.vocab_size


def test_ckpt_plus_spec_model_rejected():
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    with pytest.raises(ValueError, match="spec_model"):
        TpuBackend.from_spec(BackendSpec(
            name="X", url="tpu://llama-tiny?ckpt=/nonexistent&spec_model=llama-tiny",
            model="m"))


def test_near_window_cap_sync_does_not_corrupt_draft_cache():
    """Pad writes in the sync bites must never run past max_seq: a row
    near the window cap co-batched with a freshly-admitted long prompt
    used to have its bite padded to the fresh row's 16-token stride,
    where dynamic_update_slice clamps the start BACKWARDS and silently
    corrupts already-synced draft positions. The drafts for the capped
    row must equal a clean runtime's drafts."""
    from quorum_tpu.engine.engine import _DraftRuntime

    class R:  # draft_all touches only .hist and object identity
        def __init__(self, hist):
            self.hist = list(hist)

    spec = resolve_spec("llama-tiny", SPEC)  # max_seq 256
    a = R([(i % 97) + 3 for i in range(245)])
    rt = _DraftRuntime(spec, spec, rows=2, seed=0)
    rt.draft_all([(0, a)], g=4)              # sync A to 245
    a.hist.extend([5, 6, 7, 8, 9, 10])       # A now at 251 (cap - g - 1)
    b = R([(i % 89) + 3 for i in range(120)])  # fresh row drives big bites
    drafts = rt.draft_all([(0, a), (1, b)], g=4)

    clean = _DraftRuntime(spec, spec, rows=2, seed=0)
    clean_drafts = clean.draft_all([(0, a)], g=4)
    assert drafts[0] == clean_drafts[0], (
        "near-cap row's draft diverged — its synced cache was corrupted")


def test_explicit_spec_decode_zero_with_draft_rejected():
    spec = resolve_spec("llama-tiny", SPEC)
    with pytest.raises(ValueError, match="spec_decode"):
        InferenceEngine(spec, spec_decode=0, draft_spec=spec)


def _tiny_llama_ckpt(dirpath, seed):
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    torch.manual_seed(seed)
    cfg = LlamaConfig(
        vocab_size=512, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, rope_theta=10000.0,
        tie_word_embeddings=False,
    )
    LlamaForCausalLM(cfg).eval().save_pretrained(
        dirpath, safe_serialization=True)
    return str(dirpath)


def test_spec_ckpt_oracle_and_other_weights(tmp_path):
    """Real-checkpoint draft pairs (spec_ckpt=): the deployment story —
    a small checkpoint drafts for a checkpoint target. Oracle case (draft
    dir == target dir → identical weights) must reproduce the no-draft
    output with high acceptance; a different-weights draft must also
    reproduce it (speed-only, like every draft source)."""
    import asyncio

    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    target = _tiny_llama_ckpt(tmp_path / "target", seed=0)
    other = _tiny_llama_ckpt(tmp_path / "other", seed=1)
    body = {"model": "m", "temperature": 0.0, "max_tokens": 12,
            "messages": [{"role": "user", "content": "draft me a reply"}]}

    def text(url):
        be = TpuBackend.from_spec(BackendSpec(name="C", url=url, model="m"))
        result = asyncio.run(be.complete(body, {}, 120.0))
        assert result.ok, result.body
        return result.content, be.engine

    plain, _ = text(f"tpu://x?ckpt={target}&slots=2&max_tokens=12")
    oracle, eng = text(f"tpu://x?ckpt={target}&slots=2&max_tokens=12"
                       f"&spec_ckpt={target}")
    assert oracle == plain, "spec_ckpt oracle changed ckpt greedy content"
    m = eng.metrics()
    assert m["spec_turns_total"] > 0
    assert m["spec_accepted_total"] >= 2 * m["spec_turns_total"]

    different, _ = text(f"tpu://x?ckpt={target}&slots=2&max_tokens=12"
                        f"&spec_ckpt={other}")
    assert different == plain, "different-weights draft changed content"


def test_draft_source_knob_validation(tmp_path):
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    with pytest.raises(ValueError, match="mutually"):
        TpuBackend.from_spec(BackendSpec(
            name="X",
            url="tpu://llama-tiny?spec_model=llama-tiny&spec_ckpt=/x",
            model="m"))
    with pytest.raises(ValueError, match="config.json"):
        TpuBackend.from_spec(BackendSpec(
            name="X",
            url=f"tpu://llama-tiny?spec_ckpt={tmp_path}/typo",
            model="m"))


def test_draft_over_int8_target_is_exact():
    """quant=int8 target + draft model: the draft (bf16 init) is no longer
    a perfect oracle for the quantized target, so acceptance drops — but
    content must still equal the draft-less int8 engine token for token
    (speed-only, like every draft configuration)."""
    spec = resolve_spec("llama-tiny", SPEC)
    plain = InferenceEngine(spec, decode_chunk=4, n_slots=2, quant="int8")
    ref = _serve(plain, n=12)
    plain.shutdown()

    drafted = InferenceEngine(spec, decode_chunk=4, n_slots=2, quant="int8",
                              spec_decode=4, draft_spec=spec, draft_seed=0)
    got = _serve(drafted, n=12)
    m = drafted.metrics()
    drafted.shutdown()
    assert got == ref
    assert m["spec_turns_total"] > 0
