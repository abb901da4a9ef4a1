"""Megachunk decode-loop gating and clamping (fast tier).

The cache-key pin (same gating pattern as the PR 5 unconstrained pin): a
``decode_loop=1`` engine must compile ONLY the pre-existing "plain"
program family — never a "loop"-tagged one — so unfused users pay zero
recompiles for this feature existing. The key shapes themselves are pinned
once, in ``quorum_tpu/analysis/compile_budget.json``; these tests assert
FAMILIES via quorum_tpu.analysis.budget (classification raises on any
unknown or shape-drifted key), keeping one literal end-to-end sentinel.

The effective-C clamp unit tests pin the scheduler-side safety rails:
admission pressure → 1 (an admission must not wait C chunks), short
remaining budgets → the smallest power-of-two cover, and a tight in-flight
deadline → halved until one dispatch fits inside it (the PR 4
DEADLINE_SLACK_S backstop must never fire because a dispatch legitimately
covered C chunks).
"""

import time

import pytest

from quorum_tpu.analysis import budget
from quorum_tpu.engine.engine import MAX_DECODE_LOOP, InferenceEngine
from quorum_tpu.models.model_config import MODEL_PRESETS
from quorum_tpu.ops.sampling import SamplerConfig

TINY = MODEL_PRESETS["llama-tiny"]
GREEDY = SamplerConfig(temperature=0.0)


class _Row:
    """The slice of _Request the clamp reads."""

    def __init__(self, budget=100, emitted=0, deadline=None):
        self.budget = budget
        self.emitted = emitted
        self.deadline = deadline


def test_decode_loop_1_pins_the_unfused_program_keys():
    eng = InferenceEngine(TINY, decode_chunk=4, decode_pipeline=2,
                          decode_loop=1)
    try:
        eng.generate([5, 6, 7], max_new_tokens=12, sampler=GREEDY)
        keys = set(eng._decode_cache)
        assert keys, "the generation must have compiled decode programs"
        assert budget.decode_families(keys) == {"plain"}, (
            f"decode_loop=1 must compile only the plain family, got {keys}")
    finally:
        eng.shutdown()


def test_decode_loop_4_uses_tagged_keys_only_for_fused_dispatches():
    eng = InferenceEngine(TINY, decode_chunk=4, decode_pipeline=1,
                          decode_loop=4)
    try:
        eng.generate([5, 6, 7], max_new_tokens=16, sampler=GREEDY)
        fams = budget.decode_families(eng._decode_cache)
        assert "loop" in fams, "a 4-chunk generation must fuse"
        assert "loop_dfa" not in fams  # no grammar rows in this batch
        # the one literal end-to-end sentinel this file keeps: the fused
        # key carries n_chunks=4 right after its tag
        loop_keys = {k for k in eng._decode_cache if k[0] == "loop"}
        assert all(k[1] == 4 for k in loop_keys)
    finally:
        eng.shutdown()


def test_decode_loop_range_validated():
    with pytest.raises(ValueError):
        InferenceEngine(TINY, decode_loop=0)
    with pytest.raises(ValueError):
        InferenceEngine(TINY, decode_loop=MAX_DECODE_LOOP + 1)


def test_decode_loop_floored_to_power_of_two():
    """A non-pow2 C would double the fused program-shape families (the
    per-dispatch clamps halve); the engine floors it at construction."""
    eng = InferenceEngine(TINY, decode_loop=6)
    try:
        assert eng.decode_loop == 4
    finally:
        eng.shutdown()


def test_url_knobs_validated_at_config_time():
    """A decode_loop= out of range must fail the URL before any multi-GB
    engine construction, not per-request."""
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    for url in ("tpu://llama-tiny?decode_loop=0",
                "tpu://llama-tiny?decode_loop=9999"):
        with pytest.raises(ValueError):
            TpuBackend.from_spec(BackendSpec(name="bad", url=url, model="m"))


class TestEffectiveLoopClamp:
    @pytest.fixture()
    def eng(self):
        e = InferenceEngine(TINY, decode_chunk=4, decode_pipeline=1,
                            decode_loop=8)
        yield e
        e.shutdown()

    def test_full_fusion_when_unpressured(self, eng):
        active = [(0, _Row(budget=100))]
        assert eng._effective_loop(active, 4, 0) == 8

    def test_budget_clamps_to_pow2_cover(self, eng):
        # 10 tokens left at chunk 4 → 3 chunks → pow2 cover 4, not 8
        active = [(0, _Row(budget=10))]
        assert eng._effective_loop(active, 4, 0) == 4
        # tokens already in flight count against the remaining budget
        assert eng._effective_loop(active, 4, 8) == 1

    def test_admission_pressure_disables_fusion(self, eng):
        active = [(0, _Row(budget=100))]
        req = eng.submit([1, 2, 3], max_new_tokens=4, sampler=GREEDY)
        try:
            with eng._cond:
                pressured = eng._admission_pressure()
            # the scheduler may have admitted it already; only a still-
            # pending request exerts pressure
            if pressured:
                assert eng._effective_loop(active, 4, 0) == 1
        finally:
            list(eng.stream_results(req))

    def test_queued_request_deadline_clamps_too(self, eng, monkeypatch):
        """A queued request with no free slot exerts no admission
        pressure, but its deadline sweep runs only between dispatches —
        its deadline must clamp C exactly like an active row's."""
        class _Pending:
            deadline = time.monotonic() + 0.25
        eng._chunk_ewma_s = 0.1
        monkeypatch.setattr(eng, "_admission_pressure", lambda: False)
        with eng._cond:
            eng._pending.append(_Pending())
        try:
            active = [(0, _Row(budget=100))]  # no deadline of its own
            assert eng._effective_loop(active, 4, 0) <= 2
        finally:
            with eng._cond:
                eng._pending.clear()

    def test_deadline_clamps_the_dispatch_length(self, eng):
        eng._chunk_ewma_s = 0.1  # 100 ms per chunk, estimated
        tight = time.monotonic() + 0.25  # fits 2 chunks, not 8
        active = [(0, _Row(budget=100, deadline=tight))]
        assert eng._effective_loop(active, 4, 0) <= 2
        # an already-blown deadline degrades to single-chunk dispatch
        late = [(0, _Row(budget=100, deadline=time.monotonic() - 1))]
        assert eng._effective_loop(late, 4, 0) == 1
        # no latency estimate yet → no clamp (first dispatch measures)
        eng._chunk_ewma_s = 0.0
        assert eng._effective_loop(active, 4, 0) == 8
