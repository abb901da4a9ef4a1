"""Continuous-batching engine tests: co-batching, determinism, slot reuse.

The round-1 engine serialized concurrent requests behind a lock (VERDICT.md
weakness 4); the redesigned engine admits them into cache slots and decodes
them in one batched program. These tests pin the properties that redesign
must keep: results are independent of co-batching/slot assignment, requests
beyond the slot count queue and complete, abandoned requests release their
slot, and the per-row sampler matches the static-config sampler.
"""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from quorum_tpu.engine.engine import InferenceEngine
from quorum_tpu.models.model_config import MODEL_PRESETS
from quorum_tpu.ops.sampling import SamplerConfig, sample_token, sample_token_rows

import pytest

TINY = MODEL_PRESETS["llama-tiny"]


def _run_one(eng, seed, prompt, n=8, temp=0.8):
    return eng.generate(
        prompt, max_new_tokens=n,
        sampler=SamplerConfig(temperature=temp, top_p=0.9), seed=seed,
    ).token_ids


def test_concurrent_results_match_serial():
    """Co-batched generations must be byte-identical to serial ones —
    row-independent compute + per-request PRNG keys."""
    eng = InferenceEngine(TINY, decode_chunk=4, n_slots=4)
    jobs = [(seed, [3 + seed, 4, 5 + seed]) for seed in range(6)]
    serial = [_run_one(eng, s, p) for s, p in jobs]
    with ThreadPoolExecutor(max_workers=6) as ex:
        concurrent = list(ex.map(lambda job: _run_one(eng, *job), jobs))
    assert concurrent == serial


def test_more_requests_than_slots_all_complete():
    eng = InferenceEngine(TINY, decode_chunk=4, n_slots=2)
    with ThreadPoolExecutor(max_workers=5) as ex:
        results = list(ex.map(
            lambda seed: _run_one(eng, seed, [5, 6, 7], n=6), range(5)
        ))
    assert all(len(r) == 6 for r in results)
    assert all(all(0 <= t < TINY.vocab_size for t in r) for r in results)


def test_abandoned_stream_releases_slot():
    """Dropping the iterator early must free the slot for later requests."""
    eng = InferenceEngine(TINY, decode_chunk=2, n_slots=1)
    it = eng.generate_stream([5, 6], max_new_tokens=64,
                             sampler=SamplerConfig(temperature=0.0))
    next(it)
    it.close()  # abandon mid-generation
    res = eng.generate([7, 8], max_new_tokens=5,
                       sampler=SamplerConfig(temperature=0.0))
    assert len(res.token_ids) == 5


@pytest.mark.slow  # compares wall-clock times
def test_concurrency_is_faster_than_serial():
    """Two co-batched generations should take well under 2x one generation —
    batched decode is the whole point of continuous batching. Generous
    threshold: even modest batching wins beat the 1.8x serial bound."""
    eng = InferenceEngine(TINY, decode_chunk=8, n_slots=4)
    _run_one(eng, 0, [3, 4, 5], n=24)  # compile prefill + decode programs

    t0 = time.perf_counter()
    _run_one(eng, 1, [3, 4, 5], n=24)
    one = time.perf_counter() - t0

    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=2) as ex:
        list(ex.map(lambda s: _run_one(eng, s, [3, 4, 5], n=24), (2, 3)))
    two = time.perf_counter() - t0
    assert two < 1.8 * one, f"2 concurrent took {two:.3f}s vs 1 serial {one:.3f}s"


@pytest.mark.slow  # counts the tokens a consumer thread sees before its cancel lands
def test_cancel_event_stops_generation():
    eng = InferenceEngine(TINY, decode_chunk=2, n_slots=2)
    cancel = threading.Event()
    got = []
    for t in eng.generate_stream([5, 6], max_new_tokens=64,
                                 sampler=SamplerConfig(temperature=0.0),
                                 cancel=cancel):
        got.append(t)
        if len(got) == 3:
            cancel.set()
    assert 3 <= len(got) <= 3 + eng.decode_chunk


def test_engine_survives_failed_device_call():
    """A raising compiled call must fail the in-flight request AND leave the
    engine serviceable — the programs donate the cache/state buffers, so the
    scheduler has to rebuild device state after a failure (a poisoned request
    must not brick the shared engine)."""
    eng = InferenceEngine(TINY, decode_chunk=2, n_slots=2)

    real_decode_fn = eng._decode_fn
    calls = {"n": 0}

    def exploding_decode_fn(*args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 1:
            def boom(*a, **k):
                raise RuntimeError("injected device failure")
            return boom
        return real_decode_fn(*args, **kwargs)

    eng._decode_fn = exploding_decode_fn
    try:
        eng.generate([5, 6], max_new_tokens=6,
                     sampler=SamplerConfig(temperature=0.0))
        raise AssertionError("expected the injected failure to surface")
    except RuntimeError as e:
        assert "injected" in str(e)

    res = eng.generate([5, 6], max_new_tokens=6,
                       sampler=SamplerConfig(temperature=0.0))
    assert len(res.token_ids) == 6


def test_sample_token_rows_matches_static_config():
    """Per-row sampler (array knobs) must agree with the static-config
    sampler on every deterministic setting, including mixed rows."""
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (4, 64)) * 3.0
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(4)])

    # greedy rows (temp<=0), top_k=1 rows, and tiny top_p rows all reduce to
    # argmax — deterministic regardless of key.
    out = sample_token_rows(
        logits, keys,
        temperature=jnp.array([0.0, 1.0, 1.0, 0.7]),
        top_p=jnp.array([1.0, 1.0, 0.01, 1.0]),
        top_k=jnp.array([0, 1, 0, 1], jnp.int32),
    )
    expect = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    assert np.array_equal(np.asarray(out), np.asarray(expect))

    # stochastic row: same key/knobs via the static path must land in the
    # same top-k support set.
    cfg = SamplerConfig(temperature=0.8, top_k=4)
    static = sample_token(logits[:1], jax.random.PRNGKey(7), cfg)
    rows = sample_token_rows(
        logits[:1], jax.random.PRNGKey(7)[None],
        temperature=jnp.array([0.8]), top_p=jnp.array([1.0]),
        top_k=jnp.array([4], jnp.int32),
    )
    topk_ids = set(np.asarray(jax.lax.top_k(logits[0], 4)[1]).tolist())
    assert int(static[0]) in topk_ids
    assert int(rows[0]) in topk_ids


def test_dispatch_overlap_engages_when_idle():
    """A long single-request generation with no admissions waiting must
    dispatch ahead of the read (the overlap counter proves the device is
    being fed chunk-to-chunk; the output itself is unchanged — state chains
    on device)."""
    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.models import resolve_spec
    from quorum_tpu.ops.sampling import SamplerConfig

    spec = resolve_spec("llama-tiny", {"max_seq": "128"})
    eng = InferenceEngine(spec, decode_chunk=4)
    out = eng.generate([3, 5, 7], max_new_tokens=40,
                       sampler=SamplerConfig(temperature=0.0)).token_ids
    assert len(out) == 40
    # the first chunks compile their history buckets (overlap defers to the
    # compile guard); later chunks re-use warm programs and overlap
    assert eng.n_overlapped > 0
    assert eng.metrics()["overlapped_chunks_total"] == eng.n_overlapped
