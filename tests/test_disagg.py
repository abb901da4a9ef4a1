"""Disaggregated prefill/decode serving (``disagg=P+D``, ISSUE 8).

Fast tier: knob parsing/validation, the colocated cache-key pin (disagg
off compiles the exact pre-existing program variants and runs ONE
scheduler loop), and a 1+1-group smoke on the virtual CPU mesh — output
pinned token-for-token against the colocated engine with a live
device→device KV handoff, plus the ``engine.kv_handoff`` fault site's
containment contract (a failed handoff dooms only its own request and
requeues nothing else).

Slow tier: the full acceptance pin at ``disagg=4+4`` on the 8-device mesh
with ``decode_pipeline=4 × decode_loop=4`` across the
greedy / sampled / EOS-mid-chunk / constrained / members / prefix-restore
legs, each against a colocated mesh engine.
"""

import asyncio

import pytest

from quorum_tpu import faults
from quorum_tpu.analysis import budget
from quorum_tpu.engine.engine import InferenceEngine
from quorum_tpu.models.model_config import resolve_spec
from quorum_tpu.ops.sampling import SamplerConfig
from quorum_tpu.parallel.mesh import (
    MeshConfig,
    disagg_meshes,
    group_mesh_configs,
    make_mesh,
    parse_disagg,
)

TINY = resolve_spec("llama-tiny", {"n_kv_heads": "4"})
SAMPLED = SamplerConfig(temperature=0.8, top_p=0.9)
GREEDY = SamplerConfig(temperature=0.0)


def _gen(eng, prompt, seed=0, n=8, sampler=SAMPLED, **kw):
    return eng.generate(prompt, max_new_tokens=n, sampler=sampler,
                        seed=seed, **kw).token_ids


# ---- fast: parsing + config validation -------------------------------------


def test_parse_disagg():
    assert parse_disagg("4+4") == (4, 4)
    assert parse_disagg("1+7") == (1, 7)
    assert parse_disagg("2 2") == (2, 2)  # URL-decoded '+' arrives as space
    for bad in ("", "4", "4x4", "0+4", "4+0", "-1+2", "a+b"):
        with pytest.raises(ValueError):
            parse_disagg(bad)


def test_disagg_mesh_and_engine_validation():
    with pytest.raises(ValueError, match="devices"):
        disagg_meshes(9, 9)
    pm, dm = disagg_meshes(1, 1)
    # groups must be disjoint
    with pytest.raises(ValueError, match="disjoint"):
        InferenceEngine(TINY, pm, prefill_mesh=pm)
    # disagg rides chunked prefill; an engine without it must reject
    with pytest.raises(ValueError, match="chunked prefill"):
        InferenceEngine(TINY, dm, prefill_mesh=pm, prefill_chunk=0)


def test_group_mesh_config_rejections():
    """Every invalid disagg-side factorization fails in
    group_mesh_configs with the arithmetic, at config time."""
    for kw, frag in [
        (dict(tp=3), "does not factor"),        # non-divisible tp vs group
        (dict(sp=3), "does not factor"),        # sp must divide prefill
        (dict(tp=0), ">= 1"),
        (dict(sp=0), ">= 1"),
    ]:
        with pytest.raises(ValueError, match=frag):
            group_mesh_configs(4, 4, **kw)
    # the decode group is sharded by tp alone (prefill factors: 4 = 2x2)
    with pytest.raises(ValueError, match="decode group .* does not factor"):
        group_mesh_configs(4, 4, sp=2, tp=2)
    # the factoring identities that must pass
    pre, dec = group_mesh_configs(4, 4)
    assert (pre.tp, dec.tp) == (4, 4)  # no knobs = whole-group tp
    pre, dec = group_mesh_configs(4, 4, tp=4)
    assert (pre.sp, pre.tp, dec.pp, dec.tp) == (1, 4, 1, 4)
    pre, dec = group_mesh_configs(4, 2, sp=2)
    assert (pre.sp, pre.tp, dec.pp, dec.tp) == (2, 2, 1, 2)


def test_engine_disagg_sharding_rejections():
    """disagg-side engine rejections: sp in the DECODE group, and a
    prefill-group sp that does not divide max_seq."""
    import jax

    sp_decode = make_mesh(MeshConfig(sp=2), jax.devices()[1:3])
    with pytest.raises(ValueError, match="PREFILL group"):
        InferenceEngine(TINY, sp_decode,
                        prefill_mesh=make_mesh(MeshConfig(tp=1),
                                               jax.devices()[:1]),
                        prefill_chunk=16)
    # sp=3 cannot shard a 128-position staging cache evenly
    pm2, dm2 = disagg_meshes(3, 1, sp=3)
    with pytest.raises(ValueError, match="does not divide max_seq"):
        InferenceEngine(TINY, dm2, prefill_mesh=pm2, prefill_chunk=16)


REMOVED_URL_OPTIONS = {
    # how: (option, a value that once asked for it, PR, what the error says)
    "url-ensemble": ("ensemble", "2", 32, "members=M"),
    "url-pp": ("pp", "2", 32, "tp="),
    "url-spec_decode": ("spec_decode", "4", 51, "by chunks only"),
    "url-spec_model": ("spec_model", "llama-tiny", 51, "by chunks only"),
    "url-spec_ckpt": ("spec_ckpt", "/nowhere", 51, "by chunks only"),
    "url-spec_seed": ("spec_seed", "3", 51, "by chunks only"),
}


@pytest.mark.parametrize("how", sorted(REMOVED_URL_OPTIONS) + [
    "mesh-pp", "url-off-values", "config-speculative_aggregation"])
def test_removed_decode_forms_raise(how):
    """``ensemble=M`` and ``pp=K`` left the URL grammar in PR 32, the four
    speculation options and the ``speculative_aggregation`` key in PR 51: a
    URL or an aggregate block that still sets one fails at config time naming
    the removal (never a quiet other model), the value that always meant
    "off" is accepted, and a decode mesh with a pp axis over 1 fails at
    engine construction naming tp."""
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import AggregateParams, BackendSpec

    def build(query):
        return TpuBackend.from_spec(BackendSpec(
            name="t", url=f"tpu://llama-tiny?{query}", model="m"))

    if how == "mesh-pp":
        import jax

        with pytest.raises(ValueError, match="removed in PR 32.*tp="):
            InferenceEngine(TINY, make_mesh(MeshConfig(pp=2),
                                            jax.devices()[:2]))
    elif how == "url-off-values":
        # what every engine is: accepted
        build("ensemble=1&pp=1&spec_decode=0&spec_seed=0&slots=2")
    elif how == "config-speculative_aggregation":
        with pytest.raises(ValueError,
                           match="removed in PR 51.*by chunks only"):
            AggregateParams.from_dict({"speculative_aggregation": True})
        AggregateParams.from_dict({"speculative_aggregation": False})
    else:
        opt, value, pr, says = REMOVED_URL_OPTIONS[how]
        with pytest.raises(
                ValueError,
                match=f"{opt}={value}.*removed in PR {pr}.*{says}"):
            build(f"{opt}={value}")


def test_pp_tagged_decode_key_is_unknown():
    with pytest.raises(budget.UnbudgetedProgramKey, match="no compile_budget"):
        budget.classify_decode_key(("pp", 4, False, 128))


def test_speculation_tagged_decode_key_is_unknown():
    """The verify and spec_loop families left the budget with the programs."""
    for key in (("verify", 4, False, 128), ("dfa_verify", 4, False, 128, 2),
                ("spec_loop", 2, 4, False, 128),
                ("paged", "verify", 4, False, 128)):
        with pytest.raises(budget.UnbudgetedProgramKey):
            budget.classify_decode_key(key)


def test_disagg_url_knob_validation():
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    def build(url):
        return TpuBackend.from_spec(
            BackendSpec(name="t", url=url, model="m"))

    for url, frag in [
        ("tpu://llama-tiny?disagg=4x4", "invalid disagg"),
        # tp= composes with disagg now (the per-group factorization), but
        # a non-factoring tp still rejects at config with the arithmetic
        ("tpu://llama-tiny?disagg=1+1&tp=2", "does not factor"),
        ("tpu://llama-tiny?disagg=1+1&dp=2", "dp= does not compose"),
        ("tpu://llama-tiny?disagg=1+1&prefill_chunk=0", "chunked prefill"),
        ("tpu://llama-tiny?disagg=9+9", "devices"),
    ]:
        with pytest.raises(ValueError, match=frag.replace("/", ".")):
            build(url)


# ---- fast: colocated cache-key pin + 1+1 smoke -----------------------------


@pytest.fixture(scope="module")
def smoke_engines():
    """One colocated + one disagg=1+1 engine over identical knobs, shared
    by the fast smoke tests (compiles once per module)."""
    pm, dm = disagg_meshes(1, 1)
    kw = dict(decode_chunk=4, n_slots=2, decode_pipeline=2,
              prefill_chunk=16, seed=9300)
    eng_c = InferenceEngine(TINY, **kw)
    eng_d = InferenceEngine(TINY, dm, prefill_mesh=pm, **kw)
    yield eng_c, eng_d
    eng_c.shutdown()
    eng_d.shutdown()


def test_colocated_compiles_exact_preexisting_variants(smoke_engines):
    """disagg off = byte-for-byte the old engine: one scheduler loop, no
    prefill-group state, no handoff program variants, single-shot
    admission for short prompts, and the unconstrained decode programs
    under their exact pre-existing 3-tuple keys."""
    eng_c, _ = smoke_engines
    _gen(eng_c, [3, 4, 5], seed=1)
    assert eng_c._prefill_thread is None
    assert eng_c.prefill_params is None
    assert not eng_c.disagg
    # program families against the shared budget (classifying also pins
    # each key's exact shape — analysis/compile_budget.json)
    assert budget.admit_families(eng_c._admit_cache) == {"single_shot"}
    assert budget.decode_families(eng_c._decode_cache) == {"plain"}
    # one end-to-end literal sentinel: the plain decode key is still the
    # pre-existing (n_steps, want_lp, history) 3-tuple
    assert any(isinstance(k, tuple) and len(k) == 3
               and isinstance(k[0], int) for k in eng_c._decode_cache)
    assert eng_c.n_kv_handoffs == 0 and eng_c.kv_handoff_bytes == 0


def test_disagg_smoke_pinned_with_live_handoff(smoke_engines):
    """1+1 groups: greedy and sampled streams (short AND multi-segment
    prompts) equal the colocated engine token for token, with nonzero KV
    handoff bytes/seconds crossing the group boundary."""
    eng_c, eng_d = smoke_engines
    long_p = [(3 + 5 * i) % 500 for i in range(40)]
    legs = [([3, 4, 5], GREEDY, 0), ([7, 8, 9], SAMPLED, 11),
            (long_p, SAMPLED, 3)]
    for prompt, sampler, seed in legs:
        assert (_gen(eng_d, prompt, seed=seed, sampler=sampler)
                == _gen(eng_c, prompt, seed=seed, sampler=sampler))
    assert eng_d.n_kv_handoffs >= len(legs)
    assert eng_d.kv_handoff_bytes > 0
    assert eng_d.kv_handoff_s > 0.0
    m = eng_d.metrics()
    assert m["disagg"] == 1 and m["kv_handoff_bytes_total"] > 0
    assert m["prefill_group_devices"] == 1
    assert m["decode_group_devices"] == 1
    # never a single-shot admit program on the disagg engine; every
    # admission rides seg+handoff+register (compile_budget.json gates)
    fams = budget.admit_families(eng_d._admit_cache)
    assert "single_shot" not in fams
    assert {"seg", "register", "hslice", "hput"} <= fams, fams
    # group-aware health: both loops alive
    h = eng_d.health()
    assert h["scheduler_alive"] and h["prefill_scheduler_alive"]


def test_kv_handoff_fault_dooms_only_its_request(smoke_engines):
    """The ``engine.kv_handoff`` fault site's containment: the failed
    handoff's own request errors; a queued bystander completes unchanged
    (nothing requeued, no rebuild), and the next request matches the
    fault-free baseline."""
    eng_c, eng_d = smoke_engines
    base = _gen(eng_d, [3, 4, 5], seed=1)
    assert base == _gen(eng_c, [3, 4, 5], seed=1)
    rebuilds0 = eng_d.n_rebuilds
    faults.arm("engine.kv_handoff", times=1)
    try:
        bad = eng_d.submit([5, 6, 7], max_new_tokens=8, sampler=SAMPLED,
                           seed=2)
        bystander = eng_d.submit([3, 4, 5], max_new_tokens=8,
                                 sampler=SAMPLED, seed=1)
        with pytest.raises(faults.FaultInjected):
            list(eng_d.stream_results(bad))
        assert list(eng_d.stream_results(bystander)) == base
    finally:
        faults.disarm()
    assert _gen(eng_d, [3, 4, 5], seed=1) == base
    assert eng_d.n_rebuilds == rebuilds0  # staging survived: no rebuild
    assert eng_d.health()["prefill_scheduler_alive"]


def test_disagg_no_knob_cache_keys_unchanged(smoke_engines):
    """The no-sharding-knob disagg path keeps its exact pre-existing
    program cache keys, byte for byte (ISSUE 14 acceptance): plain
    3-tuple decode keys and only the pre-existing admit-cache tags."""
    eng_c, eng_d = smoke_engines
    _gen(eng_d, [3, 4, 5], seed=1)
    assert eng_d.prefill_sp == 1
    for k in eng_d._decode_cache:
        assert isinstance(k, tuple) and len(k) == 3, k
        assert (isinstance(k[0], int) and isinstance(k[1], bool)
                and isinstance(k[2], int)), k
    allowed_tags = {"seg", "register", "hslice", "hput"}
    for k in eng_d._admit_cache:
        tag = k if isinstance(k, str) else k[0]
        assert tag in allowed_tags, k


# ---- slow: the 4+4 acceptance legs at K=4·C=4 ------------------------------


@pytest.fixture(scope="module")
def accept_engines():
    """disagg=4+4 vs a colocated tp=4 mesh engine, both at
    decode_pipeline=4 × decode_loop=4 (the deep-fused acceptance shape)."""
    pm, dm = disagg_meshes(4, 4)
    kw = dict(decode_chunk=4, n_slots=2, decode_pipeline=4, decode_loop=4,
              prefill_chunk=16, seed=9310)
    eng_c = InferenceEngine(TINY, make_mesh(MeshConfig(tp=4)), **kw)
    eng_d = InferenceEngine(TINY, dm, prefill_mesh=pm, **kw)
    yield eng_c, eng_d
    eng_c.shutdown()
    eng_d.shutdown()


@pytest.mark.slow
def test_disagg_4p4_greedy_sampled_chunked_pin(accept_engines):
    eng_c, eng_d = accept_engines
    long_p = [(3 + 5 * i) % 500 for i in range(40)]
    for prompt, sampler, seed in [([3, 4, 5], GREEDY, 0),
                                  ([7, 8, 9], SAMPLED, 11),
                                  (long_p, SAMPLED, 3)]:
        assert (_gen(eng_d, prompt, seed=seed, n=12, sampler=sampler)
                == _gen(eng_c, prompt, seed=seed, n=12, sampler=sampler))
    assert eng_d.n_kv_handoffs > 0 and eng_d.kv_handoff_bytes > 0


@pytest.mark.slow
def test_disagg_4p4_eos_mid_chunk_pin(accept_engines):
    """A row finishing ON DEVICE mid-megachunk (EOS at a non-boundary
    position) retires identically on both engines — finish_reason stop,
    zero overrun."""
    eng_c, eng_d = accept_engines
    probe = _gen(eng_c, [5, 6, 7], seed=2, n=12)
    eos = next((t for i, t in enumerate(probe)
                if i >= 4 and i % 4 != 3 and t not in probe[:i]), None)
    assert eos is not None, probe
    over0 = eng_d.n_overrun
    r_d = eng_d.generate([5, 6, 7], max_new_tokens=12, sampler=SAMPLED,
                         seed=2, eos_id=eos)
    r_c = eng_c.generate([5, 6, 7], max_new_tokens=12, sampler=SAMPLED,
                         seed=2, eos_id=eos)
    assert r_d.token_ids == r_c.token_ids
    assert r_d.finish_reason == r_c.finish_reason == "stop"
    assert eng_d.n_overrun == over0  # on-device finish: no overrun at K·C


@pytest.mark.slow
def test_disagg_4p4_constrained_pin():
    """response_format JSON mode through the full backend: the disagg
    engine's constrained stream (DFA state riding the fused decode carry
    on the decode group, grammar placed by the decode loop) equals the
    colocated engine's byte for byte."""
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    def build(url):
        return TpuBackend.from_spec(BackendSpec(name="t", url=url,
                                                model="m"))

    opts = ("n_kv_heads=4&seed=9320&decode_pipeline=4&decode_loop=4"
            "&prefill_chunk=16&decode_chunk=4&slots=2")
    b_d = build(f"tpu://llama-tiny?{opts}&disagg=4+4")
    b_c = build(f"tpu://llama-tiny?{opts}")
    body = {"model": "m", "max_tokens": 24, "temperature": 0.0, "seed": 3,
            "messages": [{"role": "user", "content": "json please"}],
            "response_format": {"type": "json_object"}}

    async def run(b):
        res = await b.complete(dict(body), {}, timeout=300)
        return res.body["choices"][0]["message"]["content"]

    assert asyncio.run(run(b_d)) == asyncio.run(run(b_c))
    assert b_d.engine.n_constrained >= 1
    assert b_d.engine.n_kv_handoffs > 0


@pytest.mark.slow
def test_disagg_members_pin():
    """members=M on disagg 2+2: each member's stream equals the members=1
    engine with that member's seed — the stacked staging cache and the
    member-aware handoff slice/write address the right rows."""
    pm, dm = disagg_meshes(2, 2)
    eng_m = InferenceEngine(TINY, dm, prefill_mesh=pm, members=2,
                            decode_chunk=4, n_slots=2, decode_pipeline=4,
                            decode_loop=4, prefill_chunk=16, seed=0)
    singles = [InferenceEngine(TINY, seed=i, decode_chunk=4, n_slots=2)
               for i in range(2)]
    try:
        want = [_gen(singles[i], [3, 4, 5], seed=9, n=6) for i in range(2)]
        got = [_gen(eng_m, [3, 4, 5], seed=9, n=6, member=i)
               for i in range(2)]
        assert got == want
        assert eng_m.n_kv_handoffs > 0
    finally:
        eng_m.shutdown()
        for e in singles:
            e.shutdown()


@pytest.mark.slow
def test_disagg_prefix_restore_pin():
    """prefix_store=host on disagg: a churn-evicted conversation's
    follow-up restores host→PREFILL-staging, rides the tail prefill at an
    offset, hands the whole prefix off to the decode slot — and still
    equals a cold colocated prefill token for token."""
    pm, dm = disagg_meshes(1, 1)
    eng_d = InferenceEngine(TINY, dm, prefill_mesh=pm, decode_chunk=4,
                            n_slots=1, prefill_chunk=16,
                            prefix_store="host", prefix_store_chunk=16,
                            seed=9330)
    eng_c = InferenceEngine(TINY, decode_chunk=4, n_slots=1,
                            prefill_chunk=16, seed=9330)
    try:
        conv = [(3 + 5 * i) % 500 for i in range(33)]
        other = [(9 + 7 * i) % 500 for i in range(33)]
        out1 = _gen(eng_d, conv, seed=4, n=6)
        eng_d.drain_prefix_store()
        _gen(eng_d, other, seed=5, n=6)  # churn the single slot
        eng_d.drain_prefix_store()
        follow = conv + out1 + [17, 19]
        assert (_gen(eng_d, follow, seed=6, n=6)
                == _gen(eng_c, follow, seed=6, n=6))
        assert eng_d.prefix_store_hits >= 1
        assert eng_d.prefix_store_tokens_restored > 0
    finally:
        eng_d.shutdown()
        eng_c.shutdown()


# ---- slow: the sharded legs — disagg=2+2&tp=2 vs colocated tp=2 ------------
#
# ISSUE 14 acceptance: per-group tensor sharding under disagg is
# token-for-token identical to the colocated tp engine at the same
# intra-group tp, across every acceptance leg — the differently-laid-out
# meshes only change WHERE bytes live (the handoff reshards on the fly,
# route="reshard"), never what gets sampled.


@pytest.fixture(scope="module")
def sharded_engines():
    """disagg=2+2&tp=2 (both groups tp-sharded) vs a colocated tp=2 mesh
    engine, both at decode_pipeline=4 × decode_loop=4."""
    pm, dm = disagg_meshes(2, 2, tp=2)
    kw = dict(decode_chunk=4, n_slots=2, decode_pipeline=4, decode_loop=4,
              prefill_chunk=16, seed=9340)
    import jax

    eng_c = InferenceEngine(TINY, make_mesh(MeshConfig(tp=2),
                                            jax.devices()[:2]), **kw)
    eng_d = InferenceEngine(TINY, dm, prefill_mesh=pm, **kw)
    yield eng_c, eng_d
    eng_c.shutdown()
    eng_d.shutdown()


@pytest.mark.slow
def test_disagg_tp_greedy_sampled_chunked_pin(sharded_engines):
    eng_c, eng_d = sharded_engines
    long_p = [(3 + 5 * i) % 500 for i in range(40)]
    for prompt, sampler, seed in [([3, 4, 5], GREEDY, 0),
                                  ([7, 8, 9], SAMPLED, 11),
                                  (long_p, SAMPLED, 3)]:
        assert (_gen(eng_d, prompt, seed=seed, n=12, sampler=sampler)
                == _gen(eng_c, prompt, seed=seed, n=12, sampler=sampler))
    assert eng_d.n_kv_handoffs > 0 and eng_d.kv_handoff_bytes > 0
    # tp-sharded staging slices cross the group boundary via the on-the-
    # fly reshard route (quorum_tpu_kv_handoff_bytes_total{route=})
    from quorum_tpu import observability as obs

    assert obs.KV_HANDOFF_BYTES.value_of(route="reshard") > 0


@pytest.mark.slow
def test_disagg_tp_eos_mid_chunk_pin(sharded_engines):
    eng_c, eng_d = sharded_engines
    probe = _gen(eng_c, [5, 6, 7], seed=2, n=12)
    eos = next((t for i, t in enumerate(probe)
                if i >= 4 and i % 4 != 3 and t not in probe[:i]), None)
    assert eos is not None, probe
    over0 = eng_d.n_overrun
    r_d = eng_d.generate([5, 6, 7], max_new_tokens=12, sampler=SAMPLED,
                         seed=2, eos_id=eos)
    r_c = eng_c.generate([5, 6, 7], max_new_tokens=12, sampler=SAMPLED,
                         seed=2, eos_id=eos)
    assert r_d.token_ids == r_c.token_ids
    assert r_d.finish_reason == r_c.finish_reason == "stop"
    assert eng_d.n_overrun == over0


@pytest.mark.slow
def test_disagg_tp_constrained_pin():
    """response_format JSON mode through the full backend at
    disagg=2+2&tp=2 vs colocated tp=2 — byte for byte."""
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    def build(url):
        return TpuBackend.from_spec(BackendSpec(name="t", url=url,
                                                model="m"))

    opts = ("n_kv_heads=4&seed=9350&decode_pipeline=4&decode_loop=4"
            "&prefill_chunk=16&decode_chunk=4&slots=2")
    b_d = build(f"tpu://llama-tiny?{opts}&disagg=2+2&tp=2")
    b_c = build(f"tpu://llama-tiny?{opts}&tp=2")
    body = {"model": "m", "max_tokens": 24, "temperature": 0.0, "seed": 3,
            "messages": [{"role": "user", "content": "json please"}],
            "response_format": {"type": "json_object"}}

    async def run(b):
        res = await b.complete(dict(body), {}, timeout=300)
        return res.body["choices"][0]["message"]["content"]

    assert asyncio.run(run(b_d)) == asyncio.run(run(b_c))
    assert b_d.engine.n_constrained >= 1
    assert b_d.engine.n_kv_handoffs > 0


@pytest.mark.slow
def test_disagg_tp_members_pin():
    """members=2 on disagg=2+2&tp=2: each member's stream equals the
    colocated tp=2 members engine's — the stacked tp-sharded staging
    cache and the member-aware handoff address the right rows."""
    import jax

    pm, dm = disagg_meshes(2, 2, tp=2)
    kw = dict(members=2, decode_chunk=4, n_slots=2, decode_pipeline=4,
              decode_loop=4, prefill_chunk=16, seed=0)
    eng_d = InferenceEngine(TINY, dm, prefill_mesh=pm, **kw)
    eng_c = InferenceEngine(TINY, make_mesh(MeshConfig(tp=2),
                                            jax.devices()[:2]), **kw)
    try:
        for m in range(2):
            assert (_gen(eng_d, [3, 4, 5], seed=9, n=6, member=m)
                    == _gen(eng_c, [3, 4, 5], seed=9, n=6, member=m))
        assert eng_d.n_kv_handoffs > 0
    finally:
        eng_d.shutdown()
        eng_c.shutdown()


@pytest.mark.slow
def test_disagg_tp_prefix_restore_pin():
    """prefix_store=host on disagg=2+2&tp=2: the churn-evicted
    conversation's follow-up restores host→(tp-sharded) staging, rides
    the tail prefill, reshards across the handoff — and still equals a
    cold colocated tp=2 prefill token for token."""
    import jax

    pm, dm = disagg_meshes(2, 2, tp=2)
    eng_d = InferenceEngine(TINY, dm, prefill_mesh=pm, decode_chunk=4,
                            n_slots=1, prefill_chunk=16,
                            prefix_store="host", prefix_store_chunk=16,
                            seed=9360)
    eng_c = InferenceEngine(TINY, make_mesh(MeshConfig(tp=2),
                                            jax.devices()[:2]),
                            decode_chunk=4, n_slots=1, prefill_chunk=16,
                            seed=9360)
    try:
        conv = [(3 + 5 * i) % 500 for i in range(33)]
        other = [(9 + 7 * i) % 500 for i in range(33)]
        out1 = _gen(eng_d, conv, seed=4, n=6)
        eng_d.drain_prefix_store()
        _gen(eng_d, other, seed=5, n=6)  # churn the single slot
        eng_d.drain_prefix_store()
        follow = conv + out1 + [17, 19]
        assert (_gen(eng_d, follow, seed=6, n=6)
                == _gen(eng_c, follow, seed=6, n=6))
        assert eng_d.prefix_store_hits >= 1
        assert eng_d.prefix_store_tokens_restored > 0
    finally:
        eng_d.shutdown()
        eng_c.shutdown()
