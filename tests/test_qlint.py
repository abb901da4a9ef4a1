"""qlint: the hot-path static-analysis suite (fast tier).

Per-rule positive/negative fixtures: a seeded violation (a ``.item()`` in a
hot-path snippet, a guarded-field write outside ``_cond``, a jit-per-call
recompile hazard) must FAIL, the clean twin must PASS — so the checker
itself can never silently rot. Plus the merged-tree gates: the package lints
clean, the baseline stays empty (burn-down only), ``_GUARDED_BY`` covers
every field the engine documents as ``_cond``-guarded, the program-key
budget classifies every live cache key, and the runtime sentinels hold —
a warmed engine compiles nothing, and the decode loop is token-for-token
identical under ``jax.transfer_guard("disallow")``.
"""

import textwrap
import time

import pytest

from quorum_tpu.analysis import budget, compile_watch
from quorum_tpu.analysis import qlint as ql


def _lint(tmp_path, source: str):
    p = tmp_path / "fixture.py"
    p.write_text(textwrap.dedent(source))
    findings, suppressed, _, _ = ql.run_qlint([p])
    return findings


def _kinds(findings):
    return {f.kind for f in findings}


# ---- sync-taboo rule -------------------------------------------------------


def test_sync_item_call_flagged(tmp_path):
    fs = _lint(tmp_path, """
        def hot(x):
            return x.item()
    """)
    assert "item-call" in _kinds(fs)


def test_sync_tolist_and_np_asarray_flagged(tmp_path):
    fs = _lint(tmp_path, """
        import numpy as np
        def hot(x):
            a = x.tolist()
            b = np.asarray(x)
            return a, b
    """)
    assert {"tolist-call", "np-asarray"} <= _kinds(fs)


def test_sync_device_tracked_cast_and_truthiness_flagged(tmp_path):
    fs = _lint(tmp_path, """
        import jax.numpy as jnp
        def hot(x):
            y = jnp.sum(x)
            if y:                 # truthiness on a device array
                pass
            return float(y)       # blocking scalar cast
    """)
    assert {"array-truthiness", "host-scalar-cast"} <= _kinds(fs)


def test_sync_clean_host_path_passes(tmp_path):
    fs = _lint(tmp_path, """
        import numpy as np
        def _host_fetch(*xs):
            ...
        def hot(payload):
            fetched = _host_fetch(payload)
            toks = np.asarray(fetched)       # already on host
            vals = [float(v) for v in toks]  # host floats
            return toks.tolist(), vals       # host tolist
    """)
    assert fs == []


def test_sync_block_until_ready_needs_annotation(tmp_path):
    fs = _lint(tmp_path, """
        import jax
        def hot(x):
            jax.block_until_ready(x)
    """)
    assert "block-until-ready" in _kinds(fs)


def test_sync_annotated_suppression_with_reason_passes(tmp_path):
    fs = _lint(tmp_path, """
        import jax
        def hot(x):
            # qlint: allow-sync(bench-only drain point)
            jax.block_until_ready(x)
    """)
    assert fs == []


def test_sync_empty_suppression_reason_fails(tmp_path):
    fs = _lint(tmp_path, """
        import jax
        def hot(x):
            jax.block_until_ready(x)  # qlint: allow-sync()
    """)
    assert "empty-suppression-reason" in _kinds(fs)


# ---- recompile-budget rule -------------------------------------------------


def test_recompile_jit_immediate_call_flagged(tmp_path):
    fs = _lint(tmp_path, """
        import jax
        def rebuild(f, x):
            return jax.jit(f)(x)
    """)
    assert "jit-immediate-call" in _kinds(fs)


def test_recompile_jit_in_loop_flagged(tmp_path):
    fs = _lint(tmp_path, """
        import jax
        def build(fns):
            out = []
            for f in fns:
                out.append(jax.jit(f))
            return out
    """)
    assert "jit-in-loop" in _kinds(fs)


def test_recompile_non_pow2_shape_knob_flagged(tmp_path):
    fs = _lint(tmp_path, """
        def make(engine_cls):
            return engine_cls(decode_chunk=6)
    """)
    assert "non-pow2-shape-knob" in _kinds(fs)


def test_recompile_cached_wrapper_passes(tmp_path):
    fs = _lint(tmp_path, """
        import jax
        _CACHE = {}
        def get_fn(key, f):
            fn = _CACHE.get(key)
            if fn is None:
                fn = _CACHE[key] = jax.jit(f)
            return fn
        def make(engine_cls):
            return engine_cls(decode_chunk=8)
    """)
    assert fs == []


# ---- guarded-by rule -------------------------------------------------------

_GUARDED_HEADER = """
    import threading
    _GUARDED_BY = {
        "_pending": {"lock": "_cond"},
        "_slots": {"lock": "_cond", "holders": ["_release_slot"]},
        "_inflight": {"owner": ["_fill", "_drain"]},
    }
    class Engine:
        def __init__(self):
            self._cond = threading.Condition()
            self._pending = []   # __init__ precedes publication: exempt
            self._slots = [None]
            self._inflight = []
"""


def test_guarded_unlocked_mutation_flagged(tmp_path):
    fs = _lint(tmp_path, _GUARDED_HEADER + """
        def submit(self, req):
            self._pending.append(req)    # no lock: the PR 3/4/7 race class
    """)
    assert any(k.startswith("unguarded-append-_pending") for k in _kinds(fs))


def test_guarded_locked_mutation_passes(tmp_path):
    fs = _lint(tmp_path, _GUARDED_HEADER + """
        def submit(self, req):
            with self._cond:
                self._pending.append(req)
                self._slots[0] = req
    """)
    assert fs == []


def test_guarded_subscript_write_outside_lock_flagged(tmp_path):
    fs = _lint(tmp_path, _GUARDED_HEADER + """
        def steal(self, req):
            self._slots[0] = req
    """)
    assert any("unguarded-write-_slots" in k for k in _kinds(fs))


def test_guarded_holder_method_passes(tmp_path):
    fs = _lint(tmp_path, _GUARDED_HEADER + """
        def _release_slot(self, i):
            self._slots[i] = None        # documented: caller holds _cond
    """)
    assert fs == []


def test_guarded_single_owner_methods(tmp_path):
    fs = _lint(tmp_path, _GUARDED_HEADER + """
        def _fill(self, c):
            self._inflight.append(c)     # owner thread: fine, no lock
        def elsewhere(self, c):
            self._inflight.append(c)     # not an owner: race
    """)
    kinds = _kinds(fs)
    assert any("unguarded-append-_inflight" in k for k in kinds)
    assert len([f for f in fs if "_inflight" in f.kind]) == 1


def test_guarded_allow_unguarded_annotation(tmp_path):
    fs = _lint(tmp_path, _GUARDED_HEADER + """
        def racy_but_ok(self, req):
            # qlint: allow-unguarded(write happens before thread start)
            self._pending.append(req)
    """)
    assert fs == []


# ---- merged-tree gates -----------------------------------------------------


def test_package_lints_clean_and_fast():
    t0 = time.perf_counter()
    new, suppressed, stale, _ = ql.run_qlint()
    dt = time.perf_counter() - t0
    assert new == [], [f.render() for f in new]
    assert dt < 10.0, f"qlint took {dt:.1f}s; budget is 10s"
    # every suppression in the tree carries a reason (enforced by the
    # checker; this pins that the count stays deliberate)
    assert all(reason for _, reason in suppressed)


def test_baseline_is_empty_and_shrink_only():
    base = ql.load_baseline()
    assert base["findings"] == [], (
        "the shipped baseline must stay empty: fix or reason-annotate "
        "findings instead of baselining them")
    assert base["max_count"] == 0


def test_baseline_update_refuses_to_grow(tmp_path):
    import json

    base = tmp_path / "baseline.json"
    base.write_text(json.dumps({"max_count": 0, "findings": []}))
    finding = ql.Finding("sync", "item-call", "x.py", 1, "hot", "msg")
    with pytest.raises(SystemExit, match="refusing to grow"):
        ql.update_baseline([finding], path=base)
    # shrink (or stay) is always allowed
    data = ql.update_baseline([], path=base)
    assert data["findings"] == [] and data["max_count"] == 0


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def hot(x):\n    return x.item()\n")
    assert ql.main([str(bad)]) == 1
    good = tmp_path / "good.py"
    good.write_text("def cold(x):\n    return x\n")
    assert ql.main([str(good)]) == 0
    assert ql.main([]) == 0  # the merged tree is clean


def test_guarded_map_covers_documented_scheduler_state():
    from quorum_tpu.engine import engine as eng_mod

    gm = eng_mod._GUARDED_BY
    # the fields the "Scheduler state, guarded by _cond's lock" block
    # promises — the map is the machine-checked source of truth for them
    for field in ("_pending", "_slots", "_admitting", "_claimed"):
        assert gm[field].get("lock") == "_cond", field
    # the cross-loop queues added by PR 3/7 ride the same lock
    for field in ("_handoffs", "_pending_snaps", "_pending_dfa_resets"):
        assert gm[field].get("lock") == "_cond", field


# ---- program-key budget ----------------------------------------------------


def test_budget_classifies_every_documented_family():
    assert budget.classify_decode_key((4, False, 32)) == "plain"
    assert budget.classify_decode_key(("dfa", 4, False, 32, 8)) == "dfa"
    assert budget.classify_decode_key(("loop", 4, 4, False, 64)) == "loop"
    assert budget.classify_decode_key(
        ("loop", 4, "dfa", 4, False, 64, 8)) == "loop_dfa"
    assert budget.classify_admit_key(16) == "single_shot"
    assert budget.classify_admit_key("register") == "register"
    assert budget.classify_admit_key(("seg", 16, 64)) == "seg"
    assert budget.classify_admit_key(("hslice", 32)) == "hslice"


def test_budget_rejects_unknown_and_drifted_keys():
    with pytest.raises(budget.UnbudgetedProgramKey):
        budget.classify_decode_key(("mystery", 1, 2))
    with pytest.raises(budget.UnbudgetedProgramKey):
        # a 4th component on the plain key = program-key drift
        budget.classify_decode_key((4, False, 32, 99))
    with pytest.raises(budget.UnbudgetedProgramKey):
        budget.classify_admit_key(("seg", 16))  # dropped history component


# ---- runtime sentinels -----------------------------------------------------


def _tiny_engine(**kw):
    from quorum_tpu.engine.engine import InferenceEngine
    from quorum_tpu.models.model_config import MODEL_PRESETS

    return InferenceEngine(MODEL_PRESETS["llama-tiny"], decode_chunk=4,
                           **kw)


def test_decode_loop_is_clean_under_transfer_guard_disallow():
    """The acceptance pin: decode-path output under jax.transfer_guard
    ("disallow") — dispatch ring, reap, pipelining — is token-for-token
    the unguarded output, i.e. the token critical path performs zero
    implicit transfers. (conftest defaults the whole suite to the guard;
    this test pins both modes explicitly so the contract survives a
    conftest change.)"""
    from quorum_tpu.ops.sampling import SamplerConfig

    greedy = SamplerConfig(temperature=0.0)
    e_off = _tiny_engine(decode_pipeline=2, transfer_guard="")
    try:
        want = e_off.generate([5, 6, 7], max_new_tokens=16,
                              sampler=greedy).token_ids
    finally:
        e_off.shutdown()
    e_on = _tiny_engine(decode_pipeline=2, transfer_guard="disallow")
    try:
        got = e_on.generate([5, 6, 7], max_new_tokens=16,
                            sampler=greedy).token_ids
    finally:
        e_on.shutdown()
    assert got == want and len(got) == 16


def test_transfer_guard_knob_validated():
    with pytest.raises(ValueError):
        _tiny_engine(transfer_guard="definitely-not-a-level")


def test_transfer_guard_env_typo_is_loud_off_not_a_crash(monkeypatch):
    """The env-knob convention: a typo in the serving environment must not take engine construction down —
    it logs loudly and runs with the guard OFF."""
    monkeypatch.setenv("QUORUM_TPU_TRANSFER_GUARD", "Disallow")  # bad case
    eng = _tiny_engine()
    try:
        assert eng.transfer_guard is None
    finally:
        eng.shutdown()


def test_warmed_engine_compiles_nothing():
    """The log-compiles hook behind compile_budget.json: a second,
    identical generation on a warmed engine must trigger ZERO new XLA
    compiles — any new program family fails here loudly, whatever its
    cache key looks like."""
    from quorum_tpu.ops.sampling import SamplerConfig

    greedy = SamplerConfig(temperature=0.0)
    eng = _tiny_engine(decode_pipeline=2)
    try:
        first = eng.generate([5, 6, 7], max_new_tokens=12,
                             sampler=greedy).token_ids
        before = compile_watch.compiles_total()
        second = eng.generate([5, 6, 7], max_new_tokens=12,
                              sampler=greedy).token_ids
        grew = compile_watch.compiles_total() - before
        assert grew == 0, (
            f"{grew} XLA compile(s) on a warmed engine: a program family "
            "leaked past compile_budget.json")
        assert first == second
    finally:
        eng.shutdown()


def test_recompiles_total_counts_post_warmup_compiles():
    import jax
    import jax.numpy as jnp

    from quorum_tpu import observability as obs

    compile_watch.install()
    was_warm = compile_watch.is_warm()
    try:
        compile_watch.mark_warm()
        before = obs.RECOMPILES.value
        # a program jax has never seen: its compile must land on the counter
        jax.jit(lambda x: x * 3 + 0.123456)(jnp.ones((3,)))
        assert obs.RECOMPILES.value > before
    finally:
        if not was_warm:
            compile_watch.reset_for_tests()
