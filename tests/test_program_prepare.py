"""The program store (engine/prepare.py, ISSUE 44): load, store, load.

An engine built directly keeps and loads nothing. ``prepare=True`` (what the
serving entry passes where the persistent compile cache is on) loads, on a
pool of threads started before the weights are made, what an earlier start of
the same build and configuration stored; what the store lacks is its first
dispatch's, which compiles it whole and stores it. Here the persistent cache
is off (conftest), so the store gets a directory of the test's own
(``prepare.store_root`` patched). Tiny dense, stacked (``members=3``) and
patterned specs on the CPU: counts and equalities, no speeds.
"""

import os
import threading
import time

import jax
import numpy as np
import pytest

from quorum_tpu.analysis import budget
from quorum_tpu.engine import prepare as prepare_mod
from quorum_tpu.engine.engine import InferenceEngine
from quorum_tpu.models.model_config import resolve_spec
from quorum_tpu.ops.sampling import SamplerConfig

KINDS = {
    "dense": ("llama-tiny", {}),
    "stacked": ("llama-tiny", {"members": 3}),
    "patterned": ("k-exaone-tiny", {}),
}
CHUNK = 32  # prefill_chunk
MAX_SEQ = 64
GREEDY = SamplerConfig(temperature=0.0)
WARM = SamplerConfig(temperature=0.8, top_p=0.9)
FIRST = (list(range(3, 3 + CHUNK + 9)), 6)  # a segment and its tail


def build(kind: str, **kw) -> InferenceEngine:
    model, extra = KINDS[kind]
    return InferenceEngine(resolve_spec(model, {"max_seq": str(MAX_SEQ)}),
                           **{"decode_chunk": 4, "n_slots": 2,
                              "prefill_chunk": CHUNK, **extra, **kw})


def wait_loaded(eng: InferenceEngine, limit_s: float = 120.0) -> None:
    deadline = time.monotonic() + limit_s
    while eng.programs_preparing:
        assert time.monotonic() < deadline, "the loads never ended"
        time.sleep(0.02)


def first_answer(eng: InferenceEngine) -> list:
    return eng.generate(FIRST[0], max_new_tokens=FIRST[1],
                        sampler=GREEDY).token_ids


def sweep(eng: InferenceEngine) -> list:
    """Requests over every admit bucket, a prompt whose segments cross
    every history bucket and end in a shorter one, decodes that run through
    every decode history bucket, and a logprobs request: per request the
    tokens and the logprob records."""
    def prompt(n, salt):
        return [(salt + 13 * i) % 500 + 3 for i in range(n)]

    asks = [(prompt(5, 1), 50, GREEDY, -1),     # admit 16; history 16..64
            (prompt(20, 2), 8, WARM, -1),       # admit 32
            (prompt(CHUNK + 10, 3), 12, GREEDY, -1),  # a segment + its tail
            (prompt(9, 4), 45, WARM, 0)]        # the logprobs variants
    out = []
    for member in range(eng.members):
        for ids, n_new, sampler, lp in asks:
            req = eng.submit(ids, max_new_tokens=n_new, sampler=sampler,
                             seed=7, logprobs=lp, member=member)
            tokens = list(eng.stream_results(req))
            out.append((tokens, [(float(a), np.asarray(b).tolist(),
                                  np.asarray(c).tolist())
                                 for a, b, c in req.lp]))
    return out


def files(root) -> list:
    """Every program file under ``root``, relative."""
    return sorted(os.path.relpath(os.path.join(base, n), root)
                  for base, _, names in os.walk(root) for n in names)


def programs(eng: InferenceEngine) -> dict:
    """{(memo, key): program} over the engine's three memos (of the
    utility programs, the two that ``_program`` builds)."""
    return {(memo, key): fn
            for memo in ("_admit_cache", "_decode_cache", "_util_fns")
            for key, fn in getattr(eng, memo).items()
            if memo != "_util_fns" or key in ("ledger_mark", "moe_snapshot")}


@pytest.fixture
def store(tmp_path, monkeypatch):
    root = str(tmp_path / "programs")
    monkeypatch.setattr(prepare_mod, "store_root", lambda: root)
    return root


@pytest.fixture(scope="module", params=list(KINDS))
def cycle(request, tmp_path_factory):
    """One kind's load, store, load: a lazy engine; a first start on an
    empty store; a second start, sent a request while its loads are held
    back. Built once a kind: every case below reads it."""
    kind = request.param
    root = str(tmp_path_factory.mktemp(kind) / "programs")
    out = {"kind": kind, "root": root}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(prepare_mod, "store_root", lambda: root)
        lazy = build(kind)
        out.update(lazy=lazy, lazy_first=first_answer(lazy),
                   lazy_sweep=sweep(lazy), lazy_metrics=lazy.metrics())
        one = build(kind, prepare=True)
        wait_loaded(one)
        out.update(one_first=first_answer(one), one_sweep=sweep(one),
                   one_programs=programs(one), one_metrics=one.metrics())
        one.shutdown()  # what it built is on disk when this returns
        out["stored"] = files(root)
        real, gate = prepare_mod._load, threading.Event()

        def held(path, device):
            gate.wait()
            return real(path, device)

        mp.setattr(prepare_mod, "_load", held)
        two = build(kind, prepare=True)
        out.update(two=two, early=two.programs_preparing)
        threading.Timer(0.5, gate.set).start()
        out["two_first"] = first_answer(two)  # waits for its programs
        wait_loaded(two)
        out.update(two_sweep=sweep(two), two_metrics=two.metrics())
    yield out
    two.shutdown()
    lazy.shutdown()


def test_an_engine_built_directly_keeps_and_loads_nothing(cycle):
    lazy, m = cycle["lazy"], cycle["lazy_metrics"]
    assert lazy._prep is None and lazy.programs_preparing == 0
    assert m["programs_prepared_total"] == 0 and m["prepare_seconds"] == 0
    assert m["programs_on_demand_total"] > 0  # every program it ran
    assert lazy.health()["programs_preparing"] == 0
    assert not any(isinstance(fn, jax.stages.Compiled)
                   for fn in programs(lazy).values())


def test_a_first_start_builds_on_demand_and_stores_what_it_built(cycle):
    m = cycle["one_metrics"]
    assert m["programs_prepared_total"] == 0
    assert m["programs_on_demand_total"] == len(cycle["stored"]) > 8
    # one build, one configuration: one directory
    assert len({os.path.dirname(p) for p in cycle["stored"]}) == 1
    assert all(isinstance(fn, jax.stages.Compiled)
               for fn in cycle["one_programs"].values())


def test_a_second_start_loads_them_and_builds_nothing(cycle):
    two, m = cycle["two"], cycle["two_metrics"]
    assert m["programs_on_demand_total"] == 0, m
    assert m["programs_prepared_total"] == len(cycle["stored"])
    assert m["prepare_seconds"] > 0
    assert two._prep.failed == 0 and two.health()["programs_preparing"] == 0
    assert files(cycle["root"]) == cycle["stored"]  # and stored none anew


def test_tokens_and_logprobs_equal_a_lazy_engines(cycle):
    want = cycle["lazy_sweep"]
    assert cycle["one_sweep"] == want and cycle["two_sweep"] == want
    assert any(lps for _, lps in want)  # the logprobs request returned some


def test_a_request_during_the_loads_waits_and_answers(cycle):
    assert cycle["early"] > 0  # the pool still worked when it was sent
    assert cycle["two_first"] == cycle["one_first"] == cycle["lazy_first"]


def test_the_loaded_program_is_the_one_the_first_dispatch_compiled(cycle):
    """The store returns the executable a first dispatch made from its
    real arguments: the same optimised text, under the same memo and key."""
    one, two = cycle["one_programs"], programs(cycle["two"])
    assert set(one) == set(two)
    for at, prog in one.items():
        assert isinstance(two[at], jax.stages.Compiled), at
        assert two[at].as_text() == prog.as_text(), at


def test_stored_keys_are_the_budgets_families_and_no_new_one(cycle):
    two, lazy = cycle["two"], cycle["lazy"]
    assert budget.decode_families(two._decode_cache) == {"plain"}
    assert budget.admit_families(two._admit_cache) \
        == budget.admit_families(lazy._admit_cache)
    # the same keys as a lazy engine reached: the store adds none
    assert set(programs(two)) == set(programs(lazy))


@pytest.mark.parametrize("kind", ["dense", "stacked"])
def test_a_file_that_does_not_load_leaves_its_key_on_demand(kind, store):
    """One stored program is torn: logged, removed, its key built by the
    first dispatch that needs it and stored again, every other program
    loaded, the engine serving."""
    eng = build(kind, prepare=True)
    try:
        want = first_answer(eng)
    finally:
        eng.shutdown()
    stored = files(store)
    torn = next(p for p in stored if "_decode_fn=" in p)
    with open(os.path.join(store, torn), "wb") as f:
        f.write(b"not a program")
    eng = build(kind, prepare=True)
    try:
        wait_loaded(eng)
        assert eng._prep.failed == 1
        assert eng.metrics()["programs_prepared_total"] == len(stored) - 1
        assert first_answer(eng) == want
        assert eng.metrics()["programs_on_demand_total"] == 1
        assert eng.metrics()["failures_total"] == 0
    finally:
        eng.shutdown()
    assert files(store) == stored
    assert os.path.getsize(os.path.join(store, torn)) > 1000


def test_a_sharded_staged_or_paged_engine_keeps_nothing(store):
    from quorum_tpu.parallel.mesh import MeshConfig, make_mesh

    spec = resolve_spec("llama-tiny")
    for kw in (dict(mesh=make_mesh(MeshConfig(tp=2))), dict(zero_drain=True),
               dict(kv_pages=True)):
        eng = InferenceEngine(spec, n_slots=2, prefill_chunk=CHUNK,
                              prepare=True, **kw)
        try:
            assert eng._prep is None and eng.programs_preparing == 0
        finally:
            eng.shutdown()
    assert not os.path.exists(store)


def test_no_store_where_the_persistent_cache_is_off():
    assert prepare_mod.store_root() is None  # conftest turns the cache off
    eng = build("dense", prepare=True)
    try:
        assert eng._prep is None
        assert first_answer(eng)
        assert eng.metrics()["programs_prepared_total"] == 0
    finally:
        eng.shutdown()


def test_another_configuration_has_a_directory_of_its_own(store):
    """Two engines that differ in a shape option share no program: the
    second loads nothing of the first's, a third like the first loads."""
    def start(**kw):
        eng = build("dense", prepare=True, **kw)
        try:
            wait_loaded(eng)
            first_answer(eng)
            return eng.metrics()["programs_prepared_total"]
        finally:
            eng.shutdown()

    assert start() == 0
    built = len(files(store))
    assert start(n_slots=3) == 0
    assert len(files(store)) == 2 * built
    assert len({os.path.dirname(p) for p in files(store)}) == 2
    assert start() == built


def test_variants_behind_an_option_are_built_in_every_process():
    cls = InferenceEngine
    assert cls._decode_fn.kept((4, False, 64)) and cls._decode_fn.kept(
        (4, True, 64))
    assert not cls._decode_fn.kept(("dfa", 4, False, 64, 8))
    assert not cls._decode_fn.kept(("loop", 2, 4, False, 64))
    kept = {name for name in dir(cls)
            if getattr(getattr(cls, name), "kept", None)
            and getattr(cls, name).kept((4, False, 64))}
    assert kept == {"_admit_fn", "_admit_fn_members", "_seg_fn",
                    "_seg_fn_members", "_register_fn", "_decode_fn",
                    "_mark_fn", "_moe_snapshot_fn"}


def test_a_start_keeps_its_build_and_the_other_used_last(store, monkeypatch):
    """An edit to the package orphans a build's files: a start removes
    every build's directory but its own and the other used last."""
    device = jax.devices()[0]
    for i, name in enumerate(("older", "old", "flat-file-of-no-build")):
        path = os.path.join(store, name)
        if "file" in name:
            open(path, "w").close()
        else:
            os.makedirs(os.path.join(path, "engine"))
            open(os.path.join(path, "engine", "_admit_fn=16"), "w").close()
        os.utime(path, (1000 + i, 1000 + i))
    os.utime(os.path.join(store, "old"), (5000, 5000))
    prep = prepare_mod.Preparation.open("t", device, ("a configuration",))
    prep.seal()
    build_dir = prepare_mod.build_digest(device)
    assert sorted(os.listdir(store)) == sorted([build_dir, "old"])
    # another build of the package: its own directory, ours is the other
    monkeypatch.setattr(prepare_mod, "build_digest", lambda device: "new")
    prepare_mod.Preparation.open("t", device, ("a configuration",)).seal()
    assert sorted(os.listdir(store)) == sorted([build_dir, "new"])


def _small_program():
    return jax.jit(lambda a: a + 1).lower(
        jax.ShapeDtypeStruct((2, 2), np.float32)).compile()


def test_the_store_keeps_under_the_caches_size_limit(tmp_path):
    """Where the persistent cache has a size limit, so has the whole of
    the store, over every build: the least recently loaded files go
    first, and a file a peer removed meanwhile is no error."""
    root = tmp_path / "programs"
    (root / "b1" / "e").mkdir(parents=True)
    (root / "b2" / "e").mkdir(parents=True)
    prog = _small_program()
    at = {n: str(root / ("b1" if n in "ab" else "b2") / "e" / n)
          for n in "abcde"}
    prepare_mod._store(at["a"], prog, str(root))
    size = os.path.getsize(at["a"])
    jax.config.update("jax_compilation_cache_max_size", int(2.5 * size))
    try:
        for n in "bcd":
            time.sleep(0.02)
            prepare_mod._store(at[n], prog, str(root))
        assert [os.path.basename(p) for p in files(root)] == ["c", "d"]
        time.sleep(0.02)  # a file's time is the clock's last tick
        assert prepare_mod._load(at["c"], jax.devices()[0])
        time.sleep(0.02)
        prepare_mod._store(at["e"], prog, str(root))  # d goes: c was loaded
        assert [os.path.basename(p) for p in files(root)] == ["c", "e"], \
            (prepare_mod._files(str(root)), size, os.path.getsize(at["e"]))
    finally:
        jax.config.update("jax_compilation_cache_max_size", -1)
    os.remove(at["c"])
    assert [p for _, _, p in prepare_mod._files(str(root))] == [at["e"]]


def test_shutdown_waits_for_what_the_first_dispatches_built(store,
                                                           monkeypatch):
    """A program built seconds before the server stops still reaches the
    store: ``shutdown`` returns once its writer has."""
    real = prepare_mod._store

    def slow(path, prog, root):
        time.sleep(0.5)
        real(path, prog, root)

    monkeypatch.setattr(prepare_mod, "_store", slow)
    eng = build("dense", prepare=True)
    try:
        first_answer(eng)
        built = eng.metrics()["programs_on_demand_total"]
    finally:
        eng.shutdown()
    assert len(files(store)) == built > 0
    assert not [p for p in files(store) if p.endswith(".tmp")]


def test_pool_width_follows_the_machine(monkeypatch):
    for cores, want in ((1, 2), (4, 2), (8, 6), (13, 8), (30, 8)):
        monkeypatch.setattr(prepare_mod.os, "cpu_count", lambda c=cores: c)
        assert prepare_mod.pool_width() == want


def test_every_switch_that_reaches_a_program_is_in_the_digest():
    """An environment variable read where programs are built is part of
    the build's digest, or is known to act at run time only."""
    import pathlib
    import re

    import quorum_tpu

    root = pathlib.Path(quorum_tpu.__file__).parent
    read = set()
    for sub in ("models", "ops", "parallel", "cache", "engine"):
        for path in (root / sub).rglob("*.py"):
            read |= set(re.findall(r"QUORUM_TPU_[A-Z0-9_]+",
                                   path.read_text()))
    run_time_only = {"QUORUM_TPU_TRANSFER_GUARD", "QUORUM_TPU_COMPILE_CACHE",
                     "QUORUM_TPU_TOKENIZER_PATH"}
    assert read - run_time_only <= set(prepare_mod.PROGRAM_ENV), \
        read - run_time_only - set(prepare_mod.PROGRAM_ENV)
