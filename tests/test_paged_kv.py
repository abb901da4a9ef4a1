"""Paged KV slot memory (``kv_pages=1``, ISSUE 17 acceptance):

- **token identity**: every decode shape — greedy, sampled, EOS cut,
  constrained grammar, deep ring (decode_pipeline=4 × decode_loop=4),
  members=M, kv_quant=int8, zero_drain,
  prefix-store restore — generates EXACTLY what the dense rectangle
  generates. Paging is a capacity optimization, never a semantic change.
- **aliasing**: a tier-0 prefix hit installs page *references* (refcount
  bump + table rewrite) — the alias counter ticks and the pool does not
  pay a second copy of the shared span; a reuse length landing mid-page
  copies exactly the one boundary page (copy-on-write counter).
- **admission-time shed**: a request whose full page span can never fit
  the pool sheds synchronously (QueueFullError → 503 + Retry-After at the
  server); transient exhaustion queues — a running stream can never OOM
  because admission pre-reserves its whole span.
- **program-key contract**: paged programs live under "paged"-tagged
  compile-budget families; every ``kv_pages=0`` engine's keys stay
  byte-for-byte the dense tuples.

Host-side PageAllocator bookkeeping (refcounts, retained-chain LRU,
reclaim) and the pure device ops are fast-tier; engine-scale legs are
slow-tier like every other engine test."""

import dataclasses
import threading
import types

import numpy as np
import pytest

from quorum_tpu.analysis import budget
from quorum_tpu.cache.paging import (
    PageAllocator,
    PagedKV,
    init_paged_cache,
    page_read,
    paged_slice_rows,
    paged_write_rows,
    validate_page_config,
)
from quorum_tpu.engine.engine import InferenceEngine, QueueFullError
from quorum_tpu.models import resolve_spec
from quorum_tpu.models.model_config import MODEL_PRESETS
from quorum_tpu.ops.sampling import SamplerConfig

slow = pytest.mark.slow

SPEC = dataclasses.replace(MODEL_PRESETS["llama-tiny"], max_seq=128)
GREEDY = SamplerConfig(temperature=0.0)
SAMPLED = SamplerConfig(temperature=0.9, top_p=0.9)


# ---- PageAllocator bookkeeping (pure host, fast tier) ----------------------


def test_alloc_assign_release_refcounts():
    a = PageAllocator(4, 16)
    pages = a.alloc(3)
    assert pages == [1, 2, 3] and a.free_pages == 1
    a.assign(0, pages)
    a.release(0, keep_tokens=20)          # 20 tokens -> 2 pages retained
    assert a.retained_chain(0) == [1, 2]
    assert a.free_pages == 2              # tail page freed
    assert a.allocated_pages == 2


def test_alloc_shortfall_returns_none_not_partial():
    a = PageAllocator(2, 16)
    assert a.alloc(3) is None
    assert a.free_pages == 2              # nothing leaked


def test_adopt_transfers_refs_without_copy():
    a = PageAllocator(4, 16)
    a.assign(1, a.alloc(2))
    a.release(1, keep_tokens=32)
    refs_before = list(a.refs)
    chain = a.adopt(1)
    assert chain == [1, 2]
    assert a.refs == refs_before          # ref ownership moved, not bumped
    assert a.retained_chain(1) is None


def test_share_aliases_by_refcount_and_survives_donor_release():
    a = PageAllocator(4, 16)
    donor = a.alloc(2)
    a.assign(0, donor)
    a.release(0, keep_tokens=32)          # retained donor chain
    aliased = a.share(a.retained_chain(0))
    a.assign(1, aliased + a.alloc(1))
    assert all(a.is_shared(p) for p in aliased)
    # evicting the donor's retained entry must NOT free aliased pages
    a.drop_retained(0)
    assert a.free_pages == 1
    a.release(1, keep_tokens=0)
    assert a.free_pages == 4              # last ref dropped -> all free


def test_extend_appends_without_disturbing_chain():
    a = PageAllocator(4, 16)
    a.assign(2, a.alloc(1))
    head = list(a.chain(2))
    a.extend(2, a.alloc(2))
    assert a.chain(2)[: len(head)] == head
    assert len(a.chain(2)) == 3


def test_evict_lru_order_and_protect():
    a = PageAllocator(6, 16)
    for row in (0, 1, 2):
        a.assign(row, a.alloc(2))
        a.release(row, keep_tokens=32)
    a.touch(0)                            # 0 becomes MRU; LRU order: 1, 2, 0
    assert a.evict_lru(protect=(1,)) == 2
    assert a.evict_lru() == 1
    assert a.evict_lru(protect=(0,)) is None


def test_reclaimable_counts_only_sole_reference_pages():
    a = PageAllocator(6, 16)
    a.assign(0, a.alloc(2))
    a.release(0, keep_tokens=32)
    live = a.share(a.retained_chain(0))   # alias retained pages into row 1
    a.assign(1, live)
    assert a.reclaimable_pages() == 0     # evicting 0 frees nothing: aliased
    a.assign(2, a.alloc(2))
    a.release(2, keep_tokens=32)
    assert a.reclaimable_pages() == 2
    assert a.reclaimable_pages(protect=(2,)) == 0


def test_release_zero_keep_frees_everything_and_reset():
    a = PageAllocator(3, 16)
    a.assign(0, a.alloc(3))
    a.release(0, keep_tokens=0)
    assert a.free_pages == 3 and a.retained_chain(0) is None
    a.assign(1, a.alloc(2))
    a.reset()
    assert a.free_pages == 3 and a.chains == {}


def test_page_zero_is_never_handed_out():
    a = PageAllocator(3, 4)
    assert 0 not in a.alloc(3)


# ---- config validation (fast tier) -----------------------------------------


def test_validate_page_config_rejects_bad_sizes():
    with pytest.raises(ValueError, match="power of two"):
        validate_page_config(128, 24)
    with pytest.raises(ValueError, match="divide max_seq"):
        validate_page_config(96, 64)
    validate_page_config(128, 32)         # ok


# ---- program-key contract (fast tier) --------------------------------------


def _keyer(**over):
    """Call the real _decode_key with a minimal stand-in self — pins the
    dense tuples without paying an engine construction."""
    ns = types.SimpleNamespace(kv_pages=False, _g_bucket=256)
    for k, v in over.items():
        setattr(ns, k, v)
    return lambda *a, **kw: InferenceEngine._decode_key(ns, *a, **kw)


def test_dense_decode_keys_are_byte_identical_to_pre_paged():
    """kv_pages=0 engines must compile and dispatch the exact pre-paged
    program variants: the unconstrained single-chunk key stays the bare
    3-tuple, the loop/dfa tags stay in their pinned positions."""
    k = _keyer()
    assert k(4, False, 128, False) == (4, False, 128)
    assert k(4, True, 64, True) == ("dfa", 4, True, 64, 256)
    assert k(4, False, 128, False, n_chunks=4) == ("loop", 4, 4, False, 128)
    assert k(2, False, 32, True, n_chunks=2) == (
        "loop", 2, "dfa", 2, False, 32, 256)


def test_paged_decode_keys_prefix_the_dense_tuples():
    k = _keyer(kv_pages=True)
    assert k(4, False, 128, False) == ("paged", 4, False, 128)
    assert k(4, False, 128, False, n_chunks=4) == (
        "paged", "loop", 4, 4, False, 128)
    assert k(4, True, 64, True) == ("paged", "dfa", 4, True, 64, 256)


def test_budget_classifies_paged_families():
    cases = {
        ("paged", 4, False, 128): "paged_plain",
        ("paged", "dfa", 4, False, 128, 2): "paged_dfa",
        ("paged", "loop", 4, 4, False, 128): "paged_loop",
        ("paged", "loop", 4, "dfa", 4, False, 128, 2): "paged_loop_dfa",
    }
    for key, fam in cases.items():
        assert budget.classify_decode_key(key) == fam
    assert budget.classify_admit_key(("page_copy",)) == "page_copy"
    with pytest.raises(budget.UnbudgetedProgramKey):
        budget.classify_decode_key(("paged", "pp", 4, False, 128))


# ---- pure device ops (small arrays, fast tier) ------------------------------

OPS_SPEC = resolve_spec("llama-tiny", {"max_seq": "32"})


def test_wire_roundtrip_and_zero_sink():
    """paged_write_rows → paged_slice_rows is the identity on the written
    span, the zero sink stays zero, and unreserved tail reads gather
    zeros (page_read past the chain hits the sink)."""
    ck, _ = init_paged_cache(OPS_SPEC, batch=2, n_pages=8, page_size=8)
    ell, k, hd = OPS_SPEC.n_layers, OPS_SPEC.n_kv_heads, OPS_SPEC.head_dim
    # reserve pages 1..4 for row 0 host-side, upload the table
    tab = np.zeros((2, 4), np.int32)
    tab[0] = [1, 2, 3, 4]
    ck = PagedKV(ck.pool, np.broadcast_to(tab, (ell,) + tab.shape).copy())
    rng = np.random.default_rng(0)
    chunk = rng.standard_normal((ell, k, 20, hd)).astype(np.float32)
    ck = paged_write_rows(ck, chunk, 0, 3)
    out = np.asarray(paged_slice_rows(ck, 0, 3, 20))
    np.testing.assert_allclose(out, chunk, rtol=1e-2, atol=1e-2)  # bf16 pool
    pool = np.asarray(ck.pool)
    assert not pool[:, 0].any(), "zero sink was written"
    # per-layer window read: row 1 has no pages -> all zeros via the sink
    layer0 = PagedKV(ck.pool[0], ck.table[0])
    win = np.asarray(page_read(layer0, 16))
    assert not win[1].any()
    np.testing.assert_allclose(win[0, :, 3:16], chunk[0, :, :13],
                               rtol=1e-2, atol=1e-2)


def test_int8_wire_roundtrip():
    ck, _ = init_paged_cache(OPS_SPEC, batch=1, n_pages=4, page_size=8,
                             kv_quant="int8")
    ell, k, hd = OPS_SPEC.n_layers, OPS_SPEC.n_kv_heads, OPS_SPEC.head_dim
    tab = np.zeros((1, 4), np.int32)
    tab[0] = [1, 2, 0, 0]
    ck = PagedKV(ck.pool, np.broadcast_to(tab, (ell,) + tab.shape).copy())
    rng = np.random.default_rng(1)
    q8 = rng.integers(-127, 127, (ell, k, 10, hd), dtype=np.int8)
    sc = rng.random((ell, k, 10)).astype(np.float32)
    ck = paged_write_rows(ck, (q8, sc), 0, 0)
    oq, os_ = paged_slice_rows(ck, 0, 0, 10)
    np.testing.assert_array_equal(np.asarray(oq), q8)
    np.testing.assert_allclose(np.asarray(os_), sc, rtol=1e-6)


# ---- engine composition rejections (slow: engine-scale) ---------------------


@slow
def test_kv_pages_rejects_unsupported_knobs():
    with pytest.raises(ValueError, match="power of two"):
        InferenceEngine(SPEC, kv_pages=True, kv_page_size=24)


# ---- token-identity legs (slow: engine-scale) -------------------------------


def _pair(**kw):
    dense = InferenceEngine(SPEC, seed=0, **kw)
    paged = InferenceEngine(SPEC, seed=0, kv_pages=True, **kw)
    return dense, paged


def _gen(eng, p, n, sampler=GREEDY, seed=0, member=0):
    return list(eng.generate_stream(p, max_new_tokens=n, sampler=sampler,
                                    seed=seed, member=member))


@slow
def test_paged_matches_dense_and_budget_families():
    dense, paged = _pair(n_slots=4, prefill_chunk=16)
    try:
        for p in ([5, 6, 7, 8, 9], [11, 12, 13], list(range(3, 40))):
            assert _gen(dense, p, 12) == _gen(paged, p, 12)
        # EOS cut: force a stop on the token the stream actually emits
        ref = _gen(dense, [5, 6, 7], 8)
        eos = ref[1]
        a = dense.generate([5, 6, 7], max_new_tokens=8, sampler=GREEDY,
                           eos_id=eos)
        b = paged.generate([5, 6, 7], max_new_tokens=8, sampler=GREEDY,
                           eos_id=eos)
        assert a.token_ids == b.token_ids
        assert b.finish_reason == a.finish_reason == "stop"
        # every compiled key classifies into a paged family; dense engine
        # compiled zero paged programs
        fams = budget.decode_families(paged._decode_cache)
        assert fams and all(f.startswith("paged_") for f in fams)
        budget.admit_families(paged._admit_cache)  # raises on unknown keys
        assert not any(f.startswith("paged_")
                       for f in budget.decode_families(dense._decode_cache))
        m = paged.metrics()
        assert m["kv_pages"] == 1 and m["kv_page_size"] == 16
        assert m["kv_pages_allocated"] + m["kv_pages_free"] == \
            paged.kv_pool_pages
    finally:
        dense.shutdown()
        paged.shutdown()


@slow
def test_paged_matches_dense_deep_ring():
    """decode_pipeline=4 × decode_loop=4."""
    dense, paged = _pair(n_slots=3, prefill_chunk=16, decode_pipeline=4,
                         decode_loop=4)
    try:
        for s in (GREEDY, SAMPLED):
            for p in ([5, 6, 7], list(range(3, 45)), [7, 8, 9, 10] * 8):
                assert _gen(dense, p, 20, s, seed=7) == \
                    _gen(paged, p, 20, s, seed=7)
    finally:
        dense.shutdown()
        paged.shutdown()


@slow
def test_paged_matches_dense_constrained():
    from quorum_tpu.constrain import compile_response_format
    from quorum_tpu.engine.tokenizer import ByteTokenizer

    tok = ByteTokenizer(SPEC.vocab_size)
    schema = {"type": "object", "properties": {"ok": {"type": "boolean"}}}
    g = compile_response_format(
        {"type": "json_schema", "json_schema": {"schema": schema}},
        tok, SPEC.vocab_size)
    dense, paged = _pair(n_slots=2, prefill_chunk=16)
    try:
        outs = []
        for eng in (dense, paged):
            req = eng.submit(tok.encode("go"), max_new_tokens=48,
                             sampler=SamplerConfig(temperature=0.8), seed=3,
                             eos_id=tok.eos_id, grammar=g)
            outs.append(list(eng.stream_results(req)))
        assert outs[0] == outs[1]
    finally:
        dense.shutdown()
        paged.shutdown()


@slow
def test_paged_matches_dense_zero_drain_members_int8():
    for kw in (dict(n_slots=2, prefill_chunk=16, zero_drain=True),
               dict(n_slots=2, prefill_chunk=16, kv_quant="int8")):
        dense, paged = _pair(**kw)
        try:
            for p in ([5, 6, 7, 9], list(range(3, 40))):
                assert _gen(dense, p, 10) == _gen(paged, p, 10)
        finally:
            dense.shutdown()
            paged.shutdown()
    dense, paged = _pair(n_slots=2, prefill_chunk=16, members=2)
    try:
        for member in (0, 1):
            for p in ([5, 6, 7, 9], list(range(3, 40))):
                assert _gen(dense, p, 8, member=member) == \
                    _gen(paged, p, 8, member=member)
    finally:
        dense.shutdown()
        paged.shutdown()


# ---- aliasing / copy-on-write (slow) ----------------------------------------


@slow
def test_tier0_hit_aliases_pages_with_zero_kv_bytes():
    """A tier-0 prefix hit on a paged engine installs page REFERENCES: the
    alias counter ticks, prefix accounting matches dense exactly, and the
    pool never pays a second copy of the shared span (the headline
    capacity win — dense tier-0 reuse already moved zero bytes, paged
    must not regress that while gaining eviction-surviving donors)."""
    dense, paged = _pair(n_slots=2, prefill_chunk=16)
    try:
        long_p = list(range(3, 3 + 48))       # 3 pages at ps=16
        for eng in (dense, paged):
            _gen(eng, long_p, 8)
        span = paged._page_alloc.pages_for(len(long_p))
        for eng in (dense, paged):
            _gen(eng, long_p + [77], 8)
        m = paged.metrics()
        assert m["kv_page_alias_hits_total"] >= 1
        assert m["prefix_hits_total"] == dense.metrics()["prefix_hits_total"]
        # shared span counted once: well under two full copies
        assert m["kv_pages_allocated"] < 2 * span
        assert m["kv_page_cow_copies_total"] == 0  # chunk-aligned reuse
    finally:
        dense.shutdown()
        paged.shutdown()


@slow
def test_mid_page_reuse_copies_exactly_the_boundary_page():
    """page_size 32 > prefill_chunk 16: a 16-token reuse ends mid-page, so
    the tenant gets a COW clone of the boundary page — and the ORIGINAL
    chain must still decode identically after the tenant writes into its
    copy (the write-isolation half of aliasing)."""
    dense = InferenceEngine(SPEC, seed=0, n_slots=2, prefill_chunk=16)
    paged = InferenceEngine(SPEC, seed=0, n_slots=2, prefill_chunk=16,
                            kv_pages=True, kv_page_size=32)
    try:
        pre = list(range(3, 3 + 20))
        for eng in (dense, paged):
            _gen(eng, pre, 4)
        assert _gen(dense, pre[:17] + [88, 89, 90], 6) == \
            _gen(paged, pre[:17] + [88, 89, 90], 6)
        assert paged.metrics()["kv_page_cow_copies_total"] >= 1
        # the donor prefix decodes unchanged after the COW tenant wrote
        assert _gen(dense, pre + [99], 6) == _gen(paged, pre + [99], 6)
    finally:
        dense.shutdown()
        paged.shutdown()


@slow
def test_prefix_store_restore_under_paging():
    """Churn every slot so the donor's residency is gone, then re-send the
    long prompt: the host prefix store restores through paged_write_rows
    into freshly reserved pages, token-identical to the dense restore."""
    dense, paged = _pair(n_slots=2, prefill_chunk=16, prefix_store="host")
    try:
        long_p = list(range(3, 3 + 64))
        churn = [[100 + i for i in range(40)], [60 + i for i in range(40)],
                 [20 + i for i in range(40)]]
        for eng in (dense, paged):
            _gen(eng, long_p, 4)
            for c in churn:
                _gen(eng, c, 4)
        a = _gen(dense, long_p + [77], 8)
        b = _gen(paged, long_p + [77], 8)
        assert a == b
        assert paged.metrics()["prefix_store_hits_total"] == \
            dense.metrics()["prefix_store_hits_total"]
    finally:
        dense.shutdown()
        paged.shutdown()


# ---- pool exhaustion (slow) -------------------------------------------------


@slow
def test_impossible_span_sheds_at_submit():
    eng = InferenceEngine(SPEC, seed=0, n_slots=4, prefill_chunk=16,
                          kv_pages=True, kv_pool_pages=2)
    try:
        with pytest.raises(QueueFullError, match="page pool"):
            _gen(eng, list(range(3, 60)), 30)
        # a request that fits still serves — the shed is per-span, not a
        # wedged engine
        assert len(_gen(eng, [5, 6, 7], 8)) == 8
    finally:
        eng.shutdown()


@slow
def test_transient_exhaustion_queues_and_drains():
    """8 concurrent streams against an 8-page pool (4 slots): admissions
    wait for live releases instead of OOMing mid-stream, and every stream
    matches its dense twin."""
    paged = InferenceEngine(SPEC, seed=0, n_slots=4, prefill_chunk=16,
                            kv_pages=True, kv_pool_pages=8)
    dense = InferenceEngine(SPEC, seed=0, n_slots=4, prefill_chunk=16)
    try:
        outs_p, outs_d = {}, {}

        def run(eng, i, out):
            p = [3 + i, 4 + i, 5 + i] + list(range(6, 6 + 2 * i))
            out[i] = _gen(eng, p, 10, seed=i)

        ths = ([threading.Thread(target=run, args=(paged, i, outs_p))
                for i in range(8)]
               + [threading.Thread(target=run, args=(dense, i, outs_d))
                  for i in range(8)])
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=300)
        assert len(outs_p) == 8 and outs_p == outs_d
    finally:
        paged.shutdown()
        dense.shutdown()


@slow
def test_self_donor_reclaim_unwedges_own_slot():
    """A slot group's OWN retained donor chain is a page source for its
    own next claim: a donor holding most of the pool must not wedge the
    slot's re-admission (pre-PR-18 this deadlocked — _paged_fits and
    _paged_claim protected the claiming group's donor from reclaim while
    its pages were neither free nor reclaimable, so the admission waited
    forever; surfaced by chaos phase 8). The resubmission still streams
    token for token what the first run streamed."""
    eng = InferenceEngine(SPEC, seed=0, n_slots=1, kv_pages=True,
                          kv_page_size=16, decode_chunk=4)
    try:
        prompt = list(range(3, 33))  # 30 tokens
        # 30 prompt + 48 budget + 1 overshoot = 79 positions -> 5 of the
        # 8 pool pages; the retained donor after the first run holds all
        # 5, leaving only 3 free.
        first = _gen(eng, prompt, 48)
        assert len(first) == 48
        done = {}

        def run():
            done["out"] = _gen(eng, prompt, 48)

        th = threading.Thread(target=run, daemon=True)
        th.start()
        th.join(60)
        assert not th.is_alive(), "re-admission wedged on own donor"
        assert done["out"] == first
    finally:
        eng.shutdown()
