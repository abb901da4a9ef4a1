"""Tiered KV prefix store (quorum_tpu/cache/ + the engine's snapshot/
restore hooks): host-RAM retention of decoded prefixes beyond the slots.

The contract: restoring a stored prefix is a scheduling optimization,
never a semantic change — under slot churn a follow-up turn that restores
from the host store generates token-for-token what a cold full prefill
generates. Eviction honors the byte budget; the store holds the cache's
native representation (kv_quant=int8 halves host bytes); members>1 is a
config error, not silently-wrong output.
"""

import numpy as np

from quorum_tpu.cache.prefix_store import PrefixStore
from quorum_tpu.engine.engine import InferenceEngine
from quorum_tpu.models import resolve_spec
from quorum_tpu.ops.sampling import SamplerConfig

import pytest

SPEC = resolve_spec("llama-tiny", {"max_seq": "128"})
GREEDY = SamplerConfig(temperature=0.0)
CHUNK = 16  # small alignment unit so short test prompts exercise the tier


def _prompt(n, base=3):
    return [(base + i * 7) % (SPEC.vocab_size - 1) + 1 for i in range(n)]


# ---- store unit tests (no jax, no engine) ----------------------------------


def _payload(tag: int, nbytes: int = 64):
    return [np.full((nbytes,), tag % 127, np.int8)]


def test_store_longest_match_walks_chunk_chain():
    s = PrefixStore(chunk_tokens=4, max_bytes=1 << 20)
    toks = list(range(12))
    assert s.insert(toks, 0, [_payload(0), _payload(1), _payload(2)])
    n, chunks = s.longest_match(toks + [99, 98])
    assert n == 12 and len(chunks) == 3
    # diverging suffix matches only the shared chunks
    n, chunks = s.longest_match(toks[:8] + [7, 7, 7, 7])
    assert n == 8 and len(chunks) == 2
    # a partial trailing chunk never matches (chunk granularity)
    n, _ = s.longest_match(toks[:10])
    assert n == 8
    assert s.covered(toks) == 12


def test_store_shared_prefixes_share_storage():
    s = PrefixStore(chunk_tokens=4, max_bytes=1 << 20)
    a = list(range(8))
    s.insert(a, 0, [_payload(0), _payload(1)])
    held = s.bytes_held
    # same chain re-inserted: no growth, still one copy
    assert s.insert(a, 0, [_payload(0), _payload(1)])
    assert s.bytes_held == held
    # an extension stores only its new chunk
    s.insert(a + [50, 51, 52, 53], 8, [_payload(2)])
    assert s.n_entries == 3


def test_store_eviction_honors_byte_budget_lru():
    s = PrefixStore(chunk_tokens=2, max_bytes=200)
    for i in range(5):  # 5 disjoint 64-byte chains
        s.insert([100 + 2 * i, 101 + 2 * i], 0, [_payload(i)])
    assert s.bytes_held <= 200
    assert s.n_evictions >= 2
    # the oldest chains evicted first
    assert s.longest_match([100, 101])[0] == 0
    assert s.longest_match([108, 109])[0] == 2
    # a hit refreshes recency: touch chain 2, insert another, 3 evicts next
    s.longest_match([104, 105])
    s.insert([200, 201], 0, [_payload(9)])
    assert s.longest_match([104, 105])[0] == 2


def test_store_extension_insert_keeps_own_prefix_under_pressure():
    """An over-budget insert of a chain EXTENSION must evict other chains
    (or its own tail), never the prefix chunks the new suffix depends on:
    the whole chain — validated prefix included — is LRU-refreshed
    root-newest, so eviction cannot strand unmatchable suffix bytes."""
    s = PrefixStore(chunk_tokens=2, max_bytes=200)  # fits 3×64-byte chunks
    x = [1, 2, 3, 4]
    assert s.insert(x, 0, [_payload(0), _payload(1)])
    assert s.insert([50, 51], 0, [_payload(2)])  # unrelated, now LRU-oldest
    assert s.bytes_held <= 200
    # extending X breaches the budget: the unrelated chain evicts, X stays
    # matchable root-to-leaf
    assert s.insert(x + [5, 6], 4, [_payload(3)])
    assert s.longest_match(x + [5, 6])[0] == 6
    assert s.longest_match([50, 51])[0] == 0
    assert s.bytes_held <= 200


def test_store_insert_refuses_broken_chain():
    s = PrefixStore(chunk_tokens=2, max_bytes=1 << 20)
    toks = [1, 2, 3, 4]
    with pytest.raises(ValueError, match="chunk-aligned"):
        s.insert(toks, 1, [_payload(0)])
    # offset past a never-stored prefix: refused, not a gapped chain
    assert s.insert(toks, 2, [_payload(1)]) is False
    assert s.covered(toks) == 0


# ---- engine-level tests (slow tier, like test_prefix_cache.py) -------------

# NOTE: not module-level pytestmark — the store unit tests above stay in the
# fast tier; only the engine-scale tests below are slow.
slow = pytest.mark.slow


def _store_engine(**kw):
    return InferenceEngine(SPEC, decode_chunk=4, prefill_chunk=CHUNK,
                           n_slots=1, prefix_store="host", **kw)


@slow
def test_churn_restore_matches_cold_full_prefill():
    """The scenario slot-resident caching loses (ISSUE 3 acceptance): the
    conversation's slot is reclaimed by another request; the follow-up turn
    restores its history from the host store, prefills only the tail, and
    generates byte-identically to a cold full prefill."""
    eng = _store_engine()
    ref = InferenceEngine(SPEC, decode_chunk=4, prefill_chunk=CHUNK,
                          n_slots=1)
    conv = _prompt(24)
    gen1 = eng.generate(conv, max_new_tokens=6, sampler=GREEDY,
                        seed=1).token_ids
    eng.drain_prefix_store()
    # an unrelated request reclaims the ONLY slot: tier-0 reuse is gone
    eng.generate(_prompt(30, base=500), max_new_tokens=4, sampler=GREEDY,
                 seed=9)
    turn2 = conv + gen1 + _prompt(5, base=77)
    got = eng.generate(turn2, max_new_tokens=6, sampler=GREEDY,
                       seed=2).token_ids
    assert eng.prefix_store_hits == 1
    assert eng.prefix_store_tokens_restored >= CHUNK
    m = eng.metrics()
    assert m["prefix_store_hits_total"] == 1
    assert m["prefix_store_restored_tokens_total"] >= CHUNK
    cold = ref.generate(turn2, max_new_tokens=6, sampler=GREEDY,
                        seed=2).token_ids
    assert got == cold, "host-store restore changed the generation"


@slow
def test_churn_restore_matches_cold_sampled():
    """Same churn scenario under real sampling: the restore must reproduce
    the RNG-chained stream exactly, not just the greedy argmax path."""
    sampled = SamplerConfig(temperature=0.9, top_p=0.95)
    eng = _store_engine()
    ref = InferenceEngine(SPEC, decode_chunk=4, prefill_chunk=CHUNK,
                          n_slots=1)
    conv = _prompt(24, base=9)
    gen1 = eng.generate(conv, max_new_tokens=6, sampler=sampled,
                        seed=3).token_ids
    eng.drain_prefix_store()
    eng.generate(_prompt(30, base=600), max_new_tokens=4, sampler=GREEDY)
    turn2 = conv + gen1 + _prompt(5, base=42)
    got = eng.generate(turn2, max_new_tokens=8, sampler=sampled,
                       seed=4).token_ids
    assert eng.prefix_store_hits == 1
    cold = ref.generate(turn2, max_new_tokens=8, sampler=sampled,
                        seed=4).token_ids
    assert got == cold


@slow
def test_restore_transfers_only_tail_past_slot_resident_overlap():
    """When the claimed slot already holds a resident prefix of the prompt
    and the store's match is longer, only the tail past the overlap crosses
    host→device: the overlap stays a tier-0 hit and the restored-token
    accounting reports the store's actual contribution."""
    shared = _prompt(16, base=3)
    conv = shared + _prompt(16, base=101)
    eng = _store_engine()
    ref = InferenceEngine(SPEC, decode_chunk=4, prefill_chunk=CHUNK,
                          n_slots=1)
    gen1 = eng.generate(conv, max_new_tokens=6, sampler=GREEDY,
                        seed=11).token_ids
    eng.drain_prefix_store()
    # a request SHARING the first chunk reclaims the only slot: the slot
    # keeps a 16-token resident overlap with the conversation, while the
    # store still holds its full 32-token prefix
    eng.generate(shared + _prompt(20, base=202), max_new_tokens=4,
                 sampler=GREEDY, seed=12)
    eng.drain_prefix_store()
    saved0 = eng.prefix_tokens_saved
    turn2 = conv + gen1 + _prompt(5, base=77)
    got = eng.generate(turn2, max_new_tokens=6, sampler=GREEDY,
                       seed=13).token_ids
    assert eng.prefix_store_hits == 1
    # 32 matched, 16 already slot-resident: only the 16-token tail restores
    assert eng.prefix_store_tokens_restored == CHUNK
    assert eng.prefix_tokens_saved - saved0 == CHUNK
    cold = ref.generate(turn2, max_new_tokens=6, sampler=GREEDY,
                        seed=13).token_ids
    assert got == cold, "tail-only restore changed the generation"


@slow
def test_store_composes_with_kv_quant_int8():
    """The store holds the cache's NATIVE representation: with
    kv_quant=int8 the restored prefix is the same int8+scale bytes prefill
    wrote (output equality), and host bytes per token shrink vs bf16."""
    held = {}
    for kvq in (None, "int8"):
        eng = _store_engine(kv_quant=kvq)
        ref = InferenceEngine(SPEC, decode_chunk=4, prefill_chunk=CHUNK,
                              n_slots=1, kv_quant=kvq)
        conv = _prompt(24, base=21)
        gen1 = eng.generate(conv, max_new_tokens=6, sampler=GREEDY,
                            seed=5).token_ids
        eng.drain_prefix_store()
        held[kvq] = eng.prefix_store.bytes_held
        eng.generate(_prompt(30, base=700), max_new_tokens=4, sampler=GREEDY)
        turn2 = conv + gen1 + _prompt(5, base=33)
        got = eng.generate(turn2, max_new_tokens=6, sampler=GREEDY,
                           seed=6).token_ids
        assert eng.prefix_store_hits == 1, kvq
        cold = ref.generate(turn2, max_new_tokens=6, sampler=GREEDY,
                            seed=6).token_ids
        assert got == cold, kvq
    assert held["int8"] < held[None], held


@slow
def test_engine_eviction_honors_byte_budget():
    # llama-tiny, one 16-token bf16 chunk is 4096 bytes (see the store's
    # stats) — a 5000-byte budget holds exactly one chunk.
    eng = _store_engine(prefix_store_bytes=5000)
    eng.generate(_prompt(40, base=5), max_new_tokens=4, sampler=GREEDY)
    eng.generate(_prompt(40, base=900), max_new_tokens=4, sampler=GREEDY)
    eng.drain_prefix_store()
    s = eng.prefix_store.stats()
    assert s["bytes_held"] <= 5000
    assert s["evictions_total"] >= 1
    assert eng.metrics()["prefix_store_evictions_total"] >= 1


@slow
def test_snapshot_is_incremental_across_turns():
    """Turn N+1's release must snapshot only the chunks turn N+1 added —
    the already-covered chain is not re-fetched or re-stored."""
    eng = _store_engine()
    conv = _prompt(24, base=8)
    gen1 = eng.generate(conv, max_new_tokens=6, sampler=GREEDY,
                        seed=7).token_ids
    eng.drain_prefix_store()
    inserts1 = eng.prefix_store.n_inserts
    turn2 = conv + gen1 + _prompt(20, base=90)
    eng.generate(turn2, max_new_tokens=6, sampler=GREEDY, seed=8)
    eng.drain_prefix_store()
    s = eng.prefix_store.stats()
    # turn 2 extended the chain (new entries) without re-inserting turn 1's
    assert s["inserts_total"] > inserts1
    assert s["inserts_total"] == s["entries"]


@slow
def test_members_with_prefix_store_is_config_error():
    with pytest.raises(ValueError, match="prefix_store"):
        InferenceEngine(SPEC, prefill_chunk=CHUNK, members=2,
                        prefix_store="host")
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    with pytest.raises(ValueError, match="prefix_store"):
        TpuBackend.from_spec(BackendSpec(
            name="X",
            url="tpu://llama-tiny?members=2&member=0&prefix_store=host",
            model="m"))


@slow
def test_invalid_store_knobs_rejected():
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    with pytest.raises(ValueError, match="prefix_store"):
        TpuBackend.from_spec(BackendSpec(
            name="X", url="tpu://llama-tiny?prefix_store=disk", model="m"))
    # sizing knobs without the store: a misconfiguration, not a silent no-op
    with pytest.raises(ValueError, match="prefix_store_bytes"):
        TpuBackend.from_spec(BackendSpec(
            name="X", url="tpu://llama-tiny?prefix_store_bytes=1g",
            model="m"))
    with pytest.raises(ValueError, match="prefix_store_bytes"):
        TpuBackend.from_spec(BackendSpec(
            name="X",
            url="tpu://llama-tiny?prefix_store=host&prefix_store_bytes=lots",
            model="m"))


@slow
def test_store_knob_parses_through_backend_url():
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec

    b = TpuBackend.from_spec(BackendSpec(
        name="S",
        url=("tpu://llama-tiny?max_seq=64&seed=31&prefix_store=host"
             "&prefix_store_bytes=2m&prefix_store_chunk=16"),
        model="m"))
    assert b.engine.prefix_store is not None
    assert b.engine.prefix_store.max_bytes == 2 << 20
    assert b.engine.prefix_store.chunk_tokens == 16
    m = b.engine.metrics()
    assert m["prefix_store_bytes"] == 0 and m["prefix_store_entries"] == 0
