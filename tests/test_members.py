"""Stacked fan-out members: M weight sets, M separate streams, one dispatch.

Contract (quorum_tpu/engine/engine.py ``members=M``): member i of a stacked
engine produces token-for-token the stream a ``members=1`` engine with seed
``base+i`` produces. Slot co-location, coalesced admission, and the member
vmap must never change *content* — only how many host dispatches the quorum
costs. (The reference cannot co-locate models at all: its "members" are
separate HTTP services, /root/reference/src/quorum/oai_proxy.py:182-192.)
"""

from concurrent.futures import ThreadPoolExecutor

import pytest

from quorum_tpu.backends.tpu_backend import TpuBackend
from quorum_tpu.config import BackendSpec
from quorum_tpu.engine.engine import InferenceEngine
from quorum_tpu.models.model_config import MODEL_PRESETS, resolve_spec
from quorum_tpu.ops.sampling import SamplerConfig

TINY = MODEL_PRESETS["llama-tiny"]
M = 3


def _gen(eng, member, seed, prompt, n=8, temp=0.8):
    return eng.generate(
        prompt, max_new_tokens=n,
        sampler=SamplerConfig(temperature=temp, top_p=0.9),
        seed=seed, member=member,
    ).token_ids


def test_members_match_single_engines():
    """Each member's stream equals the members=1 engine with that seed."""
    stacked = InferenceEngine(TINY, seed=0, members=M, decode_chunk=4, n_slots=2)
    singles = [
        InferenceEngine(TINY, seed=i, decode_chunk=4, n_slots=2)
        for i in range(M)
    ]
    prompt = [3, 4, 5]
    want = [_gen(singles[i], 0, 7, prompt) for i in range(M)]
    got = [_gen(stacked, i, 7, prompt) for i in range(M)]
    assert got == want
    assert len({tuple(w) for w in want}) > 1, (
        "distinct member weights should usually diverge — if not, the "
        "equivalence above proved nothing")


def test_members_concurrent_matches_serial():
    """Fan-out shape: one request per member at once, co-batched in one
    program, must match the serial member-by-member runs."""
    eng = InferenceEngine(TINY, seed=0, members=M, decode_chunk=4, n_slots=2)
    jobs = [(m, 11 + m, [5, 6, 7 + m]) for m in range(M)]
    serial = [_gen(eng, *j) for j in jobs]
    with ThreadPoolExecutor(max_workers=M) as ex:
        concurrent = list(ex.map(lambda j: _gen(eng, *j), jobs))
    assert concurrent == serial


def test_member_isolation_mid_generation():
    """Admitting one member while another is mid-generation must not
    disturb the in-flight member's stream (write_gate correctness)."""
    eng = InferenceEngine(TINY, seed=0, members=2, decode_chunk=2, n_slots=2)
    solo = _gen(eng, 0, 3, [9, 8, 7], n=12)

    it = eng.generate_stream(
        [9, 8, 7], max_new_tokens=12,
        sampler=SamplerConfig(temperature=0.8, top_p=0.9), seed=3, member=0,
    )
    head = [next(it) for _ in range(2)]
    # admit member 1 into the same slot row while member 0 is active
    other = _gen(eng, 1, 4, [1, 2], n=6)
    tail = list(it)
    assert head + tail == solo
    assert len(other) == 6


def test_more_requests_than_slots_per_member():
    eng = InferenceEngine(TINY, seed=0, members=2, decode_chunk=4, n_slots=1)
    with ThreadPoolExecutor(max_workers=4) as ex:
        results = list(ex.map(
            lambda i: _gen(eng, i % 2, i, [5, 6], n=5), range(4)))
    assert all(len(r) == 5 for r in results)


@pytest.mark.slow
def test_members_chunked_prefill_matches_single_engines():
    """Long prompts on a stacked engine ride member-coalesced chunked
    prefill (one vmapped segment program per scheduler turn) and must still
    match the per-seed engines token-for-token — including when both
    members admit the same long prompt concurrently (the fan-out shape)."""
    spec = resolve_spec("llama-tiny", {"max_seq": "128"})
    stacked = InferenceEngine(spec, seed=0, members=2, decode_chunk=4,
                              n_slots=2, prefill_chunk=16)
    singles = [InferenceEngine(spec, seed=i, decode_chunk=4, n_slots=2,
                               prefill_chunk=16) for i in range(2)]
    prompt = [(3 + 7 * i) % 500 for i in range(50)]  # > prefill_chunk
    kw = dict(max_new_tokens=6, sampler=SamplerConfig(temperature=0.7),
              seed=5)
    want = [singles[i].generate(prompt, **kw).token_ids for i in range(2)]
    with ThreadPoolExecutor(max_workers=2) as ex:
        got = list(ex.map(
            lambda m: stacked.generate(prompt, member=m, **kw).token_ids,
            range(2)))
    assert got == want


@pytest.mark.slow
def test_members_prefix_reuse_exact_and_counted():
    """Warm turns on a stacked engine reuse each member's own resident
    rows: output matches a reuse-disabled stacked engine exactly and the
    hit counter advances once per member."""
    spec = resolve_spec("llama-tiny", {"max_seq": "128"})
    eng = InferenceEngine(spec, seed=0, members=2, decode_chunk=4,
                          n_slots=1, prefill_chunk=16)
    cold = InferenceEngine(spec, seed=0, members=2, decode_chunk=4,
                           n_slots=1, prefill_chunk=16, prefix_cache=False)
    prompt = [(9 + 3 * i) % 500 for i in range(40)]
    follow = prompt + [7, 8, 9]
    kw = dict(max_new_tokens=5, sampler=SamplerConfig(temperature=0.6),
              seed=2)
    for m in range(2):
        assert eng.generate(prompt, member=m, **kw).token_ids == \
            cold.generate(prompt, member=m, **kw).token_ids
    hits0 = eng.prefix_hits
    for m in range(2):
        assert eng.generate(follow, member=m, **kw).token_ids == \
            cold.generate(follow, member=m, **kw).token_ids
    assert eng.prefix_hits >= hits0 + 2


@pytest.mark.slow
def test_members_logprobs_and_choices():
    """logprobs and n>1 choices ride the members path unchanged."""
    eng = InferenceEngine(TINY, seed=0, members=2, decode_chunk=4, n_slots=2)
    req = eng.submit([4, 5, 6], max_new_tokens=4, seed=9,
                     sampler=SamplerConfig(temperature=0.0),
                     logprobs=3, member=1)
    toks = list(eng.stream_results(req))
    assert len(toks) == 4
    assert len(req.lp) >= len(toks)
    lp, top_ids, top_lps = req.lp[0]
    assert lp <= 0.0 and len(top_ids) >= 3


@pytest.mark.slow
async def test_stacked_two_hop_aggregation():
    """The reference's flagship workflow on ONE stacked engine: fan out to
    two members, then synthesize via a THIRD member as the aggregator —
    three weight sets, two hops, zero network, one engine's programs."""
    from tests.conftest import make_client

    url = "tpu://llama-tiny?members=3&member={}&slots=2&max_seq=64"
    raw = {
        "settings": {"timeout": 120},
        "primary_backends": [
            {"name": "A", "url": url.format(0), "model": "m"},
            {"name": "B", "url": url.format(1), "model": "m"},
            {"name": "AGG", "url": url.format(2), "model": "m"},
        ],
        "iterations": {"aggregation": {"strategy": "aggregate"}},
        "strategy": {
            "concatenate": {"separator": "\n---\n"},
            "aggregate": {
                "source_backends": ["A", "B"],
                "aggregator_backend": "AGG",
                "intermediate_separator": "@@SEP@@",
                "include_source_names": False,
                "suppress_individual_responses": True,
            },
        },
    }
    async with make_client(raw) as client:
        resp = await client.post(
            "/chat/completions",
            json={"model": "m", "max_tokens": 6, "temperature": 0,
                  "messages": [{"role": "user", "content": "hello"}]},
            headers={"Authorization": "Bearer x"},
        )
    assert resp.status_code == 200
    content = resp.json()["choices"][0]["message"]["content"]
    # a separator in the output would mean the join fallback ran instead of
    # the member-2 aggregation hop
    assert "@@SEP@@" not in content
    assert content


@pytest.mark.slow
def test_stacked_engine_survives_poisoned_state():
    """_fail_all on a stacked engine: waiting consumers get the error, the
    member-stacked device state rebuilds, and the engine serves again."""
    eng = InferenceEngine(TINY, seed=0, members=2, decode_chunk=4, n_slots=2)
    before = _gen(eng, 1, 5, [4, 5, 6])
    eng._fail_all(RuntimeError("injected device poison"))
    after = _gen(eng, 1, 5, [4, 5, 6])
    assert after == before  # fresh state, same seeds → same stream
    assert eng.n_failures >= 0


def test_member_sampler_state_isolation():
    """Per-member sampler state must not leak across the coalesced
    admission: a logit_bias that forces member 0 onto one token leaves
    member 1's stream exactly as it would be without any sibling."""
    import numpy as np

    eng = InferenceEngine(TINY, seed=0, members=2, decode_chunk=4, n_slots=1)
    kw = dict(max_new_tokens=5,
              sampler=SamplerConfig(temperature=0.8, top_p=0.9))
    baseline = list(eng.stream_results(
        eng.submit([4, 5, 6], seed=3, member=1, **kw)))

    forced = 7
    bias = np.zeros((TINY.vocab_size,), np.float32)
    bias[forced] = 100.0
    from concurrent.futures import ThreadPoolExecutor as _TPE
    with _TPE(max_workers=2) as ex:
        f0 = ex.submit(lambda: list(eng.stream_results(eng.submit(
            [4, 5, 6], seed=3, member=0, logit_bias=bias, **kw))))
        f1 = ex.submit(lambda: list(eng.stream_results(eng.submit(
            [4, 5, 6], seed=3, member=1, **kw))))
        biased0, plain1 = f0.result(), f1.result()
    assert all(t == forced for t in biased0), "bias must dominate member 0"
    assert plain1 == baseline, "sibling's bias leaked into member 1"


def test_member_out_of_range():
    eng = InferenceEngine(TINY, seed=0, members=2, n_slots=1)
    with pytest.raises(ValueError, match="member 5 out of range"):
        eng.submit([1, 2], max_new_tokens=2, member=5)


@pytest.mark.slow
def test_backend_urls_share_one_engine():
    """members=M&member=i backends resolve to ONE engine; distinct member
    indices; rejected for ckpt backends and out-of-range members."""
    def mk(i):
        return TpuBackend.from_spec(BackendSpec(
            name=f"LLM{i}",
            url=f"tpu://llama-tiny?members={M}&member={i}&slots=2",
            model="llama-tiny",
        ))

    backends = [mk(i) for i in range(M)]
    assert len({id(b.engine) for b in backends}) == 1
    assert [b.member for b in backends] == list(range(M))
    assert backends[0].engine.members == M

    with pytest.raises(ValueError, match="out of range"):
        TpuBackend.from_spec(BackendSpec(
            name="bad", url=f"tpu://llama-tiny?members={M}&member={M}",
            model="x"))
    with pytest.raises(ValueError, match="does not apply to ckpt"):
        TpuBackend.from_spec(BackendSpec(
            name="bad", url="tpu://llama-tiny?members=2&ckpt=/tmp/nope",
            model="x"))


@pytest.mark.slow
async def test_stacked_quorum_through_real_socket():
    """The shipped stacked shape end-to-end: a members=3 quorum served by
    the bundled h11 server over TCP streams per-member `chatcmpl-parallel-i`
    deltas and a final combined chunk whose sections are the three members'
    streams (the /verify scenario, pinned)."""
    import httpx

    from quorum_tpu.config import Config
    from quorum_tpu.server.app import create_app
    from quorum_tpu.server.serve import start_server
    from tests.conftest import ParallelStreamCollector

    config = Config(raw={
        "settings": {"timeout": 120},
        "primary_backends": [
            {"name": f"LLM{i}",
             "url": f"tpu://llama-tiny?members=3&member={i}&slots=2",
             "model": "tiny"}
            for i in range(3)
        ],
        "iterations": {"aggregation": {"strategy": "concatenate"}},
        "strategy": {"concatenate": {
            "separator": "\n---\n",
            "hide_intermediate_think": False,
            "hide_final_think": False,
            "thinking_tags": ["think"],
        }},
    })
    server = await start_server(create_app(config), "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    col = ParallelStreamCollector()
    try:
        async with httpx.AsyncClient(
            base_url=f"http://127.0.0.1:{port}", timeout=120
        ) as client:
            async with client.stream(
                "POST", "/chat/completions",
                json={"model": "tiny", "stream": True, "max_tokens": 5,
                      "temperature": 0.8, "seed": 6,
                      "messages": [{"role": "user", "content": "hi"}]},
                headers={"Authorization": "Bearer t"},
            ) as resp:
                assert resp.status_code == 200
                async for line in resp.aiter_lines():
                    col.feed_line(line)
    finally:
        server.close()
        await server.wait_closed()
    assert sorted(col.texts) == [0, 1, 2], "all three members streamed"
    streams = [col.stream(i) for i in range(3)]
    assert "".join(col.final) == "\n---\n".join(streams)


def test_stacked_engine_matches_separate_seeded_engines_via_backend():
    """End-to-end: the stacked backends' completions equal the old
    three-separate-engines completions (seed i ↔ member i)."""
    import asyncio

    def complete(backend, body):
        return asyncio.run(backend.complete(dict(body), {}, timeout=60))

    body = {
        "model": "m",
        "messages": [{"role": "user", "content": "hello quorum"}],
        "max_tokens": 6,
        "temperature": 0.8,
        "seed": 2,
    }
    stacked = [
        TpuBackend.from_spec(BackendSpec(
            name=f"S{i}",
            url=f"tpu://llama-tiny?members={M}&member={i}&slots=2",
            model="m"))
        for i in range(M)
    ]
    singles = [
        TpuBackend.from_spec(BackendSpec(
            name=f"P{i}", url=f"tpu://llama-tiny?seed={i}&slots=2",
            model="m"))
        for i in range(M)
    ]
    got = [complete(b, body).body["choices"][0]["message"]["content"]
           for b in stacked]
    want = [complete(b, body).body["choices"][0]["message"]["content"]
            for b in singles]
    assert got == want
