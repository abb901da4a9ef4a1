"""A latent spec (models/latent.py under models/patterned.py) against its
plain reference, ``benchmarks/references/dots3.py``: the file the benchmark's
``correct`` uses, not a second one.

At the family's tiny preset (``GGLLLG``: a dense layer and five expert layers,
16 experts of which 4 are held, top-4; the indexer keeps 16 positions, the
window 9 in a ring of 16) a prompt of 40 passes ``index_topk``, the window and
a ring wrap, and 12 decoded positions go through all three kinds of cache
leaf. Logits are compared, not tokens.
"""

import functools
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quorum_tpu.models import latent, patterned
from quorum_tpu.models import transformer as tr
from quorum_tpu.models.init import init_params
from quorum_tpu.models.model_config import resolve_spec

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)
import named  # noqa: E402

REFERENCE = named.load("references", "dots3")
N_PROMPT, N_NEW, SLOT, SLOTS = 40, 12, 1, 3
STAT = {name: i for i, name in enumerate(
    patterned.STATS + patterned.DSA_STATS)}
# float32 activations: what is left is the order of the sums (2e-6 read), and
# the selection is the reference's to the position: a single swapped position
# of 16 moves a log-probability by 1e-3 and more.
# bfloat16: the benchmark's limits for two bytes a weight (run.PROBE_TOL[2]),
# at the reference's own most likely id as the probe reads them; at this tiny
# width they read 0.017 and 0.0023 (the stream is float32, the sub-layers and
# the index products bfloat16, so a few positions near the 16th score swap);
# a fault moves the median by 0.013 and more (the controls).
TIGHT = 2e-4
BF16_MAX, BF16_MEDIAN = 0.1, 0.02


def f32(leaf):
    return leaf.astype(jnp.float32)


def take(leaf, *idx):
    return leaf[idx]


def reference_of(spec, params, changes=None):
    backend = types.SimpleNamespace(
        engine=types.SimpleNamespace(spec=spec, params=params))
    return REFERENCE.forward_for(backend, f32, take, changes)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(3, 512, size=N_PROMPT + N_NEW)


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def model(request):
    spec = resolve_spec("dots3-tiny", {"dtype": request.param})
    return spec, init_params(spec, 3)


@pytest.fixture(scope="module")
def model32():
    spec = resolve_spec("dots3-tiny", {"dtype": "float32"})
    return spec, init_params(spec, 3)


@functools.partial(jax.jit, static_argnums=(1,))
def _segment(params, spec, seg, off, n, ck, cv):
    return tr.prefill_segment(params, spec, seg, off, n, ck, cv,
                              jnp.int32(SLOT), history=64)


@functools.partial(jax.jit, static_argnums=(1,))
def _step(params, spec, tok, lens, live, ck, cv):
    return tr.decode_step(params, spec, tok, lens, ck, cv, write_mask=live,
                          history=64)


@pytest.fixture
def patched(monkeypatch):
    """``setattr`` for what the two programs trace: they are traced anew
    under each patch and again after it."""
    def setattr(*args):
        monkeypatch.setattr(*args)
        _segment.clear_cache(), _step.clear_cache()

    yield types.SimpleNamespace(setattr=setattr)
    monkeypatch.undo()
    _segment.clear_cache(), _step.clear_cache()


def segments(spec, params, tokens, segment: int, ck, cv):
    for off in range(0, N_PROMPT, segment):
        n = min(segment, N_PROMPT - off)
        seg = np.zeros((1, segment), np.int32)
        seg[0, :n] = tokens[off:off + n]
        ck, cv = _segment(params, spec, jnp.asarray(seg), jnp.int32(off),
                          jnp.int32(n), ck, cv)
    return ck, cv


def served(spec, params, tokens, segment: int):
    """Log-probabilities at positions N_PROMPT-1 .. N_PROMPT+N_NEW-2 as the
    engine's programs compute them: the prompt admitted in one shot
    (``segment`` 0) or in segments, then one decode step a position."""
    ck, cv = tr.init_cache(spec, SLOTS)
    out = []
    if segment:
        ck, cv = segments(spec, params, tokens, segment, ck, cv)
        start = N_PROMPT - 1
    else:
        pad = np.zeros((1, 64), np.int32)
        pad[0, :N_PROMPT] = tokens[:N_PROMPT]
        logits, ck, cv = tr.prefill(
            params, spec, jnp.asarray(pad), jnp.array([N_PROMPT]), ck, cv,
            slot=jnp.int32(SLOT))
        out.append(jax.nn.log_softmax(logits[0].astype(jnp.float32)))
        start = N_PROMPT
    for p in range(start, N_PROMPT + N_NEW - 1):
        tok = np.zeros((SLOTS,), np.int32)
        lens = np.zeros((SLOTS,), np.int32)
        live = np.zeros((SLOTS,), bool)
        tok[SLOT], lens[SLOT], live[SLOT] = tokens[p], p, True
        logits, ck, cv = _step(params, spec, jnp.asarray(tok),
                               jnp.asarray(lens), jnp.asarray(live), ck, cv)
        out.append(jax.nn.log_softmax(logits[SLOT].astype(jnp.float32)))
    return np.stack([np.asarray(o) for o in out]), ck


def reference_rows(forward, tokens):
    return np.stack([forward(list(tokens), p)
                     for p in range(N_PROMPT - 1, N_PROMPT + N_NEW - 1)])


def errors_at_best(got, want):
    best = want.argmax(-1)
    rows = np.arange(len(want))
    return np.abs(got[rows, best] - want[rows, best])


@functools.lru_cache(maxsize=None)
def _served32(segment: int = 16, **options):
    spec = resolve_spec("dots3-tiny", dict({"dtype": "float32"}, **options))
    tokens = np.random.default_rng(0).integers(3, 512, size=N_PROMPT + N_NEW)
    return served(spec, init_params(spec, 3), tokens, segment)[0]


@pytest.mark.parametrize("segment", [0, 16], ids=["single_shot", "segmented"])
def test_prefill_then_decode_through_the_latent_caches(model, tokens, segment):
    """Histories past ``index_topk`` (16), past the window (9) and past a
    ring wrap (16), chunked and single-shot."""
    spec, params = model
    assert N_PROMPT > 2 * max(spec.index_topk, spec.ring)
    want = reference_rows(reference_of(spec, params), tokens)
    got, ck = served(spec, params, tokens, segment)
    if spec.dtype == "float32":
        assert np.abs(got - want).max() < TIGHT
    else:
        err = errors_at_best(got, want)
        assert err.max() < BF16_MAX and np.median(err) < BF16_MEDIAN
    # every real token of every expert layer was counted, none dropped; the
    # three full layers' queries attended 16 of the positions before them
    stats = np.asarray(ck.stats)
    rest = stats[:, spec.held:]
    steps = N_NEW if segment else N_NEW - 1  # segmented: position 39 again
    assert (rest[:, STAT["picks"]]
            == (N_PROMPT + steps) * spec.experts_per_token).all()
    assert (rest[:, STAT["dropped"]] == 0).all()
    at = list(range(N_PROMPT)) + list(range(
        N_PROMPT - (1 if segment else 0), N_PROMPT + N_NEW - 1))
    assert rest[0, STAT["keys_in_history"]] == 3 * sum(p + 1 for p in at)
    assert rest[0, STAT["keys_attended"]] == 3 * sum(
        min(p + 1, spec.index_topk) for p in at)
    # every program here reads a history of 64, in one tile
    assert rest[0, STAT["keys_multiplied"]] == 3 * 64 * len(at)
    assert (rest[1:, len(patterned.STATS):] == 0).all()


@pytest.mark.parametrize("change", [
    {"selection": False}, {"window": 8}, {"gate": False}, {"rescale": False},
    {"index_rope": False}, {"scoring": "softmax"}],
    ids=lambda c: next(iter(c)))
def test_a_control_comes_out_as_not_the_served_model(model32, tokens, change):
    """Each control turns one stated choice of the reference into something
    else; the served path has to differ from it by far more than from the
    reference itself (the test above: under 2e-4; the nearest control,
    ``scoring``, reads a median of 0.009, the others 0.02 and more)."""
    spec, params = model32
    want = reference_rows(reference_of(spec, params, change), tokens)
    err = errors_at_best(_served32(), want)
    assert np.median(err) > 20 * TIGHT, (change, err)


def _bf16_router(x, lyr, spec):
    """patterned._route with the product, the scores and the pick in
    bfloat16."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.bfloat16),
                               lyr["router"].astype(jnp.bfloat16)))
    _, idx = jax.lax.top_k(s + lyr["router_bias"].astype(jnp.bfloat16),
                           spec.experts_per_token)
    w = jnp.take_along_axis(s, idx, axis=-1)
    return (spec.router_scale * w / jnp.sum(w, -1, keepdims=True)
            ).astype(jnp.float32), idx


def _bf16_index(q_i, w, k_i):
    """latent.index_scores with the sum over the index heads in bfloat16."""
    j, d = q_i.shape[-2:]
    p = jnp.einsum("btjd,bsd->btjs", q_i.astype(jnp.bfloat16),
                   k_i.astype(jnp.bfloat16))
    return (jnp.sum(jax.nn.relu(p) * w[..., None].astype(jnp.bfloat16),
                    axis=2) * (j ** -0.5 * d ** -0.5)).astype(jnp.float32)


@pytest.mark.parametrize("where,patch,seed", [
    ("router", (patterned, "_route", _bf16_router), 3),
    ("index", (latent, "index_scores", _bf16_index), 6)],
    ids=["router", "index"])
def test_bfloat16_where_the_configuration_says_float32_is_not_correct(
        tokens, patched, where, patch, seed):
    """The router's product and pick and the index score's accumulation are
    float32 whatever the weights' type: in bfloat16 the 4th and 5th expert,
    or the 16th and 17th position, swap somewhere, and every position after
    reads differently. A swap needs a near-tie: of the weight seeds 3 to 7,
    three have one among these 52 positions' index scores (6 reads 0.43, 4
    reads the sound 2e-6), so the seed is one that has."""
    spec = resolve_spec("dots3-tiny", {"dtype": "float32"})
    params = init_params(spec, seed)
    patched.setattr(*patch)
    got, _ = served(spec, params, tokens, 16)
    want = reference_rows(reference_of(spec, params), tokens)
    assert np.abs(got - want).max() > 10 * TIGHT, where


def test_index_topk_at_least_the_history_is_dense_latent_attention(tokens):
    """No score, no selection: the program is the reference that attends
    every position, and its counters say it kept everything."""
    spec = resolve_spec("dots3-tiny", {"dtype": "float32",
                                      "index_topk": "64"})
    params = init_params(spec, 3)
    got, ck = served(spec, params, tokens, 16)
    everything = reference_of(spec, params, {"selection": False})
    assert np.abs(got - reference_rows(everything, tokens)).max() < TIGHT
    rest = np.asarray(ck.stats)[0, spec.held:]
    assert rest[STAT["keys_attended"]] == rest[STAT["keys_in_history"]] > 0


def test_query_blocks_and_one_block_are_one_attention(
        model32, tokens, patched):
    """At the cell's size a segment's 512 queries are scored 64 at a time
    (``SCORE_BLOCK`` over 16,384 positions); here 4 at a time over 64: the
    same selection, the same sum, the same counts of keys."""
    spec, params = model32
    whole, ck = served(spec, params, tokens, 16)
    patched.setattr(latent, "SCORE_BLOCK", 4 * 64)
    blocked, ck_blocked = served(spec, params, tokens, 16)
    assert np.abs(blocked - whole).max() < 1e-5
    assert (np.asarray(ck_blocked.stats) == np.asarray(ck.stats)).all()


def test_key_tiles_and_one_tile_are_one_attention(model32, tokens, patched):
    """At the cell's size a segment's keys and values are made 1,024
    positions at a time and the loop stops at the row's last live tile; here
    16 at a time over 64: the same logits, and ``keys_multiplied`` counts
    the tiles the loop covered where one tile covers the bucket."""
    spec, params = model32
    whole, ck = served(spec, params, tokens, 16)
    patched.setattr(latent, "KEY_TILE", 16)
    in_tiles, ck_tiles = served(spec, params, tokens, 16)
    assert np.abs(in_tiles - whole).max() < 1e-5
    one, many = (np.asarray(c.stats)[:, spec.held:] for c in (ck, ck_tiles))
    col = STAT["keys_multiplied"]
    assert (np.delete(one, col, 1) == np.delete(many, col, 1)).all()
    # segments of 16, 16 and 8 real queries end in tiles 1, 2 and 3; a
    # decode step is in the latent space over its whole bucket
    steps = N_NEW
    assert many[0, col] == 3 * (16 * 16 + 16 * 32 + 8 * 48 + steps * 64)
    assert one[0, col] == 3 * (N_PROMPT + steps) * 64


def _block(t: int, hist: int, live: int, seed: int = 0):
    """A block of ``t`` queries somewhere in the first ``live`` positions of
    row SLOT of float32 leaves ``[SLOTS, hist + 8, .]``, with an arbitrary
    causal mask: what :func:`latent.tiled` and :func:`latent.absorbed`
    take."""
    spec = resolve_spec("dots3-tiny", {"dtype": "float32"})
    g = spec.latent("G")
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    lyr = {"w_kb": normal(g.kv_rank, g.heads * g.nope) * g.kv_rank ** -0.5,
           "w_vb": normal(g.kv_rank, g.heads * g.v) * g.kv_rank ** -0.5}
    pos = np.sort(rng.integers(0, live, size=(1, t)), axis=1)
    pos[0, -1] = live - 1
    keep = (rng.random((1, t, hist)) < 0.6) & (
        np.arange(hist) <= pos[..., None])
    keep[0, :, 0] = True
    rows = normal(SLOTS, hist + 8, latent.row_width(g))
    return (spec, g, lyr, normal(1, t, g.heads, g.nope),
            normal(1, t, g.heads, g.rope), rows, jnp.asarray(pos),
            jnp.asarray(keep))


TINY_G = resolve_spec("dots3-tiny").latent("G")
BREAK_EVEN = next(t for t in range(1, 64) if latent.tiles_pay(TINY_G, t))


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("hist", [24, 256], ids=["over_topk", "bucket"])
@pytest.mark.parametrize("tiles", ["one", "several", "ends_inside"])
@pytest.mark.parametrize("t", [BREAK_EVEN, 512], ids=["break_even", "512"])
def test_keys_made_a_tile_at_a_time_give_the_latent_space_attention(
        monkeypatch, t, tiles, hist, kernel):
    """The two exact forms over one mask, in float32: one tile over the
    whole history, several (the last of a history of 24 starts early and
    skips what the first held), and a live length that ends inside a tile,
    after which the work stops. XLA's loop, and the Pallas kernel through
    the interpreter (which takes the loop where the history does not
    tile)."""
    live = hist if tiles != "ends_inside" else hist * 5 // 8 + 3
    tile = hist if tiles == "one" else 16
    monkeypatch.setattr(latent, "KEY_TILE", tile)
    spec, g, lyr, q_n, q_r, rows, pos, keep = _block(t, hist, live)
    assert hist > spec.index_topk and latent.tiles_pay(g, t)
    got, extent = latent.tiled(q_n, q_r, rows, SLOT, hist, keep,
                               jnp.max(pos, axis=1), lyr, g,
                               interpret=kernel)
    want = latent.absorbed(q_n, q_r, rows[SLOT:SLOT + 1, :hist],
                           keep[:, :, None], lyr, g)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    assert int(extent) == min(-(-live // tile) * tile, hist)


@pytest.mark.parametrize("t,pays", [(1, False), (8, False), (157, False),
                                    (158, True), (512, True)])
def test_the_form_follows_the_operation_count_at_the_published_sizes(
        t, pays):
    """A position's key and value cost 512 x 256 multiply-adds a head; a
    query saves 640 + 512 - 320 a position and head by them: a decode step
    and a few verified positions stay in the latent space, a segment's 512
    queries have the keys made."""
    g = resolve_spec("dots3-note-prev").latent("G")
    assert latent.tiles_pay(g, t) is pays


@pytest.mark.parametrize("program,t,scope", [
    ("step", 1, "attn.sparse"), ("segment", BREAK_EVEN - 1, "attn.sparse"),
    ("segment", 16, "attn.tiled")])
def test_a_full_layer_takes_the_form_its_queries_earn(
        model32, program, t, scope):
    """Read off the lowered text: under ``attn.full`` a decode step and a
    segment under the break-even are in the latent space, a segment over it
    goes through the key tiles; never both."""
    spec, params = model32
    ck, cv = tr.init_cache(spec, SLOTS)
    if program == "step":
        lowered = _step.lower(
            params, spec, jnp.zeros((SLOTS,), jnp.int32),
            jnp.zeros((SLOTS,), jnp.int32), jnp.ones((SLOTS,), bool), ck, cv)
    else:
        lowered = _segment.lower(
            params, spec, jnp.zeros((1, t), jnp.int32), jnp.int32(16),
            jnp.int32(t), ck, cv)
    text = lowered.as_text(debug_info=True)
    forms = {"attn.sparse", "attn.tiled"}
    assert {f for f in forms if f"/attn.full/{f}" in text} == {scope}


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
def test_positions_past_the_live_history_are_never_read(monkeypatch, kernel):
    """A segment of 16 queries of which 11 are real, at offset 32 of a
    history bucket of 64 in tiles of 16: what the row holds behind position
    42 (another request's rows; here NaN) does not reach a real query, and
    no query comes out other than finite. The counts: 11 real queries, and
    three tiles of 16 reach position 42."""
    spec, g, lyr, q_n, q_r, rows, _, _ = _block(16, 64, 64)
    rng = np.random.default_rng(1)
    n_valid, offset = 11, 32
    pos = jnp.asarray(offset + np.arange(16))[None]
    ok = jnp.asarray(np.arange(16) < n_valid)[None]
    q_i = jnp.asarray(rng.normal(size=(1, 16, spec.index_n_heads,
                                       spec.index_head_dim)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(1, 16, spec.index_n_heads)), jnp.float32)
    k_i = jnp.asarray(rng.normal(size=(SLOTS, 72, spec.index_head_dim)),
                      jnp.float32)
    poisoned = rows.at[:, offset + n_valid:].set(jnp.nan)
    monkeypatch.setattr(latent, "KEY_TILE", 16)
    monkeypatch.setattr(latent, "tiled", functools.partial(
        latent.tiled, interpret=kernel))

    def attend(rows):
        keys: list = []
        out = latent.full_attention(q_n, q_r, q_i, w, (rows, k_i), SLOT, 64,
                                    pos, ok, lyr, spec, keys)
        return np.asarray(out), np.asarray(keys[0])

    clean, counts = attend(rows)
    got, again = attend(poisoned)
    assert np.isfinite(got).all()
    assert (got[:, :, :n_valid] == clean[:, :, :n_valid]).all()
    assert (again == counts).all()
    assert list(counts) == [11 * spec.index_topk,
                            sum(range(offset + 1, offset + n_valid + 1)),
                            11 * 48]


def test_the_mask_keeps_what_top_k_keeps_equal_scores_included():
    """A ReLU makes exact zeros: of equal scores the earlier position stays,
    in ``selected`` as in ``lax.top_k``; -0.0 is 0.0."""
    rng = np.random.default_rng(1)
    scores = rng.normal(size=(2, 5, 40)).astype(np.float32)
    scores[rng.random(scores.shape) < 0.5] = 0.0
    scores[0, 0, ::3] = -0.0
    pos = jnp.asarray(rng.integers(0, 40, size=(2, 5)), jnp.int32)
    seen = np.arange(40) <= np.asarray(pos)[..., None]
    for k in (1, 7, 16):
        vals, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), k)
        want = np.zeros(scores.shape, bool)
        for b, t in np.ndindex(2, 5):
            real = np.asarray(vals)[b, t] > -np.inf
            want[b, t, np.asarray(idx)[b, t][real]] = True
        got = np.asarray(latent.selected(jnp.asarray(scores), pos, k))
        assert (got == want).all(), k
        assert (got.sum(-1) == np.minimum(np.asarray(pos) + 1, k)).all()


def test_a_latent_program_carries_its_scopes(model32):
    """Device operations of a latent program name their part: the two
    latents, the indexer, the selection, the attention in the latent space,
    the gate, beside the shared expert layer's."""
    from quorum_tpu.analysis import hlo_names

    spec, params = model32
    ck, cv = tr.init_cache(spec, SLOTS)
    text = jax.jit(lambda p, t, n, k, v: tr.decode_step(
        p, spec, t, n, k, v, history=64)).lower(
        params, jnp.zeros((SLOTS,), jnp.int32),
        jnp.zeros((SLOTS,), jnp.int32), ck, cv).as_text(debug_info=True)
    for scope in hlo_names.LATENT + hlo_names.PATTERNED + ("attn.out",):
        if scope != "attn.tiled":  # a block of queries' form, not a step's
            assert f"/{scope}/" in text or f'{scope}"' in text, scope
    assert hlo_names.part_of(
        "jit(seg)/attn.core/attn.full/attn.select/while") == "attn.select"
    assert hlo_names.part_of(
        "jit(seg)/attn.core/attn.full/attn.tiled/while/body/dot_general"
    ) == "attn.tiled"


def test_the_cache_is_latent_rows_index_keys_and_rings():
    spec = resolve_spec("dots3-tiny")
    ck, cv = jax.eval_shape(lambda: tr.init_cache(spec, 5))
    g, w = spec.latent("G"), spec.latent("L")
    assert [a.shape for a in ck.full] == [
        (5, spec.max_seq, latent.row_width(g))] * 3
    assert [a.shape for a in ck.index] == [
        (5, spec.max_seq, spec.index_head_dim)] * 3
    assert [a.shape for a in ck.window] == [
        (5, spec.ring, latent.row_width(w))] * 3
    assert ck.stats.shape == (5, spec.held + 6)  # picks, dropped, tile rows, 3 of the selection
    assert jax.tree.leaves(cv) == []


def test_the_engine_serves_it_and_counts_its_keys():
    """Chunked and single-shot admission, decode chunks, the counters on
    metrics() and the cache's bytes by kind on health()."""
    from quorum_tpu.engine.engine import InferenceEngine

    spec = resolve_spec("dots3-tiny")
    eng = InferenceEngine(spec, n_slots=4, prefill_chunk=16, decode_chunk=4,
                          seed=1)
    try:
        long = list(eng.generate_stream(list(range(3, 63)), max_new_tokens=9))
        short = list(eng.generate_stream(list(range(3, 13)),
                                         max_new_tokens=9))
        again = list(eng.generate_stream(list(range(3, 63)),
                                         max_new_tokens=9))
        assert len(long) == len(short) == 9 and long == again
        assert not eng.prefix_cache  # a ring holds no prefix to reuse
        m = eng.metrics()
        assert m["moe_dropped_picks_total"] == 0
        assert 0 < m["moe_picks_held_total"] < m["moe_picks_total"]
        assert len(m["moe_expert_picks_total"]) == 5 * spec.held
        assert (0 < m["dsa_keys_attended_total"]
                < m["dsa_keys_in_history_total"]
                < m["dsa_keys_multiplied_total"])
        kinds = eng.health()["kv_cache_bytes"]
        assert kinds == {k: m[f"kv_cache_{k}_bytes"]
                         for k in ("full", "window", "index")}
        g, w = spec.latent("G"), spec.latent("L")
        assert kinds == {
            "full": 3 * 4 * spec.max_seq * latent.row_width(g) * 2,
            "window": 3 * 4 * spec.ring * latent.row_width(w) * 2,
            "index": 3 * 4 * spec.max_seq * spec.index_head_dim * 2}
        # a prompt of 60 under index_topk 16: 16 * 17 / 2 + 44 * 16 of
        # 60 * 61 / 2 pairs
        assert eng._keys_kept(60) == {"keys_kept_share": round(
            100.0 * (136 + 704) / 1830, 2)}
        assert eng._keys_kept(10) == {"keys_kept_share": 100.0}
    finally:
        eng.shutdown()


def test_an_engine_that_selects_nothing_says_nothing_of_keys():
    from quorum_tpu.engine.engine import InferenceEngine

    eng = InferenceEngine(resolve_spec("k-exaone-tiny"), n_slots=2, seed=1)
    try:
        m = eng.metrics()
        assert not any(k.startswith("dsa_") for k in m)
        assert m["kv_cache_index_bytes"] == 0
        assert eng._keys_kept(60) == {}
    finally:
        eng.shutdown()


@pytest.mark.parametrize("preset,options,rows,dense", [
    ("k-exaone-236b-a23b", {}, 32, True),     # 87 % of the held are picked
    ("k-exaone-236b-a23b", {}, 512, False),   # a prefill segment
    ("dots3-note-prev", {}, 16, False),       # 40 %: 8 of 256, 32 held
    ("dots3-note-prev", {}, 32, True),
    ("dots3-tiny", {}, SLOTS, True), ("k-exaone-tiny", {}, 4, True)])
def test_few_rows_run_the_held_experts_densely_only_if_they_pick_most(
        preset, options, rows, dense):
    """The form follows what the rows are expected to pick, one rule for both
    families: the accepted cell's 32 rows keep the dense form they had."""
    spec = resolve_spec(preset, options)
    assert patterned.dense_experts(spec, rows) is dense


def test_a_decode_step_that_groups_its_picks_is_the_dense_one(model32):
    """One row picks 4 of 16 experts, a quarter of the held: its step runs
    the tile loop, and gives what the dense form gives."""
    spec, params = model32
    assert not patterned.dense_experts(spec, 1)
    lyr = patterned.layer_of(params, 2)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 1, spec.d_model))
    ok = jnp.ones((1, 1), bool)
    chosen, counts = patterned.moe_layer(x, lyr, spec, ok)
    dense, _ = patterned.moe_layer(x, lyr, spec, ok, dense=True)
    np.testing.assert_allclose(np.asarray(chosen), np.asarray(dense),
                               atol=1e-5)
    assert int(counts[spec.held + STAT["dropped"]]) == 0
