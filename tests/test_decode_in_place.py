"""The dense decode step carries its cache through the layer scan, writes it
in place and reads it where it lies (models/transformer.decode_step_blocks):
positions-major, the heads flattened, ``[L, B, max_seq, K·hd]``.

The oracle is the body that was there before either: the cache K-major
(``[L, B, K, max_seq, hd]``) as the scan's ``xs``, written per row by a
vmapped ``dynamic_update_slice``, read by the K-major einsums and stacked back
as ``ys``. It is kept here as the plain reference, handed the same values
through a transpose at its entry and exit. No arithmetic moved (the same
einsums, the axes named in another order), so on the CPU logits and both
caches must be equal bit for bit, after one step and after an 8-step
``decode_chunk``, for a bf16 cache and the int8 tuple.

The static tests read the v5e compiler's text (analysis/decode_static.py):
only that text shows whether a copy, a reshape, a slice or an allocation of a
cache side or of a layer's slab sits in the program, and whether the Pallas
call is there; the CPU's text does not (its scatter copies). The last of
them hold the stacked quorum's member-vmapped programs to their weights'
store (parallel/sharding.py ``member_axes``: block leaves layers-major): no
program copies or transposes a stacked block matrix (tests/
test_stacked_weights.py has the values' side of that store, on the CPU).
"""

import dataclasses
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from quorum_tpu.analysis import decode_static
from quorum_tpu.engine.engine import _stacked_rows_call
from quorum_tpu.models import transformer as tr
from quorum_tpu.models.init import init_params
from quorum_tpu.models.model_config import MODEL_PRESETS
from quorum_tpu.models.quant import quantize_params
from quorum_tpu.ops import ssm_step
from quorum_tpu.parallel import sharding
from quorum_tpu.parallel.sharding import stack_members

TINY = dataclasses.replace(MODEL_PRESETS["llama-tiny"], max_seq=64)
N_STEPS = 8


def k_major(side, n_kv):
    """A positions-major cache side as the oracle's store held it:
    ``[L, B, S, K·hd]`` to ``[L, B, K, S, hd]``, scales ``[L, B, S, K]`` to
    ``[L, B, K, S]``."""
    def values(a):
        return a.reshape(a.shape[:3] + (n_kv, -1)).transpose(0, 1, 3, 2, 4)

    if isinstance(side, tuple):
        return values(side[0]), side[1].transpose(0, 1, 3, 2)
    return values(side)


def lines(side):
    """:func:`k_major` undone."""
    def values(a):
        return a.transpose(0, 1, 3, 2, 4).reshape(
            a.shape[:2] + (a.shape[3], -1))

    if isinstance(side, tuple):
        return values(side[0]), side[1].transpose(0, 1, 3, 2)
    return values(side)


def oracle_step_blocks(blocks, spec, x, lengths, cache_k, cache_v,
                       write_mask=None, history=None, sharded=False):
    """``decode_step_blocks`` as it was before PR 31 and PR 35: the cache
    K-major as ``xs``, stacked as ``ys``."""
    del sharded
    cache_k, cache_v = (k_major(c, spec.n_kv_heads)
                        for c in (cache_k, cache_v))
    b = x.shape[0]
    cos, sin = tr.rope_cos_sin_for(spec)

    def write_row(cache_row, new_row, idx, allow):
        start = (0, idx, 0)[: cache_row.ndim]
        old = lax.dynamic_slice(cache_row, start, new_row.shape)
        return lax.dynamic_update_slice(
            cache_row, jnp.where(allow, new_row, old), start)

    allow = (jnp.ones((b,), bool) if write_mask is None else write_mask)
    write = jax.vmap(write_row, in_axes=(0, 0, 0, 0))

    def step_write(cache, value):
        if tr.kv_is_q8(cache):
            c8, cs = cache
            q8, s = tr._kv_quantize(value)
            return (write(c8, q8, lengths, allow),
                    write(cs, s.astype(cs.dtype), lengths, allow))
        return write(cache, value.astype(cache.dtype), lengths, allow)

    def step_read(cache):
        if history is not None and history < spec.max_seq:
            return jax.tree.map(
                lambda a: lax.slice_in_dim(a, 0, history, axis=2), cache)
        return cache

    def body(carry_x, per_layer):
        block, ck, cv = per_layer
        h = tr._norm(carry_x, block["attn_norm_w"], block.get("attn_norm_b"),
                     spec)
        q, k, v = tr._qkv(h, block, spec)
        if spec.pos == "rope":
            rope_row = jax.vmap(
                lambda xr, p: tr.apply_rope(xr[None], cos, sin, p[None])[0])
            q = rope_row(q, lengths)
            k = rope_row(k, lengths)
        new_ck = step_write(ck, k)
        new_cv = step_write(cv, v)
        read_k = step_read(new_ck)
        read_v = step_read(new_cv)
        if tr.kv_is_q8(new_ck):
            attn = tr.decode_attention_q8(
                q, read_k[0], read_k[1], read_v[0], read_v[1], lengths + 1,
                window=spec.sliding_window)
        else:
            attn = tr.decode_attention(q, read_k, read_v, lengths + 1,
                                       window=spec.sliding_window)
        carry_x = carry_x + tr._attn_out(attn, block, carry_x.dtype)
        h2 = tr._norm(carry_x, block["mlp_norm_w"], block.get("mlp_norm_b"),
                      spec)
        mlp = (tr._moe_mlp(h2, block, spec) if spec.is_moe
               else tr._dense_mlp(h2, block, spec))
        return carry_x + mlp, (new_ck, new_cv)

    x, (cache_k, cache_v) = lax.scan(body, x, (blocks, cache_k, cache_v))
    return x, lines(cache_k), lines(cache_v)


def filled_cache(spec, rows, seed, kv_quant=None, members=1):
    """A cache with something at every position, position 0 included: a
    write that lands where it should not changes a value."""
    rng = np.random.default_rng(seed)
    lead = (members,) if members > 1 else ()

    def fill(leaf):
        shape = lead + leaf.shape
        if leaf.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        if leaf.dtype == jnp.float32:  # an int8 cache's scales
            return jnp.asarray(rng.uniform(0.001, 0.02, shape), jnp.float32)
        return jnp.asarray(rng.normal(size=shape), leaf.dtype)

    return jax.tree.map(fill, tr.init_cache(spec, rows, kv_quant=kv_quant))


def greedy(logits, live, carry):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), carry, ()


@dataclasses.dataclass(frozen=True)
class Case:
    spec: object = TINY
    quant: bool = False
    kv_quant: "str | None" = None
    lengths: tuple = (5, 5, 5)
    live: tuple = (True, True, True)
    history: "int | None" = None
    budget: tuple = (64, 64, 64)
    members: int = 1
    shard: "tuple | None" = None  # (first layer, last, first row, last)


CASES = {
    "bf16_weights": Case(),
    "int8_weights": Case(quant=True),
    "rows_at_different_lengths": Case(lengths=(3, 17, 40)),
    "dead_row_over_a_live_prompts_position_0": Case(
        lengths=(9, 21, 30), live=(True, False, True)),
    "all_rows_dead": Case(live=(False, False, False)),
    "history_below_max_seq": Case(lengths=(3, 11, 20), history=32),
    "history_equal_to_max_seq": Case(lengths=(3, 11, 50), history=64),
    "row_finishes_mid_chunk": Case(lengths=(3, 17, 40), budget=(64, 3, 64)),
    "three_members_under_vmap": Case(
        lengths=(3, 17, 4, 30, 9, 2), live=(True, True, True, False, True, True),
        budget=(64, 64, 2, 64, 64, 64), history=32, members=3),
    "three_members_int8_weights": Case(
        quant=True, lengths=(3, 17, 4, 30, 9, 2), live=(True,) * 6,
        budget=(64,) * 6, members=3),
    "int8_cache_tuple_leaves": Case(
        kv_quant="int8", lengths=(3, 17, 40), live=(True, False, True),
        history=48),
    "int8_cache_three_members": Case(
        kv_quant="int8", lengths=(3, 17, 4, 30, 9, 2), live=(True,) * 6,
        budget=(64,) * 6, members=3),
    "layer_shard_and_row_slab_of_a_pipeline_stage": Case(
        spec=dataclasses.replace(TINY, n_layers=4), lengths=(7, 2),
        live=(True, False), history=32, shard=(2, 4, 1, 3)),
    "learned_positions_layernorm_bias": Case(
        spec=dataclasses.replace(MODEL_PRESETS["gpt2-tiny"], max_seq=64),
        lengths=(3, 17, 40)),
    "expert_layers": Case(
        spec=dataclasses.replace(MODEL_PRESETS["mixtral-tiny"], max_seq=64),
        lengths=(3, 17, 40), history=48),
    "sliding_window": Case(
        spec=dataclasses.replace(TINY, sliding_window=8), lengths=(3, 17, 40)),
}


def run_case(case: Case):
    """One step's ``(logits, cache_k, cache_v)`` and an 8-step chunk's whole
    result, through whatever ``tr.decode_step_blocks`` is at the moment."""
    spec, mem = case.spec, case.members
    n = len(case.lengths)
    rows = n // mem
    one = [init_params(spec, seed=s) for s in range(mem)]
    if case.quant:
        one = [quantize_params(p) for p in one]
    params = one[0] if mem == 1 else stack_members(one)
    # a pipeline stage's slab holds more rows than the tick's group
    ck, cv = filled_cache(spec, case.shard[3] + 1 if case.shard else rows, 7,
                          case.kv_quant, mem)
    token = jnp.arange(3, 3 + n, dtype=jnp.int32)
    lengths = jnp.asarray(case.lengths, jnp.int32)
    live = jnp.asarray(case.live)
    budget = jnp.asarray(case.budget, jnp.int32)
    eos = jnp.full((n,), -1, jnp.int32)

    if case.shard:
        # parallel/pipeline.py: a stage's layers, the tick's rows of its slab
        l0, l1, r0, r1 = case.shard
        blocks = jax.tree.map(lambda a: a[l0:l1], params["blocks"])
        cut = lambda a: a[l0:l1, r0:r1]  # noqa: E731
        x = tr.decode_token_embed(params, spec, token, lengths)

        def stage(x, ck, cv, lens):
            return tr.decode_step_blocks(blocks, spec, x, lens, ck, cv,
                                         write_mask=live, history=case.history)

        def stage_chunk(x, ck, cv):
            for i in range(N_STEPS):
                x, ck, cv = stage(x, ck, cv, lengths + i * live)
            return x, ck, cv

        args = (x, cut(ck), cut(cv))
        return jax.jit(stage)(*args, lengths), jax.jit(stage_chunk)(*args)

    def step_of(p, k, v, t, ps, w):
        return tr.decode_step(p, spec, t, ps, k, v, write_mask=w,
                              history=case.history)

    def model_call(ck, cv, tok, pos, wm):
        if mem == 1:
            return step_of(params, ck, cv, tok, pos, wm)
        return _stacked_rows_call(mem, rows, step_of, params, ck, cv, tok,
                                  pos, wm)

    def chunk(ck, cv):
        return tr.decode_chunk(params, spec, N_STEPS, token, lengths, live,
                               budget, eos, ck, cv, greedy, (),
                               history=case.history, model_call=model_call)

    step = jax.jit(lambda ck, cv: model_call(
        ck, cv, token, jnp.where(live, lengths, 0), live))
    return step(ck, cv), jax.jit(chunk)(ck, cv)


@pytest.mark.parametrize("name", sorted(CASES))
def test_in_place_step_equals_the_stacked_body_bit_for_bit(name, monkeypatch):
    got = run_case(CASES[name])
    monkeypatch.setattr(tr, "decode_step_blocks", oracle_step_blocks)
    want = run_case(CASES[name])
    flat_got, tree_got = jax.tree.flatten(got)
    flat_want, tree_want = jax.tree.flatten(want)
    assert tree_got == tree_want
    for g, w in zip(flat_got, flat_want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_the_cases_move_what_they_say():
    """The oracle itself: a live row's write lands at its own position, a
    dead row's cache is left as it was, position 0 included."""
    case = CASES["dead_row_over_a_live_prompts_position_0"]
    before = filled_cache(case.spec, 3, 7)[0]
    (_, after, _), chunk = run_case(case)
    assert not np.array_equal(after[:, 0, 9], before[:, 0, 9])
    np.testing.assert_array_equal(after[:, 1], before[:, 1])
    np.testing.assert_array_equal(np.asarray(chunk[5])[:, 1], before[:, 1])
    assert chunk[2].tolist() == [N_STEPS, 0, N_STEPS]


# ---- the v5e compiler's text -------------------------------------------------

COMPILE_LIMIT_S = 240.0


def within(seconds, fn, *args, **kw):
    """``fn``'s result, or a skip when it raises or takes longer: a worker
    that cannot load libtpu must not hang the file."""
    box = {}

    def work():
        try:
            box["value"] = fn(*args, **kw)
        except Exception as e:  # noqa: BLE001 - reported as the skip's reason
            box["error"] = e

    thread = threading.Thread(target=work, daemon=True)
    thread.start()
    thread.join(seconds)
    if thread.is_alive():
        pytest.skip(f"{fn.__name__} did not end within {seconds:.0f} s")
    if "error" in box:
        pytest.skip(f"{fn.__name__}: {box['error']!r}")
    return box["value"]


@pytest.fixture(scope="module")
def v5e():
    return within(60.0, decode_static.v5e_device)


# the cells' shapes at a cut depth: the scan's program does not depend on it
STATIC = {
    "chat_12_rows_of_1024_int8": dict(
        quant="int8", rows=12, members=1, max_seq=1024),
    "longprompt_6_rows_of_2048_int8": dict(
        quant="int8", rows=6, members=1, max_seq=2048),
    "quorum_3_members_of_8_rows_bf16": dict(
        quant=None, rows=8, members=3, max_seq=1024),
}


@pytest.fixture(scope="module")
def programs(v5e):
    """The decode chunk of each shape at its largest history bucket,
    compiled once for the file: ``(text, temp bytes, side, slab)``."""
    done = {}

    def program(name, monkeypatch, step_blocks=None):
        key = (name, step_blocks is None)
        if key not in done:
            shape = dict(STATIC[name])
            max_seq = shape.pop("max_seq")
            spec = dataclasses.replace(
                MODEL_PRESETS["mistral-7b"], n_layers=2,
                max_seq=max_seq).validate()
            with monkeypatch.context() as patch:
                patch.setenv("QUORUM_TPU_QEINSUM_INT8", "1")  # the chip's
                if step_blocks is not None:
                    patch.setattr(tr, "decode_step_blocks", step_blocks)
                compiled = within(
                    COMPILE_LIMIT_S, decode_static.compile_decode_chunk,
                    spec, v5e, history=max_seq, **shape)
            (side,), (slab,) = decode_static.cache_sizes(
                spec, shape["rows"], shape["members"])
            done[key] = (compiled.as_text(),
                         compiled.memory_analysis().temp_size_in_bytes,
                         side, slab)
        return done[key]

    return program


@pytest.mark.parametrize("name", sorted(STATIC))
def test_no_whole_cache_copy_or_allocation_anywhere_in_the_chunk(
        name, programs, monkeypatch):
    """Neither in the step loop nor at the chunk's entry and exit (the
    K-major store was re-laid whole there, PERF.md section 5 item 1); the
    reader is shown to find the oracle's copies first."""
    if name.startswith("chat"):
        text, _, side, _ = programs(name, monkeypatch, oracle_step_blocks)
        assert decode_static.whole_cache_moves(text, side), \
            "the reader finds nothing in the body it was written against"
    text, _, side, _ = programs(name, monkeypatch)
    assert not decode_static.whole_cache_moves(text, side, loops_only=False)


@pytest.mark.parametrize("name", [n for n in sorted(STATIC)
                                  if STATIC[n]["members"] == 1])
def test_one_member_reads_the_carried_cache_through_one_pallas_call_a_layer(
        name, programs, monkeypatch):
    """No copy, reshape, dynamic-slice or allocation of a layer's slab
    anywhere, under 0.05 GB of temporaries, and exactly one Mosaic call in
    the layer loop: it reads both sides."""
    text, temp, side, slab = programs(name, monkeypatch)
    assert temp < 0.05e9
    assert not decode_static.slab_moves(text, slab)
    (call,) = decode_static.kernel_calls(text)
    assert "attn.core" in call[4] and "decode_attention_in_place" in call[4]
    # what is left with the cache's shape: the two in-place scatters
    updates = [row for row in decode_static.program_ops(text, {side})
               if row != call]
    assert len(updates) == 2
    assert all("attn.cache_write/scatter" in row[4] for row in updates)


def test_the_stacked_members_keep_xlas_einsums_over_the_same_store(
        programs, monkeypatch):
    """Under the member vmap no Pallas call is lowered (its batching rule
    would slice each member's whole side out), and what moves is one
    layer's history window a side, never the stacked cache."""
    text, _, side, slab = programs("quorum_3_members_of_8_rows_bf16",
                                   monkeypatch)
    assert not decode_static.kernel_calls(text)
    assert decode_static.slab_moves(text, slab)
    assert not decode_static.whole_cache_moves(text, side, loops_only=False)


# ---- the stacked members' weights are read where they lie --------------------

# quorum's shape, depth included: at 2 layers a stacked ``wk`` has as many
# elements as a layer's cache slab
QUORUM = dict(rows=8, members=3, quant=None)
MEMBER_PROGRAMS = {
    # name: (compile function, its shape, temp limit in GB)
    "decode_chunk": ("compile_decode_chunk", dict(history=512), 0.05),
    "member_admit_32": ("compile_member_admit", dict(bucket=32), 0.6),
    "member_admit_128": ("compile_member_admit", dict(bucket=128), 0.6),
    # its temp is the cache's two copies (0.50 GB: PERF.md section 7, not
    # the weights') and a 128-token segment's activations
    "member_segment_128": ("compile_member_segment",
                           dict(bucket=128, history=1024), 0.7),
}


@pytest.fixture(scope="module")
def quorum_spec():
    return dataclasses.replace(MODEL_PRESETS["mistral-7b"], n_layers=5,
                               max_seq=1024).validate()


@pytest.mark.parametrize("name", sorted(MEMBER_PROGRAMS))
def test_no_member_program_moves_a_stacked_block_matrix(
        name, v5e, quorum_spec):
    """No copy or transposition with a stacked block matrix's dimensions
    (the smallest is ``wk``: 3 x 5 x 4096 x 1024) and no weight-sized
    temporary: 6.54 GB of them a program when the blocks were ``[M, L, …]``
    (PERF.md section 5 item 2)."""
    fn, shape, temp_gb = MEMBER_PROGRAMS[name]
    compiled = within(COMPILE_LIMIT_S, getattr(decode_static, fn),
                      quorum_spec, v5e, **QUORUM, **shape)
    matrices = decode_static.weight_matrices(quorum_spec, 3)
    assert (3 * 5 * 4096 * 1024) == min(math.prod(m) for m in matrices)
    assert not decode_static.weight_moves(compiled.as_text(), matrices)
    assert compiled.memory_analysis().temp_size_in_bytes < temp_gb * 1e9


def test_the_reader_finds_the_copies_of_a_members_major_store(
        v5e, quorum_spec, monkeypatch):
    """The same chunk over the tree as it was held, ``[M, L, …]``: the
    member vmap moves the layer scan's ``xs`` to axis 1, seven copies and
    6.54 GB of temporaries."""
    from quorum_tpu.engine import engine

    def members_major(params):
        return dict.fromkeys(params, 0)

    monkeypatch.setattr(sharding, "member_axes", members_major)
    monkeypatch.setattr(engine, "member_axes", members_major)
    compiled = within(COMPILE_LIMIT_S, decode_static.compile_decode_chunk,
                      quorum_spec, v5e, history=512, **QUORUM)
    matrices = decode_static.weight_matrices(quorum_spec, 3)
    assert sorted(matrices)[0] == (3, 5, 4096, 1024)
    moves = decode_static.weight_moves(compiled.as_text(), matrices)
    assert len(moves) == len(matrices) == 7
    assert compiled.memory_analysis().temp_size_in_bytes > 6.5e9


# ---- the latent family's tile kernel, at the published widths -----------------


@pytest.mark.parametrize("hist", [4096, 16384])
def test_a_selecting_segment_reads_the_carried_rows_through_one_pallas_call(
        v5e, hist):
    """A full layer's attention of a 512-query segment of ``dots3-ep8`` at
    a selecting history bucket (models/latent.full_attention): Mosaic takes
    the kernel at these widths, it is the program's one Pallas call, and the
    row's slab of the leaf ``[16, 16384, 640]`` is neither sliced out nor
    copied for it (the latent-space form sliced the bucket out first)."""
    from jax.sharding import SingleDeviceSharding

    from quorum_tpu.models import latent
    from quorum_tpu.models.model_config import resolve_spec

    spec = resolve_spec("dots3-note-prev", {"max_seq": "16384"})
    g = spec.latent("G")
    t, slots = 512, 16
    assert latent.tiles_pay(g, t) and hist > spec.index_topk

    def attend(q_n, q_r, q_i, w, rows, k_i, w_kb, w_vb, offset):
        keys: list = []
        pos = (offset + jnp.arange(t))[None]
        return latent.full_attention(
            q_n, q_r, q_i, w, (rows, k_i), 5, hist, pos,
            jnp.ones((1, t), bool), {"w_kb": w_kb, "w_vb": w_vb}, spec, keys)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=SingleDeviceSharding(v5e))

    width = latent.row_width(g)
    args = (shape(1, t, g.heads, g.nope), shape(1, t, g.heads, g.rope),
            shape(1, t, spec.index_n_heads, spec.index_head_dim),
            shape(1, t, spec.index_n_heads, dtype=jnp.float32),
            shape(slots, spec.max_seq, width),
            shape(slots, spec.max_seq, spec.index_head_dim),
            shape(g.kv_rank, g.heads * g.nope),
            shape(g.kv_rank, g.heads * g.v), shape(dtype=jnp.int32))
    compiled = within(COMPILE_LIMIT_S,
                      lambda: jax.jit(attend).lower(*args).compile())
    text = compiled.as_text()
    calls = [row for row in decode_static.program_ops(text, set())
             if row[2] == "tpu_custom_call"]
    (call,) = calls
    assert "attn.tiled" in call[4] and "latent_tile_attention" in call[4]
    assert not decode_static.slab_moves(text, hist * width)


# ---- a spec with a mixer: the recurrent state is passed over once ------------

# a slab of 64 MB: one of 32 MB the compiler prefetches into fast memory whole
MIXER_ROWS = 16


@pytest.fixture(scope="module")
def mixer_programs(v5e):
    """Falcon-H1-34B's widths at 2 layers, 16 rows of 512 positions: the
    decode chunk as served (``fused``) and with the kernel refused
    (``step``: XLA's two fusions): ``(text, temp bytes)``."""
    spec = dataclasses.replace(MODEL_PRESETS["falcon-h1-34b"], n_layers=2,
                               max_seq=512).validate()
    done = {}

    def program(form, monkeypatch):
        if form not in done:
            with monkeypatch.context() as patch:
                if form == "step":
                    patch.setattr(ssm_step, "refusal",
                                  lambda *a, **kw: "refused by the test")
                compiled = within(
                    COMPILE_LIMIT_S, decode_static.compile_decode_chunk,
                    spec, v5e, rows=MIXER_ROWS, history=512)
            done[form] = (compiled.as_text(),
                          compiled.memory_analysis().temp_size_in_bytes)
        return done[form]

    return spec, program


def test_a_mixer_s_state_is_read_by_one_pallas_call_a_layer_and_not_moved(
        mixer_programs, monkeypatch):
    """One Mosaic call a layer body takes the state leaf, under
    ``ssm.step``, and nothing else does; no copy, reshape, slice or
    allocation of the leaf or of a layer's slab anywhere; the temporaries
    are not above XLA's form's by more than the kernel's blocks. The reader
    is shown to find XLA's two readers first: the in-place update and the
    readout that reads the slab again."""
    spec, program = mixer_programs
    leaf = decode_static.state_leaf(spec, MIXER_ROWS)
    (_, state), (_, slab) = decode_static.cache_sizes(spec, MIXER_ROWS)
    text, xla_temp = program("step", monkeypatch)
    update, readout = sorted(
        (row for row in decode_static.readers(text, leaf)
         if row[2] == "fusion"), key=lambda row: row[3] != leaf)
    assert "ssm.step/dynamic_update_slice" in update[4]
    assert "ssm.step/reduce_sum" in readout[4]
    text, temp = program("fused", monkeypatch)
    (call,) = decode_static.readers(text, leaf)
    assert call[2] == "tpu_custom_call" and leaf in call[3]
    assert "ssm.step" in call[4] and "ssm_step_in_place" in call[4]
    attention, mixer = decode_static.kernel_calls(text)
    assert mixer == call and "attn.core" in attention[4]
    assert not decode_static.slab_moves(text, slab)
    assert not decode_static.whole_cache_moves(text, state, loops_only=False)
    assert temp <= xla_temp + 4 * ssm_step.BLOCK_BYTES
