"""A stacked engine's weights are held where its programs read them
(parallel/sharding.py ``member_axes``): the block leaves layers-major,
``[L, M, …]``, because every member-vmapped program scans over the layers and
``jax.vmap`` of a ``lax.scan`` takes a batched ``xs`` at axis 1; the leaves
outside the blocks ``[M, …]``.

The store moved and no arithmetic did. So on the CPU each of the four model
calls the engine vmaps over its members gives, over the layers-major tree
mapped where ``member_axes`` says, logits and caches bit for bit what a plain
``jax.vmap`` gives over the same values held members-major (the oracle: the
transposition is here in the test, not in a program); the stacked init
program yields, leaf for leaf, the values the members-major init program
yielded on the same seeds (the benchmark's seeded weights are what they
were); and the one rule reaches the init's shardings, the by-member readers
and the by-index view. What the rule buys is read from the v5e compiler's
text, tests/test_decode_in_place.py.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quorum_tpu.engine.engine import InferenceEngine, _member_vmap
from quorum_tpu.models import transformer as tr
from quorum_tpu.models.init import (
    init_params,
    init_params_ensemble_sharded,
    init_params_from_key,
)
from quorum_tpu.models.model_config import MODEL_PRESETS
from quorum_tpu.models.quant import quantize_params
from quorum_tpu.parallel.mesh import make_mesh
from quorum_tpu.parallel.sharding import (
    member_axes,
    member_params,
    param_partition_specs,
    stack_members,
)

TINY = dataclasses.replace(MODEL_PRESETS["llama-tiny"], max_seq=64)
MEMBERS, ROWS = 3, 2
SEEDS = (3, 4, 5)


def members_major(layers_major):
    """The same values held ``[M, L, …]``, as a stacked tree was before."""
    out = dict(layers_major)
    out["blocks"] = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1),
                                 layers_major["blocks"])
    return out


def stacked_tree(quant: bool):
    one = [init_params(TINY, seed=s) for s in SEEDS]
    if quant:
        one = [quantize_params(p) for p in one]
    return one, stack_members(one)


def filled_cache(seed):
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda leaf: jnp.asarray(
            rng.normal(size=(MEMBERS,) + leaf.shape), leaf.dtype),
        tr.init_cache(TINY, ROWS))


def ints(*values):
    return jnp.asarray(values, jnp.int32)


def call_decode_step():
    token = ints([5, 9], [11, 3], [7, 8])
    lengths = ints([3, 17], [4, 30], [9, 2])
    live = jnp.asarray([[True, True], [True, False], [True, True]])

    def one(p, k, v, t, ps, w):
        return tr.decode_step(p, TINY, t, ps, k, v, write_mask=w, history=32)

    return one, (token, lengths, live)


def call_prefill():
    tokens = jnp.arange(MEMBERS * 16, dtype=jnp.int32).reshape(
        MEMBERS, 1, 16) + 3
    lengths = ints([16], [9], [12])
    gates = jnp.asarray([True, False, True])

    def one(p, k, v, tok, lens, gate):
        return tr.prefill(p, TINY, tok, lens, k, v, slot=jnp.int32(1),
                          write_gate=gate)

    return one, (tokens, lengths, gates)


def call_prefill_segment():
    tokens = jnp.arange(MEMBERS * 16, dtype=jnp.int32).reshape(
        MEMBERS, 1, 16) + 3
    offsets, n_valids, slots = ints(16, 0, 32), ints(16, 7, 12), ints(0, 1, 1)
    gates = jnp.asarray([True, True, False])

    def one(p, k, v, tok, off, nv, slot, gate):
        return tr.prefill_segment(p, TINY, tok, off, nv, k, v, slot,
                                  history=64, write_gate=gate)

    return one, (tokens, offsets, n_valids, slots, gates)


CALLS = {
    "decode_step": call_decode_step,
    "prefill": call_prefill,
    "prefill_segment": call_prefill_segment,
}


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("name", sorted(CALLS))
def test_member_vmap_over_the_layers_major_tree_is_bit_for_bit(name, quant):
    one, args = CALLS[name]()
    _, layers = stacked_tree(quant)
    ck, cv = filled_cache(7)
    got = jax.jit(lambda p: _member_vmap(one, p, ck, cv, *args))(layers)
    want = jax.jit(lambda p: jax.vmap(one)(p, ck, cv, *args))(
        members_major(layers))
    flat_got, tree_got = jax.tree.flatten(got)
    flat_want, tree_want = jax.tree.flatten(want)
    assert tree_got == tree_want
    for g, w in zip(flat_got, flat_want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # a member's write reached its own cache and the calls did something
    assert not np.array_equal(np.asarray(flat_got[-1]), np.asarray(cv))


@pytest.mark.parametrize("quant", [None, "int8"], ids=["bf16", "int8"])
@pytest.mark.parametrize("preset", ["llama-tiny", "gpt2-tiny", "mixtral-tiny"])
def test_the_stacked_init_yields_the_members_major_inits_values(preset, quant):
    """Leaf for leaf what the init program yielded when it stacked every
    leaf at axis 0 (``jax.vmap`` over the keys, then the quantization), on
    the same seeds: block leaves ``[L, M, …]``, the rest ``[M, …]``."""
    spec = MODEL_PRESETS[preset]
    got = init_params_ensemble_sharded(spec, make_mesh(), list(SEEDS),
                                       quant=quant)

    def members_major_init(keys):
        params = jax.vmap(lambda k: init_params_from_key(spec, k))(keys)
        return quantize_params(params) if quant else params

    was = jax.jit(members_major_init)(
        jnp.stack([jax.random.PRNGKey(s) for s in SEEDS]))
    want = members_major(was)  # swapping the two axes is its own inverse
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype, path
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    n_layers = spec.n_layers
    for leaf in jax.tree.leaves(got["blocks"]):
        assert leaf.shape[:2] == (n_layers, MEMBERS)
    assert got["final_norm_w"].shape[0] == MEMBERS


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_one_rule_says_where_the_member_axis_is(quant):
    one, layers = stacked_tree(quant)
    axes = member_axes(layers)
    assert axes == {k: 1 if k == "blocks" else 0 for k in layers}
    # a by-member reader gets that member's own tree back
    for m in range(MEMBERS):
        for g, w in zip(jax.tree.leaves(member_params(layers, m)),
                        jax.tree.leaves(one[m])):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the shardings put one replicated dim there: a block leaf keeps its
    # layer axis first, its own axes follow the member's
    def spec_of(tree, *path):
        for key in path + (("q8",) if quant else ()):
            tree = tree[key]
        return tuple(tree)

    flat = param_partition_specs(one[0])
    stacked = param_partition_specs(layers, stacked=True)
    wq = spec_of(flat, "blocks", "wq")
    assert spec_of(stacked, "blocks", "wq") == (wq[0], None) + wq[1:]
    assert spec_of(stacked, "tok_emb") == (None,) + spec_of(flat, "tok_emb")
    if quant:
        assert stacked["blocks"]["wq"]["qs"] == stacked["blocks"]["wq"]["q8"]


def test_a_stacked_engine_holds_the_layout_and_reads_by_member():
    """``weights`` is the tree the programs run; ``params`` indexes every
    leaf member first, for a reader that takes a member's layer by index
    (benchmarks/reference_check.py), and is ``weights`` where nothing is
    stacked."""
    eng = InferenceEngine(TINY, members=MEMBERS, n_slots=2, seed=3)
    try:
        blocks = eng.weights["blocks"]
        assert blocks["wq"].shape[:2] == (TINY.n_layers, MEMBERS)
        assert eng.weights["tok_emb"].shape[0] == MEMBERS
        view = eng.params
        for m in range(MEMBERS):
            own = init_params(TINY, seed=3 + m)
            for layer in range(TINY.n_layers):
                for name in ("wq", "w_down", "attn_norm_w"):
                    np.testing.assert_array_equal(
                        np.asarray(view["blocks"][name][m, layer]),
                        np.asarray(blocks[name][layer, m]))
                np.testing.assert_allclose(
                    np.asarray(view["blocks"]["wk"][m, layer], np.float32),
                    np.asarray(own["blocks"]["wk"][layer], np.float32),
                    atol=0.01)  # eager init against the jitted one
            np.testing.assert_array_equal(
                np.asarray(view["lm_head"][m]),
                np.asarray(eng.weights["lm_head"][m]))
    finally:
        eng.shutdown()
    one = InferenceEngine(TINY, n_slots=2, seed=3)
    try:
        assert one.params is one.weights
    finally:
        one.shutdown()
