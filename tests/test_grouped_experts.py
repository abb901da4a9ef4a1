"""The grouped expert product's Pallas kernel (ops/grouped_experts.py)
through the Pallas interpreter, against the loop of XLA's products it stands
in for (``patterned._experts_grouped``'s default) and against every held
expert over every row (``_experts_dense``) as the oracle; the three counters
beside each.

Small widths on the CPU: the interpreter checks the kernel's arithmetic and
its index maps, not Mosaic's tiling (PERF.md section 6, PR 53 has the chip's
numbers).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quorum_tpu.models import patterned
from quorum_tpu.models.init import init_params
from quorum_tpu.models.model_config import resolve_spec
from quorum_tpu.ops import grouped_experts

STAT = {name: i for i, name in enumerate(patterned.STATS)}
# float32: what is left is the order of the sums. bfloat16: a hidden row
# rounded one step apart after sums in another order, on outputs of 0.1-1
TOL = {"float32": 2e-5, "bfloat16": 3e-2}
TILE = 16


def _layer(dtype: str, seed: int = 7, layer: int = 1):
    spec = resolve_spec("k-exaone-tiny", {"dtype": dtype})
    return spec, patterned.layer_of(init_params(spec, seed), layer)


def _bias(spec, experts):
    """A router bias under which every token picks ``experts`` first."""
    return jnp.where(jnp.isin(jnp.arange(spec.n_experts),
                              jnp.asarray(experts)), 10.0, 0.0)


def _even(spec, lyr):
    return lyr, 40, None


def _one_expert(spec, lyr):
    """Every token's first pick on held expert 1, its others away: four
    tiles of sixteen rows, one after another, all expert 1's."""
    away = list(range(spec.held, spec.held + spec.experts_per_token - 1))
    return dict(lyr, router_bias=_bias(spec, [1] + away)), 64, None


def _experts_without_a_pick(spec, lyr):
    """Held experts 0 and 3 picked by every token, 1 and 2 by none."""
    far = jnp.where(jnp.isin(jnp.arange(spec.n_experts), jnp.asarray([1, 2])),
                    -10.0, 0.0)
    return dict(lyr, router_bias=_bias(spec, [0, 3]) + far), 24, None


def _no_held_pick(spec, lyr):
    """Every pick on an expert that is not held: no tile at all."""
    away = list(range(spec.held, spec.held + spec.experts_per_token))
    return dict(lyr, router_bias=_bias(spec, away)), 24, None


def _not_a_multiple(spec, lyr):
    return lyr, 9, None       # 36 picks: no multiple of a 16-row tile


def _pads(spec, lyr):
    return lyr, 40, jnp.arange(40) < 27


CASES = {
    "even": _even,
    "one_expert": _one_expert,
    "experts_without_a_pick": _experts_without_a_pick,
    "no_held_pick": _no_held_pick,
    "not_a_multiple": _not_a_multiple,
    "pads": _pads,
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_loop_and_the_dense_form(case, dtype, monkeypatch):
    monkeypatch.setattr(patterned, "TILE", TILE)
    spec, lyr = _layer(dtype)
    lyr, n, real = CASES[case](spec, lyr)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, n, spec.d_model))
    ok = jnp.ones((1, n), bool) if real is None else real[None, :]
    kernel, c_kernel = patterned.moe_layer(x, lyr, spec, ok, dense=False,
                                           interpret=True)
    loop, c_loop = patterned.moe_layer(x, lyr, spec, ok, dense=False)
    dense, c_dense = patterned.moe_layer(x, lyr, spec, ok, dense=True)
    live = np.asarray(ok[0])
    for other in (loop, dense):
        np.testing.assert_allclose(
            np.asarray(kernel, np.float32)[0, live],
            np.asarray(other, np.float32)[0, live], atol=TOL[dtype])
    # picks per held expert, picks made, dropped, rows multiplied
    assert np.asarray(c_kernel).tolist() == np.asarray(c_loop).tolist()
    counts = np.asarray(c_kernel)
    assert counts[spec.held + STAT["dropped"]] == 0
    assert counts[spec.held + STAT["picks"]] == (
        int(live.sum()) * spec.experts_per_token)
    assert (counts[:spec.held] == np.asarray(c_dense)[:spec.held]).all()
    tiles = counts[spec.held + STAT["tile_rows"]] // TILE
    if case == "one_expert":
        assert counts[:spec.held].tolist() == [0, 64, 0, 0] and tiles == 4
    if case == "experts_without_a_pick":
        assert counts[:spec.held].tolist() == [24, 0, 0, 24] and tiles == 4
    if case == "no_held_pick":
        # the routed part is zero: what is left is the shared expert's
        assert tiles == 0 and not counts[:spec.held].any()
        assert np.abs(np.asarray(kernel - dense, np.float32)).max() == 0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("r", [0, 2])
def test_a_slot_s_experts_are_read_at_their_period(r, dtype, monkeypatch):
    """A period's slot hands its stacked leaves over whole: the kernel and
    the loop read period ``r``'s experts where they lie, and give what the
    written-out layer of that period gives."""
    monkeypatch.setattr(patterned, "TILE", TILE)
    spec = resolve_spec("k-exaone-tiny", {"dtype": dtype})
    layers = [patterned.layer_of(init_params(spec, seed), 1)
              for seed in (3, 4, 5)]
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *layers)
    x = jax.random.normal(jax.random.PRNGKey(r), (2, 20, spec.d_model))
    ok = jnp.ones((2, 20), bool)
    slot = patterned.Slot(stacked, jnp.int32(r))
    want, c_want = patterned.moe_layer(x, layers[r], spec, ok, dense=True)
    for how in (dict(interpret=True), {}):
        got, counts = jax.jit(lambda x: patterned.moe_layer(
            x, slot, spec, ok, dense=False, **how))(x)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=TOL[dtype])
        assert (np.asarray(counts)[:spec.held + 2]
                == np.asarray(c_want)[:spec.held + 2]).all()
    written, c_written = patterned.moe_layer(x, layers[r], spec, ok,
                                             dense=False, interpret=True)
    assert np.abs(np.asarray(got - written, np.float32)).max() <= TOL[dtype]
    assert np.asarray(counts).tolist() == np.asarray(c_written).tolist()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("block_kib", [1, 4, 1024])
def test_the_blocks_of_a_matrix_add_up(block_kib, dtype):
    """One block a matrix, or several of gate/up's rows and of down's: the
    tiles' rows through their experts, tiles past the count untouched."""
    d, f, held, rows, live = 64, 32, 3, 8, 3
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (5 * rows, d), dtype)
    w_gate = jax.random.normal(keys[1], (2, held, d, f), dtype) * d ** -0.5
    w_up = jax.random.normal(keys[2], (2, held, d, f), dtype) * d ** -0.5
    w_down = jax.random.normal(keys[3], (2, held, f, d), dtype) * f ** -0.5
    expert_of_tile = jnp.asarray([0, 2, 2, 1, 1], jnp.int32)
    got = grouped_experts.grouped_product(
        x, expert_of_tile, jnp.int32(live), w_gate, w_up, w_down,
        jnp.int32(1), tile_rows=rows, interpret=True,
        block_bytes=block_kib << 10)
    itemsize = jnp.dtype(dtype).itemsize
    blocks = (d // grouped_experts.block_rows(
        d, f, itemsize, block_bytes=block_kib << 10, lanes=1),
              f // grouped_experts.block_rows(
        f, d, itemsize, block_bytes=block_kib << 10, lanes=1))
    assert blocks == {"float32": {1: (8, 8), 4: (2, 2), 1024: (1, 1)},
                      "bfloat16": {1: (4, 4), 4: (1, 1), 1024: (1, 1)}}[
        dtype][block_kib]
    for i in range(live):
        e = int(expert_of_tile[i])
        tile = x[i * rows:(i + 1) * rows]
        h = (jax.nn.silu(jnp.dot(tile, w_gate[1, e],
                                 preferred_element_type=jnp.float32))
             * jnp.dot(tile, w_up[1, e], preferred_element_type=jnp.float32)
             ).astype(dtype)
        want = jnp.dot(h, w_down[1, e], preferred_element_type=jnp.float32)
        np.testing.assert_allclose(np.asarray(got[i * rows:(i + 1) * rows]),
                                   np.asarray(want), atol=TOL[dtype])
    assert got.dtype == jnp.float32 and got.shape == (5 * rows, d)


REFUSED = {
    "sharded": (dict(tile_rows=128, d=2048, f=1792, dtype="bfloat16",
                     sharded=True), "partitioned over devices"),
    "held_elsewhere": (dict(tile_rows=64, d=5120, f=1536, dtype="bfloat16",
                            held_share=32 / 256),
                       "0.125 of the experts are held here"),
    "float32": (dict(tile_rows=128, d=2048, f=1792, dtype="float32"),
                "rows of float32"),
    "rows": (dict(tile_rows=24, d=2048, f=1792, dtype="bfloat16"),
             "tiles of 24 rows"),
    "lanes": (dict(tile_rows=128, d=2048, f=1800, dtype="bfloat16"),
              "experts of 2048 x 1800: no block of whole lanes"),
    "wide": (dict(tile_rows=128, d=32768, f=32768, dtype="bfloat16"),
             "no block of whole lanes within 2 MiB"),
    "fast_memory": (dict(tile_rows=1024, d=8192, f=8192, dtype="bfloat16"),
                    "MiB of fast memory"),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_refusal_names_each_refused_shape(name):
    how, said = REFUSED[name]
    how = dict(how)
    args = [how.pop(key) for key in ("tile_rows", "d", "f", "dtype")]
    assert said in grouped_experts.refusal(*args, **how)


@pytest.mark.parametrize("d,f", [(2048, 1792), (5120, 1536), (6144, 2048)])
def test_the_served_widths_are_taken(d, f):
    """LFM2's, dots3's and K-EXAONE's experts tile, in blocks of whole
    rows of a matrix within the bytes asked of fast memory."""
    assert grouped_experts.refusal(128, d, f, "bfloat16") == ""
    bd = grouped_experts.block_rows(d, f, 2)
    bf = grouped_experts.block_rows(f, d, 2)
    assert d % bd == 0 and f % bf == 0 and bd % 128 == 0 and bf % 128 == 0
    assert max(bd * f, bf * d) * 2 <= grouped_experts.BLOCK_BYTES
    assert grouped_experts.vmem_bytes(128, d, f, bd, bf, 2) < (
        grouped_experts.VMEM_CAP)
    assert grouped_experts.refusal(128, d, f, "bfloat16",
                                   held_share=0.5) == ""
    # interpret mode lifts Mosaic's limits and the chip's, not a partitioned
    # program's
    assert grouped_experts.refusal(24, 48, 40, "float32", held_share=0.25,
                                   interpret=True) == ""
    assert grouped_experts.refusal(24, 48, 40, "float32", interpret=True,
                                   sharded=True)


def test_a_refused_call_runs_the_loop(monkeypatch):
    """A program partitioned over devices keeps the loop, interpret mode or
    not: the kernel is never built, and the answer is the dense form's."""
    monkeypatch.setattr(patterned, "TILE", TILE)

    def never(*args, **kwargs):
        raise AssertionError("the kernel was built for a refused call")

    monkeypatch.setattr(grouped_experts, "grouped_product", never)
    spec, lyr = _layer("float32")
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, spec.d_model))
    ok = jnp.ones((1, 40), bool)
    for how in (dict(sharded=True), dict(sharded=True, interpret=True)):
        got, counts = patterned.moe_layer(x, lyr, spec, ok, dense=False,
                                          **how)
        dense, _ = patterned.moe_layer(x, lyr, spec, ok, dense=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                                   atol=TOL["float32"])
        assert counts[spec.held + STAT["dropped"]] == 0
    # float32 rows where the program is lowered for a chip: the loop, too
    assert grouped_experts.refusal(patterned.TILE, spec.d_model,
                                   spec.d_ff_expert, "float32")
    got, _ = jax.jit(lambda x: patterned.moe_layer(
        x, lyr, spec, ok, dense=False))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               atol=TOL["float32"])
