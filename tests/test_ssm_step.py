"""The one-step recurrence in the carried state leaf (ops/ssm_step.py): the
kernel through the Pallas interpreter against ``models/ssm.py::_scan_step``,
the XLA form it replaces on a TPU and stays the refused path; what the kernel
may not touch (every other layer's slab, a row whose step is masked) bit for
bit; and that every call the kernel refuses says why and takes the XLA form.
The Mosaic program itself is read in tests/test_decode_in_place.py (the v5e
compiler's text)."""

from __future__ import annotations

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quorum_tpu.models import ssm
from quorum_tpu.models.init import init_params
from quorum_tpu.models.model_config import MODEL_PRESETS
from quorum_tpu.ops import ssm_step

LAYERS, PER, P, N = 3, 4, 8, 128


def operands(rows: int, groups: int, seed: int = 0):
    """A step's grouped operands as ``ssm.mixer`` makes them, and a leaf. Row
    1 takes no position: ``dt = 0`` and ``x = 0``."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (rows, 1, groups, PER)
    real = (jnp.arange(rows) != 1).reshape(rows, 1, 1, 1)
    x = jnp.where(real[..., None], jax.random.normal(k[0], shape + (P,)), 0)
    dt = jnp.where(real, jax.random.uniform(k[1], shape, minval=1e-3,
                                            maxval=0.5), 0.0)
    bm = jax.random.normal(k[2], (rows, 1, groups, N))
    cm = jax.random.normal(k[3], (rows, 1, groups, N)) / N ** 0.5
    a = -jax.random.uniform(k[4], (groups, PER), minval=1.0, maxval=16.0)
    leaf = jax.random.normal(k[5], (LAYERS, rows, groups * PER, P, N))
    return x, bm, cm, dt, a, leaf


@pytest.mark.parametrize("rows,groups,hb,layer", list(itertools.product(
    (2, 5), (1, 2), (1, 2, 4), (0, 2))))
def test_the_kernel_is_the_xla_step_and_touches_nothing_else(
        rows, groups, hb, layer):
    x, bm, cm, dt, a, leaf = operands(rows, groups, seed=hb + layer)
    heads = groups * PER
    slab = leaf[layer].reshape(rows, groups, PER, P, N)
    want_y, want = ssm._scan_step(x, bm, cm, dt, a, slab)
    dt1 = dt[:, 0]
    y, out = ssm_step.step_in_place(
        leaf, jnp.int32(layer), jnp.exp(dt1 * a).reshape(rows, heads),
        (dt1[..., None] * x[:, 0]).reshape(rows, heads, P), bm[:, 0],
        cm[:, 0], hb=hb, interpret=True)
    np.testing.assert_allclose(y, want_y.reshape(rows, heads, P), atol=1e-6)
    np.testing.assert_allclose(out[layer], want.reshape(leaf.shape[1:]),
                               atol=1e-6)
    for other in set(range(LAYERS)) - {layer}:
        np.testing.assert_array_equal(out[other], leaf[other])
    np.testing.assert_array_equal(out[layer, 1], leaf[layer, 1])
    assert not np.array_equal(out[layer, 0], leaf[layer, 0])


def test_a_block_is_the_most_heads_of_one_group_within_its_bytes():
    assert ssm_step.heads_block(16, 128, 256) == 16  # the cell's: 2 MiB
    assert ssm_step.heads_block(16, 128, 256, block_bytes=1 << 20) == 8
    assert ssm_step.heads_block(12, 128, 256, block_bytes=1 << 20) == 6
    assert ssm_step.heads_block(4, 1024, 1024) == 0


TINY = MODEL_PRESETS["falcon-h1-tiny"]
TILED = dataclasses.replace(TINY, ssm_state=128).validate()
# name: (spec, the state's dtype, sharded, the form logged, its reason)
CALLS = {
    "a_bfloat16_state": (TILED, jnp.bfloat16, False, "step",
                         "a bfloat16 state"),
    "a_state_off_the_lanes": (TINY, jnp.float32, False, "step",
                              "heads of [16, 16]: not 8 sublanes by 128"),
    "a_sharded_call": (TILED, jnp.float32, True, "step",
                       "partitioned over devices"),
    "a_cpu_call": (TILED, jnp.float32, False, "fused",
                   "pallas where lowered for a tpu, xla's step elsewhere"),
}


def mixer_operands(spec, rows: int, layer: int, dtype=jnp.float32):
    """A decode step's call of ``ssm.mixer`` at ``layer``: the block's normed
    input, its weights, the state leaf of every layer and the layer's tail."""
    block = jax.tree.map(lambda leaf: leaf[layer],
                         init_params(spec, 5)["blocks"])
    k = jax.random.split(jax.random.PRNGKey(layer), 3)
    u = jax.random.normal(k[0], (rows, 1, spec.d_model))
    state, tail = ssm.init_carry(spec, rows, jnp.float32, (spec.n_layers,))
    return (u, block, jax.random.normal(k[1], state.shape).astype(dtype),
            jax.random.normal(k[2], tail.shape)[layer])


@pytest.mark.parametrize("name", sorted(CALLS))
def test_a_call_the_kernel_does_not_take_says_why_and_runs_the_xla_step(
        name, caplog, monkeypatch):
    spec, dtype, sharded, form, reason = CALLS[name]
    assert (form == "step") == bool(ssm_step.refusal(
        (spec.n_layers, 2, spec.ssm_heads, spec.ssm_head_dim, spec.ssm_state),
        dtype, sharded=sharded))
    if form == "step":  # a refused call does not even trace the kernel
        monkeypatch.setattr(ssm_step, "step_in_place", None)
    caplog.set_level("INFO", logger="quorum_tpu.models.ssm")
    layer, n_valid = 1, jnp.array([1, 0], jnp.int32)
    u, block, state, tail = mixer_operands(spec, 2, layer, dtype)
    out, leaf, new_tail = jax.jit(
        lambda state: ssm.mixer(u, block, spec, state, tail, n_valid,
                                layer=layer, sharded=sharded))(state)
    want, slab, want_tail = jax.jit(
        lambda state: ssm.mixer(u, block, spec, state[layer], tail,
                                n_valid))(state)
    np.testing.assert_array_equal(out, want)
    np.testing.assert_array_equal(new_tail, want_tail)
    np.testing.assert_array_equal(
        leaf, ssm.rows_write(state, slab, layer, 0))
    line, sliced = [r.getMessage() for r in caplog.records
                    if r.getMessage().startswith("mixer-path")]
    assert f"form={form} " in line and reason in line
    assert "form=step " in sliced and "sliced out" in sliced


@pytest.mark.parametrize("layer", [0, 2])
def test_the_mixer_s_step_through_the_kernel_is_its_step_over_the_slab(layer):
    """``mixer`` handed the leaf and the layer (the decode step's call), the
    kernel interpreted, against ``mixer`` handed the slab: what the block
    adds, the slab written, and nothing else touched."""
    spec, n_valid = TILED, jnp.array([1, 0, 1], jnp.int32)
    u, block, state, tail = mixer_operands(spec, 3, layer)
    out, leaf, new_tail = jax.jit(lambda state: ssm.mixer(
        u, block, spec, state, tail, n_valid, layer=layer,
        interpret=True))(state)
    want, slab, want_tail = jax.jit(lambda state: ssm.mixer(
        u, block, spec, state[layer], tail, n_valid))(state)
    np.testing.assert_allclose(out, want, atol=1e-6)
    np.testing.assert_allclose(leaf[layer], slab, atol=1e-6)
    np.testing.assert_array_equal(new_tail, want_tail)
    for other in set(range(spec.n_layers)) - {layer}:
        np.testing.assert_array_equal(leaf[other], state[other])
    np.testing.assert_array_equal(leaf[layer, 1], state[layer, 1])
