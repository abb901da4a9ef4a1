"""Sharding tests on the virtual 8-device CPU mesh (SURVEY.md §4(c)).

Verifies that the TP/DP-sharded model produces the same numbers as the
single-device run, that parameter layouts follow the Megatron rules, and
that the MoE experts axis shards over tp (expert parallelism).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from quorum_tpu.models import init_params, prefill, resolve_spec
from quorum_tpu.models.transformer import decode_step, init_cache
from quorum_tpu.parallel import MeshConfig, make_mesh, shard_pytree
from quorum_tpu.parallel.sharding import (
    kv_cache_sharding,
    param_partition_specs,
)

import pytest
# Engine-scale / compile-heavy / multi-process: slow tier (make test skips,
# make test-all and CI run everything — VERDICT r3 item 6).
pytestmark = pytest.mark.slow


def test_mesh_shapes():
    mesh = make_mesh(MeshConfig(dp=2, tp=4))
    assert mesh.shape == {"dp": 2, "pp": 1, "sp": 1, "tp": 4}
    assert len(mesh.devices.flatten()) == 8


def test_param_partition_specs_follow_megatron_rules():
    spec = resolve_spec("mixtral-tiny")
    params = init_params(spec, seed=0)
    specs = param_partition_specs(params)
    blocks = specs["blocks"]
    # The leading scanned-layer dim stage-shards over pp (a no-op placement
    # on every mesh whose pp axis is 1; the pipelined training mesh's
    # stages each hold L/pp layers — parallel/pipeline.py).
    assert blocks["wq"] == P("pp", None, "tp")     # project-in: shard output
    assert blocks["wo"] == P("pp", "tp", None)     # project-out: shard input
    assert blocks["router"] == P("pp", None, "tp")  # router over experts axis
    assert blocks["moe_w_up"] == P("pp", "tp", None, None)  # experts over tp (EP)
    assert specs["tok_emb"] == P("tp", None)       # vocab-sharded embedding
    assert blocks["attn_norm_w"] == P("pp", None)  # norms replicated within a stage


def _run(spec, params, mesh=None):
    toks = jnp.array([[5, 6, 7, 8, 0, 0], [9, 10, 0, 0, 0, 0]], dtype=jnp.int32)
    lengths = jnp.array([4, 2], dtype=jnp.int32)
    ck, cv = init_cache(spec, 2)
    if mesh is not None:
        params = shard_pytree(mesh, params)
        kv_sh = kv_cache_sharding(mesh, spec.n_kv_heads, batch=2)
        ck, cv = jax.device_put(ck, kv_sh), jax.device_put(cv, kv_sh)
    pf = jax.jit(prefill, static_argnums=(1,), donate_argnums=(4, 5))
    logits, ck, cv = pf(params, spec, toks, lengths, ck, cv)
    ds = jax.jit(decode_step, static_argnums=(1,), donate_argnums=(4, 5))
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    dlogits, ck, cv = ds(params, spec, nxt, lengths, ck, cv)
    return np.asarray(jax.device_get(logits)), np.asarray(jax.device_get(dlogits))


def test_tp_dp_sharded_matches_single_device():
    spec = resolve_spec("llama-tiny", {"n_kv_heads": "4"})
    mesh = make_mesh(MeshConfig(dp=2, tp=4))
    l_sh, d_sh = _run(spec, init_params(spec, 0), mesh)
    l_1, d_1 = _run(spec, init_params(spec, 0))
    np.testing.assert_allclose(l_sh, l_1, rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(d_sh, d_1, rtol=2e-2, atol=2e-2)


# De-quarantined (PR 17): the PR 16 divergence was a GSPMD miscompile in
# the grouped dispatch's expert-buffer gather (a gather from a concat of a
# dp-sharded token matrix with a replicated pad row read the wrong shard)
# — fixed in models/transformer.py by the clamp-index+mask
# formulation.
def test_moe_expert_parallel_matches_single_device():
    spec = resolve_spec("mixtral-tiny")  # 4 experts over tp=4
    mesh = make_mesh(MeshConfig(dp=2, tp=4))
    l_sh, d_sh = _run(spec, init_params(spec, 0), mesh)
    l_1, d_1 = _run(spec, init_params(spec, 0))
    np.testing.assert_allclose(l_sh, l_1, rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(d_sh, d_1, rtol=2e-2, atol=2e-2)


def test_full_tp8_sharding():
    spec = resolve_spec("llama-tiny", {"n_heads": "8", "n_kv_heads": "8", "d_model": "64"})
    mesh = make_mesh(MeshConfig(tp=8))
    l_sh, _ = _run(spec, init_params(spec, 0), mesh)
    l_1, _ = _run(spec, init_params(spec, 0))
    np.testing.assert_allclose(l_sh, l_1, rtol=2e-2, atol=2e-2)
