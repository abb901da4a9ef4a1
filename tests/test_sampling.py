"""Direct unit tests of ops/sampling.py (previously pinned only through
engine-level equality tests): greedy reduction, top-k/top-p truncation,
row independence, and single-vs-batched consistency.
"""

import numpy as np

import jax
import jax.numpy as jnp

from quorum_tpu.ops.sampling import SamplerConfig, sample_token, sample_token_rows


def _logits(seed, shape=(4, 64)):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def test_greedy_is_argmax_any_knobs():
    lg = _logits(0)
    key = jax.random.PRNGKey(1)
    out = sample_token(lg, key, SamplerConfig(temperature=0.0, top_p=0.3,
                                              top_k=5))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jnp.argmax(lg, -1)))


def test_top_k_one_and_tiny_top_p_reduce_to_argmax():
    lg = _logits(2)
    key = jax.random.PRNGKey(3)
    am = np.asarray(jnp.argmax(lg, -1))
    for cfg in (SamplerConfig(temperature=1.0, top_k=1),
                SamplerConfig(temperature=1.0, top_p=1e-6)):
        np.testing.assert_array_equal(
            np.asarray(sample_token(lg, key, cfg)), am)


def test_top_k_never_samples_outside_k():
    lg = _logits(4, (2, 32))
    k = 4
    topk_sets = [set(np.asarray(jax.lax.top_k(lg, k)[1])[r]) for r in (0, 1)]
    for seed in range(40):
        out = np.asarray(sample_token(lg, jax.random.PRNGKey(seed),
                                      SamplerConfig(temperature=1.5, top_k=k)))
        for r in (0, 1):
            assert out[r] in topk_sets[r]


def test_rows_match_single_and_are_independent():
    lg = _logits(5, (3, 64))
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(10, 13))
    temp = jnp.array([0.0, 0.8, 1.2])
    topp = jnp.array([1.0, 0.9, 1.0])
    topk = jnp.array([0, 0, 8], jnp.int32)
    out = np.asarray(sample_token_rows(lg, keys, temp, topp, topk))
    # row 0 greedy
    assert out[0] == int(jnp.argmax(lg[0]))
    # row independence: mutating OTHER rows' logits/knobs leaves a row alone
    lg2 = lg.at[0].set(-lg[0])
    out2 = np.asarray(sample_token_rows(
        lg2, keys, jnp.array([1.0, 0.8, 1.2]), topp, topk))
    assert out2[1] == out[1] and out2[2] == out[2]
    # batched row matches the single-stream sampler given the same key/knobs
    one = sample_token(lg[2][None], keys[2],
                       SamplerConfig(temperature=1.2, top_k=8))
    assert out[2] == int(one[0])
