"""On-device text embeddings from the serving engine's resident weights.

The OpenAI surface exposes embeddings as their own endpoint; a local TPU
serving framework can produce them from the SAME decoder weights already
resident for chat (no second model, no extra HBM): run the scanned
transformer body WITHOUT the unembed matmul (`forward_hidden` — at 128k
vocab the unembed is most of a short sequence's FLOPs), mean-pool the
final-norm hidden states over the valid (non-pad) positions, and
L2-normalize — the standard causal-LM embedding recipe, and unit-norm
vectors match the OpenAI contract's convention.

Engine integration: a pure function of (params, tokens, lengths) — no slot
state, no KV cache, no scheduler involvement. Programs are jitted per
(batch bucket, sequence bucket) and cached on the engine instance; inputs
pad to power-of-two buckets so arbitrary request shapes reuse a handful of
compiled programs (the same discipline as the engine's prefill buckets).
Stacked-members engines carry a leading member axis on every
param leaf; the backend's member index selects one weight set inside the
jitted program (no host-side copy). Quantized engines work unchanged —
the transformer dequantizes per-leaf via ``qeinsum``.

No reference equivalent: the reference proxy forwards nothing but
``/chat/completions`` (SURVEY.md §2) and could only have relayed
embeddings over HTTP; this is TPU-native surface beyond parity.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from quorum_tpu.models.transformer import forward_hidden
from quorum_tpu.parallel.sharding import member_params

# Requests above this many inputs are rejected at the API layer; buckets
# stop here.
MAX_BATCH = 64


def _batch_bucket(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return min(b, MAX_BATCH)


def _seq_bucket(n: int, max_seq: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return min(b, max_seq)


def _embed_fn(engine, b_bucket: int, t_bucket: int):
    cache = engine.__dict__.setdefault("_embed_cache", {})
    fn = cache.get((b_bucket, t_bucket))
    if fn is not None:
        return fn
    spec = engine.spec
    stacked = engine.members > 1

    def run(params, tokens, lengths, member):
        if stacked:
            params = member_params(params, member)
        h = forward_hidden(params, spec, tokens, lengths)  # [B, T, D]
        mask = (jnp.arange(t_bucket)[None, :] < lengths[:, None]).astype(
            jnp.float32)
        pooled = (h.astype(jnp.float32) * mask[..., None]).sum(axis=1)
        pooled = pooled / jnp.maximum(lengths, 1).astype(jnp.float32)[:, None]
        norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
        return pooled / jnp.maximum(norm, 1e-9)

    fn = jax.jit(run)
    cache[(b_bucket, t_bucket)] = fn
    return fn


def embed_token_batch(
    engine, token_lists: list[list[int]], member: int = 0
) -> np.ndarray:
    """Unit-norm embeddings [n, d_model] float32 for ``token_lists``.

    Inputs longer than the engine's ``max_seq`` are truncated to the FIRST
    ``max_seq`` tokens (documented in docs/api.md; embeddings conventionally
    keep the head of an over-long document).
    """
    if not token_lists:
        return np.zeros((0, engine.spec.d_model), np.float32)
    if len(token_lists) > MAX_BATCH:
        raise ValueError(f"at most {MAX_BATCH} inputs per request")
    max_seq = engine.spec.max_seq
    clipped = [t[:max_seq] for t in token_lists]
    n = len(clipped)
    t_bucket = _seq_bucket(max(len(t) for t in clipped), max_seq)
    b_bucket = _batch_bucket(n)
    tokens = np.zeros((b_bucket, t_bucket), np.int32)
    lengths = np.zeros((b_bucket,), np.int32)
    for i, t in enumerate(clipped):
        tokens[i, : len(t)] = t
        lengths[i] = max(len(t), 1)  # empty input → one pad-id token
    out = _embed_fn(engine, b_bucket, t_bucket)(
        engine.weights, tokens, lengths, np.int32(member))
    from quorum_tpu.engine.engine import _host_fetch

    return np.asarray(_host_fetch(out))[:n]
