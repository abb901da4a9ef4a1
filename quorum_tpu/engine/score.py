"""Teacher-forced prompt scoring from the serving engine's resident weights.

The legacy OpenAI ``/completions`` surface with ``echo=true, logprobs=k``
returns the log-probability of every PROMPT token under the model — the
contract eval harnesses (lm-eval and friends) use for perplexity and
multiple-choice scoring. A causal LM scores a whole prompt in ONE forward:
``forward_logits`` gives the next-token distribution at every position, so
``logprob(tokens[j])`` is read from position ``j-1``'s row (the first token
has no conditioning prefix — the API reports ``null`` for it).

Same engine integration as embeddings (quorum_tpu/engine/embed.py): a pure
function of (params, tokens, lengths), jitted per (batch, seq, top-k)
bucket and cached on the engine instance, no slot/scheduler involvement.
The full [B, T, V] log-softmax never leaves the device — only the gathered
per-token logprobs and the top-k alternatives are fetched.

No reference equivalent: the reference proxies only /chat/completions.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from quorum_tpu.engine.embed import MAX_BATCH, _batch_bucket, _seq_bucket
from quorum_tpu.models.transformer import forward_logits
from quorum_tpu.parallel.sharding import member_params


def _score_fn(engine, b_bucket: int, t_bucket: int, top_k: int):
    cache = engine.__dict__.setdefault("_score_cache", {})
    fn = cache.get((b_bucket, t_bucket, top_k))
    if fn is not None:
        return fn
    spec = engine.spec
    stacked = engine.members > 1

    def run(params, tokens, lengths, member):
        if stacked:
            params = member_params(params, member)
        # lengths gates MoE expert capacity: without it, an earlier row's
        # pad tokens would evict a later row's REAL tokens from the fixed
        # capacity buffers, making logprobs batch-composition-dependent.
        logits = forward_logits(params, spec, tokens,
                                lengths=lengths)  # [B, T, V]
        lps = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        # Position j's row predicts token j+1: shift so out[:, j] scores
        # tokens[:, j] (j >= 1); column 0 is meaningless and masked by the
        # caller (the API reports null for the first token).
        shifted = jnp.roll(lps, 1, axis=1)
        token_lp = jnp.take_along_axis(
            shifted, tokens[..., None], axis=-1)[..., 0]  # [B, T]
        if top_k:
            top_lp, top_ix = jax.lax.top_k(shifted, top_k)  # [B, T, K]
            return token_lp, top_ix, top_lp
        return (token_lp,)

    fn = jax.jit(run)
    cache[(b_bucket, t_bucket, top_k)] = fn
    return fn


def score_token_batch(
    engine, token_lists: list[list[int]], member: int = 0, top_k: int = 0
) -> list[dict]:
    """Per-prompt teacher-forced logprobs.

    Returns one dict per prompt: ``{"token_logprobs": [None, f, ...],
    "top": [(ids, lps) | None, ...]}`` — index 0 is ``None`` (no prefix),
    ``top`` present only when ``top_k`` > 0. Prompts longer than the
    engine's ``max_seq`` are rejected by the caller (scoring a truncated
    prompt would silently mis-score).
    """
    if not token_lists:
        return []
    if len(token_lists) > MAX_BATCH:
        raise ValueError(f"at most {MAX_BATCH} inputs per request")
    max_seq = engine.spec.max_seq
    n = len(token_lists)
    t_bucket = _seq_bucket(max(len(t) for t in token_lists), max_seq)
    b_bucket = _batch_bucket(n)
    tokens = np.zeros((b_bucket, t_bucket), np.int32)
    lengths = np.zeros((b_bucket,), np.int32)
    for i, t in enumerate(token_lists):
        tokens[i, : len(t)] = t
        lengths[i] = len(t)
    out = _score_fn(engine, b_bucket, t_bucket, top_k)(
        engine.weights, tokens, lengths, np.int32(member))
    from quorum_tpu.engine.engine import _host_fetch

    fetched = [np.asarray(x) for x in _host_fetch(*out)] if len(out) > 1 \
        else [np.asarray(_host_fetch(out[0]))]
    token_lp = fetched[0]
    results = []
    for i, t in enumerate(token_lists):
        lps = [None] + [float(x) for x in token_lp[i, 1: len(t)]]
        entry: dict = {"token_logprobs": lps}
        if top_k:
            top_ix, top_lp = fetched[1], fetched[2]
            entry["top"] = [None] + [
                (top_ix[i, j].tolist(), top_lp[i, j].tolist())
                for j in range(1, len(t))
            ]
        results.append(entry)
    return results
