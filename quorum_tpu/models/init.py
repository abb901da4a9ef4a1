"""Random parameter initialization for any :class:`ModelSpec`.

Produces the exact pytree layout quorum_tpu.models.transformer consumes and
quorum_tpu.parallel.sharding knows how to shard. Init is seeded and scaled
(normal, 1/sqrt(fan_in)) so generated text is stable across runs and logits
stay O(1) — what the serving tests and benchmarks need; real weights come
from quorum_tpu.models.hf_loader when a local checkpoint exists.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from quorum_tpu.models.model_config import ModelSpec
from quorum_tpu.models.transformer import Params


# Seeded latent attention (:func:`init_patterned_from_key`). ``SCORE_DEV`` is
# the deviation of a layer's attention scores, how peaked seeded attention is:
# at 1 a query spreads over thousands of positions and attention adds next to
# nothing to the stream; at 6 it attends one or two positions of 4,096.
# ``VALUE_GAIN`` is the values' size as a share of the rescale's
# ``sqrt(D / rank)``: what attention writes into the stream grows with it.
# Why 4 and 0.7: the served configuration's ``assumed.weights``
# (benchmarks/configs/dots3-ep8.json).
SCORE_DEV = 4.0
VALUE_GAIN = 0.7


def init_params(spec: ModelSpec, seed: int = 0) -> Params:
    return init_params_from_key(spec, jax.random.PRNGKey(seed))


def init_patterned_from_key(spec: ModelSpec, key) -> Params:
    """A patterned spec's weights (models/patterned.py): one dict per layer
    under ``layers``, every leaf with a leading dim of 1; where the spec has
    whole periods (``spec.periods``), one dict a slot of the period, every
    leaf with a leading dim of their count (``patterned.period_key``).

    The scales give the residual stream the proportions of a deep model and
    not of a shallow toy, whatever ``n_layers`` is cut to: embedding rows at
    unit rms, and what each sub-layer writes into the stream scaled by
    ``1/sqrt(2 * init_depth)`` (the GPT-2 / Megatron scaled initialisation),
    so that one expert's output is a few percent of the stream and a
    near-tie in the router's pick moves a served log-probability by little.
    Where the blocks are post-norm that scale is the gain of the two norms
    (an RMSNorm after ``wo`` or ``w_down`` undoes any scale on the matrix:
    seeded at 1 each sub-layer would add a unit-rms vector, and one flipped
    pick moved a log-probability by 0.1-0.3 on the chip, PERF.md section 6);
    in pre-norm blocks it is on ``wo``, ``w_down`` and the experts'
    down-projections, and the norms' gains are 1.
    The router's selection bias (``spec.init_bias_dev`` times a normal) is
    small and non-zero, so that the score and the score-plus-bias differ.
    A short convolution's three matrices are at ``1/sqrt(fan_in)`` like the
    rest (the taps' fan-in is their count), its output product carrying the
    sub-layer's scale. Where the head is the embedding (``tied_lm_head``)
    the final norm's gain is ``1/sqrt(D)`` with a random sign a channel and
    not 1: the embedding's rows stay of unit rms and the larger part of the
    float32 stream, as in the other patterned specs, the logits come out of
    unit deviation, and the signs take the token just read out of them;
    with a gain of one sign the stream's own embedding row would meet itself
    in the head and every position's logits would be one spike of sqrt(D)
    deviations on that token, which no fault moves."""
    dt = jnp.dtype(spec.dtype)
    D, V = spec.d_model, spec.vocab_size
    H = spec.n_heads * spec.head_dim
    K = spec.n_kv_heads * spec.head_dim
    F, Fe, E, held = spec.d_ff, spec.d_ff_expert, spec.n_experts, spec.held
    gain = (2.0 * (spec.init_depth or spec.n_layers)) ** -0.5
    latent = spec.kv_lora_rank > 0
    # pre-norm blocks have no norm after a sub-layer to carry the scale: it
    # is on the matrix that writes into the stream
    norm_gain, out = (gain, 1.0) if spec.post_norm else (1.0, gain)

    def w(k, *shape, fan_in, scale=1.0):
        return (jax.random.normal(k, shape, jnp.float32)
                * (scale * fan_in ** -0.5)).astype(dt)

    def kv_heads(ks) -> dict:
        return {
            "wq": w(next(ks), 1, D, H, fan_in=D),
            "wk": w(next(ks), 1, D, K, fan_in=D),
            "wv": w(next(ks), 1, D, K, fan_in=D),
            "wo": w(next(ks), 1, H, D, fan_in=H),
            "q_norm_w": jnp.ones((1, spec.head_dim), dt),
            "k_norm_w": jnp.ones((1, spec.head_dim), dt),
        }

    def latent_heads(i: int, key) -> dict:
        """models/latent.py's leaves; ``w_qb``'s columns are a head's
        unrotated then rotated dims, head after head; ``w_kva``'s the latent
        then the rotated key. The key/value up-projection is kept as its two
        halves, ``w_kb`` and ``w_vb`` (a decode step multiplies the query by
        the one and the output by the other)."""
        kind = spec.attn_kind(i)
        g = spec.latent(kind)
        ks = iter(jax.random.split(key, 12))
        # the latents come out of their norms multiplied by sqrt(D / rank):
        # what makes queries, keys and index queries of them counts that into
        # its fan-in (D, not the rank), so that they are of unit size as
        # everywhere else, the queries SCORE_DEV times that. The values keep
        # VALUE_GAIN of the rescale's gain (fan-in the rank), which makes a
        # full layer's output as large a part of the stream as an expert
        # layer's
        out_w = {
            "w_qa": w(next(ks), 1, D, g.q_rank, fan_in=D),
            "q_a_norm_w": jnp.ones((1, g.q_rank), dt),
            "w_qb": w(next(ks), 1, g.q_rank, g.heads * (g.nope + g.rope),
                      fan_in=D, scale=SCORE_DEV),
            "w_kva": w(next(ks), 1, D, g.kv_rank + g.rope, fan_in=D),
            "kv_a_norm_w": jnp.ones((1, g.kv_rank), dt),
            "w_kb": w(next(ks), 1, g.kv_rank, g.heads * g.nope,
                      fan_in=D),
            "w_vb": w(next(ks), 1, g.kv_rank, g.heads * g.v,
                      fan_in=g.kv_rank, scale=VALUE_GAIN),
            "w_head_gate": w(next(ks), 1, D, g.heads, fan_in=D),
            "wo": w(next(ks), 1, g.heads * g.v, D, fan_in=g.heads * g.v,
                    scale=out),
        }
        if kind == "G":
            ih, idim = spec.index_n_heads, spec.index_head_dim
            out_w.update(
                w_iq=w(next(ks), 1, g.q_rank, ih * idim, fan_in=D),
                w_ik=w(next(ks), 1, D, idim, fan_in=D),
                ik_norm_w=jnp.ones((1, idim), dt),
                ik_norm_b=jnp.zeros((1, idim), dt),
                w_iw=w(next(ks), 1, D, ih, fan_in=D))
        return out_w

    def short_conv(ks) -> dict:
        """models/shortconv.py's leaves: ``conv_in``'s columns are the input
        gate, the output gate and the convolution's input, in that order."""
        taps = spec.conv_taps
        return {
            "conv_in": w(next(ks), 1, D, 3 * D, fan_in=D),
            "conv_w": w(next(ks), 1, taps, D, fan_in=taps),
            "conv_out": w(next(ks), 1, D, D, fan_in=D, scale=out),
        }

    def layer(i: int, key) -> dict:
        ks = iter(jax.random.split(key, 16))
        out_w = {
            "attn_norm_w": jnp.full((1, D), norm_gain, dt),
            "mlp_norm_w": jnp.full((1, D), norm_gain, dt),
            **(latent_heads(i, next(ks)) if latent
               else short_conv(ks) if spec.attn_kind(i) == "C"
               else kv_heads(ks)),
        }
        if i < spec.first_dense:
            out_w.update(
                w_gate=w(next(ks), 1, D, F, fan_in=D),
                w_up=w(next(ks), 1, D, F, fan_in=D),
                w_down=w(next(ks), 1, F, D, fan_in=F, scale=out))
            return out_w
        out_w.update(
            router=w(next(ks), 1, D, E, fan_in=D),
            router_bias=(spec.init_bias_dev
                         * jax.random.normal(next(ks), (1, E))
                         ).astype(jnp.float32),
            moe_w_gate=w(next(ks), 1, held, D, Fe, fan_in=D),
            moe_w_up=w(next(ks), 1, held, D, Fe, fan_in=D),
            moe_w_down=w(next(ks), 1, held, Fe, D, fan_in=Fe, scale=out))
        if spec.n_shared_experts:
            Fs = Fe * spec.n_shared_experts
            out_w["shared"] = {
                "w_gate": w(next(ks), 1, D, Fs, fan_in=D),
                "w_up": w(next(ks), 1, D, Fs, fan_in=D),
                "w_down": w(next(ks), 1, Fs, D, fan_in=Fs, scale=out)}
        return out_w

    k_emb, k_head, k_layers = jax.random.split(key, 3)
    keys = jax.random.split(k_layers, spec.n_layers)
    start, length, count = spec.periods
    layers = {f"{i:02d}": layer(i, keys[i]) for i in range(start)}
    from quorum_tpu.models.patterned import period_key

    for i in range(start, start + length):
        # a slot of the period: its layers' leaves stacked ``[count, ...]``,
        # each layer from its own key, as written out
        layers[period_key(i, count)] = jax.vmap(
            lambda k, i=i: jax.tree.map(lambda a: a[0], layer(i, k)))(
            keys[i::length])
    return {
        "tok_emb": jax.random.normal(k_emb, (V, D), jnp.float32).astype(dt),
        "final_norm_w": ((jax.random.rademacher(k_head, (D,), jnp.float32)
                          * D ** -0.5).astype(dt) if spec.tied_lm_head
                         else jnp.ones((D,), dt)),
        "lm_head": (None if spec.tied_lm_head
                    else w(k_head, D, V, fan_in=D)),
        "layers": layers,
    }


def init_mixer_from_key(spec: ModelSpec, key) -> Params:
    """The weights of a spec with a mixer beside attention (models/ssm.py;
    family "falcon_h1"), in the dense family's stacked layout.

    The published multipliers are small (the logits' is 1/128, the keys'
    1/90) because the trained matrices they meet are large. Unit-scale seeded
    matrices under them would give flat logits over the vocabulary and scores
    of zero, and every fault would pass. So each matrix is seeded at
    ``1/sqrt(fan_in)`` DIVIDED BY the multipliers its product meets: what
    comes out of every product is then of the size the dense family's seeded
    init gives (a position's log-probabilities spread by a few nats), and a
    multiplier left out or put on the wrong product moves the result by its
    own factor. Embedding rows are of unit rms after ``emb_scale``. The
    recurrence's own numbers are Mamba-2's: ``A`` uniform in [1, 16] a head,
    ``softplus(dt_bias)`` log-uniform in [0.001, 0.1], ``D`` 1, convolution
    taps uniform in +-1/sqrt(taps) with a zero bias, norm gains 1. The
    benchmark's configuration states the same under ``assumed.weights``."""
    dt = jnp.dtype(spec.dtype)
    ks = iter(jax.random.split(key, 16))
    L, D, V = spec.n_layers, spec.d_model, spec.vocab_size
    H = spec.n_heads * spec.head_dim
    K = spec.n_kv_heads * spec.head_dim
    F = spec.d_ff
    d, gn, heads = (spec.ssm_width, spec.ssm_groups * spec.ssm_state,
                    spec.ssm_heads)
    taps, width = spec.ssm_conv, spec.ssm_conv_width

    def w(k, *shape, under=1.0):
        return (jax.random.normal(k, shape, jnp.float32)
                * (shape[-2] ** -0.5 / under)).astype(dt)

    m_z, m_x, m_b, m_c, m_dt = spec.ssm_mults
    # the input projection's columns: z, x, B, C, dt, each under its own
    # multiplier and the mixer's input multiplier
    part = jnp.concatenate([
        jnp.full((n,), m * spec.ssm_in_mult, jnp.float32)
        for n, m in ((d, m_z), (d, m_x), (gn, m_b), (gn, m_c),
                     (heads, m_dt))])
    ssm_in = (jax.random.normal(next(ks), (L, D, 2 * d + 2 * gn + heads),
                                jnp.float32) * D ** -0.5 / part).astype(dt)
    step = jnp.exp(jax.random.uniform(
        next(ks), (L, heads), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
    blocks = {
        "attn_norm_w": jnp.ones((L, D), dt),
        "mlp_norm_w": jnp.ones((L, D), dt),
        "wq": w(next(ks), L, D, H, under=spec.attn_in_mult),
        "wk": w(next(ks), L, D, K, under=spec.attn_in_mult * spec.key_mult),
        "wv": w(next(ks), L, D, K, under=spec.attn_in_mult),
        "wo": w(next(ks), L, H, D, under=spec.attn_out_mult),
        "w_gate": w(next(ks), L, D, F, under=spec.mlp_gate_mult),
        "w_up": w(next(ks), L, D, F),
        "w_down": w(next(ks), L, F, D, under=spec.mlp_down_mult),
        "ssm_in": ssm_in,
        "ssm_conv_w": jax.random.uniform(
            next(ks), (L, taps, width), jnp.float32, -taps ** -0.5,
            taps ** -0.5).astype(dt),
        "ssm_conv_b": jnp.zeros((L, width), dt),
        # softplus(dt_bias) = step: its inverse, step + log(1 - exp(-step))
        "ssm_dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "ssm_a_log": jnp.log(jax.random.uniform(
            next(ks), (L, heads), jnp.float32, 1.0, 16.0)),
        "ssm_d": jnp.ones((L, heads), jnp.float32),
        "ssm_norm_w": jnp.ones((L, d), dt),
        "ssm_out": w(next(ks), L, d, D, under=spec.ssm_out_mult),
    }
    return {
        "tok_emb": (jax.random.normal(next(ks), (V, D), jnp.float32)
                    / spec.emb_scale).astype(dt),
        "final_norm_w": jnp.ones((D,), dt),
        "lm_head": w(next(ks), D, V, under=spec.lm_head_mult),
        "blocks": blocks,
    }


def init_params_from_key(spec: ModelSpec, key) -> Params:
    """Init from a PRNG key (traced-friendly: vmappable over stacked keys —
    how stacked members materialize directly into their slices,
    :func:`init_params_ensemble_sharded`)."""
    spec.validate()
    if spec.layer_pattern:
        return init_patterned_from_key(spec, key)
    if spec.ssm_heads:
        return init_mixer_from_key(spec, key)
    dt = jnp.dtype(spec.dtype)
    keys = iter(jax.random.split(key, 32))

    def w(k, *shape, fan_in=None):
        fan = fan_in if fan_in is not None else shape[-2] if len(shape) >= 2 else shape[-1]
        return (jax.random.normal(k, shape, jnp.float32) * (fan ** -0.5)).astype(dt)

    L, D, V = spec.n_layers, spec.d_model, spec.vocab_size
    H = spec.n_heads * spec.head_dim
    K = spec.n_kv_heads * spec.head_dim
    F, E = spec.d_ff, spec.n_experts
    # Stored norm weight such that the effective multiplier (norm_offset + w)
    # is identity: 1.0 for llama-style, 0.0 for gemma's (1 + w) convention.
    norm_one = 1.0 - spec.norm_offset

    blocks: dict = {
        "attn_norm_w": jnp.full((L, D), norm_one, dt),
        "attn_norm_b": jnp.zeros((L, D), dt) if spec.norm == "layernorm" else None,
        "wq": w(next(keys), L, D, H),
        "wk": w(next(keys), L, D, K),
        "wv": w(next(keys), L, D, K),
        "wo": w(next(keys), L, H, D),
        "bq": jnp.zeros((L, H), dt) if spec.use_bias else None,
        "bk": jnp.zeros((L, K), dt) if spec.use_bias else None,
        "bv": jnp.zeros((L, K), dt) if spec.use_bias else None,
        "bo": jnp.zeros((L, D), dt) if spec.use_bias else None,
        "mlp_norm_w": jnp.full((L, D), norm_one, dt),
        "mlp_norm_b": jnp.zeros((L, D), dt) if spec.norm == "layernorm" else None,
    }
    if spec.is_moe:
        blocks.update(
            router=w(next(keys), L, D, E),
            moe_w_gate=w(next(keys), L, E, D, F, fan_in=D),
            moe_w_up=w(next(keys), L, E, D, F, fan_in=D),
            moe_w_down=w(next(keys), L, E, F, D, fan_in=F),
        )
    else:
        blocks.update(
            w_gate=w(next(keys), L, D, F) if spec.gated_mlp else None,
            w_up=w(next(keys), L, D, F),
            w_down=w(next(keys), L, F, D),
            b_up=jnp.zeros((L, F), dt) if spec.use_bias else None,
            b_down=jnp.zeros((L, D), dt) if spec.use_bias else None,
        )

    params: Params = {
        "tok_emb": w(next(keys), V, D, fan_in=D),
        "pos_emb": w(next(keys), spec.max_seq, D, fan_in=D) if spec.pos == "learned" else None,
        "final_norm_w": jnp.full((D,), norm_one, dt),
        "final_norm_b": jnp.zeros((D,), dt) if spec.norm == "layernorm" else None,
        "lm_head": None if spec.tied_lm_head else w(next(keys), D, V),
        "blocks": blocks,
    }
    return params


def init_params_sharded(spec: ModelSpec, mesh, seed: int = 0) -> Params:
    """Initialize parameters directly on the mesh, sharded, in ONE compiled
    program.

    At 7B scale the eager path (``init_params`` + ``shard_pytree``) dispatches
    a dozen separate device ops and round-trips layouts; jitting the whole
    init with the target shardings as ``out_shardings`` makes XLA materialize
    every leaf in place — no host copy, no replicated intermediate, one
    compile. This is how a 14 GB bf16 model comes up on a 16 GB chip."""
    from quorum_tpu.parallel.sharding import param_shardings

    shapes = jax.eval_shape(lambda: init_params(spec, seed))
    shardings = param_shardings(mesh, shapes, n_kv_heads=spec.n_kv_heads)
    return jax.jit(
        lambda: init_params(spec, seed), out_shardings=shardings
    )()


def init_params_ensemble_sharded(
    spec: ModelSpec, mesh, seeds: list[int], quant: str | None = None
) -> Params:
    """Member-stacked parameters for a stacked engine (``members=M``), in
    the layout ``parallel.sharding.member_axes`` names: block leaves
    layers-major ``[L, M, …]`` (the member-vmapped layer scan reads a
    layer's ``[M, …]`` slice where it lies), the rest ``[M, …]``. Each
    member is an independent seeded init, vmapped over stacked PRNG keys
    with the layout as the ``vmap``'s ``out_axes``, so every leaf
    materializes directly into its place: no per-member temporaries and
    stack copy, no transposition of a finished tree (either would
    transiently need ~2× the stack's weight HBM). The member axis is
    replicated (vmapped, never communicated).

    ``quant="int8"`` fuses per-member quantization into the same program
    (scales reduce over the contraction axis, so the stacked tree's scales
    are exactly each member's own, laid as their leaf is) — two int8 7B
    members fit one 16 GB chip."""
    from quorum_tpu.parallel.sharding import member_axes, param_shardings

    keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])

    def one(key) -> Params:
        return init_params_from_key(spec, key)

    def build(ks) -> Params:
        params = jax.vmap(one, out_axes=member_axes(
            jax.eval_shape(one, ks[0])))(ks)
        if quant == "int8":
            from quorum_tpu.models.quant import quantize_params

            params = quantize_params(params)
        return params

    shapes = jax.eval_shape(build, keys)
    shardings = param_shardings(mesh, shapes, stacked=True,
                                n_kv_heads=spec.n_kv_heads)
    return jax.jit(build, out_shardings=shardings)(keys)


def param_count(params: Params) -> int:
    return sum(
        x.size for x in jax.tree.leaves(params) if hasattr(x, "size")
    )
