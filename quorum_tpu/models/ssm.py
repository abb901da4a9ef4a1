"""The Mamba-2 mixer that stands beside attention in a block (``ssm_heads``).

A block of a spec with ``ssm_heads`` > 0 (family "falcon_h1") gives its
normed input ``u`` to attention AND to this mixer, and adds both to the
stream (models/transformer.py). The mixer, with ``d = ssm_heads x
ssm_head_dim`` channels in ``H`` heads of ``P``, a state of ``N`` a channel
and ``G`` groups (Dao & Gu, "Transformers are SSMs", 2024):

  ``[z | x | B | C | dt] = W_in (u * ssm_in_mult)`` of widths ``d | d | G N |
  G N | H``, each part times its entry of ``ssm_mults``;
  ``[x | B | C]`` through a depthwise causal convolution of ``ssm_conv`` taps
  with bias (tap ``k`` meets the input ``ssm_conv - 1 - k`` positions back),
  then SiLU;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, one of each a head;
  per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` (``S`` is ``[P,
  N]``; the heads of a group share B and C), ``y_t = S_t C_t + D x_t``;
  ``y = RMSNorm_grouped(y * silu(z))``: the gate first, then the norm over
  each group's ``d / G`` channels, with a learned weight;
  ``W_out y * ssm_out_mult``.

What a row carries from one program to the next is of a fixed size whatever
its length: the float32 state ``[H, P, N]`` and the convolution's last
``ssm_conv - 1`` inputs ``[ssm_conv - 1, d + 2 G N]``. They are one more leaf
on each side of the slot cache (:class:`StateKV`), per layer and row, beside
the K and V rectangles.

Two forms of the recurrence. A program of more than one position (an
admit, a prefill segment, the cache-free forward) runs it in chunks of
``ssm_chunk`` (:func:`_scan_chunked`): inside a chunk matrix products, from
chunk to chunk the state. A decode step updates the state once
(:func:`_scan_step`), and where it is handed the carried leaf and its layer
(:func:`_step_in_leaf`, the decode step's layer scan) one Pallas call
updates the layer's slab where it lies and reads it out in the same pass
(``ops/ssm_step.py``: on a TPU, a float32 state of whole tiles, an
unpartitioned program; elsewhere :func:`_scan_step` over the slab, which is
also what the kernel is tested against). Positions past a row's ``n_valid``
(the pad of a bucket, a row a decode step may not write) leave state and
tail as the last real position left them: their ``dt`` and input are zero,
so the state decays by ``exp(0)`` and gains nothing, and the tail is taken
at the true length.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from quorum_tpu.models.model_config import ModelSpec
from quorum_tpu.models.quant import qeinsum
from quorum_tpu.models.shortconv import causal_taps
from quorum_tpu.ops import ssm_step
from quorum_tpu.ops.flash_attention import traced_program

logger = logging.getLogger(__name__)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StateKV:
    """One side (K or V) of the slot cache of a spec with a mixer.

    ``kv``: the side's ``[L, slots, max_seq, K·hd]`` rectangle, as every
    dense spec has it. ``carry``: what the mixer carries, on the K side the
    float32 state ``[L, slots, H, P, N]``, on the V side the convolution
    tail ``[L, slots, ssm_conv - 1, d + 2 G N]`` in the cache's dtype."""

    kv: Any
    carry: Any


def init_carry(spec: ModelSpec, batch: int, dtype, lead: tuple = ()) -> tuple:
    """(state, tail) of ``batch`` rows, zero: what a row holds before its
    first position. ``lead``: leading dims, the cache's layers."""
    return (jnp.zeros(lead + (batch, spec.ssm_heads, spec.ssm_head_dim,
                              spec.ssm_state), jnp.float32),
            jnp.zeros(lead + (batch, spec.ssm_conv - 1, spec.ssm_conv_width),
                      dtype))


def rows_read(leaf, layer, row, n: int):
    """``leaf[layer, row:row + n]`` of a ``[L, slots, ...]`` carry leaf."""
    rest = leaf.shape[2:]
    return lax.dynamic_slice(
        leaf, (layer, row) + (0,) * len(rest), (1, n) + rest)[0]


def rows_write(leaf, value, layer, row):
    """``leaf`` with ``value`` ``[n, ...]`` at ``[layer, row:row + n]``."""
    return lax.dynamic_update_slice(
        leaf, value[None].astype(leaf.dtype),
        (layer, row) + (0,) * (leaf.ndim - 2))


def log_mixer_path(form: str, shape: tuple, chunk: int, why: str = "") -> None:
    """One line a traced program: which form of the recurrence it runs.
    ``chunked`` (more than one position), ``fused`` (a step, the kernel of
    ``ops/ssm_step.py`` where the program is lowered for a TPU) or ``step``
    and ``why`` the kernel does not take it."""
    logger.info(
        "mixer-path program=%s form=%s rows=%d positions=%d chunk=%d "
        "reason=%s", traced_program(), form, shape[0], shape[1], chunk,
        why or {"chunked": "more than one position",
                "fused": "pallas where lowered for a tpu, xla's step "
                         "elsewhere"}[form])


@jax.named_scope("ssm.in_proj")
def _in_proj(u, block, spec: ModelSpec):
    """``u`` [B, T, D] -> z [B, T, d] and xBC [B, T, d + 2GN] in ``u``'s
    dtype, dt [B, T, H] float32, each part times its multiplier."""
    d, width = spec.ssm_width, spec.ssm_conv_width
    gn = spec.ssm_groups * spec.ssm_state
    if spec.ssm_in_mult != 1.0:
        u = u * jnp.asarray(spec.ssm_in_mult, u.dtype)
    proj = qeinsum("btd,de->bte", u, block["ssm_in"])
    m_z, m_x, m_b, m_c, m_dt = spec.ssm_mults
    z, xbc, dt = jnp.split(proj, [d, d + width], axis=-1)
    if (m_x, m_b, m_c) != (1.0, 1.0, 1.0):
        xbc = xbc * np.concatenate([np.full(d, m_x, np.float32),
                                    np.full(gn, m_b, np.float32),
                                    np.full(gn, m_c, np.float32)])
    if m_z != 1.0:
        z = z * m_z
    if m_dt != 1.0:
        dt = dt * m_dt
    return z.astype(u.dtype), xbc.astype(u.dtype), dt


@jax.named_scope("ssm.conv")
def _conv(xbc, tail, block, n_valid):
    """The depthwise causal convolution over ``[tail ; xbc]`` and the SiLU:
    ``xbc`` [B, T, C], ``tail`` [B, K-1, C] the row's last inputs before this
    program's first position. Returns the activations [B, T, C] and the tail
    after each row's ``n_valid`` real positions (``n_valid`` 0: unchanged)."""
    out, new_tail = causal_taps(xbc, tail, block["ssm_conv_w"], n_valid,
                                block["ssm_conv_b"])
    return jax.nn.silu(out).astype(xbc.dtype), new_tail


def _grouped(x, bm, cm, dt, spec: ModelSpec):
    """Heads by group: x [B, T, G, H/G, P], B and C [B, T, G, N], dt
    [B, T, G, H/G]."""
    b, t = dt.shape[:2]
    g, per = spec.ssm_groups, spec.ssm_heads // spec.ssm_groups
    return (x.reshape(b, t, g, per, spec.ssm_head_dim),
            bm.reshape(b, t, g, spec.ssm_state),
            cm.reshape(b, t, g, spec.ssm_state), dt.reshape(b, t, g, per))


@jax.named_scope("ssm.scan")
def _scan_chunked(x, bm, cm, dt, a, state, chunk: int):
    """The recurrence over T positions in chunks of ``chunk``. Grouped
    operands (:func:`_grouped`), ``a`` [G, H/G] negative, ``state``
    [B, G, H/G, P, N] float32. Returns y [B, T, G, H/G, P] float32 and the
    state after the last position.

    With ``l_t = sum_{s <= t} dt_s A`` counted from the chunk's start: inside
    a chunk ``y_i = sum_{j <= i} exp(l_i - l_j) (C_i . B_j) dt_j x_j``, a
    [chunk, chunk] product a head; what the chunk adds to the state is
    ``sum_j exp(l_last - l_j) dt_j x_j B_j^T``; the state entering chunk
    ``c + 1`` is ``exp(l_last) S_c`` plus that, a scan over the chunks; and a
    position reads the state its chunk was entered with,
    ``exp(l_i) C_i S_c``."""
    b, t, g, per, p = x.shape
    n = bm.shape[-1]
    q = min(chunk, t)
    if t % q:
        # a length off a chunk's multiple (the cache-free forward's): more
        # positions with dt = 0 behind the last, which move nothing
        x, bm, cm, dt = (jnp.pad(v, [(0, 0), (0, q - t % q)]
                                 + [(0, 0)] * (v.ndim - 2))
                         for v in (x, bm, cm, dt))
    nc = x.shape[1] // q
    f32 = jnp.float32
    xc = x.reshape(b, nc, q, g, per, p)
    bc, cc = bm.reshape(b, nc, q, g, n), cm.reshape(b, nc, q, g, n)
    dtc = dt.reshape(b, nc, q, g, per)
    cum = jnp.cumsum(dtc * a, axis=2)                    # l_i, [b,nc,q,g,per]
    cb = jnp.einsum("bcign,bcjgn->bcijg", cc, bc, preferred_element_type=f32)
    lower = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None, None]
    span = jnp.where(lower, cum[:, :, :, None] - cum[:, :, None], -jnp.inf)
    mix = jnp.exp(span) * cb[..., None] * dtc[:, :, None]  # [b,nc,i,j,g,per]
    y = jnp.einsum("bcijgh,bcjghp->bcighp", mix.astype(x.dtype), xc,
                   preferred_element_type=f32)
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dtc           # [b,nc,q,g,per]
    added = jnp.einsum("bcjghp,bcjgn->bcghpn",
                       to_end[..., None] * xc.astype(f32), bc.astype(f32),
                       preferred_element_type=f32)
    through = jnp.exp(cum[:, :, -1])                       # [b,nc,g,per]

    def enter(s, per_chunk):
        keep, add = per_chunk
        return keep[..., None, None] * s + add, s

    state, entered = lax.scan(
        enter, state, (jnp.moveaxis(through, 1, 0), jnp.moveaxis(added, 1, 0)))
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bcign,cbghpn->bcighp", cc.astype(f32), entered,
        preferred_element_type=f32)
    return y.reshape(b, nc * q, g, per, p)[:, :t], state


@jax.named_scope("ssm.step")
def _scan_step(x, bm, cm, dt, a, state):
    """The recurrence's one step (T = 1), in float32 where the state lies:
    ``S = exp(dt A) S + dt x B^T``, ``y = S C``."""
    f32 = jnp.float32
    x1, b1, c1, dt1 = x[:, 0].astype(f32), bm[:, 0].astype(f32), \
        cm[:, 0].astype(f32), dt[:, 0]
    state = (jnp.exp(dt1 * a)[..., None, None] * state
             + (dt1[..., None] * x1)[..., None] * b1[:, :, None, None, :])
    y = jnp.sum(state * c1[:, :, None, None, :], axis=-1)
    return y[:, None], state


@jax.named_scope("ssm.step")
def _step_in_leaf(x, bm, cm, dt, a, leaf, layer, *, refused: str,
                  interpret: bool):
    """:func:`_scan_step` over layer ``layer`` of the carried leaf ``[L, B,
    H, P, N]``, the leaf handed back with the slab updated in place: one
    kernel call where ``refused`` is empty and the program is lowered for a
    TPU (``ops/ssm_step.py``), else the XLA form over the slab."""
    b, _, g, per, p = x.shape

    def step(x, bm, cm, dt, leaf, layer):
        slab = lax.dynamic_index_in_dim(leaf, layer, 0, False)
        y, state = _scan_step(
            x, bm, cm, dt, a, slab.reshape((b, g, per) + slab.shape[2:]))
        return y, rows_write(leaf, state.reshape(slab.shape), layer, 0)

    def fused(x, bm, cm, dt, leaf, layer):
        f32 = jnp.float32
        dt1 = dt[:, 0]
        y, leaf = ssm_step.step_in_place(
            leaf, layer, jnp.exp(dt1 * a).reshape(b, g * per),
            (dt1[..., None] * x[:, 0].astype(f32)).reshape(b, g * per, p),
            bm[:, 0].astype(f32), cm[:, 0].astype(f32), interpret=interpret)
        return y.reshape(b, 1, g, per, p), leaf

    args = (x, bm, cm, dt, leaf, layer)
    if refused:
        return step(*args)
    if interpret:
        return fused(*args)
    return lax.platform_dependent(*args, tpu=fused, default=step)


@jax.named_scope("ssm.gate_norm")
def _gate_norm(y, z, block, spec: ModelSpec, dtype):
    """``RMSNorm_grouped(y * silu(z))``: y [B, T, d] float32."""
    b, t, d = y.shape
    v = (y * jax.nn.silu(z.astype(jnp.float32))).reshape(
        b, t, spec.ssm_groups, d // spec.ssm_groups)
    v = v * lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + spec.norm_eps)
    return (v.reshape(b, t, d)
            * block["ssm_norm_w"].astype(jnp.float32)).astype(dtype)


@jax.named_scope("ssm.out_proj")
def _out_proj(v, block, spec: ModelSpec):
    out = qeinsum("bte,ed->btd", v, block["ssm_out"])
    if spec.ssm_out_mult != 1.0:
        out = out * spec.ssm_out_mult
    return out.astype(v.dtype)


def mixer(u, block, spec: ModelSpec, state, tail, n_valid, *, layer=None,
          sharded: bool = False, interpret: bool = False):
    """The mixer's branch of a block over ``u`` [B, T, D], the block's normed
    input. ``state`` [B, H, P, N] float32 and ``tail`` [B, ssm_conv - 1,
    d + 2GN] are what each row carried in; ``n_valid`` [B] int32 counts each
    row's real positions, the first ones. Returns what the branch adds to
    the stream [B, T, D] and the rows' state and tail after their last real
    position.

    With ``layer`` (the decode step, T = 1) ``state`` is the carried leaf
    ``[L, B, H, P, N]`` whole and comes back whole, layer ``layer``'s slab
    updated where it lies (:func:`_step_in_leaf`); ``sharded`` says the
    caller's program is partitioned over devices, ``interpret`` runs the
    kernel through the Pallas interpreter, for tests."""
    b, t, _ = u.shape
    d, gn = spec.ssm_width, spec.ssm_groups * spec.ssm_state
    g, per = spec.ssm_groups, spec.ssm_heads // spec.ssm_groups
    if layer is None:
        form, refused = ("chunked", "") if t > 1 else (
            "step", "the caller holds the rows' state sliced out")
    else:
        refused = ssm_step.refusal(state.shape, state.dtype, sharded=sharded,
                                   interpret=interpret)
        form = "step" if refused else "fused"
    log_mixer_path(form, (b, t), spec.ssm_chunk, refused)
    z, xbc, dt = _in_proj(u, block, spec)
    xbc, tail = _conv(xbc, tail, block, n_valid)
    real = jnp.arange(t)[None, :] < n_valid[:, None]             # [B, T]
    x = jnp.where(real[..., None], xbc[..., :d], 0)
    dt = jnp.where(real[..., None], jax.nn.softplus(
        dt + block["ssm_dt_bias"].astype(jnp.float32)), 0.0)
    a = -jnp.exp(block["ssm_a_log"].astype(jnp.float32)).reshape(g, per)
    xg, bg, cg, dtg = _grouped(x, xbc[..., d:d + gn], xbc[..., d + gn:], dt,
                               spec)
    if layer is not None:
        y, grouped = _step_in_leaf(xg, bg, cg, dtg, a, state, layer,
                                   refused=refused, interpret=interpret)
    else:
        grouped = state.reshape((b, g, per) + state.shape[2:])
        if t == 1:
            y, grouped = _scan_step(xg, bg, cg, dtg, a, grouped)
        else:
            y, grouped = _scan_chunked(xg, bg, cg, dtg, a, grouped,
                                       spec.ssm_chunk)
    y = y + (block["ssm_d"].astype(jnp.float32).reshape(g, per, 1)
             * xg.astype(jnp.float32))
    v = _gate_norm(y.reshape(b, t, d), z, block, spec, u.dtype)
    return _out_proj(v, block, spec), grouped.reshape(state.shape), tail
