"""Pallas attention of a block of selecting queries over a latent cache: the
keys and values of a tile of positions made in fast memory, head by head.

models/latent.py's ``tiled`` is this attention as XLA writes it: a loop over
tiles of the cached rows that makes a tile's keys and values for all heads,
multiplies the queries against them and folds the tile into a running
softmax. On a v5e the compiler computes a tile's score product three times
(once in each of the maximum, the sum and the probability fusions, to avoid
writing 268 MB of float32 logits) and passes the logits' elementwise work
three times with it: 1.36 ms a tile of 1,024 for 512 queries x 128 heads,
what the latent-space form takes over as many positions. This kernel
computes each product and each exponential once, 0.55 ms a tile (my chip
runs, PR 41; PERF.md section 6):

  - grid = (rows, heads, key tiles), the key tiles innermost; the slot row,
    each row's last live position and the number of live tiles are
    scalar-prefetch arguments, so the BlockSpec of the cached rows addresses
    ``leaf[first + row, tile]`` of the carried array itself, and a grid step
    past the last live tile maps to the block the step before it fetched (no
    copy) and skips its arithmetic (``pl.when``);
  - a grid step holds one tile of rows ``[tile, C]``: the latent (the first
    ``kv_rank`` lanes) goes through the head's ``W_kb`` and ``W_vb`` blocks,
    the rotated key is the lanes after it (zeros behind it up to ``C``; the
    queries' rotated part is padded with zeros to as many lanes);
  - the selection is an int8 mask ``[T, tile]`` the heads share;
  - running maximum, sum and accumulator are float32 scratch carried over a
    head's tiles; operands are bfloat16 as stored, every product accumulates
    in float32, keys, values and probabilities are rounded to the rows'
    dtype where XLA's form rounds them.

:func:`tile_attention` is what ``latent.tiled`` calls where the program is
lowered for a TPU and :func:`kernel_refusal` names nothing against it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from quorum_tpu.ops.attention import NEG_INF


def _kernel(
    first_ref,  # SMEM [1] scalar-prefetch: the cache row of query row 0
    last_ref,   # SMEM [B]: the last live position of a row
    live_ref,   # SMEM [1]: key tiles that hold a live position of any row
    qn_ref,     # VMEM [1, T, nope]: one head's unrotated queries
    qr_ref,     # VMEM [1, 1, T, C - kv_rank]: its rotated ones, zeros after
    rows_ref,   # VMEM [1, tile, C]: one tile of the cached rows
    keep_ref,   # VMEM [1, T, tile] int8: the selection
    wkb_ref,    # VMEM [kv_rank, nope]: the head's block of W_kb
    wvb_ref,    # VMEM [kv_rank, v]
    o_ref,      # VMEM [1, 1, T, v]
    m_scr,      # VMEM [T, 1] f32: running max
    l_scr,      # VMEM [T, 1] f32: running sum
    acc_scr,    # VMEM [T, v] f32: running weighted values
    *,
    scale: float,
    tile: int,
    kv_rank: int,
):
    del first_ref
    ib, it = pl.program_id(0), pl.program_id(2)
    dt = rows_ref.dtype

    @pl.when(it == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(it < live_ref[0])
    def _update():
        part = rows_ref[0]                                   # [tile, C]
        c_kv, k_r = part[:, :kv_rank], part[:, kv_rank:]
        # what lies behind the row's last live position is another
        # request's: taken as zeros, keys and values alike
        at = it * tile + lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        live = at <= last_ref[ib]
        k_n = jnp.where(live, jnp.dot(
            c_kv, wkb_ref[...], preferred_element_type=jnp.float32), 0.0)
        v = jnp.where(live, jnp.dot(
            c_kv, wvb_ref[...], preferred_element_type=jnp.float32), 0.0)
        k_r = jnp.where(live, k_r, jnp.zeros_like(k_r))
        nt = (((1,), (1,)), ((), ()))
        logits = (lax.dot_general(qn_ref[0], k_n.astype(dt), nt,
                                  preferred_element_type=jnp.float32)
                  + lax.dot_general(qr_ref[0, 0], k_r, nt,
                                    preferred_element_type=jnp.float32)
                  ) * scale                                   # [T, tile]
        logits = jnp.where(keep_ref[0] != 0, logits, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        shrink = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        l_scr[...] = shrink * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = shrink * acc_scr[...] + jnp.dot(
            p.astype(dt), v.astype(dt), preferred_element_type=jnp.float32)

    @pl.when(it == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def kernel_refusal(t: int, hist: int, tile: int, rows, g, *,
                   interpret: bool = False) -> str:
    """Why the kernel cannot take this block ('' = it can), from the call's
    shapes alone. ``interpret`` lifts the limits that are Mosaic's tiling
    and not the kernel's arithmetic."""
    if hist % tile:
        return f"history {hist} does not tile by {tile}"
    if interpret:
        return ""
    width = rows.shape[-1]
    if (g.kv_rank % 128 or g.nope % 128 or g.v % 128 or width % 128
            or width == g.kv_rank or str(rows.dtype) != "bfloat16"):
        return (f"latent {g.kv_rank}, heads of {g.nope} and {g.v}, rows of "
                f"{width} {rows.dtype}: not lanes of 128 over bfloat16")
    if t % 32 or tile % 128:
        return f"{t} queries over tiles of {tile}: not an int8 mask's tiles"
    return ""


def tile_attention(q_n, q_r, rows, first, hist: int, keep, last, n_live,
                   w_kb, w_vb, g, *, tile: int, interpret: bool = False):
    """Queries ``q_n`` ``[B, T, H, nope]``, ``q_r`` ``[B, T, H, rope]`` over
    the first ``n_live`` tiles of rows ``first ..`` of the leaf ``rows``
    ``[N, S, C]``, row ``b`` to position ``last[b]``; ``keep`` ``[B, T,
    hist]`` bool; ``w_kb`` ``[kv_rank, H * nope]``, ``w_vb`` ``[kv_rank, H *
    v]``. Returns ``[B, H, T, v]``."""
    b, t, heads, _ = q_n.shape
    width = rows.shape[-1]
    dt = rows.dtype
    # the rotated queries head-major and as wide as the lanes behind the
    # latent: the rotated key, then the row's zeros
    qr = jnp.pad(q_r.astype(dt).transpose(0, 2, 1, 3), (
        (0, 0), (0, 0), (0, 0), (0, width - g.kv_rank - g.rope)))

    def tile_of(it, live_ref):
        return jnp.minimum(it, live_ref[0] - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, heads, hist // tile),
        in_specs=[
            pl.BlockSpec((1, t, g.nope), lambda ib, ih, it, *_: (ib, 0, ih)),
            pl.BlockSpec((1, 1, t, width - g.kv_rank),
                         lambda ib, ih, it, *_: (ib, ih, 0, 0)),
            pl.BlockSpec((1, tile, width),
                         lambda ib, ih, it, first_ref, last_ref, live_ref: (
                             first_ref[0] + ib, tile_of(it, live_ref), 0)),
            pl.BlockSpec((1, t, tile),
                         lambda ib, ih, it, first_ref, last_ref, live_ref: (
                             ib, 0, tile_of(it, live_ref))),
            pl.BlockSpec((g.kv_rank, g.nope), lambda ib, ih, it, *_: (0, ih)),
            pl.BlockSpec((g.kv_rank, g.v), lambda ib, ih, it, *_: (0, ih)),
        ],
        out_specs=pl.BlockSpec((1, 1, t, g.v),
                               lambda ib, ih, it, *_: (ib, ih, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((t, 1), jnp.float32),
            pltpu.VMEM((t, 1), jnp.float32),
            pltpu.VMEM((t, g.v), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=(g.nope + g.rope) ** -0.5,
                          tile=tile, kv_rank=g.kv_rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, heads, t, g.v), dt),
        interpret=interpret,
        name="latent_tile_attention",
    )(jnp.reshape(first, (1,)).astype(jnp.int32), last.astype(jnp.int32),
      jnp.reshape(n_live, (1,)).astype(jnp.int32),
      q_n.astype(dt).reshape(b, t, heads * g.nope), qr, rows,
      keep.astype(jnp.int8), w_kb.astype(dt), w_vb.astype(dt))
