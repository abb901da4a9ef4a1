"""Pallas decode attention that reads the carried KV cache where it lies.

The dense cache stores a side positions-major with the heads flattened,
``[L, B, max_seq, K·hd]`` (models/transformer.py). The decode step's layer
scan carries both sides and writes a row's line with one scatter a layer;
this kernel is the matching read: one ``pallas_call`` a layer that takes the
two carried leaves whole, as operands that stay in HBM, and streams into
fast memory only the tiles that hold a live row's history. XLA's own read of
the same store slices the layer's whole history window out of the carry,
re-lays it and contracts every row to the shared history bucket: a third of
a 7B decode step at twelve rows (PERF.md §5 item 1).

  - grid = (rows, history / tile); the layer index, the per-row lengths
    (0 for a dead row) and a fetch plan are scalar-prefetch arguments, so
    the K and V BlockSpec index maps can address ``leaf[layer, row, tile]``
    of the carried array itself: nothing is sliced out first;
  - a grid step whose tile lies past its row's length (or before its
    sliding window, or in a dead row) maps to the block the last live step
    fetched (:func:`fetch_plan`): Pallas's pipeline skips the copy when
    consecutive steps map to the same block, and ``pl.when`` skips the
    arithmetic, so a short or dead row costs a grid step and no bytes;
  - every KV head of a row is handled in one grid step: a head's keys are
    a ``hd``-lane slice of the ``[tile, K·hd]`` block, and its G = H/K query
    heads contract against it together;
  - running max, normaliser and accumulator are float32 scratch carried
    over a row's tiles (TPU grids run sequentially per core); keys and
    values are read as stored (bfloat16), both products accumulate in
    float32 and the probabilities are cast to the values' dtype as
    ops.attention.attention casts them.

:func:`cache_decode_attention` is what the decode step calls. It chooses
from what it can observe: the program lowers the kernel when it is lowered
for a TPU (``lax.platform_dependent``: a described chip counts, so
``analysis/decode_static.py`` reads the kernel's program without one) and
XLA's einsums over the same store elsewhere; under a member ``vmap`` (its
own batching rule: ``custom_vmap``), in a program partitioned over devices,
or at shapes Mosaic cannot tile, the einsums everywhere. ``interpret=True`` runs the kernel through the Pallas
interpreter: for tests, as a function argument only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from quorum_tpu.ops.attention import decode_attention, decode_attention_q8
from quorum_tpu.ops.flash_attention import log_attention_path

NEG_INF = -1e30

# Positions a grid step reads of one row: [tile, K·hd] a side. At 8 KV heads
# of 128 that is 1 MB a side a step, double-buffered 4 MB of fast memory;
# a grid step costs about 0.35 us, so a smaller tile pays more steps for a
# finer skip (PERF.md §6, PR 35 has the tiles measured).
DECODE_TILE = 512


def decode_tile(history: int) -> int:
    """The tile the kernel reads a history bucket in (buckets are powers of
    two, so a tile divides a longer bucket and a shorter one is one tile)."""
    return min(DECODE_TILE, history)


def live_tiles(entries, tile: int, window: int = 0):
    """How many tiles of a row the kernel fetches at ``entries`` valid cache
    entries (the current token included; ints or a numpy array of them):
    the tiles up to the row's length, less those wholly before its sliding
    window. The engine's host-side count of :func:`fetch_plan`'s rule."""
    first = (entries - window).clip(0) // tile if window > 0 else 0
    return (entries - 1) // tile - first + 1


def fetch_plan(lens: jnp.ndarray, history: int, tile: int, window: int = 0):
    """Which block each grid step fetches, as two ``[rows · tiles]`` int32
    arrays (row, tile): a step whose tile holds live entries of its row
    fetches that tile; every other step repeats the block of the last live
    step before it (of the first live step, where none came before), so the
    pipeline copies nothing for it. ``lens`` [B]: valid entries a row, the
    current token included; 0 for a dead row."""
    n_t = history // tile
    start = jnp.arange(n_t, dtype=jnp.int32)[None, :] * tile
    live = start < lens[:, None]
    if window > 0:
        live = live & (start + tile > lens[:, None] - window)
    live = live.reshape(-1)
    step = jnp.arange(live.shape[0], dtype=jnp.int32)
    src = lax.cummax(jnp.where(live, step, -1))
    src = jnp.where(src < 0, jnp.argmax(live).astype(jnp.int32), src)
    return src // n_t, src % n_t


def _decode_kernel(
    layer_ref,  # SMEM [1] scalar-prefetch: the layer of the carried leaves
    len_ref,    # SMEM [B]: valid cache entries a row (0: dead row)
    row_ref,    # SMEM [B·T]: the fetch plan (unused here: the index maps')
    tile_ref,   # SMEM [B·T]
    q_ref,      # VMEM [1, K, G, hd]
    k_ref,      # VMEM [1, 1, tile, K·hd]: one row's tile of the K leaf
    v_ref,      # VMEM [1, 1, tile, K·hd]
    o_ref,      # VMEM [1, K, G, hd]
    m_scr,      # VMEM [K, G, 1] f32: running max
    l_scr,      # VMEM [K, G, 1] f32: running normaliser
    acc_scr,    # VMEM [K, G, hd] f32: running weighted values
    *,
    scale: float,
    tile: int,
    window: int,
):
    del layer_ref, row_ref, tile_ref
    ib, it = pl.program_id(0), pl.program_id(1)
    n_kv, group, hd = q_ref.shape[1:]
    length = len_ref[ib]
    start = it * tile

    @pl.when(it == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = start < length
    if window > 0:  # queries sit at length-1; keys >= length-window show
        live = live & (start + tile > length - window)

    @pl.when(live)
    def _update():
        col = start + lax.broadcasted_iota(jnp.int32, (group, tile), 1)
        keep = col < length
        if window > 0:
            keep = keep & (col >= length - window)
        for ik in range(n_kv):  # static: a head is a lane slice of the block
            lanes = slice(ik * hd, (ik + 1) * hd)
            k_blk = k_ref[0, 0, :, lanes]                  # [tile, hd]
            v_blk = v_ref[0, 0, :, lanes]
            logits = lax.dot_general(
                q_ref[0, ik], k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [G, tile]
            logits = jnp.where(keep, logits, NEG_INF)
            m_prev = m_scr[ik]
            m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
            p = jnp.exp(logits - m_new)
            corr = jnp.exp(m_prev - m_new)
            m_scr[ik] = m_new
            l_scr[ik] = corr * l_scr[ik] + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[ik] = corr * acc_scr[ik] + jnp.dot(
                p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)

    @pl.when(it == pl.num_programs(1) - 1)
    def _finalize():
        # a live row holds at least the current token, so l > 0; the floor
        # keeps a dead row's (discarded) output finite
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def _decode_call(q, k_cache, v_cache, layer, lens, *, history: int, tile: int,
                 window: int, interpret: bool):
    """The kernel over the carried leaves ``[L, B, max_seq, K·hd]``: ``q``
    [B, H, 1, hd], ``layer`` scalar, ``lens`` [B] (0: dead row). Returns
    [B, H, 1, hd]."""
    b, h, _, hd = q.shape
    n_kv = k_cache.shape[-1] // hd
    group = h // n_kv
    n_t = history // tile
    lens = lens.astype(jnp.int32)
    rows, tiles = fetch_plan(lens, history, tile, window)

    def kv_index(ib, it, layer_ref, len_ref, row_ref, tile_ref):
        step = ib * n_t + it
        return (layer_ref[0], row_ref[step], tile_ref[step], 0)

    def q_index(ib, it, *_):
        return (ib, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, n_t),
        in_specs=[
            pl.BlockSpec((1, n_kv, group, hd), q_index),
            pl.BlockSpec((1, 1, tile, n_kv * hd), kv_index),
            pl.BlockSpec((1, 1, tile, n_kv * hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, n_kv, group, hd), q_index),
        scratch_shapes=[
            pltpu.VMEM((n_kv, group, 1), jnp.float32),
            pltpu.VMEM((n_kv, group, 1), jnp.float32),
            pltpu.VMEM((n_kv, group, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=hd ** -0.5, tile=tile,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, group, hd), q.dtype),
        interpret=interpret,
        name="decode_attention_in_place",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lens, rows, tiles,
      q.reshape(b, n_kv, group, hd), k_cache, v_cache)
    return out.reshape(b, h, 1, hd)


def kernel_refusal(q_shape: tuple, leaf, history: int, *, sharded: bool,
                   interpret: bool = False) -> str:
    """Why the kernel cannot read this cache side ('' = it can): what the
    caller can observe of the call, never a setting. ``interpret`` lifts the
    limits that are Mosaic's tiling and not the kernel's arithmetic."""
    b, h, s_q, hd = q_shape
    if isinstance(leaf, tuple):
        return "an int8 cache side contracts natively in int8"
    if sharded:
        return "the program is partitioned over devices (Mosaic has no rule)"
    width = leaf.shape[-1]
    if s_q != 1:
        return f"{s_q} query positions (decode attends one)"
    if width % hd or h % (width // hd):
        return f"{h} query heads do not group over lines of {width}"
    if history % decode_tile(history):
        return f"history {history} does not tile by {DECODE_TILE}"
    if not interpret and (hd % 128 or history % 16
                          or str(leaf.dtype) != "bfloat16"):
        return (f"head_dim {hd}, history {history}, {leaf.dtype}: not lanes "
                "of 128 over bfloat16 tiles")
    return ""


def cache_decode_attention(
    q: jnp.ndarray,   # [B, H, 1, hd]
    k_cache,          # carried K side [L', B, max_seq, K·hd] (or int8 pair)
    v_cache,
    layer,            # scalar int32: the layer within the carried leaves
    lengths: jnp.ndarray,  # [B]: valid cache entries a row, current included
    live: jnp.ndarray,     # [B] bool: rows whose output is used
    *,
    history: int,
    window: int = 0,
    sharded: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """One decode step's attention of every row against layer ``layer`` of
    a dense bf16 cache carried whole: the kernel above where the program is
    lowered for a TPU, :func:`kernel_refusal` names no reason against it and
    no ``vmap`` batches the call (the Pallas batching rule would slice each
    member's whole cache side out before the kernel: the stacked quorum
    keeps the einsums), else XLA's einsums over a ``[B, history, K, hd]``
    view of the same store (:func:`window_attention`). A dead row's output
    is finite and discarded by the caller on both paths."""
    tile = decode_tile(history)

    def log(refusal):
        log_attention_path(
            "decode_in_place", refusal, interpret=interpret, q_shape=q.shape,
            kv_shape=jax.tree.leaves(k_cache)[0].shape, block=tile,
            window=window,
            accepted="pallas where lowered for a tpu, xla's einsums elsewhere")

    refusal = kernel_refusal(q.shape, k_cache, history, sharded=sharded,
                             interpret=interpret)
    log(refusal)

    def einsums(q, k_cache, v_cache, layer, lengths, live):
        del live
        return window_attention(q, k_cache, v_cache, layer, lengths,
                                history=history, window=window)

    def kernel(q, k_cache, v_cache, layer, lengths, live):
        return _decode_call(q, k_cache, v_cache, layer,
                            jnp.where(live, lengths, 0), history=history,
                            tile=tile, window=window, interpret=interpret)

    args = (q, k_cache, v_cache, layer, lengths, live)
    if refusal:
        return einsums(*args)
    if interpret:
        return kernel(*args)

    @jax.custom_batching.custom_vmap
    def read(*args):
        return lax.platform_dependent(*args, tpu=kernel, default=einsums)

    @read.def_vmap
    def read_members(axis_size, in_batched, *args):
        log("called under a vmap (stacked members)")
        in_axes = [0 if batched else None for batched in in_batched]
        return jax.vmap(einsums, in_axes=in_axes)(*args), True

    return read(*args)


def window_attention(q, k_cache, v_cache, layer, lengths, *, history: int,
                     window: int = 0):
    """XLA's read of the positions-major store: layer ``layer``'s first
    ``history`` lines of every row as a ``[B, history, K, hd]`` view,
    contracted as it lies (``rows_major``). An int8 side (the pair of values
    and scales [.., K]) contracts natively in int8."""
    hd = q.shape[-1]

    def lines(leaf):  # the prefix that can hold valid entries, not the tail
        sizes = (1, leaf.shape[1], history, leaf.shape[3])
        return lax.dynamic_slice(leaf, (layer, 0, 0, 0), sizes)[0]

    def heads(x):
        return x.reshape(x.shape[:2] + (x.shape[2] // hd, hd))

    read_k, read_v = jax.tree.map(lines, (k_cache, v_cache))
    if isinstance(read_k, tuple):
        return decode_attention_q8(
            q, heads(read_k[0]), read_k[1], heads(read_v[0]), read_v[1],
            lengths, window=window, rows_major=True)
    return decode_attention(q, heads(read_k), heads(read_v), lengths,
                            window=window, rows_major=True)
