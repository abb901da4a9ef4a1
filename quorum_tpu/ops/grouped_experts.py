"""Pallas grouped expert product: every tile of sorted picks through its
expert's three matrices in one call a layer, the next tile's matrices
fetched while this one multiplies.

models/patterned.py sorts the picks that fall on held experts into tiles of
rows that each belong to one expert. Its own walk over them is a
``lax.fori_loop`` of XLA dots with a trip count known only on the device:
each turn slices the expert's three matrices out, runs three dots that each
fill and drain their own pipeline, and scatter-adds the tile into the
output, and nothing of the next turn starts before this one ends. On a v5e
that streams an expert's matrices at 370-480 GB/s of 819 (PERF.md section 5,
``crowd``). This kernel computes ``(silu(rows @ W_gate[e]) * (rows @
W_up[e])) @ W_down[e]`` a tile with the weights' copies running ahead:

  - grid = (tiles, blocks); a tile's blocks are first ``nd`` blocks of rows
    of ``W_gate`` and ``W_up`` (the contraction over D, accumulated in
    float32 scratch), then ``nf`` blocks of rows of ``W_down`` (the
    contraction over F, accumulated in the output block): every copy moves
    whole contiguous rows of a matrix;
  - ``expert_of_tile``, the number of tiles that hold a pick and the period
    index of a stacked leaf are scalar-prefetch arguments, so the weights'
    BlockSpec index maps address ``leaf[r, e, block]`` of the array itself
    (nothing is sliced out first) and the pipeline fetches tile i + 1's
    first blocks while tile i's last multiply;
  - a grid step past the last tile maps every operand to the block the last
    live step had (no copy) and ``pl.when`` skips its arithmetic;
  - operands are read as stored (bfloat16 on the chip), every product
    accumulates in float32, the hidden rows are rounded to the operands'
    dtype where the loop rounds them, and a tile's rows are written in
    float32 in sorted order: the caller weights them and sums a token's
    picks by a gather (no scatter, one deterministic order).

:func:`refusal` names, from the call's shapes, dtype and the share of the
experts held, why a call does not take the kernel; ``interpret=True`` runs it
through the Pallas interpreter, for tests, as a function argument only.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from quorum_tpu.ops.flash_attention import traced_program

logger = logging.getLogger(__name__)

# Bytes of one weight block in fast memory. A grid step holds two blocks of
# gate and of up and two of down (double-buffered): 12 MB beside the rows at
# the three shapes served (D 2048 / F 1792, 5120 / 1536, 6144 / 2048). The
# copies are the bound (a call that only copies its blocks moves 690 GB/s at
# any block from 0.5 to 8 MiB; the products of a 32-row tile take a quarter of
# its copies' time, of a 128-row tile three quarters), and a step's products
# hide behind the next step's copies best where the steps are alike: at 128
# rows 1 and 2 MiB blocks read 596 and 561 GB/s, 4 and 8 MiB 515 and 496; a
# row block of dots3's down matrix needs 1.25 MiB (my chip runs, PR 53).
BLOCK_BYTES = 2 << 20
# What the kernel may ask of fast memory (``vmem_limit_bytes``): a v5e core
# has 128 MiB; the compiler's own scratch needs room beside the blocks.
VMEM_CAP = 100 << 20
LANES = 128


def block_rows(rows: int, width: int, itemsize: int, *,
               block_bytes: int = BLOCK_BYTES, lanes: int = LANES) -> int:
    """Rows of a ``[rows, width]`` matrix a grid step fetches: the most that
    divide ``rows`` in whole lanes within ``block_bytes``; 0 where none
    does."""
    fits = [b for b in range(lanes, rows + 1, lanes)
            if rows % b == 0 and b * width * itemsize <= block_bytes]
    return max(fits, default=0)


def vmem_bytes(tile_rows: int, d: int, f: int, bd: int, bf: int,
               itemsize: int) -> int:
    """Fast memory a call holds: double-buffered blocks of the three
    matrices, of the rows and of the output, the scratch, and the float32
    values of a step's products."""
    weights = 2 * (2 * bd * f + bf * d) * itemsize
    rows = 2 * tile_rows * bd * itemsize + 2 * tile_rows * d * 4
    scratch = tile_rows * f * (8 + itemsize)
    values = tile_rows * (2 * f + d) * 4
    return weights + rows + scratch + values


def refusal(tile_rows: int, d: int, f: int, dtype, *, held_share: float = 1.0,
            sharded: bool = False, interpret: bool = False) -> str:
    """Why the kernel does not take these tiles ('' = it does): what the
    caller can observe of the call, never a setting. ``held_share``: the
    share of the layer's experts that are held here, so of the picks that
    are expected here. ``interpret`` lifts the limits that are Mosaic's
    tiling or the chip's speed and not the kernel's arithmetic."""
    if sharded:
        return "the program is partitioned over devices (Mosaic has no rule)"
    if interpret:
        return ""
    if held_share < 0.5:
        # the sorted rows' gather and the sum over a token's picks are sized
        # by every pick, the loop's turns by the picks that are here: with an
        # eighth of the experts held, 512 rows took 3.10 ms a layer at
        # K-EXAONE's widths for the loop's 2.73 and 3.39 for 2.97 at dots3's,
        # the kernel alone 2.43 and 2.66; with every expert held (LFM2's) 1.47
        # for 1.80 (my chip run, PR 53; ROADMAP S15 has what would lift it)
        return (f"{held_share:.3g} of the experts are held here: the rows' "
                "gather and the picks' sum are sized by every pick")
    itemsize = jnp.dtype(dtype).itemsize
    if str(jnp.dtype(dtype)) != "bfloat16" or tile_rows % 16:
        return (f"tiles of {tile_rows} rows of {jnp.dtype(dtype)}: not "
                "bfloat16 tiles of 16 sublanes")
    bd, bf = block_rows(d, f, itemsize), block_rows(f, d, itemsize)
    if not bd or not bf:
        return (f"experts of {d} x {f}: no block of whole lanes within "
                f"{BLOCK_BYTES >> 20} MiB")
    need = vmem_bytes(tile_rows, d, f, bd, bf, itemsize)
    if need > VMEM_CAP:
        return (f"experts of {d} x {f} at {tile_rows} rows need {need >> 20} "
                f"MiB of fast memory (over {VMEM_CAP >> 20})")
    return ""


def log_moe_path(path: str, why: str, rows: int, tile_rows: int,
                 max_tiles: int, held: int, d: int, f: int, *,
                 interpret: bool) -> None:
    """The trace-time line that makes the choice visible, one a traced
    program: ``dense`` (every held expert over every row), ``kernel``, or
    ``loop`` and :func:`refusal`'s ``why``."""
    logger.info(
        "moe_tiles program=%s path=%s interpret=%s rows=%d tile_rows=%d "
        "max_tiles=%d experts=%dx[%d, %d] reason=%s", traced_program(), path,
        interpret, rows, tile_rows, max_tiles, held, d, f,
        why or {"dense": "few rows pick most of the held experts",
                "kernel": "interpret mode asked for" if interpret else
                "pallas where lowered for a tpu, xla's loop elsewhere"}[path])


def _kernel(
    expert_ref,  # SMEM [max_tiles] scalar-prefetch: a tile's expert
    tiles_ref,   # SMEM [1]: tiles that hold a pick
    period_ref,  # SMEM [1]: the period of a stacked leaf (0 of a layer's)
    x_ref,       # VMEM [T, bd]: one block of the tile's rows
    wg_ref,      # VMEM [bd, F]: the same rows of the expert's gate matrix
    wu_ref,      # VMEM [bd, F]
    wd_ref,      # VMEM [bf, D]: one block of rows of its down matrix
    o_ref,       # VMEM [T, D] f32: the tile's output rows
    gate_scr,    # VMEM [T, F] f32
    up_scr,      # VMEM [T, F] f32
    h_scr,       # VMEM [nf, T, bf]: the hidden rows by block of down's rows
    *,
    nd: int,
    nf: int,
):
    del expert_ref, period_ref
    i, j = pl.program_id(0), pl.program_id(1)
    bf = h_scr.shape[2]

    @pl.when(i < tiles_ref[0])
    def _tile():
        @pl.when(j < nd)
        def _gate_up():
            rows = x_ref[...]
            gate = jnp.dot(rows, wg_ref[...],
                           preferred_element_type=jnp.float32)
            up = jnp.dot(rows, wu_ref[...],
                         preferred_element_type=jnp.float32)
            if nd > 1:
                @pl.when(j == 0)
                def _first():
                    gate_scr[...] = gate
                    up_scr[...] = up

                @pl.when((j > 0) & (j < nd - 1))
                def _add():
                    gate_scr[...] += gate
                    up_scr[...] += up

            @pl.when(j == nd - 1)
            def _hidden():
                g, u = ((gate_scr[...] + gate, up_scr[...] + up) if nd > 1
                        else (gate, up))
                h = (jax.nn.silu(g) * u).astype(h_scr.dtype)
                for b in range(nf):
                    h_scr[b] = h[:, b * bf:(b + 1) * bf]

        @pl.when(j >= nd)
        def _down():
            y = jnp.dot(h_scr[j - nd], wd_ref[...],
                        preferred_element_type=jnp.float32)

            @pl.when(j == nd)
            def _first():
                o_ref[...] = y

            @pl.when(j > nd)
            def _add():
                o_ref[...] += y


def grouped_product(x_rows, expert_of_tile, n_tiles, w_gate, w_up, w_down,
                    period=0, *, tile_rows: int, interpret: bool = False,
                    block_bytes: int = BLOCK_BYTES):
    """The tiles' rows through their experts. ``x_rows`` ``[max_tiles *
    tile_rows, D]``: the sorted rows (any finite row where a tile has no
    pick); ``expert_of_tile`` ``[max_tiles]``; ``n_tiles`` the tiles that
    hold a pick, the first ones; the matrices ``[periods, held, D, F]`` and
    ``[periods, held, F, D]`` (a period's stacked leaves, or a layer's own
    as one period's), read at ``period``. Returns ``[max_tiles * tile_rows,
    D]`` float32, the rows of tiles past ``n_tiles`` unwritten (whatever the
    buffer held)."""
    _, _, d, f = w_gate.shape
    dt = x_rows.dtype
    max_tiles = expert_of_tile.shape[0]
    lanes = 1 if interpret else LANES
    itemsize = jnp.dtype(dt).itemsize
    bd = block_rows(d, f, itemsize, block_bytes=block_bytes, lanes=lanes)
    bf = block_rows(f, d, itemsize, block_bytes=block_bytes, lanes=lanes)
    nd, nf = d // bd, f // bf
    # a block of a tile's rows a grid step: [max_tiles, nd, T, bd]
    x_blocks = x_rows.reshape(max_tiles, tile_rows, nd, bd).transpose(
        0, 2, 1, 3)

    def at(i, j, tiles_ref):
        """The live step a grid step reads: itself, or the last live one."""
        last = tiles_ref[0] - 1
        return (jnp.clip(i, 0, jnp.maximum(last, 0)),
                jnp.where(i <= last, j, nd + nf - 1))

    def rows_map(i, j, expert_ref, tiles_ref, period_ref):
        it, jj = at(i, j, tiles_ref)
        return it, jnp.minimum(jj, nd - 1), 0, 0

    def in_map(i, j, expert_ref, tiles_ref, period_ref):
        it, jj = at(i, j, tiles_ref)
        return period_ref[0], expert_ref[it], jnp.minimum(jj, nd - 1), 0

    def down_map(i, j, expert_ref, tiles_ref, period_ref):
        it, jj = at(i, j, tiles_ref)
        return period_ref[0], expert_ref[it], jnp.maximum(jj - nd, 0), 0

    def out_map(i, j, expert_ref, tiles_ref, period_ref):
        return at(i, j, tiles_ref)[0], 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(max_tiles, nd + nf),
        in_specs=[
            pl.BlockSpec((None, None, tile_rows, bd), rows_map),
            pl.BlockSpec((None, None, bd, f), in_map),
            pl.BlockSpec((None, None, bd, f), in_map),
            pl.BlockSpec((None, None, bf, d), down_map),
        ],
        out_specs=pl.BlockSpec((tile_rows, d), out_map),
        scratch_shapes=[
            pltpu.VMEM((tile_rows, f), jnp.float32),
            pltpu.VMEM((tile_rows, f), jnp.float32),
            pltpu.VMEM((nf, tile_rows, bf), dt),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, nd=nd, nf=nf),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((max_tiles * tile_rows, d),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # what refusal let through, and room for the compiler's own
            vmem_limit_bytes=vmem_bytes(tile_rows, d, f, bd, bf, itemsize)
            + (16 << 20)),
        interpret=interpret,
        name="grouped_experts",
    )(expert_of_tile.astype(jnp.int32),
      jnp.reshape(n_tiles, (1,)).astype(jnp.int32),
      jnp.reshape(period, (1,)).astype(jnp.int32),
      x_blocks, w_gate, w_up, w_down)
