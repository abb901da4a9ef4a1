"""Pallas one-step Mamba-2 recurrence that updates the carried state where
it lies and reads it out in the same pass.

A decode step of a spec with a mixer (models/ssm.py) computes, a layer, ``S'
= exp(dt A) S + dt x B^T`` and ``y = S' C`` over the layer's slab of the
carried float32 leaf ``[L, B, H, P, N]``. XLA makes two fused computations
of it: an in-place update that reads and writes the slab, and a reduction
that reads the slab a second time and recomputes the update on the way
(6.7 ms of a 19-20 ms step at 64 rows of Falcon-H1-34B's six layers, where
one read and one write of the 1.61 GB are 4.6 ms at the rate the chip
copies at: PERF.md section 5, ``manychat``). This kernel holds a block of
heads in fast memory, updates it, reduces it against ``C`` and writes it
back:

  - grid = (rows, blocks of heads); the layer index is a scalar-prefetch
    argument, so the state's BlockSpec index map addresses ``leaf[layer,
    row, block]`` of the carried array itself, and ``input_output_aliases``
    hands the same buffer back: nothing is sliced out and nothing of the
    leaf's size or a slab's is allocated;
  - a block is ``hb`` heads ``[hb, P, N]`` (:func:`heads_block`), fetched
    while the block before it is updated and written while the next one is;
  - the step's small operands arrive as the body wants them: the decay
    ``exp(dt A)`` a scalar a head in SMEM, ``dt x`` with ``P`` on the
    sublanes and a block's heads on the lanes (``[B, H/hb, P, hb]``: a
    head's column broadcasts over the ``N`` lanes), ``B`` and ``C`` a row
    of ``N`` lanes a group; ``y`` leaves in ``dt x``'s layout, a lane
    reduction a head;
  - everything is float32, the sums are the XLA form's own (the readout is
    of the updated state), and a row with ``dt = 0`` and ``x = 0`` has a
    decay of exactly 1 and a gain of exactly 0: it is written back as read.

:func:`refusal` names, from the state's shape and dtype and what the caller
says of its program, why a call does not take the kernel; ``interpret=True``
runs it through the Pallas interpreter, for tests, as a function argument
only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Bytes of one block of the state in fast memory. The block read and the
# block written are each double-buffered: four blocks beside the small
# operands. The copies are the bound and larger blocks copy a little faster:
# at 64 rows of [32, 128, 256] a layer takes 0.836 ms at 16 heads a block
# (2 MiB: 642 GB/s, read and write), 0.854 at 8, 0.872 at 4, and a call that
# only copies its blocks 0.837 and 0.853; XLA's two fusions 1.19 (my chip
# run, PR 54).
BLOCK_BYTES = 2 << 20
LANES, SUBLANES = 128, 8


def heads_block(per_group: int, p: int, n: int, *,
                block_bytes: int = BLOCK_BYTES) -> int:
    """Heads of one group a grid step holds: the most that divide the
    group's ``per_group`` within ``block_bytes`` of float32 ``[P, N]`` states;
    0 where one head is already over."""
    fits = [hb for hb in range(1, per_group + 1)
            if per_group % hb == 0 and hb * p * n * 4 <= block_bytes]
    return max(fits, default=0)


def refusal(state_shape: tuple, dtype, *, sharded: bool = False,
            interpret: bool = False) -> str:
    """Why the kernel does not take this state ('' = it does): what the
    caller can observe of the call, never a setting. ``state_shape`` is the
    carried leaf's ``[L, B, H, P, N]``. ``interpret`` lifts the limits that
    are Mosaic's tiling and not the kernel's arithmetic."""
    _, _, _, p, n = state_shape
    if sharded:
        return "the program is partitioned over devices (Mosaic has no rule)"
    if str(jnp.dtype(dtype)) != "float32":
        return f"a {jnp.dtype(dtype)} state (the kernel's is float32)"
    if interpret:
        return ""
    if n % LANES or p % SUBLANES:
        return (f"heads of [{p}, {n}]: not {SUBLANES} sublanes by "
                f"{LANES} lanes")
    if not heads_block(1, p, n):
        return (f"one head of [{p}, {n}] float32 is over a block of "
                f"{BLOCK_BYTES >> 20} MiB")
    return ""


def _kernel(
    layer_ref,  # SMEM [1] scalar-prefetch (unused here: the index maps')
    decay_ref,  # SMEM [B, H]: exp(dt A) a row and head
    dtx_ref,    # VMEM [P, hb]: dt x, a head a lane
    b_ref,      # VMEM [1, N]: the group's B
    c_ref,      # VMEM [1, N]
    s_ref,      # VMEM [hb, P, N]: the block of the state, as read
    y_ref,      # VMEM [P, hb]
    o_ref,      # VMEM [hb, P, N]: the same block, as written
):
    del layer_ref
    row, blk = pl.program_id(0), pl.program_id(1)
    hb = s_ref.shape[0]
    b_row, c_row = b_ref[...], c_ref[...]
    for h in range(hb):  # static: a head is a lane of the small operands
        s = (decay_ref[row, blk * hb + h] * s_ref[h]
             + dtx_ref[:, h:h + 1] * b_row)
        o_ref[h] = s
        y_ref[:, h:h + 1] = jnp.sum(s * c_row, axis=-1, keepdims=True)


def step_in_place(leaf, layer, decay, dtx, bm, cm, *, hb: int = 0,
                  interpret: bool = False):
    """One step of every row's recurrence in layer ``layer`` of the carried
    ``leaf`` ``[L, B, H, P, N]`` float32: ``S = decay S + dtx B^T``, ``y = S
    C``. ``decay`` ``[B, H]``, ``dtx`` ``[B, H, P]``, ``bm`` and ``cm`` ``[B,
    G, N]``, all float32; ``hb`` heads a block (0: :func:`heads_block`).
    Returns ``y`` ``[B, H, P]`` float32 and the leaf, the same buffer with
    the layer's slab updated."""
    _, b, h, p, n = leaf.shape
    g = bm.shape[1]
    per = h // g
    hb = hb or heads_block(per, p, n)
    nb = per // hb  # blocks a group

    def small_map(ib, ik, layer_ref):
        return ib, ik, 0, 0

    def group_map(ib, ik, layer_ref):
        return ib, ik // nb, 0, 0

    def state_map(ib, ik, layer_ref):
        return layer_ref[0], ib, ik, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h // hb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, None, p, hb), small_map),
            pl.BlockSpec((None, None, 1, n), group_map),
            pl.BlockSpec((None, None, 1, n), group_map),
            pl.BlockSpec((None, None, hb, p, n), state_map),
        ],
        out_specs=[
            pl.BlockSpec((None, None, p, hb), small_map),
            pl.BlockSpec((None, None, hb, p, n), state_map),
        ],
    )
    f32 = jnp.float32
    y, leaf = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h // hb, p, hb), f32),
                   jax.ShapeDtypeStruct(leaf.shape, f32)],
        # operand 5 (the scalar-prefetch layer counts) is the leaf
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=4 * hb * p * n * 4 + (16 << 20)),
        interpret=interpret,
        name="ssm_step_in_place",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), decay.astype(f32),
      dtx.astype(f32).reshape(b, h // hb, hb, p).swapaxes(2, 3),
      bm.astype(f32)[:, :, None], cm.astype(f32)[:, :, None], leaf)
    return y.swapaxes(2, 3).reshape(b, h, p), leaf
