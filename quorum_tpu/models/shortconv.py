"""The gated short convolution of a ``"C"`` layer (family "lfm2_moe").

A ``"C"`` layer of a patterned spec (models/patterned.py) has no attention:
its first sub-layer gives the block's normed input ``h`` to

  ``[B | C | X] = h W_in``, three parts of ``d_model`` each, in that order;
  ``u = B * X``, elementwise;
  ``v[t] = sum_k w[k] * u[t - (taps - 1) + k]``, a depthwise causal
  convolution of ``conv_taps`` taps with no bias (tap ``k`` meets the input
  ``taps - 1 - k`` positions back, ``u`` zero before the row's first
  position);
  ``(C * v) W_out``;

with no activation anywhere. What a row carries from one program to the next
is ``u`` at its last ``conv_taps - 1`` positions, ``[conv_taps - 1,
d_model]``: one leaf a ``"C"`` layer on the K side of the cache
(``KindKV.conv``, ``[slots, conv_taps - 1, d_model]`` in the cache's dtype),
which does not grow with the row.

:func:`causal_taps` is the one place a convolution's tail is read and taken:
models/ssm.py's mixer runs its own convolution (with a bias and a SiLU)
through it. Positions past a row's ``n_valid`` (the pad of a bucket, a row a
decode step may not write) never reach the tail: it is taken at the row's
true last positions.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
from jax import lax

from quorum_tpu.models.model_config import ModelSpec
from quorum_tpu.models.quant import qeinsum
from quorum_tpu.ops.flash_attention import traced_program

logger = logging.getLogger(__name__)


def causal_taps(x, tail, w, n_valid, bias=None):
    """The depthwise causal convolution of ``[tail ; x]``: ``x`` [B, T, C]
    this program's positions, ``tail`` [B, taps - 1, C] the row's last inputs
    before them, ``w`` [taps, C], ``bias`` [C] or None. Returns the float32
    sums [B, T, C] and the tail after each row's ``n_valid`` real positions,
    in ``x``'s dtype (``n_valid`` 0: the tail as it came)."""
    taps, t = tail.shape[1] + 1, x.shape[1]
    seq = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w = w.astype(jnp.float32)
    out = None if bias is None else bias.astype(jnp.float32)
    for k in range(taps):
        term = w[k] * seq[:, k:k + t].astype(jnp.float32)
        out = term if out is None else out + term
    new_tail = jax.vmap(
        lambda row, n: lax.dynamic_slice_in_dim(row, n, taps - 1, axis=0))(
        seq, n_valid)
    return out, new_tail


def log_conv_path(spec: ModelSpec, shape: tuple) -> None:
    """One line a traced program: the rows and positions its short
    convolutions run over, and the state a row carries through them."""
    logger.info("conv-path program=%s rows=%d positions=%d layers=%d taps=%d "
                "row_state=tail[%d, %d]", traced_program(), shape[0],
                shape[1], len(spec.layers_of("C")), spec.conv_taps,
                spec.conv_taps - 1, spec.d_model)


def operator(h, lyr, spec: ModelSpec, tail, n_valid):
    """The operator over ``h`` [B, T, D], the block's normed input, from the
    rows' ``tail`` [B, conv_taps - 1, D]; ``n_valid`` [B] int32 counts each
    row's real positions, the first ones. Returns what the sub-layer adds to
    the stream and the rows' tail after their last real position."""
    h = h.astype(jnp.dtype(spec.dtype))
    with jax.named_scope("conv.in_proj"):
        gate_in, gate_out, x = jnp.split(
            qeinsum("btd,de->bte", h, lyr["conv_in"]), 3, axis=-1)
        u = gate_in * x
    with jax.named_scope("conv.taps"):
        v, tail = causal_taps(u, tail, lyr["conv_w"], n_valid)
        y = (gate_out.astype(jnp.float32) * v).astype(h.dtype)
    with jax.named_scope("conv.out_proj"):
        return qeinsum("bte,ed->btd", y, lyr["conv_out"]), tail


def rows(h, lyr, spec: ModelSpec, leaf, row, n_valid, fresh, lead=()):
    """The operator on rows ``row ..`` of a layer's cache leaf ``[slots,
    conv_taps - 1, D]`` (``lead``: the leaf is a stack of such, this layer's
    at that index): from a zero tail where ``fresh`` (True, or a bool
    scalar: the rows' first position is this program's, whatever the slot's
    last tenant left), else from what the rows hold. Returns the sub-layer's
    output and the leaf with the rows' new tail written."""
    at = tuple(lead) + (row, 0, 0)
    size = (h.shape[0],) + leaf.shape[-2:]
    held = lax.dynamic_slice(leaf, at, (1,) * len(lead) + size).reshape(size)
    tail = jnp.where(fresh, jnp.zeros_like(held), held)
    out, tail = operator(h, lyr, spec, tail, n_valid)
    return out, lax.dynamic_update_slice(
        leaf, tail.astype(leaf.dtype).reshape((1,) * len(lead) + size), at)
