"""The forward pass of a spec with a ``layer_pattern``: each layer has its own
attention kind, the first layers a dense MLP and the rest experts, and the
cache is kept by layer kind.

What differs from models/transformer.py, whose entry points hand a patterned
spec over to the functions of the same name here:

  - **Per-layer weights.** ``params["layers"]["00"]…`` hold one layer each
    (every leaf with a leading dim of 1, so the sharding table's axes fit);
    the depth loop is written out, so a layer's attention kind, MLP kind and
    cache are static where the program is compiled.
  - **A cache per layer kind** (:class:`KindKV`): a full-attention layer keeps
    ``[slots, K, max_seq, hd]``; a window layer keeps a ring of ``spec.ring``
    positions per row, written at ``position mod ring`` and read whole under
    a mask made from the absolute position each ring entry holds. The K side
    also carries the expert layers' counters, so that they ride the cache
    through every program and cost no output of their own.
    The decode step writes a row's new K and V in a loop over the rows, a
    ``dynamic_slice`` of what the row holds, a select by the row's mask and a
    ``dynamic_update_slice``, all three at scalar starts, K and V of a layer
    in one loop. A masked row keeps what it holds: a row in chunked prefill
    is masked and its positions are live data. Not ``jax.vmap`` of the same
    three: a per-row start under ``vmap`` turns the slice into a gather, the
    v5e compiler wants a gather's operand positions-major, and so every step
    copied both sides of both full layers (268 MB each) and all twelve rings
    to fetch 64 KB; attention reads the carried K-major buffer in place and
    never wanted the copy. Nor an unrolled loop: with no ``while`` around the
    updates the compiler flips the carried buffers positions-major and
    re-lays the history window K-major each step (PERF.md section 6, PR 33).
  - **Expert layers over a held share.** The router scores all ``n_experts``
    in float32 and picks the top k; only the picks that fall on the experts
    held here are computed, beside the shared expert, and no pick is dropped.
  - **Whole periods run as a scan** (``spec.periods``: the layers from
    ``first_dense`` on, where they are two or more repeats of one sequence
    of full-attention and short-convolution layers): their weights are held
    stacked, a period's slot a leaf ``[count, ...]``, and so are their
    tails; a slot's K and V are one array with the periods' heads side by
    side, ``[slots, count x K, ...]`` (with the period as a fifth, leading
    dim the v5e compiler re-lays both sides at a chunk's entry and exit,
    3.2 GB of temporaries). One ``lax.scan`` over the periods carries the
    stream and the cache, which every write updates in place at its
    period's index; a weight is read where it lies, at ``(period, ...)``
    (as the scan's ``xs`` a slot's 32 experts were copied out for the tile
    loop every period of every step). The
    program the compiler sees has one period's layers and not ``count``
    times them (fourteen written-out layers of lfm2 took 405 s of a cold
    set-up, PERF.md section 6). Every other spec's loop is written out.
  - **A layer kind that is not attention** (``"C"``, models/shortconv.py): a
    gated short convolution in the place of attention and its output
    product, whose cache is the row's last ``conv_taps - 1`` inputs
    (``KindKV.conv``), taken at the row's true length, zero where a row
    starts and kept by a row a decode step may not write.
  - The families' conventions: RMSNorm over each q and k head and a sigmoid
    router written in; post-norm blocks (``h + norm(f(h))``) or pre-norm and
    rotary embedding on the window layers only or on full layers too
    (``spec.rope_full``) by the spec.
  - **The residual stream is float32**; each sub-layer computes in the spec's
    dtype and its normalised output is added in float32. The router reads the
    float32 stream: with a bfloat16 stream (rounded to 2**-9 at each of 16
    adds) the 8th and 9th scores swapped in one (position, layer) of twenty
    against the float32 reference, and one swapped pick moves a served
    log-probability by 0.03 and more (PERF.md section 6, PR 30).

Members, paging, int8 and sequence parallelism do not reach these
functions: the engine refuses them for a patterned spec at start-up.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from quorum_tpu.models import latent, shortconv
from quorum_tpu.models.model_config import ModelSpec
from quorum_tpu.ops import grouped_experts
from quorum_tpu.ops.attention import attention, decode_attention
from quorum_tpu.ops.flash_attention import flash_prefill_attention
from quorum_tpu.ops.norms import rmsnorm
from quorum_tpu.ops.rotary import rope_cos_sin_for

# Rows up to which the held experts run densely over every row (a decode
# step: the step is bound by the experts' bytes, and at 32 rows 87 % of 16
# held experts of 128 are picked anyway); above it picks are grouped by
# expert, which is what a prefill's rows need: 512 tokens put one expert's
# worth of picks on 16 held experts, a sixteenth of the dense form's products.
# Grouped below it too where the rows are expected to pick under half of the
# held experts (:func:`dense_experts`): 16 rows pick 8 of 256 each and reach
# 40 % of 32 held ones, so the dense form read 2.5 times the bytes the step
# needs, 2.67 ms a layer against 1.1 (my chip run, PR 34).
DENSE_ROWS = 64


def dense_experts(spec: ModelSpec, rows: int) -> bool:
    """Whether a program of ``rows`` rows runs every held expert over every
    row: few rows, that under even routing pick at least half of them, and
    expert layers that are written out. Where they are a period's slots
    (``spec.periods``, which start at the first expert layer) every held
    expert at once would be a copy of the slot's experts, so an expert is
    read where it lies, a tile at a time (:func:`_experts_grouped`)."""
    share = 1.0 - (1.0 - spec.experts_per_token / spec.n_experts) ** rows
    return not spec.periods[2] and rows <= DENSE_ROWS and share >= 0.5


# The most rows of a grouped tile: the picks on held experts are sorted by
# expert into tiles that each belong to one expert, and as many tiles are
# multiplied as the picks fill, so the work follows the load whatever its skew
# and no buffer can overflow. (XLA's ragged product on this chip walks 512-row
# tiles, one visit a group: at the 30-odd rows a held expert gets of a
# 512-token segment that is the dense form's cost again.) Where the program
# is lowered for a TPU one Pallas call a layer walks the tiles
# (ops/grouped_experts.py): an expert's three matrices are copied to fast
# memory block by block while the blocks before them multiply, the tiles'
# rows come out in sorted order and each token gathers its picks'. A loop of
# XLA's products, three and a scatter-add a turn, walks them elsewhere, and on
# the chip where the kernel refuses the call (`grouped_experts.refusal`:
# widths that do not tile, a program partitioned over devices, a layer most of
# whose experts are held elsewhere).
TILE = 128


def tile_rows(spec: ModelSpec, n: int) -> int:
    """Rows of a tile for a program of ``n`` rows: four times what even
    routing gives an expert, as a power of two from 16 (a bfloat16 tile's
    sublanes) to :data:`TILE`. The walk is bound by the experts' bytes, and
    a tile's empty rows are read, multiplied and written all the same: 64
    rows at 8 picks an expert took 1.70 ms a layer through 128-row tiles
    and 1.29 through 32-row ones as the loop, 1.28 and 1.10 as the kernel;
    an expert whose picks overflow its tile reads its matrices once more,
    which at four times the mean a hot expert seldom does (my chip run,
    PR 53)."""
    mean = n * spec.experts_per_token / spec.n_experts
    return min(TILE, max(16, 1 << max(0, math.ceil(math.log2(4 * mean)))))


# The counters' columns after the held experts' own: picks made (k a real
# token), picks on a held expert that no product computed (held picks less
# the rows the products say they took), and the rows the expert products
# multiplied: tiles x their rows on the grouped path, held experts x counted
# rows on the dense one. Held picks less dropped over tile rows is the share
# of the multiplied rows that were picks.
STATS = ("picks", "dropped", "tile_rows")
# Three more where the full layers select what they attend (models/latent.py),
# summed over those layers into the first row: the positions their queries
# attended, the positions their histories held, and the positions their
# attention products covered (a program's history bucket, or the tiles of it
# a block of queries stopped at).
DSA_STATS = ("keys_attended", "keys_in_history", "keys_multiplied")


def stats_of(spec: ModelSpec) -> tuple:
    return STATS + (DSA_STATS if spec.index_topk else ())


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KindKV:
    """One side (K or V) of a patterned spec's cache.

    ``full``: one ``[slots, K, max_seq, hd]`` array per full-attention layer
    (``[slots, K, hd, max_seq]`` where ``spec.kv_positions_minor``: heads
    narrower than the chip's lanes).
    ``window``: one ``[slots, K, ring, hd]`` ring per window layer.
    ``stats``: on the K side, int32 ``[expert layers, held + len(stats_of)]``,
    counted up by every program since the cache was made: picks per held
    expert, then :func:`stats_of`. None on the V side.
    ``index``: a latent spec's index keys, one ``[slots, max_seq,
    index_head_dim]`` per full layer. Such a spec (models/latent.py) has no
    K and V: its ``full`` and ``window`` are the cached latent rows,
    ``[slots, T, latent.row_width]``, all on the first side, and its second
    side is empty.
    ``conv``: on the K side, a short convolution's tail, one ``[slots,
    conv_taps - 1, d_model]`` per ``"C"`` layer: the row's last inputs,
    whatever its length.
    Where the spec has whole periods (``spec.periods``), ``full`` and
    ``conv`` hold the written-out layers' leaves first and then one leaf a
    slot of the period: that slot's tails stacked, ``[count, slots, ...]``;
    its K or V with the periods' heads side by side, ``[slots, count x K,
    ...]``, period ``r``'s at heads ``r K .. (r + 1) K``."""

    full: tuple
    window: tuple
    stats: Any = None
    index: tuple = ()
    conv: tuple = ()


def init_cache(spec: ModelSpec, batch: int, dtype=None):
    dt = jnp.dtype(dtype or spec.dtype)
    rows = (batch, spec.n_kv_heads)
    n_sparse = spec.n_layers - spec.first_dense
    stats = jnp.zeros((n_sparse, spec.held + len(stats_of(spec))), jnp.int32)
    if spec.kv_lora_rank:
        def rows_of(kind, t):
            width = latent.row_width(spec.latent(kind))
            return tuple(jnp.zeros((batch, t, width), dt)
                         for _ in spec.layers_of(kind))

        return (KindKV(rows_of("G", spec.max_seq), rows_of("L", spec.ring),
                       stats,
                       tuple(jnp.zeros((batch, spec.max_seq,
                                        spec.index_head_dim), dt)
                             for _ in spec.layers_of("G"))),
                KindKV((), ()))

    start, length, count = spec.periods

    def leaves(kind: str, shape: tuple) -> tuple:
        """A leaf a written-out layer of ``kind``, then one a slot of the
        period for its ``count`` layers: K and V heads side by side, tails
        stacked."""
        slot = ((shape[0], count * shape[1]) + shape[2:] if kind == "G"
                else (count,) + shape)
        return tuple(jnp.zeros(shape, dt)
                     for i in spec.layers_of(kind) if i < start) + tuple(
            jnp.zeros(slot, dt) for j in range(length)
            if spec.attn_kind(start + j) == kind)

    def side(stats, conv=()):
        return KindKV(
            leaves("G", rows + ((spec.head_dim, spec.max_seq)
                                if spec.kv_positions_minor else
                                (spec.max_seq, spec.head_dim))),
            leaves("L", rows + (spec.ring, spec.head_dim)),
            stats, (), conv)

    return side(stats, leaves(
        "C", (batch, spec.conv_taps - 1, spec.d_model))), side(None)


def layer_of(params, i: int):
    """Layer ``i``'s leaves without their leading dim of 1."""
    return jax.tree.map(lambda a: a[0], params["layers"][f"{i:02d}"])


# ---- the ring ---------------------------------------------------------------


def ring_positions(last, ring: int):
    """The absolute position each ring entry holds once positions 0..``last``
    have been written: ``[..., ring]``, negative where nothing was written
    (``last`` < 0 included)."""
    last = jnp.asarray(last)[..., None]
    return last - ((last - jnp.arange(ring)) % ring)


@jax.named_scope("attn.cache_write")
def write_from_start(cache, value, row, head0=0):
    """A prompt block's K or V (``value`` [B, K, T, ..]) into a full layer's
    K-major side from position 0 of row ``row`` (B = 1 with a slot), its
    heads from ``head0`` of the leaf's."""
    return lax.dynamic_update_slice(cache, value.astype(cache.dtype),
                                    (row, head0, 0, 0))


def _lanes(value, spec: ModelSpec, lanes: int = 128):
    """A prompt's keys or values ``[B, K, T, hd]`` as a full side takes them
    from position 0: where the positions are the side's lanes, in whole
    lanes (zeros behind a bucket under 128; no position behind a prompt is
    read before a decode step has written it). A narrower block written
    inside the scan over periods made the v5e compiler re-lay both whole
    sides around the program (3.2 GB of temporaries at a bucket of 32)."""
    value = _as_kept(value, spec)
    if not spec.kv_positions_minor:
        return value
    t = value.shape[3]
    short = min(-(-t // lanes) * lanes, spec.max_seq) - t
    return jnp.pad(value, ((0, 0),) * 3 + ((0, short),)) if short else value


def _as_kept(value, spec: ModelSpec):
    """New keys or values ``[B, K, T, hd]`` as a full layer's side keeps
    them."""
    return jnp.swapaxes(value, 2, 3) if spec.kv_positions_minor else value


def ring_write(ring_kv, value, offset, n_valid):
    """Write positions ``offset .. offset + n_valid - 1`` of ``value``
    ``[B, K, T, hd]`` into ``ring_kv`` ``[B, K, R, hd]``: entry ``j`` takes
    the newest of them that is ``j`` mod R and keeps what it holds where there
    is none, so padding past ``n_valid`` is never written and ``T`` may be
    larger or smaller than the ring."""
    r, t = ring_kv.shape[2], value.shape[2]
    held = ring_positions(offset + n_valid - 1, r)            # [B, R]
    take = held >= offset[:, None]
    src = jnp.clip(held - offset[:, None], 0, t - 1)
    new = jnp.take_along_axis(value, src[:, None, :, None], axis=2)
    return jnp.where(take[:, None, :, None], new.astype(ring_kv.dtype),
                     ring_kv)


def _head0(spec: ModelSpec, lead: tuple):
    """The first head of a layer's K or V in its leaf: 0, or ``r K`` where
    the leaf is a period's slot and the layer its ``r``-th (``lead``)."""
    return lead[0] * spec.n_kv_heads if lead else 0


class Slot:
    """A period's slot of stacked weight leaves, read as the layer at
    period ``r``: ``slot[name]`` is that layer's leaf, sliced where it lies.
    What walks the experts takes ``stacked`` and ``r`` themselves and reads
    one expert where it lies: a slice of every expert of the layer would be
    a copy of them (:func:`_experts_grouped`)."""

    def __init__(self, stacked: dict, r):
        self.stacked, self.r = stacked, r

    def __contains__(self, name) -> bool:
        return name in self.stacked

    def __getitem__(self, name):
        leaf = self.stacked[name]
        if isinstance(leaf, dict):
            return Slot(leaf, self.r)
        return lax.dynamic_index_in_dim(leaf, self.r, 0, keepdims=False)

    def get(self, name, default=None):
        return self[name] if self.stacked.get(name) is not None else default


def _rows_of(cache, slot, n: int):
    """``n`` rows of a ``[slots, K, T, hd]`` cache from row ``slot``."""
    return lax.dynamic_slice_in_dim(cache, slot, n, axis=0)


def _block_window_attn(q, k_new, v_new, ring_k, ring_v, pos, window: int):
    """Window attention of a block of new positions ``pos`` ``[B, T]`` over
    the ring as it stood before the block (positions up to ``pos[:, 0] - 1``)
    and the block itself."""
    r = ring_k.shape[2]
    held = ring_positions(pos[:, 0] - 1, r)                   # [B, R]
    key_pos = jnp.concatenate([held, pos], axis=1)[:, None, :]
    key_ok = jnp.concatenate(
        [held >= 0, jnp.ones(pos.shape, bool)], axis=1)[:, None, :]
    at = pos[:, :, None]
    keep = key_ok & (key_pos <= at) & (key_pos > at - window)  # [B, T, R+T]
    keys = jnp.concatenate([ring_k.astype(q.dtype), k_new], axis=2)
    vals = jnp.concatenate([ring_v.astype(q.dtype), v_new], axis=2)
    return attention(q, keys, vals, keep[:, None, None, :, :])


# ---- one layer's parts --------------------------------------------------------


def _rope(x, cos, sin, pos):
    """x ``[B, h, T, hd]`` rotated by ``pos`` ``[B, T]``: the pairs
    ``(x[i], x[i + hd/2])``, as ops.rotary.apply_rope."""
    c, s = cos[pos][:, None], sin[pos][:, None]
    d2 = x.shape[-1] // 2
    x1 = x[..., :d2].astype(jnp.float32)
    x2 = x[..., d2:].astype(jnp.float32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def _qkv(h, lyr, spec: ModelSpec, kind: str, cos, sin, pos):
    from quorum_tpu.models.transformer import _qkv as project

    q, k, v = project(h.astype(jnp.dtype(spec.dtype)), lyr, spec)
    with jax.named_scope("norm"):
        q = rmsnorm(q, lyr["q_norm_w"], spec.norm_eps)
        k = rmsnorm(k, lyr["k_norm_w"], spec.norm_eps)
    if kind == "L" or spec.rope_full:
        q, k = _rope(q, cos, sin, pos), _rope(k, cos, sin, pos)
    return q, k, v


def _sub(x, w, fn, spec: ModelSpec):
    """One residual sub-layer on the float32 stream ``x``: its output
    normalised before the add (``spec.post_norm``), or its input; ``fn``
    takes float32 and casts what it feeds to a matrix product."""
    from quorum_tpu.models.transformer import _norm

    if spec.post_norm:
        return x + _norm(fn(x).astype(jnp.float32), w.astype(jnp.float32),
                         None, spec)
    return x + fn(_norm(x, w.astype(jnp.float32), None, spec)).astype(
        jnp.float32)


def _head(params, spec: ModelSpec, x):
    """Logits of the float32 stream ``x``: the final norm in float32, the
    head's product in the spec's dtype."""
    from quorum_tpu.models import transformer as tr

    x = tr._final_norm(params, spec, x).astype(jnp.dtype(spec.dtype))
    return tr._unembed(params, spec, x)


def _route(x, lyr, spec: ModelSpec):
    """Scores and the pick, in float32: ``(weights [N, k], experts [N, k])``
    for x ``[N, D]``."""
    with jax.named_scope("moe.router"):
        logits = jnp.dot(x.astype(jnp.float32),
                         lyr["router"].astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        _, idx = lax.top_k(s + lyr["router_bias"].astype(jnp.float32),
                           spec.experts_per_token)
        w = jnp.take_along_axis(s, idx, axis=-1)
        return spec.router_scale * w / jnp.sum(w, -1, keepdims=True), idx


def _experts_dense(x, lyr, w_held):
    """Every held expert over every row; ``w_held`` ``[N, held]`` is zero
    where a row did not pick the expert."""
    from quorum_tpu.models.quant import qeinsum

    gate = qeinsum("nd,edf->enf", x, lyr["moe_w_gate"])
    up = qeinsum("nd,edf->enf", x, lyr["moe_w_up"])
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    out = qeinsum("enf,efd->end", h, lyr["moe_w_down"])
    return jnp.einsum("ne,end->nd", w_held.astype(out.dtype), out)


def _experts_grouped(x, lyr, spec: ModelSpec, w_pick, local, on, *,
                     sharded: bool = False, interpret: bool = False):
    """The picks that fall on held experts, sorted by expert into tiles of
    :func:`tile_rows` rows, an expert's picks filling whole tiles of its own;
    the tiles that hold any are multiplied by their expert's matrices and each
    token sums its picks' weighted rows: by ops/grouped_experts.py's kernel
    where the program is lowered for a TPU and :func:`grouped_experts.refusal`
    names nothing against it, by a loop of XLA's products elsewhere.
    ``w_pick`` / ``local`` / ``on`` ``[N, k]``: a pick's weight, its
    expert's index among the held, and whether it counts. Returns ``(out
    [N, D] float32, rows the products took, tiles multiplied)``."""
    n, d = x.shape
    k, held = spec.experts_per_token, spec.held
    p, t = n * k, tile_rows(spec, n)
    e_p = jnp.where(on, local, held).reshape(p)             # held: not here
    oh = jax.nn.one_hot(e_p, held, dtype=jnp.int32)            # [P, held]
    rank = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - 1,
                               jnp.minimum(e_p, held - 1)[:, None], 1)[:, 0]
    tiles_of = -(-jnp.sum(oh, axis=0) // t)                    # [held]
    ends = jnp.cumsum(tiles_of)
    max_tiles = max_tiles_of(spec, n)
    row = ((ends - tiles_of)[jnp.minimum(e_p, held - 1)] * t + rank)
    row = jnp.where(e_p < held, row, max_tiles * t)
    pick_of_row = jnp.full((max_tiles * t,), p, jnp.int32).at[row].set(
        jnp.arange(p, dtype=jnp.int32), mode="drop")
    tok_of_row = jnp.where(pick_of_row < p, pick_of_row // k, n)
    expert_of_tile = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(max_tiles), side="right"),
        held - 1)
    # the matrices [periods, held, ...] and the period read: a slot's stacked
    # leaves whole, a written-out layer's own as one period's
    stacked = isinstance(lyr, Slot)
    leaves = tuple(lyr.stacked[name] if stacked else lyr[name][None]
                   for name in ("moe_w_gate", "moe_w_up", "moe_w_down"))
    r = lyr.r if stacked else 0

    def loop(x, w_pick, leaves, r):
        """Tile after tile: three products and a scatter-add a turn."""
        w_of_row = jnp.where(
            pick_of_row < p, w_pick.reshape(p)[jnp.minimum(pick_of_row,
                                                           p - 1)], 0.0)

        def matrix(leaf, e):
            return lax.dynamic_slice(
                leaf, (r, e, 0, 0), (1, 1) + leaf.shape[2:]).reshape(
                leaf.shape[2:])

        def tile(i, carry):
            out, taken = carry
            e = expert_of_tile[i]
            tok = lax.dynamic_slice_in_dim(tok_of_row, i * t, t)
            w = lax.dynamic_slice_in_dim(w_of_row, i * t, t)
            rows = x[jnp.minimum(tok, n - 1)]
            gate = jnp.dot(rows, matrix(leaves[0], e),
                           preferred_element_type=jnp.float32)
            up = jnp.dot(rows, matrix(leaves[1], e),
                         preferred_element_type=jnp.float32)
            h = (jax.nn.silu(gate) * up).astype(x.dtype)
            y = jnp.dot(h, matrix(leaves[2], e),
                        preferred_element_type=jnp.float32)
            # an empty row's token is n: its add falls outside and is dropped
            return (out.at[tok].add(y * w[:, None], mode="drop"),
                    taken + jnp.sum(tok < n))

        return lax.fori_loop(0, ends[-1], tile,
                             (jnp.zeros((n, d), jnp.float32), jnp.int32(0)))

    def kernel(x, w_pick, leaves, r):
        """One call over the tiles, then each token's picks gathered."""
        y = grouped_experts.grouped_product(
            x[jnp.minimum(tok_of_row, n - 1)], expert_of_tile, ends[-1],
            *leaves, r, tile_rows=t, interpret=interpret)
        # a pick that is not here reads some row and counts as zero
        mine = y[jnp.minimum(row, max_tiles * t - 1)] * w_pick.reshape(
            p, 1)
        out = jnp.sum(jnp.where((e_p < held)[:, None], mine, 0.0).reshape(
            n, k, d), axis=1)
        # the picks among the rows of the tiles the grid walked
        return out, jnp.sum((tok_of_row < n) & (
            jnp.arange(max_tiles * t) < ends[-1] * t), dtype=jnp.int32)

    args = (x, w_pick, leaves, jnp.asarray(r, jnp.int32))
    if grouped_experts.refusal(t, d, leaves[0].shape[-1], x.dtype,
                               held_share=held / spec.n_experts,
                               sharded=sharded, interpret=interpret):
        return loop(*args) + (ends[-1],)
    if interpret:
        return kernel(*args) + (ends[-1],)
    return lax.platform_dependent(*args, tpu=kernel, default=loop) + (
        ends[-1],)


def log_moe_path(spec: ModelSpec, n: int, *, sharded: bool = False,
                 interpret: bool = False) -> None:
    """One line a traced program with expert layers: which form its ``n``
    rows' expert products take and, for the loop, why not the kernel."""
    t, d, f = tile_rows(spec, n), spec.d_model, spec.d_ff_expert
    refused = grouped_experts.refusal(
        t, d, f, spec.dtype, held_share=spec.held / spec.n_experts,
        sharded=sharded, interpret=interpret)
    path = ("dense" if dense_experts(spec, n) else
            "loop" if refused else "kernel")
    grouped_experts.log_moe_path(
        path, refused if path == "loop" else "", n, t, max_tiles_of(spec, n),
        spec.held, d, f, interpret=interpret)


def max_tiles_of(spec: ModelSpec, n: int) -> int:
    """The tiles ``n`` rows can fill: a token picks an expert once, so at
    most min(k, held) of its picks land here; every held expert may leave
    one tile part-filled."""
    return (n * min(spec.experts_per_token, spec.held) // tile_rows(spec, n)
            + spec.held)


def moe_layer(x, lyr, spec: ModelSpec, token_ok, dense: bool | None = None,
              *, sharded: bool = False, interpret: bool = False):
    """An expert layer on x ``[B, T, D]``: the routed part over the experts
    held here plus the shared expert. Returns ``(out, counts)``, the counts
    one row of ``KindKV.stats``; ``token_ok`` ``[B, T]`` keeps padding and
    idle rows out of the counts (and out of the tiles). ``dense`` None
    chooses by the rows. ``sharded``: the caller's program is partitioned
    over devices, which the grouped tiles' kernel cannot be; ``interpret``
    runs that kernel through the Pallas interpreter, for tests."""
    from quorum_tpu.models.transformer import _dense_mlp_core

    b, t, d = x.shape
    n = b * t
    ok = token_ok.reshape(n, 1)
    # the router reads the stream as it is (float32); the experts its cast
    w_pick, idx = _route(x.reshape(n, d), lyr, spec)
    x = x.astype(jnp.dtype(spec.dtype))
    xf = x.reshape(n, d)
    local = idx - spec.expert_first
    on = (local >= 0) & (local < spec.held) & ok
    with jax.named_scope("moe.experts"):
        one_hot = jax.nn.one_hot(jnp.where(on, local, spec.held), spec.held,
                                 dtype=jnp.float32)            # [N, k, held]
        per_expert = jnp.sum(one_hot, axis=(0, 1)).astype(jnp.int32)

        computed = held_picks = jnp.sum(per_expert)
        if dense if dense is not None else dense_experts(spec, n):
            w_held = jnp.einsum("nk,nke->ne", w_pick, one_hot)
            routed = _experts_dense(xf, lyr, w_held)
            multiplied = jnp.sum(ok) * spec.held
        else:
            routed, computed, tiles = _experts_grouped(
                xf, lyr, spec, w_pick, local, on, sharded=sharded,
                interpret=interpret)
            multiplied = tiles * tile_rows(spec, n)
    out = routed.astype(x.dtype).reshape(b, t, d)
    if spec.n_shared_experts:
        with jax.named_scope("moe.shared"):
            out = out + _dense_mlp_core(x, lyr["shared"], spec)
    counts = jnp.concatenate([
        per_expert,
        (jnp.sum(ok) * spec.experts_per_token).astype(jnp.int32)[None],
        (held_picks - computed)[None],
        multiplied.astype(jnp.int32)[None]])
    return out, counts


def _mlp(x, lyr, spec: ModelSpec, i: int, token_ok, counts: list, **how):
    from quorum_tpu.models.transformer import _dense_mlp

    if i < spec.first_dense:
        return _dense_mlp(x.astype(jnp.dtype(spec.dtype)), lyr, spec)
    out, c = moe_layer(x, lyr, spec, token_ok, **how)
    counts.append(c)
    return out


def _layers(params, spec: ModelSpec, x, cache_k: KindKV, cache_v: KindKV,
            attend, token_ok, keys=(), conv=None, **how):
    """The depth loop, written out, shared by the three served paths and the
    families: ``attend(h, lyr, kind, leaves) -> (attention output, leaves)``
    is what differs between them, ``leaves`` the layer's own of the cache:
    ``(K, V)``, or a latent spec's ``(rows, index keys)`` and ``(ring,)``;
    ``conv(h, lyr, leaf, lead) -> (sub-layer output, leaf)`` is a ``"C"``
    layer's whole first sub-layer over its tail leaf (:func:`_conv_of`);
    ``lead``, given to ``attend`` too where it is not empty, is the index of
    the layer in leaves that are a period's slot. ``x`` is the
    float32 stream; returns it with the two caches, the first side's
    counters counted up (``keys``: what a latent spec's ``attend`` leaves
    there of :data:`DSA_STATS`, a triple a full layer). ``how``: what
    :func:`moe_layer` is told of its caller (``sharded``, ``interpret``)."""
    from quorum_tpu.models import transformer as tr

    latent = bool(spec.kv_lora_rank)
    caches = ({"G": list(zip(cache_k.full, cache_k.index)),
               "L": [(ring,) for ring in cache_k.window]} if latent else
              {"G": list(zip(cache_k.full, cache_v.full)),
               "L": list(zip(cache_k.window, cache_v.window))})
    caches["C"] = list(cache_k.conv)
    if cache_k.conv:
        shortconv.log_conv_path(spec, x.shape)
    if spec.first_dense < spec.n_layers:
        log_moe_path(spec, x.shape[0] * x.shape[1], **how)
    start, length, n_periods = spec.periods
    seen = {"G": 0, "L": 0, "C": 0}

    def layer(x, lyr, i: int, j: int, lead: tuple, counts: list):
        """Layer ``i``, its cache leaves the ``j``-th of its kind (at index
        ``lead`` of them, where they are a period's slot)."""
        kind = spec.attn_kind(i)
        of_kind = caches[kind]

        def attn(h):
            if kind == "C":
                out, of_kind[j] = conv(h, lyr, of_kind[j], lead)
                return out
            out, of_kind[j] = (attend(h, lyr, kind, of_kind[j], lead) if lead
                               else attend(h, lyr, kind, of_kind[j]))
            return tr._attn_out(out, lyr, jnp.dtype(spec.dtype))

        x = _sub(x, lyr["attn_norm_w"], attn, spec)
        return _sub(x, lyr["mlp_norm_w"],
                    lambda h: _mlp(h, lyr, spec, i, token_ok, counts, **how),
                    spec)

    counts: list = []
    for i in range(start):
        kind = spec.attn_kind(i)
        x = layer(x, layer_of(params, i), i, seen[kind], (), counts)
        seen[kind] += 1
    moe = jnp.stack(counts) if counts else None
    if n_periods:
        slots = []  # a slot's (layer index, index among its kind's leaves)
        for j in range(length):
            kind = spec.attn_kind(start + j)
            slots.append((start + j, seen[kind]))
            seen[kind] += 1
        names = ("G", "C")

        def period(carry, r):
            x, kept = carry
            for kind, leaves in zip(names, kept):
                caches[kind] = list(leaves)
            counted: list = []
            for i, j in slots:
                lyr = Slot(params["layers"][period_key(i, n_periods)], r)
                x = layer(x, lyr, i, j, (r,), counted)
            return (x, tuple(tuple(caches[kind]) for kind in names)), \
                jnp.stack(counted)

        (x, kept), per = lax.scan(
            period, (x, tuple(tuple(caches[kind]) for kind in names)),
            jnp.arange(n_periods))
        for kind, leaves in zip(names, kept):
            caches[kind] = list(leaves)
        per = per.reshape((-1,) + per.shape[2:])      # layer order
        moe = per if moe is None else jnp.concatenate([moe, per])
    stats = cache_k.stats
    if moe is not None and stats is not None:
        if keys:
            moe = jnp.pad(moe, ((0, 0), (0, len(DSA_STATS)))).at[
                0, -len(DSA_STATS):].add(sum(keys).astype(jnp.int32))
        stats = stats + moe

    def of(kind: str, n: int):
        return tuple(leaves[n] for leaves in caches[kind])

    if latent:
        return (x, KindKV(of("G", 0), of("L", 0), stats, of("G", 1)),
                KindKV((), ()))
    return (x, KindKV(of("G", 0), of("L", 0), stats, (), tuple(caches["C"])),
            KindKV(of("G", 1), of("L", 1), None))


def period_key(i: int, count: int) -> str:
    """The key of ``params["layers"]`` that holds layer ``i`` and the same
    slot of the ``count - 1`` periods after it, stacked."""
    return f"{i:02d}x{count}"


def _conv_of(spec: ModelSpec, row, n_valid, fresh):
    """``_layers``' ``conv`` of one program: the short convolution over rows
    ``row ..`` of a layer's tail leaf, ``n_valid`` real positions a row, from
    zeros where ``fresh``."""
    def conv(h, lyr, leaf, lead=()):
        return shortconv.rows(h, lyr, spec, leaf, row, n_valid, fresh, lead)

    return conv


def _scope(kind: str):
    return jax.named_scope("attn.window" if kind == "L" else "attn.full")


# ---- the three served paths -------------------------------------------------


def prefill(params, spec: ModelSpec, tokens, lengths, cache_k, cache_v,
            slot=None, sharded: bool = False):
    """Single-shot admission: attention over the prompt itself, a full layer's
    keys and values written from position 0, a window layer's last ring's
    worth written into its ring, a short convolution's tail taken at the
    prompt's true end, from zeros. As transformer.prefill."""
    from quorum_tpu.models import transformer as tr

    b, t = tokens.shape
    row = slot if slot is not None else 0
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    x = tr._embed(params, spec, tokens, pos[0]).astype(jnp.float32)
    cos, sin = rope_cos_sin_for(spec)
    token_ok = pos < lengths[:, None]
    zero = jnp.zeros((b,), jnp.int32)
    keys: list = []

    def attend(h, lyr, kind, leaves, lead=()):
        ck, cv = leaves
        q, k, v = _qkv(h, lyr, spec, kind, cos, sin, pos)
        with jax.named_scope("attn.core"), _scope(kind):
            out = flash_prefill_attention(
                q, k, v, lengths,
                window=spec.sliding_window if kind == "L" else 0)
        with jax.named_scope("attn.cache_write"):
            if kind == "G":
                h0 = _head0(spec, lead)
                return out, (write_from_start(ck, _lanes(k, spec), row, h0),
                             write_from_start(cv, _lanes(v, spec), row, h0))
            return out, tuple(
                lax.dynamic_update_slice_in_dim(
                    c, ring_write(_rows_of(c, row, b), new, zero, lengths),
                    row, axis=0)
                for c, new in ((ck, k), (cv, v)))

    if spec.kv_lora_rank:
        attend = latent.prefill_attend(spec, pos, lengths, row, keys)
    x, cache_k, cache_v = _layers(
        params, spec, x, cache_k, cache_v, attend, token_ok, keys=keys,
        conv=_conv_of(spec, row, lengths, True), sharded=sharded)
    last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return _head(params, spec, last), cache_k, cache_v


def prefill_segment(params, spec: ModelSpec, tokens, offset, n_valid,
                    cache_k, cache_v, slot, history=None,
                    sharded: bool = False):
    """Chunked prefill of positions [offset, offset + T) of one slot. A full
    layer writes the segment and attends over the row's first ``history``
    positions; a window layer attends over its ring as the segments before
    left it (the up to window - 1 positions before ``offset``) and the
    segment itself, then writes the segment's real positions into the ring;
    a short convolution starts from the tail the segment before left (from
    zeros at offset 0) and leaves its own at the segment's last real
    position. As transformer.prefill_segment."""
    from quorum_tpu.models import transformer as tr

    _, t = tokens.shape
    hist = spec.max_seq if history is None else min(history, spec.max_seq)
    pos = (offset + jnp.arange(t))[None, :]
    x = tr._embed(params, spec, tokens, pos[0]).astype(jnp.float32)
    cos, sin = rope_cos_sin_for(spec)
    causal = (jnp.arange(hist)[None, :] <= pos[0][:, None])[None, None, None]
    token_ok = (jnp.arange(t) < n_valid)[None, :]
    off1, valid1 = offset[None], n_valid[None]
    keys: list = []

    def attend(h, lyr, kind, leaves, lead=()):
        ck, cv = leaves
        q, k, v = _qkv(h, lyr, spec, kind, cos, sin, pos)
        if kind == "G":
            lanes, h0 = spec.kv_positions_minor, _head0(spec, lead)
            at = (slot, h0, 0, offset) if lanes else (slot, h0, offset, 0)
            with jax.named_scope("attn.cache_write"):
                ck = lax.dynamic_update_slice(
                    ck, _as_kept(k, spec).astype(ck.dtype), at)
                cv = lax.dynamic_update_slice(
                    cv, _as_kept(v, spec).astype(cv.dtype), at)
            with jax.named_scope("attn.core"), _scope(kind):
                size = (1, spec.n_kv_heads) + (
                    (spec.head_dim, hist) if lanes else (hist, spec.head_dim))
                out = attention(
                    q, lax.dynamic_slice(ck, (slot, h0, 0, 0), size),
                    lax.dynamic_slice(cv, (slot, h0, 0, 0), size), causal,
                    positions_minor=lanes)
            return out, (ck, cv)
        rk, rv = _rows_of(ck, slot, 1), _rows_of(cv, slot, 1)
        with jax.named_scope("attn.core"), _scope(kind):
            out = _block_window_attn(q, k, v, rk, rv, pos,
                                     spec.sliding_window)
        with jax.named_scope("attn.cache_write"):
            return out, (
                lax.dynamic_update_slice_in_dim(
                    ck, ring_write(rk, k, off1, valid1), slot, axis=0),
                lax.dynamic_update_slice_in_dim(
                    cv, ring_write(rv, v, off1, valid1), slot, axis=0))

    if spec.kv_lora_rank:
        attend = latent.segment_attend(spec, pos, offset, n_valid, slot,
                                       hist, keys)
    return _layers(params, spec, x, cache_k, cache_v, attend, token_ok,
                   keys=keys,
                   conv=_conv_of(spec, slot, valid1, offset == 0),
                   sharded=sharded)[1:]


def decode_step_blocks(params, spec: ModelSpec, x, lengths, cache_k, cache_v,
                       write_mask=None, history=None, **how):
    """One position per row: a full layer writes at the row's position and
    reads its first ``history``; a window layer writes at position mod ring
    and reads the ring whole. As transformer.decode_step_blocks, but over
    ``params`` (the layers are not a stack)."""
    b = x.shape[0]
    cos, sin = rope_cos_sin_for(spec)
    allow = jnp.ones((b,), bool) if write_mask is None else write_mask
    pos = lengths[:, None]
    hist = (history if history is not None and history < spec.max_seq
            else spec.max_seq)

    def write(caches: tuple, news: tuple, at, lanes: bool = False, head0=0):
        # row by row through scalar starts, a layer's leaves in one loop (the
        # module docstring says why; a loop a side is 0.47 ms a step slower)
        def put(cache, new, r):
            start = (r, head0, 0, at[r]) if lanes else (r, head0, at[r], 0)
            held = lax.dynamic_slice(cache, start, (1,) + new.shape[1:])
            mine = lax.dynamic_slice_in_dim(new, r, 1, axis=0)
            return lax.dynamic_update_slice(
                cache, jnp.where(allow[r], mine, held), start)

        return lax.fori_loop(
            0, b, lambda r, kv: tuple(
                put(c, new, r) for c, new in zip(kv, news)), caches)

    held = ring_positions(lengths, spec.ring)                  # [B, R]
    ring_keep = ((held >= 0) & (held > pos - spec.sliding_window)
                 )[:, None, None, None, :]

    keys: list = []

    def window(cache, lanes: bool, lead: tuple):
        """A layer's first ``hist`` positions of a full side, every row."""
        if not lead:
            return lax.slice_in_dim(cache, 0, hist, axis=3 if lanes else 2)
        return lax.dynamic_slice(
            cache, (0, _head0(spec, lead), 0, 0),
            (b, spec.n_kv_heads) + ((spec.head_dim, hist) if lanes
                                    else (hist, spec.head_dim)))

    def attend(h, lyr, kind, leaves, lead=()):
        ck, cv = leaves
        q, k, v = _qkv(h, lyr, spec, kind, cos, sin, pos)
        at = lengths if kind == "G" else lengths % spec.ring
        lanes = kind == "G" and spec.kv_positions_minor
        if lanes:
            k, v = _as_kept(k, spec), _as_kept(v, spec)
        with jax.named_scope("attn.cache_write"):
            ck, cv = write((ck, cv),
                           (k.astype(ck.dtype), v.astype(cv.dtype)), at,
                           lanes, _head0(spec, lead))
        with jax.named_scope("attn.core"), _scope(kind):
            if kind == "G":
                out = decode_attention(
                    q, window(ck, lanes, lead), window(cv, lanes, lead),
                    lengths + 1, positions_minor=lanes)
            else:
                out = attention(q, ck, cv, ring_keep)
        return out, (ck, cv)

    if spec.kv_lora_rank:
        attend = latent.decode_attend(spec, lengths, allow, hist, write, keys)
    return _layers(params, spec, x, cache_k, cache_v, attend,
                   allow[:, None], keys=keys,
                   conv=_conv_of(spec, 0, allow.astype(jnp.int32), False),
                   **how)


def decode_step(params, spec: ModelSpec, token, lengths, cache_k, cache_v,
                write_mask=None, history=None, sharded: bool = False,
                interpret: bool = False):
    from quorum_tpu.models import transformer as tr

    x = tr.decode_token_embed(params, spec, token, lengths)
    x, cache_k, cache_v = decode_step_blocks(
        params, spec, x.astype(jnp.float32), lengths, cache_k, cache_v,
        write_mask=write_mask, history=history, sharded=sharded,
        interpret=interpret)
    return _head(params, spec, x[:, 0, :]), cache_k, cache_v
