"""The attention sub-layer of a patterned spec with ``kv_lora_rank`` set
(family ``dots3_note``): latent attention of two geometries, a learned
selection on the full layers, a gate per head. models/patterned.py hands its
three served paths' attention over to the three functions at the end of this
file; the depth loop, the expert layer, the counters, the ring and the decode
step's cache write are patterned.py's own and the same for both families.

One position of a layer keeps (``spec.latent(kind)`` gives the sizes):

  - ``c_kv``: the key/value latent, RMS-normalised and multiplied by
    ``sqrt(d_model / kv_rank)``, and ``k_r``: one rotated key for all heads;
    cached together as one row ``[kv_rank + rope]``, zeros after them up to
    a multiple of 128 lanes (:func:`row_width`). A full layer keeps every
    position, a window layer a ring (patterned.ring_write);
  - on a full layer ``k_I``: the indexer's key ``[index_head_dim]``, after
    its LayerNorm and with its first ``rope`` dims rotated, in a cache of its
    own, since scoring reads it for the whole history and nothing else.

A query's score of a position is ``(q_n . W_kb c_kv + q_r . k_r) / sqrt(nope
+ rope)``. Two ways to compute it, chosen by the program from its static
shapes and never by an option:

  - **materialised**: keys and values made from the latents (``W_kb c_kv``,
    ``W_vb c_kv``): ``nope + rope + v`` multiply-adds a (query, position,
    head), and ``kv_rank (nope + v)`` a head to make a position's key and
    value, once for all the queries of a block. Cheapest where many queries
    read the same positions: a prefill block over a window or over a history
    the indexer keeps whole (ops.attention over all of it at once), and a
    block of selecting queries large enough to pay for the keys
    (:func:`tiles_pay`; :func:`tiled`: the selection is a mask the heads
    share, so the queries still share every position they multiply).
  - **absorbed**: the query taken into the latent space (``W_kb^T q_n``,
    beside ``q_r``), scored against the cached rows as they are, the
    attended latents summed and only that sum taken through ``W_vb``:
    ``row_width + kv_rank`` a (query, position, head), 3.6 times the
    materialised form's at the published sizes, and nothing to make. One
    cached row serves every head. What a decode step runs.

**The selection.** ``index_scores`` gives every (query, earlier position)
pair ``sum_j w_j relu(qI_j . kI)``: the products of bfloat16 operands
accumulated in float32 on the matrix unit, the ReLU, the weighting and the
sum over the index heads in float32. Of the positions at or before the query
the ``index_topk`` of largest score are kept (:func:`selected`: a mask over
the history the program reads, so the products are dense over what they
cover); where that history (a static extent) is no longer than
``index_topk``, no score is computed and every position is attended. Queries
are scored in blocks so that a block's scores over 16,384 positions stay
under 300 MB. The scores and the mask cover the program's whole history
bucket; :func:`tiled`'s products stop at the last tile a counted query can
see, the absorbed and the whole-history forms multiply the bucket.

Counted beside the expert counters (patterned.DSA_STATS): the positions the
full layers' queries attended, the positions their histories held, and the
positions their attention products covered.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax import lax

from quorum_tpu.models.model_config import ModelSpec
from quorum_tpu.ops import latent_flash
from quorum_tpu.ops.attention import NEG_INF, attention
from quorum_tpu.ops.flash_attention import log_attention_path
from quorum_tpu.ops.norms import layernorm, rmsnorm
from quorum_tpu.ops.rotary import rope_cos_sin

# query block x history positions whose index products are held at once
# (float32, times ``index_n_heads``: 64 heads x 2**20 x 4 B = 268 MB)
SCORE_BLOCK = 1 << 20
# positions whose keys and values :func:`tiled` makes at once. The kernel
# holds a tile's rows and one head's keys, values and logits in fast memory;
# a full layer's attention of 512 queries at 8,192 live positions of a 16,384
# bucket took 6.7 / 8.0 / 7.3 / 11.4 ms in tiles of 1,024 / 512 / 2,048 / 256
# (my chip run, PR 41: a shorter tile pays more grid steps, a longer one
# rounds the live history up further). XLA's loop makes them for all heads:
# 84 MB of keys and values and 268 MB of float32 logits a tile of 1,024.
KEY_TILE = 1024


def row_width(g) -> int:
    """Lanes of a cached row: the latent and the rotated key, then zeros up
    to a multiple of 128. With rows of 576 (4.5 x 128 lanes) the v5e compiler
    re-laid every full layer's cache positions-minor for the score product
    and back, 302 MB each way a layer and decode step (5.8 ms of 25.9; my
    chip run, PR 34); with 640 it reads the rows as they lie."""
    return -(-(g.kv_rank + g.rope) // 128) * 128


def tables(spec: ModelSpec) -> dict:
    """Rotary tables by layer kind: each has its own base and rotated size."""
    return {kind: rope_cos_sin(spec.max_seq, spec.latent(kind).rope,
                               spec.latent(kind).theta)
            for kind in set(spec.layer_pattern[:spec.n_layers])}


def _rope(x, cos_sin, pos):
    """x ``[B, T, ..., r]`` rotated by ``pos`` ``[B, T]``: the pairs
    ``(x[i], x[i + r/2])``, as ops.rotary.apply_rope."""
    cos, sin = cos_sin
    lead = pos.shape + (1,) * (x.ndim - 3)
    c = cos[pos].reshape(lead + cos.shape[-1:])
    s = sin[pos].reshape(lead + sin.shape[-1:])
    d2 = x.shape[-1] // 2
    x1 = x[..., :d2].astype(jnp.float32)
    x2 = x[..., d2:].astype(jnp.float32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def project(h, lyr, spec: ModelSpec, kind: str, rope, pos):
    """The normed stream ``h`` ``[B, T, D]`` to a layer's queries and the row
    it caches: ``(q_n [B, T, H, nope], q_r [B, T, H, rope] rotated, row
    [B, T, row_width], c_q [B, T, q_rank], h in the spec's dtype)``."""
    g = spec.latent(kind)
    dt = jnp.dtype(spec.dtype)
    h = h.astype(dt)
    b, t, d = h.shape
    with jax.named_scope("attn.latent_q"):
        c_q = rmsnorm(jnp.dot(h, lyr["w_qa"]), lyr["q_a_norm_w"],
                      spec.norm_eps) * jnp.asarray(
                          (d / g.q_rank) ** 0.5, dt)
        q = jnp.dot(c_q, lyr["w_qb"]).reshape(b, t, g.heads, g.nope + g.rope)
        q_n, q_r = q[..., :g.nope], _rope(q[..., g.nope:], rope, pos)
    with jax.named_scope("attn.latent_kv"):
        kv = jnp.dot(h, lyr["w_kva"])
        c_kv = rmsnorm(kv[..., :g.kv_rank], lyr["kv_a_norm_w"],
                       spec.norm_eps) * jnp.asarray(
                           (d / g.kv_rank) ** 0.5, dt)
        row = jnp.concatenate(
            [c_kv, _rope(kv[..., g.kv_rank:], rope, pos),
             jnp.zeros((b, t, row_width(g) - g.kv_rank - g.rope), dt)],
            axis=-1)
    return q_n, q_r, row, c_q, h


def index_parts(h, c_q, lyr, spec: ModelSpec, rope, pos):
    """The indexer's three: ``(qI [B, T, J, d] and kI [B, T, d], the first
    ``rope`` dims of each rotated, w [B, T, J] float32)``."""
    r = spec.qk_rope_head_dim
    b, t, _ = h.shape
    with jax.named_scope("attn.index"):
        q_i = jnp.dot(c_q, lyr["w_iq"]).reshape(
            b, t, spec.index_n_heads, spec.index_head_dim)
        k_i = layernorm(jnp.dot(h, lyr["w_ik"]), lyr["ik_norm_w"],
                        lyr["ik_norm_b"], spec.norm_eps)
        q_i = jnp.concatenate(
            [_rope(q_i[..., :r], rope, pos), q_i[..., r:]], axis=-1)
        k_i = jnp.concatenate(
            [_rope(k_i[..., :r], rope, pos), k_i[..., r:]], axis=-1)
        w = jnp.dot(h, lyr["w_iw"], preferred_element_type=jnp.float32)
    return q_i, k_i, w


def index_scores(q_i, w, k_i):
    """``[B, T, S]`` float32: every query's score of every cached index key
    ``k_i`` ``[B, S, d]``."""
    j, d = q_i.shape[-2:]
    with jax.named_scope("attn.index"):
        p = jnp.einsum("btjd,bsd->btjs", q_i, k_i.astype(q_i.dtype),
                       preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(p) * w[..., None], axis=2) * (
            j ** -0.5 * d ** -0.5)


def selected(scores, pos, k: int):
    """The ``k`` positions at or before ``pos`` ``[B, T]`` of largest score,
    the earlier first of equals, as a mask ``[B, T, S]``: what ``lax.top_k``
    keeps, without the sort. The ``k``-th largest score is found bit by bit
    (32 counts over the row, 0.35 ms for 64 x 16,384 on a v5e; ``lax.top_k``
    of 512 x 16,384 takes 4.8 ms and of 16 x 16,384 1.5 ms, PERF.md section
    6), then everything above it is kept and of its equals the earliest."""
    with jax.named_scope("attn.select"):
        seen = jnp.arange(scores.shape[-1]) <= pos[..., None]
        bits = lax.bitcast_convert_type(      # -0.0 is 0.0 here too
            jnp.where(scores == 0, 0.0, scores), jnp.uint32)
        # an unsigned key in the order of the floats; 0 for what is not seen
        key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
        key = jnp.where(seen, key, jnp.uint32(0))

        def bit(i, kth):
            higher = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
            enough = jnp.sum(key >= higher[..., None], axis=-1) >= k
            return jnp.where(enough, higher, kth)

        kth = lax.fori_loop(0, 32, bit,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
        above = key > kth[..., None]
        equal = key == kth[..., None]
        room = k - jnp.sum(above, axis=-1)
        # of the equals the earliest ``room``: the last of them is found the
        # same way, bit by bit (a cumulative sum over the positions lowers to
        # a windowed reduction that took 190 ms a layer at 8,192 positions)
        at = jnp.arange(scores.shape[-1])
        width = max(scores.shape[-1] - 1, 1).bit_length()

        def place(i, last):
            later = last | (1 << (width - 1 - i))
            fewer = jnp.sum(equal & (at < later[..., None]), axis=-1) < room
            return jnp.where(fewer, later, last)

        last = lax.fori_loop(0, width, place,
                             jnp.zeros(scores.shape[:-1], jnp.int32))
        return seen & (above | (equal & (at <= last[..., None])))


def _heads(w, h: int):
    """An up-projection ``[rank, H * n]`` as ``[rank, H, n]``."""
    return w.reshape(w.shape[0], h, w.shape[1] // h)


def absorbed(q_n, q_r, rows, keep, lyr, g):
    """Attention in the latent space over cached ``rows`` ``[B, S, C]``;
    ``keep`` broadcasts to ``[B, T, 1, S]``. Returns ``[B, H, T, v]``."""
    with jax.named_scope("attn.sparse"):
        q = jnp.einsum("bthn,chn->bthc", q_n, _heads(lyr["w_kb"], g.heads))
        q = jnp.concatenate(
            [q, q_r, jnp.zeros(q.shape[:-1] + (
                rows.shape[-1] - g.kv_rank - g.rope,), q.dtype)], axis=-1)
        logits = jnp.einsum("bthc,bsc->bths", q, rows,
                            preferred_element_type=jnp.float32) * (
                                (g.nope + g.rope) ** -0.5)
        logits = jnp.where(keep, logits, NEG_INF)
        # the barriers keep the row's largest and its sum reductions: taken
        # straight into the subtraction and the division the v5e compiler
        # made each a windowed reduction over the 8,192 positions of a
        # 128-query block, 190 ms a layer (my chip run, PR 34)
        p = jnp.exp(logits - lax.optimization_barrier(
            jnp.max(logits, axis=-1, keepdims=True)))
        p = (p / lax.optimization_barrier(
            jnp.sum(p, axis=-1, keepdims=True))).astype(rows.dtype)
        summed = jnp.einsum("bths,bsc->bthc", p, rows[..., :g.kv_rank])
        return jnp.einsum("bthc,chv->bhtv", summed,
                          _heads(lyr["w_vb"], g.heads))


def _made(rows, lyr, g):
    """Keys ``[B, H, S, nope + rope]`` and values ``[B, H, S, v]`` made from
    ``rows`` ``[B, S, C]``: the latent through ``W_kb`` and ``W_vb``, the
    rotated key beside every head's."""
    c_kv = rows[..., :g.kv_rank]
    k_r = rows[..., g.kv_rank:g.kv_rank + g.rope]
    k_n = jnp.einsum("bsc,chn->bhsn", c_kv, _heads(lyr["w_kb"], g.heads))
    v = jnp.einsum("bsc,chv->bhsv", c_kv, _heads(lyr["w_vb"], g.heads))
    k = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r[:, None], k_n.shape[:3] + k_r.shape[-1:])],
        axis=-1)
    return k, v


def materialised(q_n, q_r, rows, keep, lyr, g):
    """Attention over keys and values made from ``rows`` ``[B, S, C]``;
    ``keep`` broadcasts to ``[B, 1, 1, T, S]``. Returns ``[B, H, T, v]``."""
    k, v = _made(rows, lyr, g)
    q = jnp.concatenate([q_n, q_r], axis=-1).transpose(0, 2, 1, 3)
    return attention(q, k.astype(q.dtype), v.astype(q.dtype), keep)


def tiles_pay(g, t: int) -> bool:
    """Whether a block of ``t`` selecting queries multiplies less through
    keys and values made for it (:func:`tiled`) than in the latent space
    (:func:`absorbed`): what the queries save a position against what the
    position's key and value cost, a head. 158 queries at the published
    sizes."""
    saved = row_width(g) + g.kv_rank - (g.nope + g.rope + g.v)
    return t * saved >= g.kv_rank * (g.nope + g.v)


def tiled(q_n, q_r, rows, first, hist: int, keep, last, lyr, g,
          interpret: bool = False):
    """Attention of a block of queries through keys and values made
    ``KEY_TILE`` positions at a time, folded into a running softmax. Row
    ``b`` reads row ``first + b`` of ``rows`` ``[N, S, C]``, and of it the
    tiles up to position ``last[b]``, the last a counted query is at: the
    work stops after the furthest row's tile, and what lies behind
    ``last[b]`` in a tile is taken as zeros. ``keep`` ``[B, T, hist]``.
    Returns ``([B, H, T, v], the positions a row's products covered)``.

    One Pallas call where the program is lowered for a TPU and the shapes
    tile (ops/latent_flash.py: every product and exponential once), XLA's
    loop elsewhere; ``interpret`` runs the kernel through the Pallas
    interpreter, for tests."""
    b, t = q_n.shape[:2]
    dt = q_n.dtype
    tile = min(KEY_TILE, hist)
    n_tiles = jnp.clip((jnp.max(last) + tile) // tile, 1, -(-hist // tile))
    scale = (g.nope + g.rope) ** -0.5

    def loop(q_n, q_r, rows, first, keep, last, n_tiles, ups):
        q = jnp.concatenate([q_n, q_r], axis=-1).transpose(0, 2, 1, 3)

        def fold(i, carry):
            top, total, acc = carry
            # the last tile of a history that is no multiple of the tile
            # starts early, and leaves out what the tile before it held
            start = jnp.minimum(i * tile, hist - tile)
            at = start + jnp.arange(tile)
            live = (at >= i * tile) & (at <= last[:, None])     # [B, tile]
            # the rows at their 640 lanes, the latent and the key cut out of
            # the tile: a narrower slice of the leaf re-lays the cache
            # (:func:`row_width`)
            part = lax.dynamic_slice(rows, (first, start, 0),
                                     (b, tile, rows.shape[-1]))
            k, v = _made(jnp.where(live[..., None], part, 0).astype(dt),
                         ups, g)
            logits = jnp.einsum("bhtd,bhsd->bhts", q, k,
                                preferred_element_type=jnp.float32) * scale
            seen = lax.dynamic_slice_in_dim(keep, start, tile, axis=2) & (
                live[:, None])
            logits = jnp.where(seen[:, None], logits, NEG_INF)
            # the barriers: as in :func:`absorbed`
            new_top = jnp.maximum(top, lax.optimization_barrier(
                jnp.max(logits, axis=-1)))
            p = jnp.exp(logits - new_top[..., None])
            shrink = jnp.exp(top - new_top)
            total = total * shrink + lax.optimization_barrier(
                jnp.sum(p, axis=-1))
            acc = acc * shrink[..., None] + jnp.einsum(
                "bhts,bhsv->bhtv", p.astype(dt), v,
                preferred_element_type=jnp.float32)
            return new_top, total, acc

        # a query that kept nothing yet has seen NEG_INF everywhere: what it
        # summed of masked positions goes when its first kept one scales it
        # by exp(NEG_INF - logit), which is 0
        top, total, acc = lax.fori_loop(0, n_tiles, fold, (
            jnp.full((b, g.heads, t), NEG_INF, jnp.float32),
            jnp.zeros((b, g.heads, t), jnp.float32),
            jnp.zeros((b, g.heads, t, g.v), jnp.float32)))
        return (acc / total[..., None]).astype(dt)

    def kernel(q_n, q_r, rows, first, keep, last, n_tiles, ups):
        return latent_flash.tile_attention(
            q_n, q_r, rows, first, hist, keep, last, n_tiles, ups["w_kb"],
            ups["w_vb"], g, tile=tile, interpret=interpret)

    refusal = latent_flash.kernel_refusal(t, hist, tile, rows, g,
                                          interpret=interpret)
    log_attention_path(
        "latent_tiles", refusal, interpret=interpret, q_shape=q_n.shape,
        kv_shape=rows.shape, block=tile, window=0,
        accepted="pallas where lowered for a tpu, xla's loop elsewhere")
    args = (q_n, q_r, rows, jnp.asarray(first, jnp.int32), keep, last,
            n_tiles, {name: lyr[name] for name in ("w_kb", "w_vb")})
    with jax.named_scope("attn.tiled"):
        if refusal:
            out = loop(*args)
        elif interpret:
            out = kernel(*args)
        else:
            out = lax.platform_dependent(*args, tpu=kernel, default=loop)
    return out, jnp.minimum(n_tiles * tile, hist)


def gate(out, h, lyr):
    """``out`` ``[B, H, T, v]`` times a sigmoid per head of the layer's
    input."""
    with jax.named_scope("attn.gate"):
        g = jax.nn.sigmoid(jnp.dot(h, lyr["w_head_gate"],
                                   preferred_element_type=jnp.float32))
        return out * g.transpose(0, 2, 1)[..., None].astype(out.dtype)


def full_attention(q_n, q_r, q_i, w, cached, first, hist: int, pos, ok, lyr,
                   spec: ModelSpec, keys: list):
    """A full layer's attention of queries at ``pos`` ``[B, T]``. Row ``b``
    reads the first ``hist`` entries of row ``first + b`` of ``cached``, the
    layer's rows ``[N, S, C]`` and index keys ``[N, S, d]``, whose entry
    ``s`` is position ``s``. ``ok`` ``[B, T]`` marks the queries that count;
    ``keys`` takes this layer's three counts (patterned.DSA_STATS)."""
    g = spec.latent("G")
    b, t = pos.shape
    rows, k_i = cached
    ok = jnp.broadcast_to(ok, pos.shape)
    counted = jnp.sum(ok)
    in_history = jnp.sum(jnp.where(ok, pos + 1, 0))
    whole = hist * counted          # what a product over the bucket covers

    def mine(c):
        return lax.dynamic_slice(c, (first, 0, 0), (b, hist, c.shape[-1]))

    in_tiles = hist > spec.index_topk and tiles_pay(g, t)
    if not in_tiles:    # every other form reads the row's bucket whole
        rows = mine(rows)
    if hist <= spec.index_topk:
        # the indexer would keep everything: no score
        causal = jnp.arange(hist) <= pos[..., None]             # [B, T, S]
        if t > 1:
            out = materialised(q_n, q_r, rows, causal[:, None, None], lyr, g)
        else:
            out = absorbed(q_n, q_r, rows, causal[:, :, None], lyr, g)
        keys.append(jnp.stack([in_history, in_history, whole]))
        return out

    k_i = mine(k_i)
    tq = max(1, min(t, SCORE_BLOCK // hist))
    if t % tq:
        tq = t

    def blocks(fn, t_axes: tuple):
        """``fn`` of the queries' arrays ``[B, tq, ...]``, ``tq`` queries at
        a time; its outputs have their queries at ``t_axes``."""
        args = (q_n, q_r, q_i, w, pos)
        if tq == t:
            return fn(args)
        outs = lax.map(fn, tuple(
            jnp.moveaxis(x.reshape((b, t // tq, tq) + x.shape[2:]), 1, 0)
            for x in args))
        return tuple(
            jnp.moveaxis(o, 0, ax).reshape(
                o.shape[1:ax + 1] + (t,) + o.shape[ax + 2:])
            for o, ax in zip(outs, t_axes))

    def keep_of(q_i, w, pos):
        return selected(index_scores(q_i, w, k_i), pos, spec.index_topk)

    if in_tiles:
        (keep,) = blocks(lambda a: (keep_of(*a[2:]),), (1,))
        kept = jnp.sum(keep, axis=-1)
        last = jnp.max(jnp.where(ok, pos, -1), axis=1)
        out, extent = tiled(q_n, q_r, rows, first, hist, keep, last, lyr, g)
        covered = extent * counted
    else:
        def block(args):
            q_n, q_r, q_i, w, pos = args
            keep = keep_of(q_i, w, pos)
            return (absorbed(q_n, q_r, rows, keep[:, :, None], lyr, g),
                    jnp.sum(keep, axis=-1))

        out, kept = blocks(block, (2, 1))
        covered = whole
    keys.append(jnp.stack(
        [jnp.sum(jnp.where(ok, kept, 0)), in_history, covered]))
    return out


def window_keys(ring, first, window: int):
    """The ``window - 1`` positions before ``first`` ``[B]`` out of a ring
    ``[B, 1, R, C]``: ``(rows [B, window - 1, C], their positions
    [B, window - 1])``, negative where the row has none."""
    at = first[:, None] - (window - 1) + jnp.arange(window - 1)
    rows = jnp.take_along_axis(
        ring[:, 0], (at % ring.shape[2])[..., None], axis=1)
    return rows, at


# ---- the three served paths' attention --------------------------------------
#
# Each returns patterned._layers' ``attend(h, lyr, kind, leaves) -> (output
# [B, H, T, v], leaves)``: a full layer's leaves are ``(rows, index keys)``
# ``[slots, 1, max_seq, .]``, a window layer's ``(ring,)``
# ``[slots, 1, ring, .]``.


def _unit_heads(attend):
    """The cache keeps a latent spec's leaves as ``[slots, T, .]``;
    patterned's writes and the ring take ``[slots, K, T, .]``: a K of 1
    around ``attend``. (Kept with the K of 1, the decode step copied every
    full layer's rows, 302 MB each at 16 rows of 16,384: the compiler gave
    the write loop's carried buffer the layout slots-within-K, which for a K
    of 1 is the same bytes, and copied into it all the same.)"""
    def wrapped(h, lyr, kind, leaves):
        out, leaves = attend(h, lyr, kind, tuple(c[:, None] for c in leaves))
        return out, tuple(c[:, 0] for c in leaves)

    return wrapped


@contextlib.contextmanager
def _core(kind: str):
    """``attn.core`` and inside it the layer kind's scope, as patterned's own
    attention names its operations."""
    from quorum_tpu.models.patterned import _scope

    with jax.named_scope("attn.core"), _scope(kind):
        yield


def _window_block(q_n, q_r, rows, ring, pos, first, lyr, spec: ModelSpec):
    """A block of new positions ``pos`` ``[B, T]`` of a window layer over
    the ``window - 1`` positions before ``first`` ``[B]``, out of the ring
    as it stood, and the block's own ``rows``."""
    before, at = window_keys(ring, first, spec.sliding_window)
    key_pos = jnp.concatenate([at, pos], axis=1)[:, None, :]
    q_pos = pos[:, :, None]
    keep = ((key_pos >= 0) & (key_pos <= q_pos)
            & (key_pos > q_pos - spec.sliding_window))     # [B, T, W-1+T]
    return materialised(
        q_n, q_r, jnp.concatenate([before.astype(rows.dtype), rows], axis=1),
        keep[:, None, None], lyr, spec.latent("L"))


def prefill_attend(spec: ModelSpec, pos, lengths, row, keys: list):
    from quorum_tpu.models import patterned

    b, t = pos.shape
    rope = tables(spec)
    token_ok = pos < lengths[:, None]
    zero = jnp.zeros((b,), jnp.int32)
    at = jnp.arange(t)
    in_window = ((at[None, :] <= at[:, None])
                 & (at[None, :] > at[:, None] - spec.sliding_window))

    def attend(h, lyr, kind, leaves):
        q_n, q_r, rows, c_q, h = project(h, lyr, spec, kind, rope[kind], pos)
        if kind == "G":
            q_i, k_i, w = index_parts(h, c_q, lyr, spec, rope[kind], pos)
            with _core(kind):
                out = full_attention(q_n, q_r, q_i, w, (rows, k_i), 0, t,
                                     pos, token_ok, lyr, spec, keys)
            with jax.named_scope("attn.cache_write"):
                leaves = tuple(
                    patterned.write_from_start(c, new[:, None], row)
                    for c, new in zip(leaves, (rows, k_i)))
            return gate(out, h, lyr), leaves
        (ring,) = leaves
        with _core(kind):
            out = materialised(q_n, q_r, rows, in_window[None, None, None],
                               lyr, spec.latent(kind))
        with jax.named_scope("attn.cache_write"):
            ring = lax.dynamic_update_slice_in_dim(
                ring, patterned.ring_write(
                    patterned._rows_of(ring, row, b), rows[:, None], zero,
                    lengths), row, axis=0)
        return gate(out, h, lyr), (ring,)

    return _unit_heads(attend)


def segment_attend(spec: ModelSpec, pos, offset, n_valid, slot, hist: int,
                   keys: list):
    from quorum_tpu.models import patterned

    t = pos.shape[1]
    rope = tables(spec)
    token_ok = (jnp.arange(t) < n_valid)[None, :]
    off1, valid1 = offset[None], n_valid[None]

    def attend(h, lyr, kind, leaves):
        q_n, q_r, rows, c_q, h = project(h, lyr, spec, kind, rope[kind], pos)
        if kind == "G":
            q_i, k_i, w = index_parts(h, c_q, lyr, spec, rope[kind], pos)
            with jax.named_scope("attn.cache_write"):
                leaves = tuple(
                    lax.dynamic_update_slice(
                        c, new[:, None].astype(c.dtype), (slot, 0, offset, 0))
                    for c, new in zip(leaves, (rows, k_i)))
            with _core(kind):
                out = full_attention(
                    q_n, q_r, q_i, w, tuple(c[:, 0] for c in leaves), slot,
                    hist, pos, token_ok, lyr, spec, keys)
            return gate(out, h, lyr), leaves
        (ring,) = leaves
        mine = patterned._rows_of(ring, slot, 1)
        with _core(kind):
            out = _window_block(q_n, q_r, rows, mine, pos, off1, lyr, spec)
        with jax.named_scope("attn.cache_write"):
            ring = lax.dynamic_update_slice_in_dim(
                ring, patterned.ring_write(mine, rows[:, None], off1, valid1),
                slot, axis=0)
        return gate(out, h, lyr), (ring,)

    return _unit_heads(attend)


def decode_attend(spec: ModelSpec, lengths, allow, hist: int, write,
                  keys: list):
    """``write(leaves, news, at)`` is patterned's row-by-row cache write."""
    from quorum_tpu.models import patterned

    rope = tables(spec)
    pos = lengths[:, None]
    held = patterned.ring_positions(lengths, spec.ring)         # [B, R]
    ring_keep = ((held >= 0) & (held > pos - spec.sliding_window)
                 )[:, None, None, :]

    def attend(h, lyr, kind, leaves):
        q_n, q_r, rows, c_q, h = project(h, lyr, spec, kind, rope[kind], pos)
        if kind == "G":
            q_i, k_i, w = index_parts(h, c_q, lyr, spec, rope[kind], pos)
            with jax.named_scope("attn.cache_write"):
                leaves = write(leaves, tuple(
                    new[:, None].astype(c.dtype)
                    for c, new in zip(leaves, (rows, k_i))), lengths)
            with _core(kind):
                out = full_attention(
                    q_n, q_r, q_i, w, tuple(c[:, 0] for c in leaves), 0,
                    hist, pos, allow[:, None], lyr, spec, keys)
            return gate(out, h, lyr), leaves
        with jax.named_scope("attn.cache_write"):
            (ring,) = write(leaves, (rows[:, None].astype(leaves[0].dtype),),
                            lengths % spec.ring)
        with _core(kind):
            out = absorbed(q_n, q_r, ring[:, 0], ring_keep, lyr,
                           spec.latent(kind))
        return gate(out, h, lyr), (ring,)

    return _unit_heads(attend)
