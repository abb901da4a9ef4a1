"""Pallas decode attention over the slot KV cache (TPU kernel).

The engine's decode step attends each co-batched row against
``cache[:, :, :history]`` where ``history`` is one power-of-two bucket ≥ the
LONGEST active row (models/transformer.decode_step). That bucketing already
removed the full-``max_seq`` scan (PERF.md §2), but every row still streams
the whole shared bucket: co-batch a 4k-context chat with a 100-token one and
the short row pays the long row's cache traffic. Decode is HBM-bandwidth-
bound, so those wasted bytes are wasted time.

This kernel makes cache reads PER-ROW exact:

  - grid = (batch, kv_heads, kv_tiles) with the per-row valid lengths as a
    scalar-prefetch argument, so the K/V BlockSpec index maps can clamp the
    tile index to each row's own last live tile. Pallas's pipeline skips the
    DMA when consecutive grid steps map a block to the same index — tiles
    past a row's length are never fetched from HBM, giving per-row early
    exit without data-dependent grid shapes;
  - compute for those clamped (repeated) tiles is skipped via ``pl.when``;
  - all G = H/K query heads of one KV head process together in one program
    ([G, hd] × [hd, BLOCK_K] contractions — tiny M dim, irrelevant: decode
    is bandwidth-bound, the MXU is idle either way);
  - online softmax (m, l, acc) in f32 VMEM scratch across kv tiles, exactly
    the flash_attention recipe (TPU grids run sequentially per core).

Functional contract: identical to ops.attention.decode_attention (the
masked-dense reference path) — pinned by tests/test_flash_decode.py in
interpret mode on CPU. Off by default (measured-first policy, PERF.md §5):
``QUORUM_TPU_FLASH_DECODE=1`` enables it on TPU; the win case is skewed
co-batched context lengths, and the first on-chip session should measure
before promoting the default. No reference equivalent: the reference proxy
has no attention at all (/root/reference/src/quorum/oai_proxy.py:182-192).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from quorum_tpu.ops.flash_attention import log_attention_path

NEG_INF = -1e30

# Small default tile: decode histories start at the 128 bucket, and the
# per-row DMA skip gets finer-grained with smaller tiles. 256×128×2B×2 (k+v)
# = 128 KiB of VMEM traffic per step — far below the ~16 MiB budget.
DEFAULT_BLOCK_K = 256


def _decode_kernel(
    len_ref,   # SMEM [B] scalar-prefetch — valid cache entries per row
    q_ref,     # VMEM [1, 1, G, hd]
    k_ref,     # VMEM [1, 1, BK, hd] (tile of this row's KV head)
    v_ref,     # VMEM [1, 1, BK, hd]
    o_ref,     # VMEM [1, 1, G, hd]
    m_scr,     # VMEM [G, 1] f32 — running row max
    l_scr,     # VMEM [G, 1] f32 — running row normalizer
    acc_scr,   # VMEM [G, hd] f32 — running weighted-V accumulator
    *,
    scale: float,
    block_k: int,
    window: int,
):
    ib, it = pl.program_id(0), pl.program_id(2)
    n_t = pl.num_programs(2)
    length = len_ref[ib]
    k_start = it * block_k

    @pl.when(it == 0)
    def _init():
        m_scr[:, :] = jnp.full_like(m_scr[:, :], NEG_INF)
        l_scr[:, :] = jnp.zeros_like(l_scr[:, :])
        acc_scr[:, :] = jnp.zeros_like(acc_scr[:, :])

    live = k_start < length  # tile holds live cache entries for THIS row
    if window > 0:
        # …within this row's sliding window (queries sit at length-1; keys
        # ≥ length-window are visible).
        live = live & (k_start + block_k > length - window)

    @pl.when(live)
    def _update():
        q = q_ref[0, 0, :, :].astype(jnp.float32) * scale      # [G, hd]
        k_blk = k_ref[0, 0, :, :].astype(jnp.float32)          # [BK, hd]
        v_blk = v_ref[0, 0, :, :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [G, BK]
        g = q.shape[0]
        col_ids = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (g, block_k), 1)
        keep = col_ids < length
        if window > 0:  # sliding-window attention (static; mistral)
            keep = keep & (col_ids >= length - window)
        logits = jnp.where(keep, logits, NEG_INF)

        m_prev = m_scr[:, :]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_scr[:, :] = m_new
        l_scr[:, :] = corr * l_scr[:, :] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:, :] = corr * acc_scr[:, :] + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32
        )

    @pl.when(it == n_t - 1)
    def _finalize():
        # length ≥ 1 always (the row holds at least the current token), so
        # l > 0 for live rows; the floor only guards dead padding rows.
        out = acc_scr[:, :] / jnp.maximum(l_scr[:, :], 1e-30)
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret", "window"))
def _decode_call(q, k_cache, v_cache, lengths, *, block_k: int,
                 interpret: bool, window: int = 0):
    b, h, _, hd = q.shape
    n_kv, t = k_cache.shape[1], k_cache.shape[2]
    group = h // n_kv
    n_tiles = t // block_k
    qg = q.reshape(b, n_kv, group, hd)

    def last_live_tile(ib, lens):
        # Last tile holding live entries for row ib; lengths ≥ 1 always.
        return (lens[ib] - 1) // block_k

    def first_live_tile(ib, lens):
        # With a sliding window, tiles entirely below length-window hold
        # nothing visible — clamp from below too, so their DMAs are also
        # skipped (repeated index → no copy).
        if window <= 0:
            return 0
        return jnp.maximum(lens[ib] - window, 0) // block_k

    def kv_index(ib, ik, it, lens):
        return (ib, ik,
                jnp.clip(it, first_live_tile(ib, lens),
                         last_live_tile(ib, lens)), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_kv, n_tiles),
        in_specs=[
            pl.BlockSpec((1, 1, group, hd),
                         lambda ib, ik, it, lens: (ib, ik, 0, 0)),
            # Clamp the tile index into the row's live range: repeated
            # indices on clamped grid steps skip the HBM→VMEM copy entirely
            # (compute for them is skipped by pl.when in the kernel).
            pl.BlockSpec((1, 1, block_k, hd), kv_index),
            pl.BlockSpec((1, 1, block_k, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd),
                               lambda ib, ik, it, lens: (ib, ik, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=hd**-0.5, block_k=block_k,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(b, h, 1, hd)


def flash_decode_refusal(q_shape: tuple, k_shape: tuple, block_k: int) -> str:
    """Why the kernel cannot take these shapes ('' = it can)."""
    b, h, s_q, hd = q_shape
    n_kv, t = k_shape[1], k_shape[2]
    if s_q != 1:
        return f"{s_q} query positions (decode attends one)"
    if h % n_kv:
        return f"{h} query heads do not group over {n_kv} kv heads"
    if t % block_k or t < block_k:
        return f"history {t} does not tile by {block_k}"
    if hd % 8:
        return f"head_dim {hd} is not a multiple of 8"
    return ""


def flash_decode_mode() -> str:
    """'' (off — the default), 'tpu' (QUORUM_TPU_FLASH_DECODE=1 on a real
    TPU), or 'interpret' (=interpret: the kernel anywhere via the Pallas
    interpreter — engine-level CPU tests only, far too slow to serve with).
    Off by default: the masked-dense path stays until the kernel is
    measured on real silicon (PERF.md §5's measured-first policy).

    Read at TRACE time: the engine caches its jitted decode programs, so
    flipping the env var inside a live process gives a mix of old and new
    programs. A/B runs must use separate processes (the bench's phase
    subprocesses already do)."""
    flag = os.environ.get("QUORUM_TPU_FLASH_DECODE", "0")
    if flag == "1" and jax.default_backend() == "tpu":
        return "tpu"
    if flag == "interpret":
        return "interpret"
    return ""


def parse_flash_decode(raw: str) -> str:
    """Validate a ``flash_decode=`` config value → "0" | "1" | "interpret".

    Strict at config time (a typo must not silently mean "off"): accepts
    the boolean spellings plus the Pallas-interpreter mode used by CPU
    engine tests."""
    val = str(raw).strip().lower()
    if val in ("0", "false", "no", "off", ""):
        return "0"
    if val in ("1", "true", "yes", "on"):
        return "1"
    if val == "interpret":
        return "interpret"
    raise ValueError(
        f"invalid flash_decode={raw!r} (use 0/1, true/false, yes/no, or "
        "interpret)")


def resolve_flash_decode(knob: str | None) -> str:
    """Effective flash-decode mode for ONE engine: '' (masked-dense),
    'tpu', or 'interpret'.

    Precedence: the ``QUORUM_TPU_FLASH_DECODE`` env var, when set, wins
    over the per-backend ``flash_decode=`` URL knob — the process-wide
    override the on-chip A/B scripts flip (they must beat a config file
    they don't control); otherwise the knob drives it, so two backends in
    one process can run the §5 flash A/B against each other. ``knob`` is
    None/'' when the URL never set it (falls back to the env gate's
    default-off). Resolved ONCE at engine construction — programs are
    cached per engine, so a mid-life flip could never take effect anyway
    (the same trace-time caveat as :func:`flash_decode_mode`)."""
    env = os.environ.get("QUORUM_TPU_FLASH_DECODE")
    if env is not None:
        # The env value takes the same spellings the URL knob does — an
        # operator's FLASH_DECODE=on must not silently measure the
        # masked-dense path in the kernel arm of an A/B. Unparseable
        # values are a LOUD off (never a crash at engine construction:
        # one typo'd env var must not brick every engine in the process).
        try:
            val = parse_flash_decode(env)
        except ValueError:
            import logging

            logging.getLogger(__name__).warning(
                "ignoring invalid QUORUM_TPU_FLASH_DECODE=%r "
                "(use 0/1 or interpret) — flash decode forced OFF", env)
            val = "0"
    else:
        val = knob or "0"
    if val == "1":
        return "tpu" if jax.default_backend() == "tpu" else ""
    if val == "interpret":
        return "interpret"
    return ""


def flash_decode_attention(
    q: jnp.ndarray,        # [B, H, 1, hd]
    k_cache: jnp.ndarray,  # [B, K, T, hd]
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,  # [B] or scalar: #valid cache entries (incl. current)
    *,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    window: int = 0,
) -> jnp.ndarray:
    """Per-row-exact decode attention; Pallas kernel when supported, the
    masked-dense reference (ops.attention.decode_attention) otherwise.
    Whether the kernel is wanted at all is the caller's decision
    (:func:`resolve_flash_decode` per engine, :func:`flash_decode_mode` for
    direct callers); this wrapper only refuses what cannot run here."""
    lengths = jnp.asarray(lengths)
    if lengths.ndim == 0:
        lengths = jnp.broadcast_to(lengths[None], (q.shape[0],))
    block_k = min(block_k, k_cache.shape[2])
    refusal = (("" if interpret or jax.default_backend() == "tpu"
                else f"platform is {jax.default_backend()}, not tpu")
               or flash_decode_refusal(q.shape, k_cache.shape, block_k))
    log_attention_path("flash_decode", refusal, interpret=interpret,
                       q_shape=q.shape, kv_shape=k_cache.shape, block=block_k,
                       window=window)
    if not refusal:
        return _decode_call(q, k_cache, v_cache, lengths,
                            block_k=block_k, interpret=interpret,
                            window=window)
    from quorum_tpu.ops.attention import decode_attention

    return decode_attention(q, k_cache, v_cache, lengths, window=window)
