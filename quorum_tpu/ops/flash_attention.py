"""Pallas flash attention for the prefill path (TPU kernel).

Blockwise causal attention with online softmax — O(BLOCK) VMEM instead of
materializing the [S, S] score matrix, the standard memory-bandwidth win for
long-prompt prefill on TPU:

  - grid = (batch, q_heads, q_blocks, kv_blocks); the kv dimension is
    innermost, so each program sees one [BLOCK_Q, hd] query tile and one
    [BLOCK_K, hd] K/V tile in VMEM — K/V is *streamed tile by tile*, never
    resident whole, so VMEM stays bounded at any sequence length;
  - GQA is pure index mapping — query head h reads kv head h//group — so no
    repeat_kv copies exist anywhere;
  - online-softmax state (m, l, acc) lives in f32 VMEM scratch carried across
    the kv grid steps (TPU grids run sequentially per core, so scratch
    persists); it is initialized at the first kv block of each query tile and
    the normalized output is written at the last;
  - KV tiles entirely above the causal diagonal skip their compute via
    ``pl.when`` (their DMA still happens — BlockSpec fetches are
    unconditional; acceptable: attention compute, not HBM traffic, dominates
    at the tile sizes used);
  - right-padding is masked via per-row ``lengths`` so bucketed batches share
    one compiled program (same contract as quorum_tpu.ops.attention).

`flash_prefill_attention` takes the XLA-native reference path
(quorum_tpu.ops.attention) off-TPU or for unsupported shapes, and says which
path it took and why: one INFO line per traced program
(:func:`log_attention_path`). Tests run the kernel in interpreter mode on
CPU against that reference; chip_smoke.py compiles it with Mosaic and
compares on the chip. The reference proxy
has no attention at all (models are remote HTTP calls,
/root/reference/src/quorum/oai_proxy.py:182-192) — this kernel exists for the
tpu:// backends' performance, not behavioral parity.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import logging
import os

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

logger = logging.getLogger(__name__)

NEG_INF = -1e30

_PROGRAM = contextvars.ContextVar("attention_program", default="direct")


@contextlib.contextmanager
def tracing_program(label: str):
    """Name the program being TRACED (``admit/64``, ``admit_members/256``)
    for the attention-path line. Entered inside the jitted function's Python
    body, which runs only at trace time, so it costs nothing per call."""
    token = _PROGRAM.set(label)
    try:
        yield
    finally:
        _PROGRAM.reset(token)


def traced_program() -> str:
    """The label :func:`tracing_program` gave the program being traced."""
    return _PROGRAM.get()


def log_attention_path(kernel: str, refusal: str, *, interpret: bool,
                       q_shape: tuple, kv_shape: tuple, block: int,
                       window: int,
                       accepted: str = "tpu backend, shapes tile") -> None:
    """The trace-time line that makes the kernel choice visible: ``pallas``
    or ``xla`` and why, per traced program. chip_smoke.py asserts from these
    lines that every single-shot prefill bucket ran the Mosaic-compiled
    kernel and that nothing ran in interpret mode."""
    logger.info(
        "attention-path program=%s kernel=%s path=%s interpret=%s q=%s kv=%s "
        "block=%d window=%d reason=%s",
        _PROGRAM.get(), kernel, "xla" if refusal else "pallas", interpret,
        "x".join(map(str, q_shape)), "x".join(map(str, kv_shape)), block,
        window, refusal or ("interpret mode asked for" if interpret
                            else accepted))

# 512-tiles measured ~22% faster than XLA's fused attention at 16k tokens on
# v5e (84.8 vs 108.8 ms; 128-tiles were on par) — grid overhead amortizes and
# the MXU gets deeper contractions. Tiles clamp to the sequence for short
# prompts.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _flash_kernel(
    len_ref,   # SMEM [B, 1] — valid lengths, indexed by program_id(0)
    q_ref,     # VMEM [1, 1, BQ, hd]
    k_ref,     # VMEM [1, 1, BK, hd] (tile of the matching KV head)
    v_ref,     # VMEM [1, 1, BK, hd]
    o_ref,     # VMEM [1, 1, BQ, hd]
    m_scr,     # VMEM [BQ, 1] f32 — running row max
    l_scr,     # VMEM [BQ, 1] f32 — running row normalizer
    acc_scr,   # VMEM [BQ, hd] f32 — running weighted-V accumulator
    *,
    scale: float,
    block_k: int,
    window: int,
):
    iq, ik = pl.program_id(2), pl.program_id(3)
    n_k = pl.num_programs(3)
    bq = q_ref.shape[2]
    length = len_ref[pl.program_id(0), 0]
    q_start = iq * bq
    k_start = ik * block_k

    @pl.when(ik == 0)
    def _init():
        m_scr[:, :] = jnp.full_like(m_scr[:, :], NEG_INF)
        l_scr[:, :] = jnp.zeros_like(l_scr[:, :])
        acc_scr[:, :] = jnp.zeros_like(acc_scr[:, :])

    live = k_start <= q_start + bq - 1  # tile intersects the causal region
    if window > 0:
        # …and is not entirely left of every query's sliding window —
        # recovers SWA's O(S·W) compute (the DMA still streams; masked
        # tiles skip the matmuls/softmax, the dominant cost at these tile
        # sizes).
        live = live & (k_start + block_k > q_start - window + 1)

    @pl.when(live)
    def _update():
        q = q_ref[0, 0, :, :].astype(jnp.float32) * scale
        k_blk = k_ref[0, 0, :, :].astype(jnp.float32)
        v_blk = v_ref[0, 0, :, :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [BQ, BK]
        row_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
        col_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        keep = (col_ids <= row_ids) & (col_ids < length)
        if window > 0:  # sliding-window attention (static; mistral)
            keep = keep & (col_ids > row_ids - window)
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

    @pl.when(ik == n_k - 1)
    def _finalize():
        # Fully-masked rows (all logits NEG_INF with m == NEG_INF) accumulate
        # p = exp(0) = 1 per column, so they produce a finite mean-of-V —
        # garbage but NaN-free, and never read downstream (right-padding).
        out = acc_scr[:, :] / jnp.maximum(l_scr[:, :], 1e-30)
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "interpret", "window")
)
def _flash_call(
    q, k, v, lengths, *, block_q: int, block_k: int, interpret: bool,
    window: int = 0,
):
    b, h, s_q, hd = q.shape
    n_kv = k.shape[1]
    s_kv = k.shape[2]
    group = h // n_kv
    grid = (b, h, s_q // block_q, s_kv // block_k)

    kernel = functools.partial(_flash_kernel, scale=hd**-0.5, block_k=block_k,
                               window=window)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # Scalars live 2D in SMEM; the whole [B, 1] array is one block
            # (Mosaic wants block dims divisible by (8, 128) OR equal to the
            # array dims).
            pl.BlockSpec((b, 1), lambda ib, ih, iq, ik: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, hd), lambda ib, ih, iq, ik: (ib, ih, iq, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(lengths.reshape(b, 1), q, k, v)


def flash_refusal(q_shape: tuple, k_shape: tuple, block_q: int,
                  block_k: int, tp: int = 1) -> str:
    """Why the kernel cannot take these shapes ('' = it can). ``tp`` > 1:
    the call runs per tensor-parallel shard, so both head counts must split
    over it (a replicated kv head would break the local q→kv head map)."""
    b, h, s_q, hd = q_shape
    n_kv, s_kv = k_shape[1], k_shape[2]
    if s_q % block_q or s_kv % block_k or s_q < block_q:
        return (f"sequence {s_q}x{s_kv} does not tile by "
                f"{block_q}x{block_k}")
    if h % n_kv:
        return f"{h} query heads do not group over {n_kv} kv heads"
    if hd % 8:
        return f"head_dim {hd} is not a multiple of 8"
    if h % tp or n_kv % tp:
        return f"heads {h}/{n_kv} do not split over tp={tp}"
    return ""


def flash_disabled() -> str:
    """Why the kernel is off in this process ('' = it is on): it runs on
    TPU unless QUORUM_TPU_FLASH=0; off-TPU the XLA reference path runs
    (interpret mode is for tests only — too slow to serve with)."""
    if os.environ.get("QUORUM_TPU_FLASH", "1") == "0":
        return "QUORUM_TPU_FLASH=0"
    if jax.default_backend() != "tpu":
        return f"platform is {jax.default_backend()}, not tpu"
    return ""


def flash_prefill_attention(
    q: jnp.ndarray,        # [B, H, S, hd]
    k: jnp.ndarray,        # [B, K, S_kv, hd]
    v: jnp.ndarray,
    lengths: jnp.ndarray,  # [B] valid prompt lengths
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    window: int = 0,
    tp_mesh: Mesh | None = None,
) -> jnp.ndarray:
    """Causal, length-masked prefill attention (``window`` > 0 adds the
    sliding-window constraint); flash kernel when supported, XLA-native
    reference otherwise. Returns [B, H, S, hd].

    ``tp_mesh``: the mesh of a tensor-parallel caller (heads sharded over
    its ``tp`` axis). A Mosaic kernel has no partitioning rule — XLA refuses
    to compile one inside a GSPMD-partitioned program — and heads are
    independent, so the kernel runs under ``shard_map`` over ``tp``, each
    shard on its own head slice."""
    # Clamp tiles to the sequence (buckets are powers of two, so they divide).
    block_q = min(block_q, q.shape[2])
    block_k = min(block_k, k.shape[2])
    tp = tp_mesh.shape["tp"] if tp_mesh is not None else 1
    refusal = ("" if interpret else flash_disabled()) or flash_refusal(
        q.shape, k.shape, block_q, block_k, tp)
    log_attention_path("flash_prefill", refusal, interpret=interpret,
                       q_shape=q.shape, kv_shape=k.shape, block=block_q,
                       window=window)
    if not refusal:
        call = functools.partial(
            _flash_call, block_q=block_q, block_k=block_k,
            interpret=interpret, window=window)
        if tp > 1:
            # "tp" is parallel.mesh.AXIS_TP (not imported: parallel/ imports
            # models/, which imports this module).
            heads = P(None, "tp", None, None)
            call = shard_map(call, mesh=tp_mesh,
                             in_specs=(heads, heads, heads, P()),
                             out_specs=heads, check_vma=False)
        return call(q, k, v, jnp.asarray(lengths, jnp.int32))
    from quorum_tpu.ops.attention import prefill_attention

    return prefill_attention(q, k, v, lengths, window=window)
