"""Ulysses sequence parallelism: all-to-all attention over the ``sp`` axis.

The second standard SP strategy beside ring attention
(parallel/ring_attention.py). Where the ring keeps the sequence sharded and
circulates K/V blocks device-to-device (sp ppermutes per layer), Ulysses
re-shards ONCE per attention: a single packed all-to-all (q/k/v
interleaved per sp-group along the head axis) converts sequence-sharded
activations into head-sharded ones — each device holds the FULL sequence
for its H/sp head slice — attention runs entirely locally, and one
all-to-all converts back. TWO collective launches per layer, total bytes
O(B·S·(D + 2·K·hd)/sp), rather than sp dependent ring hops.

Trade-offs vs the ring (why both exist):

  - Ulysses holds full-length K/V for its head slice — per-device attention
    memory is O(S·K/sp · hd), not O(S/sp). Fine for prefill at serving
    context lengths; the ring remains the answer when even one head's
    full-length K/V cannot fit.
  - Ulysses needs the HEAD counts divisible by sp (H/tp-shard and K must
    split over sp); GQA models with few KV heads cap sp at K. The ring has
    no head constraint.
  - Because each device sees the whole sequence, windowed (mistral) specs
    work unchanged — the ring rejects them (it would widen the receptive
    field).

The reference proxy has no sequence handling at all
(/root/reference/src/quorum/oai_proxy.py:185-192); north-star
functionality, not behavioral parity.
"""

from __future__ import annotations

from functools import partial

import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from quorum_tpu.ops.attention import prefill_attention
from quorum_tpu.parallel.mesh import AXIS_DP, AXIS_SP, AXIS_TP
from quorum_tpu.parallel.ring_attention import gqa_axis_selection


def _ulysses_local(q, k, v, lengths, *, axis: str, sp_size: int, window: int):
    """Per-device body: seq-sharded in → ONE packed all-to-all → full-seq
    attention on a head slice → one all-to-all back to seq-sharded out.

    q/k/v share one inbound transfer: ``all_to_all(split_axis=1)`` hands
    destination device d the d-th sp-slice of the packed head axis, so the
    packing interleaves PER-GROUP — group d carries (q-heads d·hq/sp…,
    k-heads d·hk/sp…, v-heads …) contiguously and every split boundary
    stays pure. The head-divisibility preconditions are enforced by
    ``ulysses_supported`` before shard_map dispatches here."""
    b, hq, s_loc, hd = q.shape
    hk = k.shape[1]
    gq, gk = hq // sp_size, hk // sp_size

    def grouped(x, g):
        # [B, sp·g, s, hd] → [B, sp, g, s, hd]
        return x.reshape(b, sp_size, g, s_loc, hd)

    packed = jnp.concatenate(
        [grouped(q, gq), grouped(k, gk), grouped(v, gk)], axis=2
    ).reshape(b, hq + 2 * hk, s_loc, hd)
    ph = lax.all_to_all(packed, axis, split_axis=1, concat_axis=2, tiled=True)
    # ph [B, gq+2·gk, S, hd]: this device's q/k/v head slices, full sequence.
    qh = ph[:, :gq]
    kh = ph[:, gq:gq + gk]
    vh = ph[:, gq + gk:]
    out = prefill_attention(qh, kh, vh, lengths, window=window)
    # [B, hq/sp, S, hd] → [B, hq, s_loc, hd]: split seq, gather heads.
    return lax.all_to_all(out, axis, split_axis=2, concat_axis=1, tiled=True)


def ulysses_supported(h: int, n_kv: int, mesh: Mesh, sp: str = AXIS_SP) -> bool:
    """Statically checkable Ulysses requirement: the per-device head counts
    must split over sp. The engine uses this to FAIL FAST at construction —
    a silent dense fallback at serving time would materialize full
    replicated attention at exactly the context lengths sp exists for.
    (Sequence-length divisibility stays a per-request dynamic fallback.)"""
    _, haxis, kaxis = gqa_axis_selection(1, h, n_kv, mesh)
    tp_div = mesh.shape[AXIS_TP] if haxis else 1
    sp_size = mesh.shape[sp]
    return (h // tp_div) % sp_size == 0 and (n_kv // tp_div) % sp_size == 0


def ulysses_prefill_attention(
    q: jnp.ndarray,        # [B, H, S, hd] (global view)
    k: jnp.ndarray,        # [B, K, S, hd]
    v: jnp.ndarray,
    lengths: jnp.ndarray,  # [B]
    mesh: Mesh,
    *,
    sp: str = AXIS_SP,
    window: int = 0,
) -> jnp.ndarray:
    """Causal, length-masked GQA attention, sequence sharded over ``sp``
    via head↔sequence all-to-alls. Falls back to the dense replicated path
    when the shapes don't divide (short admission buckets, few heads)."""
    sp_size = mesh.shape[sp]
    b, h, s, _ = q.shape
    n_kv = k.shape[1]
    baxis, haxis, kaxis = gqa_axis_selection(b, h, n_kv, mesh)
    if (sp_size == 1 or s % sp_size != 0
            or not ulysses_supported(h, n_kv, mesh, sp)):
        return prefill_attention(q, k, v, lengths, window=window)
    qs = P(baxis, haxis, sp, None)
    ks = P(baxis, kaxis, sp, None)
    fn = shard_map(
        partial(_ulysses_local, axis=sp, sp_size=sp_size, window=window),
        mesh=mesh,
        in_specs=(qs, ks, ks, P(baxis)),
        out_specs=qs,
    )
    return fn(q, k, v, lengths)
