"""Mesh construction over the TPU slice.

Axis conventions (used consistently across the framework):

  ``dp``  data parallel      — batch dimension of activations and KV caches
  ``pp``  pipeline parallel  — transformer layer *stages*; microbatches flow
                               stage→stage over ppermute (parallel/pipeline.py)
  ``sp``  sequence parallel  — sequence blocks for ring attention / long context
  ``tp``  tensor parallel    — attention heads, MLP hidden, vocab shards;
                               doubles as ``ep`` (expert parallel) for MoE —
                               experts are sharded over the same axis so dense
                               and MoE layers share one mesh.

All communication happens as XLA collectives over these axes (psum /
all_gather / ppermute inserted by GSPMD or written explicitly in shard_map),
riding ICI within a slice. There is no NCCL/MPI analog to port — the
reference's only communication backend is HTTP (SURVEY.md §5.8).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh

from quorum_tpu.devices import serving_devices

AXIS_DP = "dp"
AXIS_PP = "pp"
AXIS_SP = "sp"
AXIS_TP = "tp"
MESH_AXES = (AXIS_DP, AXIS_PP, AXIS_SP, AXIS_TP)


@dataclass(frozen=True)
class MeshConfig:
    """Requested mesh shape. Any axis left at 1 is effectively disabled."""

    dp: int = 1
    sp: int = 1
    tp: int = 1
    pp: int = 1

    @property
    def n_devices(self) -> int:
        return self.dp * self.pp * self.sp * self.tp


def make_mesh(cfg: MeshConfig | None = None, devices=None) -> Mesh:
    """Build a ``(dp, pp, sp, tp)`` mesh over ``devices`` (default: all local).

    The tp axis is placed innermost so tensor-parallel collectives (the
    highest-traffic ones: all-reduce after attention/MLP) map onto
    nearest-neighbour ICI links; pp sits next-outermost so stage hand-offs
    (one activation ppermute per microbatch tick) are also neighbor hops.
    """
    if devices is None:
        devices = serving_devices()
    cfg = cfg or MeshConfig(tp=len(devices))
    if cfg.n_devices > len(devices):
        raise ValueError(
            f"mesh {cfg} needs {cfg.n_devices} devices, have {len(devices)}"
        )
    arr = np.asarray(devices[: cfg.n_devices]).reshape(
        cfg.dp, cfg.pp, cfg.sp, cfg.tp)
    return Mesh(arr, MESH_AXES)


def best_mesh(n_devices: int | None = None, *, want_dp: bool = False) -> Mesh:
    """A sensible default mesh: all devices on tp, or split dp×tp if asked."""
    devices = serving_devices()
    n = len(devices) if n_devices is None else n_devices
    if want_dp and n % 2 == 0 and n > 1:
        return make_mesh(MeshConfig(dp=2, tp=n // 2), devices)
    return make_mesh(MeshConfig(tp=n), devices)


def single_device_mesh() -> Mesh:
    """A 1×1×1 mesh — lets all code paths be mesh-agnostic."""
    return make_mesh(MeshConfig(), serving_devices()[:1])


def parse_disagg(raw: str) -> tuple[int, int]:
    """``"4+4"`` → ``(n_prefill, n_decode)``. Strict: the knob is structural
    (it decides device-group placement for the engine's lifetime), so a typo
    must fail at config time, not silently colocate. URL query parsing
    decodes ``+`` to a space, so a bare space separator is accepted too
    (``disagg=4+4`` in config.yaml arrives here as ``"4 4"``)."""
    import re

    m = re.fullmatch(r"(\d+)[+ ](\d+)", str(raw).strip())
    if not m:
        raise ValueError(
            f"invalid disagg={raw!r} (expected P+D device counts, e.g. 4+4)")
    n_p, n_d = int(m.group(1)), int(m.group(2))
    if n_p < 1 or n_d < 1:
        raise ValueError(
            f"invalid disagg={raw!r} (both device groups need >= 1 device)")
    return n_p, n_d


def group_mesh_configs(n_prefill: int, n_decode: int, *,
                       tp: int | None = None,
                       sp: int = 1) -> tuple[MeshConfig, MeshConfig]:
    """Per-group mesh shapes for ``disagg=P+D`` with intra-group sharding.

    ``tp`` shards weights/KV within BOTH groups (``None`` = each group's
    whole device count, the pre-sharding default); ``sp`` scales the
    PREFILL group with sequence parallelism (100k+-token admission
    contexts, staging KV sharded over sequence). Every invalid
    combination fails here with the reason, at config time — never at
    first dispatch."""
    if sp < 1 or (tp is not None and tp < 1):
        raise ValueError(
            f"invalid sharding knobs tp={tp} sp={sp} beside disagg= "
            "(each must be >= 1)")
    tp_p = tp if tp is not None else n_prefill // sp
    tp_d = tp if tp is not None else n_decode
    if tp_p < 1 or sp * tp_p != n_prefill:
        raise ValueError(
            f"prefill group of disagg={n_prefill}+{n_decode} does not "
            f"factor as sp={sp} x tp={tp_p} ({sp * max(tp_p, 0)} != "
            f"{n_prefill} devices) — pick tp/sp whose product is the "
            "prefill group size, or resize the group")
    if tp_d != n_decode:
        raise ValueError(
            f"decode group of disagg={n_prefill}+{n_decode} does not "
            f"factor as tp={tp_d}: it is sharded by tp alone — pick tp "
            "equal to the decode group size, or resize the group")
    return MeshConfig(sp=sp, tp=tp_p), MeshConfig(tp=tp_d)


def disagg_meshes(n_prefill: int, n_decode: int, devices=None, *,
                  tp: int | None = None,
                  sp: int = 1) -> tuple[Mesh, Mesh]:
    """Two DISJOINT device-group meshes for disaggregated serving
    (``tpu://…&disagg=P+D``): the first ``n_prefill`` devices become the
    prefill group's mesh, the next ``n_decode`` the decode group's.

    MPMD-style placement ("Scaling Deep Learning Training with MPMD Pipeline
    Parallelism", PAPERS.md): admission prefill programs compile and run on
    the first mesh, the decode ring on the second, and a completed
    admission's KV prefix hands off device→device between them
    (quorum_tpu/cache/kv_transfer.py). With no sharding knobs tp is the
    only axis per group (the pre-sharding default — byte-for-byte the old
    layout); ``tp=``/``sp=`` pick the intra-group factorization
    (:func:`group_mesh_configs`). Either way the highest-traffic
    collectives stay nearest-neighbour inside each group, and the
    inter-group hop is the explicit KV handoff — never a GSPMD collective
    spanning both (the handoff reshards on the fly when the two groups'
    layouts differ)."""
    if devices is None:
        devices = serving_devices()
    need = n_prefill + n_decode
    if need > len(devices):
        raise ValueError(
            f"disagg={n_prefill}+{n_decode} needs {need} devices, have "
            f"{len(devices)}")
    pre_cfg, dec_cfg = group_mesh_configs(n_prefill, n_decode, tp=tp, sp=sp)
    prefill = make_mesh(pre_cfg, devices[:n_prefill])
    decode = make_mesh(dec_cfg, devices[n_prefill:n_prefill + n_decode])
    return prefill, decode
