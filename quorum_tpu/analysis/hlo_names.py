"""Which part of a layer a device operation belongs to.

A profile names device operations as XLA numbered them (``fusion.277``,
``copy.128``), and a TPU capture's events carry the operation's HLO text
without its metadata. The metadata is in XLA's text of the optimized
module: every instruction there has ``metadata={op_name="jit(chunk)/…/
mlp/dot_general"}``, and the ``jax.named_scope`` parts of
``models/transformer.py``, ``models/patterned.py``, ``models/ssm.py`` and
``models/shortconv.py`` (:data:`PARTS`) are components of that path. This
module reads that text, from ``compiled.as_text()`` or from the files an
``--xla_dump_to`` run leaves behind::

    XLA_FLAGS=--xla_dump_to=/tmp/hlo python -m quorum_tpu.server.serve …
    python -m quorum_tpu.analysis.hlo_names /tmp/hlo jit_chunk fusion.277

prints one tab-separated line per operation: module file, operation,
opcode, layer part (``-`` outside every scope), ``op_name`` (empty on an
operation the compiler made itself). Without operation names it lists
every top-level operation of the matching modules.
Scopes are metadata and stay out of the persistent compile cache's key, so
dump from a start with an empty cache: a cached program is not compiled
again, and is not dumped. A program's variants number their fusions
differently; pick the module whose shapes match the profile's event.

Pure stdlib.
"""

from __future__ import annotations

import glob
import os
import re
import sys

# a spec with a layer pattern (models/patterned.py) adds: attention by layer
# kind, inside attn.core, and an expert layer's three parts
PATTERNED = ("attn.window", "attn.full", "moe.router", "moe.experts",
             "moe.shared")
# a latent spec (models/latent.py) adds: the two latents' projections, the
# indexer's products and scores, the selection's mask, attention in the
# latent space (a decode step's) or through keys and values made a tile at a
# time (a block of selecting queries'), the gate per head
LATENT = ("attn.latent_q", "attn.latent_kv", "attn.index", "attn.select",
          "attn.sparse", "attn.tiled", "attn.gate")
# a spec with a mixer beside attention (models/ssm.py) adds: the fused input
# projection, the depthwise convolution, the recurrence in chunks (a prefill
# program's) or as one step (a decode step's), the gated grouped norm, the
# output projection
MIXER = ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.step", "ssm.gate_norm",
         "ssm.out_proj")
# a short-convolution layer (models/shortconv.py) adds: the fused input
# projection and the input gate, the depthwise taps over the carried tail and
# the output gate, the output projection
SHORTCONV = ("conv.in_proj", "conv.taps", "conv.out_proj")
PARTS = ("embed", "norm", "attn.qkv", "attn.cache_write", "attn.core",
         "attn.out", "mlp", "lm_head", "sample") + PATTERNED + LATENT + MIXER \
    + SHORTCONV

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*?\s([a-z][a-z\-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')


def part_of(op_name: str) -> "str | None":
    """The innermost :data:`PARTS` scope on an ``op_name`` path."""
    for piece in reversed(op_name.split("/")):
        if piece in PARTS:
            return piece
    return None


def instructions(text: str) -> "dict[str, tuple[str, str]]":
    """``{operation: (opcode, op_name)}`` for every instruction of an
    optimized module's text outside its fused computations, parameters
    left out: the operations a device trace has one event for. ``op_name`` is empty where XLA gave
    none: an operation the compiler made itself, such as a layout copy."""
    out: dict[str, tuple[str, str]] = {}
    fused = False
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            fused = "fused_computation" in head.group(1)
        elif not fused:
            found = _INSTRUCTION.match(line)
            if found and found.group(2) != "parameter":
                op_name = _OP_NAME.search(line)
                out[found.group(1)] = (found.group(2),
                                       op_name.group(1) if op_name else "")
    return out


def main(argv: "list[str]") -> int:
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    dump, module, wanted = argv[0], "".join(argv[1:2]), set(argv[2:])
    pattern = os.path.join(dump, f"*{module}*after_optimizations.txt")
    for path in sorted(glob.glob(pattern)):
        with open(path, encoding="utf-8", errors="replace") as fh:
            table = instructions(fh.read())
        for name, (opcode, op_name) in table.items():
            if not wanted or name in wanted:
                print(os.path.basename(path), name, opcode,
                      part_of(op_name) or "-", op_name, sep="\t")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
