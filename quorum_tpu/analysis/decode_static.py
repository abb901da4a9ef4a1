"""What a decode chunk moves of its cache, read from the v5e compiler's text
without a chip.

libtpu compiles for a chip that is described and not attached
(``jax.experimental.topologies``), so the optimized module of the engine's
decode chunk (``transformer.decode_chunk``: a step loop around
``decode_step``'s layer scan, the cache donated) can be read here: how many
bytes of temporaries it takes (``memory_analysis().temp_size_in_bytes``; a
second K and V shows there), and which operations inside the step loop
allocate or copy an array as large as a whole cache side. The text has the
names a device profile shows (``copy.128``), and ``hlo_names`` reads the
same text for the layer part of each::

    JAX_PLATFORMS=cpu python -m quorum_tpu.analysis.decode_static \
        mistral-7b 'quant=int8&max_seq=1024&slots=12'
    JAX_PLATFORMS=cpu python -m quorum_tpu.analysis.decode_static \
        mistral-7b 'n_layers=5&max_seq=1024&slots=8&members=3'
    JAX_PLATFORMS=cpu python -m quorum_tpu.analysis.decode_static \
        k-exaone-236b-a23b \
        'n_layers=8&experts_held=16&vocab_size=19200&max_seq=4096&slots=32'

prints ``temp``, then one line per operation of the program whose result is
a cache side or one layer's slab of it (for a spec with a ``layer_pattern``:
a full-attention layer's side or a ring, :func:`cache_sizes`) and per Pallas
call (``tpu_custom_call``) of a loop body: computation, operation, opcode,
shape, ``op_name``, then ``WHOLE-CACHE MOVE`` on those
:func:`whole_cache_moves` lists (a copy or allocation of a whole side, in the
step loop or at the chunk's entry and exit) and ``SLAB MOVE`` on a ``copy``,
``reshape`` or ``dynamic-slice`` of one layer's slab (:func:`slab_moves`: what
a read that re-lays its history window leaves, and a kernel handed a layout
it cannot take: that one's ``op_name`` was ``attn.cache_write/scatter``). An
in-place update of the carry has the cache's shape and no mark. For a spec
with a mixer (``falcon-h1-34b 'n_layers=6&max_seq=2048&slots=64'``) the
recurrent state's leaf and slab are listed too, and ``STATE READ`` marks
every operation of a loop body that takes the leaf (:func:`readers`: one
Pallas call a layer, ops/ssm_step.py; XLA's form is an update fusion and a
reduction that reads the slab a second time). Nothing runs,
so this gives no time; the scan's program does not depend on depth, and the
full-depth int8 member compiles in some ten seconds.

With ``members`` > 1 (the second example) the member-vmapped programs are all
compiled, over the stacked weight tree as the engine holds it
(``parallel.sharding.member_axes``: block leaves layers-major): the decode
chunk, the member admit at buckets 32 and 128 and the member segment
(:func:`compile_member_admit`, :func:`compile_member_segment`), each under a
``== <program>`` line with its ``temp``; and an operation that copies or
transposes an array with a stacked block matrix's dimensions is listed and
marked ``WEIGHT MOVE`` (:func:`weight_moves`: what a member ``vmap`` leaves
at a program's head when the layer scan's ``xs`` are not batched at axis 1;
6.5 GB a program at the second example's shape with the blocks held
``[M, L, …]``, none as they are held).
"""

from __future__ import annotations

import math
import os
import re
import sys
from urllib.parse import parse_qsl

from quorum_tpu.analysis.hlo_names import _COMPUTATION, _INSTRUCTION, _OP_NAME

# the arrays of a result, a tuple's (a Pallas call that also hands back the
# buffer it updated in place) or the one; layouts hold no "dtype[" of theirs
_RESULT = re.compile(r"=\s+(\(.*?\)|\S+)\s+[a-z][a-z\-]*\(")
_ARRAY = re.compile(r"([a-z]+\d*)\[([\d,]+)\]")
_OPERANDS = re.compile(r"\s[a-z][a-z\-]*\(([^()]*)\)")
_CALLED = re.compile(
    r"(?:body|condition|to_apply|calls|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
_MOVES = ("copy", "copy-done", "AllocateBuffer")
_SLAB_MOVES = ("copy", "copy-done", "reshape", "dynamic-slice")
_WEIGHT_MOVES = ("copy", "copy-start", "copy-done", "transpose")
_KERNEL = "tpu_custom_call"
_NO_DEVICE_OP = ("get-tuple-element", "bitcast", "parameter", "tuple")


def v5e_device():
    """One described v5e chip to compile for (raises where libtpu cannot
    describe the topology; ``v5e:1x1`` is refused, so one of 2x2)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]


def _stacked_weights(spec, members: int, quant: "str | None"):
    """The shapes of the weight tree an engine of ``members`` holds: a
    single member's, or the stacked tree in the engine's own layout."""
    import jax

    from quorum_tpu.models.init import init_params_from_key
    from quorum_tpu.models.quant import quantize_params
    from quorum_tpu.parallel.sharding import stack_members

    def weights():
        params = init_params_from_key(spec, jax.random.PRNGKey(0))
        params = quantize_params(params) if quant == "int8" else params
        return stack_members([params] * members) if members > 1 else params

    return jax.eval_shape(weights)


def _stacked_cache(spec, rows: int, members: int):
    """The shapes of the two cache sides, the member axis first."""
    import jax

    from quorum_tpu.models.transformer import init_cache

    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            ((members,) if members > 1 else ()) + s.shape, s.dtype),
        jax.eval_shape(lambda: init_cache(spec, rows)))


def _compile(fn, device, args, donate: tuple):
    import jax
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(device)
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        args)
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


def compile_decode_chunk(spec, device, *, rows: int, members: int = 1,
                         n_steps: int = 8, history: int | None = 512,
                         quant: str | None = None):
    """Compile ``decode_chunk`` (greedy sampling, cache donated) for
    ``device`` on shapes alone, as the engine runs it: ``members`` > 1 folds
    the rows member-major and vmaps ``decode_step`` over stacked weights and
    caches (``engine._stacked_rows_call``). Returns the compiled program:
    ``as_text()`` and ``memory_analysis()`` are what this module reads.

    ``quant="int8"`` compiles the CPU's f32 form of the int8 products unless
    ``QUORUM_TPU_QEINSUM_INT8=1`` is set (``quant._use_native_int8`` asks
    for the default backend, which is the CPU here)."""
    import jax
    import jax.numpy as jnp

    from quorum_tpu.engine.engine import _stacked_rows_call
    from quorum_tpu.models.transformer import decode_chunk, decode_step

    def greedy(logits, live, carry):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), carry, ()

    def chunk(params, token, lengths, live, budget, eos, ck, cv):
        model_call = None
        if members > 1:
            def model_call(ck, cv, tok, pos, wm):
                return _stacked_rows_call(
                    members, rows,
                    lambda p, k, v, t, ps, w: decode_step(
                        p, spec, t, ps, k, v, write_mask=w, history=history),
                    params, ck, cv, tok, pos, wm)
        return decode_chunk(params, spec, n_steps, token, lengths, live,
                            budget, eos, ck, cv, greedy, (), history=history,
                            model_call=model_call)

    n = rows * members
    ck, cv = _stacked_cache(spec, rows, members)
    args = (_stacked_weights(spec, members, quant),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.bool_),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            ck, cv)
    return _compile(chunk, device, args, donate=(6, 7))


def compile_member_admit(spec, device, *, rows: int, members: int,
                         bucket: int, quant: str | None = None):
    """Compile a stacked engine's coalesced admit for ``device`` on shapes
    alone: ``transformer.prefill`` of one prompt of ``bucket`` tokens a
    member into a shared slot row, under the member vmap and with a write
    gate a member, the cache donated, as ``engine._admit_fn_members`` writes
    it (less the first token's sampling, which touches no weight)."""
    import jax
    import jax.numpy as jnp

    from quorum_tpu.engine.engine import _member_vmap
    from quorum_tpu.models.transformer import prefill

    def admit(params, tokens, lengths, slot, enables, ck, cv):
        def one(p, tok, lens, k, v, gate):
            return prefill(p, spec, tok, lens, k, v, slot=slot,
                           write_gate=gate)

        return _member_vmap(one, params, tokens, lengths, ck, cv, enables)

    ck, cv = _stacked_cache(spec, rows, members)
    args = (_stacked_weights(spec, members, quant),
            jax.ShapeDtypeStruct((members, 1, bucket), jnp.int32),
            jax.ShapeDtypeStruct((members, 1), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((members,), jnp.bool_),
            ck, cv)
    return _compile(admit, device, args, donate=(5, 6))


def compile_member_segment(spec, device, *, rows: int, members: int,
                           bucket: int, history: int,
                           quant: str | None = None):
    """Compile a stacked engine's member-coalesced prompt segment
    (``engine._seg_fn_members``: ``transformer.prefill_segment`` under the
    member vmap, the cache donated) for ``device`` on shapes alone."""
    import jax
    import jax.numpy as jnp

    from quorum_tpu.engine.engine import _member_vmap
    from quorum_tpu.models.transformer import prefill_segment

    def seg(params, tokens, offsets, n_valids, slots, enables, ck, cv):
        def one(p, tok, off, nv, slot, en, k, v):
            return prefill_segment(p, spec, tok, off, nv, k, v, slot,
                                   history=history, write_gate=en)

        return _member_vmap(
            one, params, tokens, offsets, n_valids, slots, enables, ck, cv)

    ck, cv = _stacked_cache(spec, rows, members)
    ints = jax.ShapeDtypeStruct((members,), jnp.int32)
    args = (_stacked_weights(spec, members, quant),
            jax.ShapeDtypeStruct((members, 1, bucket), jnp.int32),
            ints, ints, ints,
            jax.ShapeDtypeStruct((members,), jnp.bool_),
            ck, cv)
    return _compile(seg, device, args, donate=(6, 7))


def _computations(text: str) -> "dict[str, list[str]]":
    out: dict[str, list[str]] = {}
    lines: list[str] = []
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            lines = out.setdefault(head.group(1), [])
        else:
            lines.append(line)
    return out


def _loop_computations(comps: "dict[str, list[str]]") -> "set[str]":
    """The ``while`` bodies of a module and the computations they call (a
    nested loop, a branch): what runs once a decode step, or once a layer
    of a step."""
    bodies = {m.group(1) for lines in comps.values() for line in lines
              for m in re.finditer(r"body=%?([\w.\-]+)", line)}
    reached, todo = set(), sorted(bodies)
    while todo:
        name = todo.pop()
        if name in reached or name not in comps or "fused_computation" in name:
            continue
        reached.add(name)
        for line in comps[name]:
            for one, many in _CALLED.findall(line):
                todo += [one] if one else re.findall(r"[\w.\-]+", many)
    return reached


def program_ops(text: str, sizes: "set[int]", *,
                loops_only: bool = False) -> "list[tuple]":
    """``(computation, operation, opcode, shape, op_name)`` for every
    instruction of an optimized module's text whose result has one of
    ``sizes`` elements, and for every Pallas call (``tpu_custom_call``)
    whatever its size; ``loops_only`` keeps the ``while`` bodies and what
    they call. Fused computations are left out: their instructions are no
    device operations of their own."""
    comps = _computations(text)
    names = _loop_computations(comps) if loops_only else {
        name for name in comps if "fused_computation" not in name}
    out = []
    for name in sorted(names):
        for line in comps[name]:
            found, arrays = _INSTRUCTION.match(line), _result_arrays(line)
            if not (found and arrays):
                continue
            opcode = _opcode(found.group(2), line)
            if opcode == _KERNEL or (
                    opcode not in _NO_DEVICE_OP and len(arrays) == 1
                    and math.prod(_dims(arrays[0])) in sizes):
                out.append(_row(name, found.group(1), opcode, arrays, line))
    return out


def _result_arrays(line: str) -> "list[str]":
    """``["f32[64,32]", ...]``: the arrays an instruction's result holds, a
    tuple's one by one (a scalar has no dimensions and is left out)."""
    result = _RESULT.search(line)
    return [f"{dtype}[{dims}]" for dtype, dims
            in _ARRAY.findall(result.group(1))] if result else []


def _opcode(opcode: str, line: str) -> str:
    """A custom call goes by its target (``tpu_custom_call``)."""
    if opcode == "custom-call":
        target = re.search(r'custom_call_target="([^"]*)"', line)
        return target.group(1) if target else opcode
    return opcode


def _row(computation: str, operation: str, opcode: str,
         arrays: "list[str]", line: str) -> tuple:
    op_name = _OP_NAME.search(line)
    return (computation, operation, opcode, "+".join(arrays),
            op_name.group(1) if op_name else "")


def state_leaf(spec, rows: int) -> str:
    """The recurrent state's carried leaf as the text writes it."""
    return (f"f32[{spec.n_layers},{rows},{spec.ssm_heads},"
            f"{spec.ssm_head_dim},{spec.ssm_state}]")


def readers(text: str, array: str) -> "list[tuple]":
    """The rows (as :func:`program_ops`') of the loop bodies' operations that
    take ``array`` (``"f32[6,64,32,128,256]"``) as an operand: what reads a
    carried leaf a step. A loop, a tuple and the like pass it on and are no
    readers; a fusion that slices a layer's slab out of it inside is one."""
    comps = _computations(text)
    out = []
    for name in sorted(_loop_computations(comps)):
        held = set()
        for line in comps[name]:
            found = _INSTRUCTION.match(line)
            if found and _result_arrays(line) == [array]:
                held.add(found.group(1))
        for line in comps[name]:
            found, taken = _INSTRUCTION.match(line), _OPERANDS.search(line)
            if not (found and taken) or found.group(2) in _NO_DEVICE_OP + (
                    "while", "call", "conditional"):
                continue
            if held & set(re.findall(r"%([\w.\-]+)", taken.group(1))):
                out.append(_row(name, found.group(1),
                                _opcode(found.group(2), line),
                                _result_arrays(line), line))
    return out


def loop_body_ops(text: str, sizes: "set[int]") -> "list[tuple]":
    """:func:`program_ops` of the loop bodies alone."""
    return program_ops(text, sizes, loops_only=True)


def _dims(shape: str) -> "list[int]":
    """``bf16[3,5,4096,1024]`` (a row's shape column) to its dimensions."""
    return [int(d) for d in shape[shape.index("[") + 1:-1].split(",")]


def _moves(rows: "list[tuple]", opcodes: tuple) -> "list[tuple]":
    # by opcode, or a fusion the compiler named for what it does
    return [row for row in rows if row[2] in opcodes or (
        row[2] == "fusion" and any(op in row[1] for op in opcodes))]


def whole_cache_moves(text: str, cache_elements: int, *,
                      loops_only: bool = True) -> "list[tuple]":
    """The rows of :func:`program_ops` that allocate or copy an array as
    large as a whole cache side: a ``copy``, a fusion the compiler named for
    its copy, an ``AllocateBuffer``; in the step loop, or with
    ``loops_only=False`` at the chunk's entry and exit too. An in-place
    update of the carried cache (a scatter, a dynamic-update-slice fusion)
    has the cache's shape too and moves only what it writes: not listed."""
    return _moves(program_ops(text, {cache_elements}, loops_only=loops_only),
                  _MOVES)


def slab_moves(text: str, slab_elements: int) -> "list[tuple]":
    """The rows of :func:`program_ops` that copy, reshape or slice out an
    array as large as one layer's slab of a cache side, anywhere in the
    program: a read that moves its history window before it contracts it."""
    return _moves(program_ops(text, {slab_elements}),
                  _SLAB_MOVES + ("AllocateBuffer",))


def weight_matrices(spec, members: int,
                    quant: "str | None" = None) -> "list[tuple]":
    """The dimensions of every stacked block matrix of a ``members`` engine
    (the leaves ``quant.QUANT_REDUCE_AXIS`` names under ``blocks``; an int8
    leaf's values, not its scales), as the engine holds them."""
    from quorum_tpu.models.quant import QUANT_REDUCE_AXIS, is_quantized

    blocks = _stacked_weights(spec, members, quant)["blocks"]
    leaves = [blocks[name] for name in QUANT_REDUCE_AXIS
              if blocks.get(name) is not None]
    return [tuple((leaf["q8"] if is_quantized(leaf) else leaf).shape)
            for leaf in leaves]


def weight_moves(text: str, matrices: "list[tuple]") -> "list[tuple]":
    """The rows of :func:`program_ops`, anywhere in the program, that copy or
    transpose an array with the dimensions of one of ``matrices`` in any
    order (:func:`weight_matrices`): a ``copy``, ``copy-start``,
    ``copy-done`` or ``transpose``, or a fusion the compiler named for one.
    A member ``vmap`` whose layer scan's ``xs`` are not batched at axis 1
    leaves one per block matrix at the head of the program."""
    wanted = {tuple(sorted(m)) for m in matrices}
    rows = program_ops(text, {math.prod(m) for m in matrices})
    return [row for row in _moves(rows, _WEIGHT_MOVES)
            if tuple(sorted(_dims(row[3]))) in wanted]


def kernel_calls(text: str) -> "list[tuple]":
    """The Pallas calls (``tpu_custom_call``) of the loop bodies."""
    return [row for row in program_ops(text, set(), loops_only=True)
            if row[2] == _KERNEL]


def cache_sizes(spec, rows: int, members: int = 1) -> "tuple[tuple, tuple]":
    """``(carried, slabs)``: the element counts of the arrays a decode chunk
    carries its cache in, and of the smaller pieces worth listing beside
    them. One stacked side and a layer's slab of it; for a spec with a
    ``layer_pattern`` (a cache per layer kind, models/patterned.py) one
    full-attention layer's side and one window layer's ring, no slab; for a
    spec with a mixer (``ssm_heads``, a ``StateKV`` a side) also the
    recurrent state's leaf ``[L, rows, H, P, N]`` and a layer's slab of
    it."""
    row = rows * spec.n_kv_heads * spec.head_dim
    if spec.layer_pattern:
        return (row * spec.max_seq, row * spec.ring), ()
    side = members * spec.n_layers * row * spec.max_seq
    carried = (side,)
    if spec.ssm_heads:
        carried += (spec.n_layers * rows * spec.ssm_width * spec.ssm_state,)
    return carried, tuple(size // spec.n_layers for size in carried)


def main(argv: "list[str]") -> int:
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    os.environ.setdefault("QUORUM_TPU_QEINSUM_INT8", "1")
    from quorum_tpu.models.model_config import resolve_spec

    options = dict(parse_qsl("".join(argv[1:2])))
    spec = resolve_spec(argv[0], options)
    rows, members = int(options.get("slots", 8)), int(options.get("members", 1))
    shape = dict(rows=rows, members=members, quant=options.get("quant"))
    device = v5e_device()
    programs = {"decode chunk": compile_decode_chunk(
        spec, device, history=int(options.get("history", 512)), **shape)}
    if members > 1:
        for bucket in (32, 128):
            programs[f"member admit, bucket {bucket}"] = compile_member_admit(
                spec, device, bucket=bucket, **shape)
        programs["member segment, bucket 512"] = compile_member_segment(
            spec, device, bucket=min(512, spec.max_seq),
            history=spec.max_seq, **shape)
    carried, slabs = cache_sizes(spec, rows, members)
    matrices = weight_matrices(spec, members, shape["quant"]) \
        if members > 1 else []
    for name, compiled in programs.items():
        text = compiled.as_text()
        temp = compiled.memory_analysis().temp_size_in_bytes
        if members > 1:
            print(f"== {name}")
        print(f"temp\t{temp / 1e9:.4f} GB")
        whole = [row for size in carried
                 for row in whole_cache_moves(text, size, loops_only=False)]
        slab = [row for size in slabs for row in slab_moves(text, size)]
        weight = weight_moves(text, matrices)
        for row in program_ops(text, set(carried + slabs)) + [
                row for row in weight if row not in whole + slab]:
            print(*row, "WHOLE-CACHE MOVE" if row in whole
                  else "SLAB MOVE" if row in slab
                  else "WEIGHT MOVE" if row in weight else "", sep="\t")
        if spec.ssm_heads:  # one Pallas call a layer body, and no other
            for row in readers(text, state_leaf(spec, rows)):
                print(*row, "STATE READ", sep="\t")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
