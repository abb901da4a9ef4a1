"""The patterned decode step writes a row's K and V through scalar starts
(models/patterned.decode_step_blocks), not through a gather of the old rows.

The oracle is the write that was there before: ``jax.vmap`` of slice-old,
select, update-slice, whose per-row start makes the slice a gather. It is
kept here as the plain reference. No arithmetic moved, so a step's logits,
an 8-step ``decode_chunk``'s whole result and both ``KindKV`` sides must be
equal bit for bit.

The static test reads the v5e compiler's text (analysis/decode_static.py), as
tests/test_decode_in_place.py does for the dense step: the gather wanted its
operand positions-major, so each step copied both sides of every full layer
and of every ring; only that text shows it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from test_decode_in_place import COMPILE_LIMIT_S, greedy, within

from quorum_tpu.analysis import decode_static
from quorum_tpu.models import patterned
from quorum_tpu.models import transformer as tr
from quorum_tpu.models.init import init_params
from quorum_tpu.models.model_config import resolve_spec

TINY = resolve_spec("k-exaone-tiny", {"max_seq": "64"})  # ring 8, window 8
N_STEPS = 8


def oracle_step_blocks(params, spec, x, lengths, cache_k, cache_v,
                       write_mask=None, history=None, **how):
    """``patterned.decode_step_blocks`` as it was: the vmapped write (``how``:
    what the expert layers are told of the caller, which a CPU's loop does
    not read)."""
    b = x.shape[0]
    cos, sin = patterned.rope_cos_sin_for(spec)
    allow = jnp.ones((b,), bool) if write_mask is None else write_mask
    pos = lengths[:, None]
    hist = (history if history is not None and history < spec.max_seq
            else spec.max_seq)

    def write_row(cache_row, new_row, idx, ok):
        old = lax.dynamic_slice(cache_row, (0, idx, 0), new_row.shape)
        return lax.dynamic_update_slice(
            cache_row, jnp.where(ok, new_row, old), (0, idx, 0))

    write = jax.vmap(write_row)
    held = patterned.ring_positions(lengths, spec.ring)
    ring_keep = ((held >= 0) & (held > pos - spec.sliding_window)
                 )[:, None, None, None, :]

    def attend(h, lyr, kind, leaves):
        ck, cv = leaves
        q, k, v = patterned._qkv(h, lyr, spec, kind, cos, sin, pos)
        at = lengths if kind == "G" else lengths % spec.ring
        with jax.named_scope("attn.cache_write"):
            ck = write(ck, k.astype(ck.dtype), at, allow)
            cv = write(cv, v.astype(cv.dtype), at, allow)
        with jax.named_scope("attn.core"), patterned._scope(kind):
            if kind == "G":
                out = patterned.decode_attention(
                    q, lax.slice_in_dim(ck, 0, hist, axis=2),
                    lax.slice_in_dim(cv, 0, hist, axis=2), lengths + 1)
            else:
                out = patterned.attention(q, ck, cv, ring_keep)
        return out, (ck, cv)

    return patterned._layers(params, spec, x, cache_k, cache_v, attend,
                             allow[:, None])


def filled_cache(spec, rows, seed):
    """Both sides with something at every position of every layer and ring,
    position 0 included: a write that lands where it should not, or a masked
    row that does not keep what it held, changes a value."""
    rng = np.random.default_rng(seed)

    def fill(leaf):
        if leaf.dtype == jnp.int32:  # the K side's counters
            return jnp.asarray(rng.integers(0, 99, leaf.shape), jnp.int32)
        return jnp.asarray(rng.normal(size=leaf.shape), leaf.dtype)

    return jax.tree.map(fill, tr.init_cache(spec, rows))


@dataclasses.dataclass(frozen=True)
class Case:
    lengths: tuple = (5, 5, 5)
    live: tuple = (True, True, True)
    history: "int | None" = None
    budget: tuple = (64, 64, 64)


CASES = {
    "every_row_live": Case(),
    "masked_row_over_a_live_prompts_data": Case(
        lengths=(9, 21, 30), live=(True, False, True)),
    "row_at_position_0": Case(lengths=(0, 3, 17)),
    "rows_past_the_ring_wrap": Case(lengths=(8, 13, 39)),
    "row_at_max_seq_minus_1": Case(lengths=(63, 5, 40), budget=(1, 64, 64)),
    "history_bucket_under_max_seq": Case(lengths=(3, 11, 20), history=32),
    "row_finishes_mid_chunk": Case(lengths=(3, 17, 40), budget=(64, 3, 64)),
    "every_row_masked": Case(lengths=(4, 12, 33), live=(False,) * 3),
}


def run_case(case: Case, seed: int = 7):
    """One step's ``(logits, cache_k, cache_v)``, a masked row left at its own
    position (which holds data), and an 8-step chunk's whole result, through
    whatever ``patterned.decode_step_blocks`` is at the moment."""
    spec, n = TINY, len(case.lengths)
    params = init_params(spec, seed=3)
    ck, cv = filled_cache(spec, n, seed)
    token = jnp.arange(3, 3 + n, dtype=jnp.int32)
    lengths = jnp.asarray(case.lengths, jnp.int32)
    live = jnp.asarray(case.live)
    budget = jnp.asarray(case.budget, jnp.int32)
    eos = jnp.full((n,), -1, jnp.int32)

    def step(ck, cv):
        return tr.decode_step(params, spec, token, lengths, ck, cv,
                              write_mask=live, history=case.history)

    def chunk(ck, cv):
        return tr.decode_chunk(params, spec, N_STEPS, token, lengths, live,
                               budget, eos, ck, cv, greedy, (),
                               history=case.history)

    return jax.jit(step)(ck, cv), jax.jit(chunk)(ck, cv)


@pytest.mark.parametrize("name", sorted(CASES))
def test_row_by_row_write_equals_the_vmapped_one_bit_for_bit(
        name, monkeypatch):
    got = run_case(CASES[name])
    monkeypatch.setattr(patterned, "decode_step_blocks", oracle_step_blocks)
    want = run_case(CASES[name])
    flat_got, tree_got = jax.tree.flatten(got)
    flat_want, tree_want = jax.tree.flatten(want)
    assert tree_got == tree_want
    for g, w in zip(flat_got, flat_want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_the_cases_move_what_they_say():
    """The new write itself, not only its agreement with the oracle: a live
    row's K lands at its own position of a full layer and at position mod
    ring of a ring, a masked row's caches are left as they were."""
    case = CASES["masked_row_over_a_live_prompts_data"]
    before = filled_cache(TINY, 3, 7)[0]
    (_, after, _), chunk = run_case(case)
    full = lambda kv: np.asarray(kv.full[0])  # noqa: E731
    ring = lambda kv: np.asarray(kv.window[0])  # noqa: E731
    assert not np.array_equal(full(after)[0, :, 9], full(before)[0, :, 9])
    assert not np.array_equal(ring(after)[2, :, 30 % 8], ring(before)[2, :, 6])
    for got in (after, chunk[5]):
        for g, b in zip(got.full + got.window, before.full + before.window):
            np.testing.assert_array_equal(np.asarray(g)[1], np.asarray(b)[1])
    changed = full(chunk[5])[0] != full(before)[0]
    assert sorted(set(np.nonzero(changed)[1])) == list(range(9, 9 + N_STEPS))
    assert chunk[2].tolist() == [N_STEPS, 0, N_STEPS]


# ---- the v5e compiler's text -------------------------------------------------

# the cell's shape (k-exaone-ep8.reason) cut to one LLLG group: a dense layer
# and three expert layers, three rings and one full layer
CELL = {"n_layers": "4", "experts_held": "16", "vocab_size": "19200",
        "max_seq": "4096"}
ROWS = 32
TEMP_LIMIT_GB = 0.05  # the vmapped write held one full layer side twice: 0.27


@pytest.fixture(scope="module")
def v5e():
    return within(60.0, decode_static.v5e_device)


def test_no_copy_of_a_layer_side_or_a_ring_in_the_step_loop(
        v5e, monkeypatch):
    spec = resolve_spec("k-exaone-236b-a23b", CELL)
    sizes = set(decode_static.cache_sizes(spec, ROWS)[0])

    def program(step_blocks):
        """The step loop's copies and allocations of a full layer side's or
        a ring's size, and the program's temporaries in GB. Not counted: the
        ``copy-done`` of a ring that the compiler itself moves into fast
        memory for the write loop and attention and back, asynchronously and
        as it lies (6 with this write, 11 with the vmapped one)."""
        monkeypatch.setattr(patterned, "decode_step_blocks", step_blocks)
        compiled = within(COMPILE_LIMIT_S, decode_static.compile_decode_chunk,
                          spec, v5e, rows=ROWS, history=1024)
        moves = [row for row in decode_static.loop_body_ops(
                     compiled.as_text(), sizes)
                 if row[2] in ("copy", "AllocateBuffer")
                 or (row[2] == "fusion" and "copy" in row[1])]
        return moves, compiled.memory_analysis().temp_size_in_bytes / 1e9

    new = patterned.decode_step_blocks
    moves, temp = program(oracle_step_blocks)
    assert moves and temp > TEMP_LIMIT_GB, \
        "the reader finds nothing in the body it was written against"
    moves, temp = program(new)
    assert not moves and temp < TEMP_LIMIT_GB, (moves, temp)
