"""The checked-in program-key contract (analysis/compile_budget.json).

PR 5/6/7 each shipped bespoke tests pinning literal cache-key tuples
(the 3-tuple decode key, the "dfa"/"loop" tags, the disagg "hslice"/"hput"
pair). Those literals now live in ONE place — ``compile_budget.json`` — and
tests assert *families*: :func:`decode_families` / :func:`admit_families`
classify every key in an engine's program caches against the budget and
raise on anything unknown or shape-drifted, so adding a program family (or
silently changing a key tuple) fails every consuming test at once instead
of whichever literal pin happened to notice.
"""

from __future__ import annotations

import json
from functools import lru_cache
from pathlib import Path

BUDGET_PATH = Path(__file__).resolve().parent / "compile_budget.json"


@lru_cache(maxsize=1)
def load_budget() -> dict:
    with open(BUDGET_PATH) as f:
        return json.load(f)


class UnbudgetedProgramKey(AssertionError):
    """A program-cache key that matches no compile_budget.json family."""


def _check_len(cache: str, family: str, key) -> str:
    spec = load_budget()[cache][family]
    n = len(key) if isinstance(key, tuple) else 1
    if n != spec["key_len"]:
        raise UnbudgetedProgramKey(
            f"{cache} key {key!r} matches family {family!r} but has "
            f"length {n}, budget says {spec['key_len']} "
            f"(shape {spec['shape']}) — update compile_budget.json "
            "deliberately if the program key really changed")
    return family


def classify_decode_key(key) -> str:
    """Family name for one ``engine._decode_cache`` key; raises
    :class:`UnbudgetedProgramKey` on an unknown or shape-drifted key."""
    if isinstance(key, tuple) and key:
        if key[0] == "paged":
            # Paged-KV decode variants (kv_pages=1): the dense key with a
            # leading "paged" tag — table-gather attention can never share
            # a compiled program with its rectangular twin.
            rest = key[1:]
            if rest and rest[0] == "loop":
                fam = ("paged_loop_dfa" if len(rest) > 2 and rest[2] == "dfa"
                       else "paged_loop")
            elif rest and rest[0] == "dfa":
                fam = "paged_dfa"
            elif rest and all(isinstance(x, (int, bool)) for x in rest):
                fam = "paged_plain"
            else:
                raise UnbudgetedProgramKey(
                    f"decode_cache key {key!r} has the 'paged' tag but "
                    "matches no paged family")
            return _check_len("decode_cache", fam, key)
        if key[0] == "loop":
            fam = "loop_dfa" if len(key) > 2 and key[2] == "dfa" else "loop"
            return _check_len("decode_cache", fam, key)
        if key[0] == "dfa":
            return _check_len("decode_cache", "dfa", key)
        if all(isinstance(x, (int, bool)) for x in key):
            return _check_len("decode_cache", "plain", key)
    raise UnbudgetedProgramKey(
        f"decode_cache key {key!r} matches no compile_budget.json family")


def classify_admit_key(key) -> str:
    """Family name for one ``engine._admit_cache`` key; raises
    :class:`UnbudgetedProgramKey` on an unknown or shape-drifted key."""
    if isinstance(key, int) and not isinstance(key, bool):
        return _check_len("admit_cache", "single_shot", key)
    if isinstance(key, str):
        if key in ("register", "dfa_reset"):
            return _check_len("admit_cache", key, key)
    if isinstance(key, tuple) and key and isinstance(key[0], str):
        fam = key[0]
        if fam in load_budget()["admit_cache"]:
            return _check_len("admit_cache", fam, key)
    raise UnbudgetedProgramKey(
        f"admit_cache key {key!r} matches no compile_budget.json family")


def decode_families(decode_cache) -> set[str]:
    """Classify every key of an engine's ``_decode_cache``; the returned
    set is what tests assert against (presence/absence of families)."""
    return {classify_decode_key(k) for k in decode_cache}


def admit_families(admit_cache) -> set[str]:
    return {classify_admit_key(k) for k in admit_cache}
