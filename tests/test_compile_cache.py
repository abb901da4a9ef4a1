"""Persistent XLA compilation cache (quorum_tpu/compile_cache.py).

Placement rules first (pure functions of the environment, no jax state
touched): ``JAX_COMPILATION_CACHE_DIR`` set → this code sets no directory;
unset → one fixed path inside the checkout; the CPU, when asked for by name,
is opt-in. Then, in the slow tier, the end-to-end behaviour in child
processes: a fresh process serving the same model reloads its executables
instead of recompiling.
"""

import json
import os
import subprocess
import sys

import pytest

from quorum_tpu import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_outside_placement_wins_and_code_sets_nothing():
    env = {"JAX_COMPILATION_CACHE_DIR": "/mnt/cache"}
    assert compile_cache.cache_enabled(env)
    assert compile_cache.cache_dir_to_set(env) is None
    # ...not even when the repo's own knob names another directory
    env["QUORUM_TPU_COMPILE_CACHE"] = "/somewhere/else"
    assert compile_cache.cache_dir_to_set(env) is None


def test_unset_means_the_fixed_path_inside_the_checkout():
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.REPO_CACHE_DIR == want
    assert compile_cache.cache_dir_to_set({}) == want
    assert compile_cache.cache_dir_to_set({"JAX_PLATFORMS": "tpu"}) == want
    assert compile_cache.cache_dir_to_set(
        {"QUORUM_TPU_COMPILE_CACHE": "1", "JAX_PLATFORMS": "cpu"}) == want
    # an explicit directory is taken as given: nothing temporary, no pid,
    # no time is ever mixed into a cache path (the path is part of the key)
    assert compile_cache.cache_dir_to_set(
        {"QUORUM_TPU_COMPILE_CACHE": "/var/cache/q"}) == "/var/cache/q"
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cpu_by_name_is_opt_in_and_zero_disables():
    assert not compile_cache.cache_enabled({"JAX_PLATFORMS": "cpu"})
    assert compile_cache.cache_dir_to_set({"JAX_PLATFORMS": "cpu"}) is None
    # libtpu being installed decides nothing: only the environment does
    assert compile_cache.cache_enabled({})
    assert not compile_cache.cache_enabled(
        {"QUORUM_TPU_COMPILE_CACHE": "0"})
    assert compile_cache.cache_dir_to_set(
        {"QUORUM_TPU_COMPILE_CACHE": "0", "JAX_PLATFORMS": "tpu"}) is None


def test_only_one_place_sets_the_directory():
    """No code path but compile_cache.py hands jax a cache directory."""
    hits = []
    for root in ("quorum_tpu", "scripts"):
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            hits += [os.path.join(dirpath, f) for f in files
                     if f.endswith(".py")]
    hits += [os.path.join(REPO, f)
             for f in ("bench.py", "chip_smoke.py", "__graft_entry__.py")]
    setters = []
    for path in hits:
        with open(path) as f:
            if '"jax_compilation_cache_dir"' in f.read():
                setters.append(os.path.relpath(path, REPO))
    assert setters == ["quorum_tpu/compile_cache.py"]


_CHILD = """
import json, os, sys, time
t0 = time.time()
from quorum_tpu.models.model_config import resolve_spec
from quorum_tpu.engine.engine import InferenceEngine
from quorum_tpu.ops.sampling import SamplerConfig
spec = resolve_spec("gpt2-tiny", {"max_seq": "128"})
eng = InferenceEngine(spec, decode_chunk=4, n_slots=2)
toks = eng.generate([3, 4, 5], max_new_tokens=8,
                    sampler=SamplerConfig(temperature=0.8, top_p=0.9),
                    seed=1).token_ids
import jax
print(json.dumps({"tokens": toks, "wall": time.time() - t0,
                  "cache_dir": jax.config.jax_compilation_cache_dir}))
"""


def _run_child(cache_env: str, **extra) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    if cache_env:
        env["QUORUM_TPU_COMPILE_CACHE"] = cache_env
    else:
        env.pop("QUORUM_TPU_COMPILE_CACHE", None)
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_opt_in_cache_populates_and_reloads(tmp_path):
    cache = str(tmp_path / "xla")
    cold = _run_child(cache)
    assert cold["cache_dir"] == cache
    entries = os.listdir(cache)
    assert entries, "cold run wrote no cache entries"
    warm = _run_child(cache)
    # Same executables → byte-identical sampling; no new entries compiled.
    assert warm["tokens"] == cold["tokens"]
    assert sorted(os.listdir(cache)) == sorted(entries)


@pytest.mark.slow
def test_env_placed_cache_is_used_where_it_was_put(tmp_path):
    outside = str(tmp_path / "outside")
    got = _run_child(str(tmp_path / "ignored"),
                     JAX_COMPILATION_CACHE_DIR=outside)
    assert got["cache_dir"] == outside
    assert os.listdir(outside) and not os.path.exists(tmp_path / "ignored")


@pytest.mark.slow
def test_cpu_defaults_off_and_disable_knob_wins(tmp_path):
    # Without the explicit opt-in, a CPU run must not set up a cache
    # (XLA:CPU AOT reloads are host-feature-sensitive).
    assert not _run_child("")["cache_dir"]
    assert not _run_child("0")["cache_dir"]
