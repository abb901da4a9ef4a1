# Parity with the reference's Makefile targets (install/run/dev/test/coverage/
# clean — /root/reference/Makefile:1-25), adapted to this environment: no uv,
# no uvicorn — the bundled h11 ASGI server serves the app.

.PHONY: install run dev test test-all coverage chip-smoke bench hostpath-bench prefix-bench router-bench dryrun metrics-check chaos-check qlint verify clean

install:
	pip install -e .

run:
	python -m quorum_tpu.server.serve --port 8000

dev:
	python -m quorum_tpu.server.serve --port 8001 --log-level DEBUG --watch

# Fast tier: server/strategy/protocol tests — the pre-commit loop.
# Engine-scale / compile-heavy / multi-process tests are marked
# @pytest.mark.slow; run everything with `make test-all`.
# The suite runs with the persistent XLA compile cache OFF
# (tests/conftest.py): cache-deserialized CPU executables can differ in
# float reassociation from in-process compiles of the same program, which
# flipped near-tie samples and made the engine determinism tests flaky
# (compile_cache.py's CPU caveat has the full story). Expect cold-compile
# times every run (~2 min fast tier, ~26 min test-all on the 1-core box);
# opt back in at your own risk with
# `make test QUORUM_TPU_COMPILE_CACHE=tests/.jax_compile_cache` exported.
# CI adds pytest-xdist (-n 4 --dist loadscope) on its multi-core runners.
# PYTEST_EXTRA lets CI (or an operator) add flags without re-encoding the
# invocation — e.g. `make test-all PYTEST_EXTRA="-n 4 --dist loadscope"`.
test:
	python -m pytest tests/ -x -q -m "not slow" $(PYTEST_EXTRA)

test-all:
	python -m pytest tests/ -x -q $(PYTEST_EXTRA)

coverage:
	@python -c "import pytest_cov" 2>/dev/null \
	  || (echo "pytest-cov is not installed (pip install pytest-cov)"; exit 1)
	python -m pytest tests/ --cov=quorum_tpu --cov-report=term-missing

# The serving path on one TPU, end to end, as the only process on the chip
# (kernels vs references, the shipped quorum through `serve`, a warm restart
# that must hit the compile cache, a full-depth mistral-7b). Exits non-zero
# without a TPU. `python chip_smoke.py --rehearsal` is the CPU dry run of
# the script itself at tiny presets.
chip-smoke:
	python chip_smoke.py

# A builder's tool, not the round's benchmark (ROADMAP S1/D6): phase children
# one at a time, the device named on every JSON line, non-zero exit when a
# phase fails. On a TPU it needs the chip to itself.
bench:
	python bench.py

# Tiny-model CPU microbench of the decode-dispatch host path: prints
# dispatches/request, blocking syncs/request, overrun tokens, the
# host-turnaround share the depth-K pipeline hides (PERF.md §2), and the
# prefill-interference A/B — streaming inter-token p50/p95/p99 under
# admission churn, colocated vs disagg=1+1 device groups with the
# device->device KV handoff live (docs/tpu_backends.md).
# tests/test_hostpath_bench.py runs the same entry points as fast smokes.
hostpath-bench:
	JAX_PLATFORMS=cpu python scripts/hostpath_bench.py

# Tiny-model CPU microbench of the tiered KV prefix store under slot
# churn (more conversations than slots, multi-turn): prints the prefill
# tokens the host store saves, restore latency, and pins output equality
# store-on vs store-off (docs/prefix_cache.md). tests/test_prefix_bench.py
# runs the same entry point as a fast smoke.
prefix-bench:
	JAX_PLATFORMS=cpu python scripts/prefix_bench.py

# Multi-replica router tier bench (scripts/router_bench.py, docs/
# scaling.md "Replica tier"): prefix-affinity routing vs a random baseline
# — fake (jax-free scripted replicas, N=2 and 4, seconds) and real legs
# (subprocess tiny-engine replicas with prefix_store=host under slot
# churn, N=2, minutes on CPU). Asserts affinity's prefix-hit rate strictly
# above random and per-conversation outputs token-for-token identical to
# single-replica serving. The fake leg's fast smoke
# (tests/test_router_bench.py) rides `make test` inside `make verify`.
router-bench:
	JAX_PLATFORMS=cpu python scripts/router_bench.py

# Promtool-style exposition lint (pure Python, no extra deps): spins the
# app over a tiny tpu:// backend, pulls the FULL /metrics output, and
# fails on malformed lines, duplicated TYPE lines, non-monotonic histogram
# buckets, or _sum/_count inconsistencies — covering every family incl.
# the constrained-decoding quorum_tpu_constrain_* set
# (docs/structured_output.md). See docs/observability.md.
metrics-check:
	python -m pytest tests/test_exposition.py -x -q $(PYTEST_EXTRA)

# Fault-injection chaos sweep (scripts/chaos_check.py, docs/robustness.md):
# injects each named fault site (quorum_tpu/faults.py) under concurrent
# load on a tiny CPU engine and asserts containment — only the affected
# requests error, the next request succeeds, deadlines answer within
# slack, the breaker opens under a failure storm and /health reflects it,
# and fault-free output stays pinned token-for-token. Exit 2 = hung
# (the script carries its own watchdog). The suite's slow-tier smoke over
# the same entry point is tests/test_robustness.py (chaos quick subset).
chaos-check:
	JAX_PLATFORMS=cpu python scripts/chaos_check.py

# Hot-path static analysis (quorum_tpu/analysis/qlint.py, pure stdlib ast,
# <10s — docs/static_analysis.md): device-sync taboo on the token critical
# path, jit-boundary recompile hazards, and _GUARDED_BY lock-discipline
# race checking over the engine's scheduler state. Fails on any finding
# not fixed, reason-annotated (# qlint: allow-*(<reason>)), or listed in
# analysis/qlint_baseline.json — whose entry count may only shrink
# (`--baseline-update` refuses to grow max_count; burn-down is deliberate).
qlint:
	python -m quorum_tpu.analysis.qlint

# The local verify path: static analysis + fast tier + exposition lint +
# chaos containment. qlint runs FIRST — it is the cheapest gate and its
# guarded-by/sync findings are exactly the bugs the later stages flake on.
verify: qlint test metrics-check chaos-check

# Multi-chip sharding validation on a virtual 8-device CPU mesh.
# dryrun_multichip re-execs itself with JAX_PLATFORMS=cpu, so it runs the
# same way whatever platform the calling shell is configured for.
dryrun:
	python -c "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8)"

clean:
	rm -rf build dist *.egg-info .pytest_cache .coverage logs
	find . -name __pycache__ -type d -exec rm -rf {} +
