"""Worker process for the TRUE two-process SERVING test.

NOT a test module (no ``test_`` prefix): spawned twice by
``test_distributed.test_two_process_serving``, once per simulated host.
Round 3 proved a real cross-process *train* step; this is the serving
analog (VERDICT r3 next-round item 9): each worker joins the jax
distributed runtime, builds the SAME ``tpu://`` backend over a global
dp×tp mesh that spans both processes (dp is the DCN axis — the slot/batch
dimension of the KV cache shards across hosts, weights shard over tp
within each host), and serves the SAME request SPMD-style through the real
engine+backend stack. This mirrors production multi-host serving, where a
front-end broadcasts each request to every host in the replica and the
hosts execute identical dispatch sequences; the spawning test plays the
front-end. Both hosts must emit byte-identical completions.
"""

import asyncio
import json
import os
import sys

# Script execution puts tests/ on sys.path, not the repo root.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")


def main() -> None:
    from quorum_tpu.backends.tpu_backend import TpuBackend
    from quorum_tpu.config import BackendSpec
    from quorum_tpu.parallel.distributed import initialize

    assert initialize() is True, "expected to join a 2-process group"
    assert jax.process_count() == 2
    assert jax.device_count() == 4 and len(jax.local_devices()) == 2

    # dp=2 spans the process (DCN) boundary — make_mesh reshapes the global
    # device list dp-major, so each host's 2 local devices form one tp=2
    # group. slots=2 with dp=2 shards the KV-cache batch axis across hosts.
    be = TpuBackend.from_spec(BackendSpec(
        name="M",
        url="tpu://llama-tiny?tp=2&dp=2&n_kv_heads=4&max_seq=128&slots=2"
            "&max_tokens=8",
        model="m"))

    cache = be.engine._ck
    n_cache_devices = len(cache.sharding.device_set)

    body = {"model": "m", "temperature": 0.0, "max_tokens": 8,
            "messages": [{"role": "user", "content": "two hosts, one engine"}]}
    result = asyncio.run(be.complete(body, {}, 240.0))
    assert result.ok, result.error_message
    content = result.body["choices"][0]["message"]["content"]

    # A second request exercises the warm path (prefix cache + slot reuse)
    # under the same SPMD discipline.
    result2 = asyncio.run(be.complete(body, {}, 240.0))
    assert result2.ok, result2.error_message

    print(json.dumps({
        "process": jax.process_index(),
        "content": content,
        "content_warm": result2.body["choices"][0]["message"]["content"],
        "completion_tokens": result.body["usage"]["completion_tokens"],
        "cache_devices": n_cache_devices,
    }), flush=True)


if __name__ == "__main__":
    sys.exit(main())
