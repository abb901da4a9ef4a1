"""Worker process for tests/test_distributed.py's true multi-process run.

NOT a test module (no ``test_`` prefix): spawned twice by
``test_two_process_train_step``, once per simulated host. Each worker joins
the jax distributed runtime through quorum_tpu's own helpers, builds the
hybrid DCN×ICI mesh, feeds only its local dp rows, and runs one real
training step — the dp gradient all-reduce crosses the process boundary
(the DCN analog on a CPU pair). Prints one JSON line the test asserts on.
"""

import json
import os
import sys

# Script execution puts tests/ on sys.path, not the repo root.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Clean CPU platform before jax initializes (same recipe as conftest.py —
# the spawning test also scrubs the env, this is belt-and-braces for direct
# invocation).
os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import numpy as np

jax.config.update("jax_platforms", "cpu")


def main() -> None:
    from quorum_tpu.models import resolve_spec
    from quorum_tpu.parallel import MeshConfig
    from quorum_tpu.parallel.distributed import (
        assemble_global_batch,
        hybrid_mesh,
        initialize,
        local_data_shard,
    )
    from quorum_tpu.training.trainer import make_train_step, train_init

    # Coordinator/process env vars set by the spawning test.
    assert initialize() is True, "expected to join a 2-process group"
    assert jax.process_count() == 2
    assert jax.device_count() == 4 and len(jax.local_devices()) == 2

    # Per-slice (ICI) shape tp=2 — each simulated host's 2 local devices;
    # dcn_dp=2 spans the dp axis across the two processes.
    mesh = hybrid_mesh(MeshConfig(tp=2), dcn_dp=2)
    assert mesh.shape == {"dp": 2, "pp": 1, "sp": 1, "tp": 2}

    global_batch, seqlen = 4, 32
    start, size = local_data_shard(global_batch)
    assert size == 2 and start == 2 * jax.process_index()

    # Deterministic global batch; each host materializes ONLY its rows.
    full = (np.arange(global_batch * seqlen, dtype=np.int32) % 97 + 3
            ).reshape(global_batch, seqlen)
    tokens = assemble_global_batch(full[start:start + size], mesh, global_batch)
    assert tokens.shape == (global_batch, seqlen)

    spec = resolve_spec("llama-tiny", {"max_seq": str(seqlen)})
    state = train_init(spec, mesh, seed=0)
    step = make_train_step(spec, mesh)
    _, loss = step(state, tokens)
    print(json.dumps({"process": jax.process_index(),
                      "loss": float(jax.device_get(loss))}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
