"""Worker process for tests/test_multihost_2proc.py — NOT a pytest file.

Each of the two worker processes joins the jax.distributed runtime via
``multihost.initialize`` (the rendezvous path under test), builds the
hybrid ICI/DCN mesh over the 4 global CPU devices (2 local to each
process), and runs a real cross-process psum through it. Prints one JSON
line with what this process observed; the parent test asserts on it.
"""

import json
import sys

import jax

# Persistent XLA compilation cache, same knobs as the suite (this file
# is launched as a bare subprocess, so conftest never runs here; script
# dir is sys.path[0]). The cross-process psum + engine graphs dominate
# this worker's runtime.
import _xla_cache

_xla_cache.enable(jax)

import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_inference.config import EngineConfig, ParallelConfig, tiny_llama
from tpu_inference.parallel import multihost

# Shared with the parent test's oracle — drift between worker and oracle
# geometry would fail the token comparison confusingly.
ENGINE_KW = dict(page_size=8, num_pages=32, max_pages_per_seq=4,
                 max_batch_size=2, prefill_buckets=(16,))
PROMPTS = [[1, 2, 3, 4, 5], [9, 8, 7]]
MAX_NEW = 6


def main() -> None:
    coord, nproc, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    multihost.initialize(coordinator_address=coord, num_processes=nproc,
                         process_id=pid)
    # Idempotency: a second call must be a no-op, not a crash.
    multihost.initialize(coordinator_address=coord, num_processes=nproc,
                         process_id=pid)
    assert jax.process_count() == nproc
    assert len(jax.devices()) == 2 * nproc

    # dp spans the two processes (the DCN-like boundary), tp stays within
    # a process — the serving layout build_hybrid_mesh exists for.
    pcfg = ParallelConfig(dp=2, tp=2, sp=1)
    mesh = multihost.build_hybrid_mesh(pcfg, num_slices=2)
    role = multihost.process_local_engine_role(mesh)

    # Cross-process collective through the mesh: every element is 1, so
    # the full psum must see all 16 — impossible without real
    # inter-process reduction over the dp axis.
    sh = NamedSharding(mesh, P("dp", "tp"))
    x = jax.make_array_from_callback(
        (4, 4), sh, lambda idx: np.ones((2, 2), np.float32))
    f = jax.jit(jax.shard_map(
        lambda a: jax.lax.psum(jnp.sum(a), ("dp", "tp")),
        mesh=mesh, in_specs=P("dp", "tp"), out_specs=P()))
    psum = float(f(x))

    # A dp-replica SERVING step under the hybrid mesh (VERDICT r4 item
    # 6): each process builds the engine for its own dp row (tp stays on
    # the slice's ICI; DCN carries no serving traffic — the point of dp
    # over DCN) and generates. The parent asserts the two processes'
    # tokens are identical and match an unsharded oracle.
    from tpu_inference.engine.engine import InferenceEngine

    replicas = multihost.replica_meshes(mesh)
    assert len(replicas) == 1, replicas
    ridx, rmesh = replicas[0]
    assert dict(rmesh.shape) == {"dp": 1, "tp": 2, "sp": 1}
    assert all(d in set(jax.local_devices()) for d in rmesh.devices.flat)
    eng = InferenceEngine(tiny_llama(), EngineConfig(**ENGINE_KW),
                          seed=0, mesh=rmesh)
    tokens = eng.generate(PROMPTS, max_new_tokens=MAX_NEW)

    print(json.dumps({"pid": pid, "process_count": jax.process_count(),
                      "global_devices": len(jax.devices()),
                      "mesh_shape": dict(mesh.shape), "psum": psum,
                      "replica_row": ridx, "tokens": tokens,
                      "role": role}), flush=True)


if __name__ == "__main__":
    main()
