"""End-to-end test of the multi-host rendezvous path (VERDICT r3 item 6).

``parallel/multihost.py``'s ``initialize()`` was previously verified only
as a single-process no-op. Here two REAL processes rendezvous through
``jax.distributed`` (coordinator on localhost), build the hybrid ICI/DCN
mesh over their combined device set, and run a cross-process psum — the
same control flow a 2-host TPU pod slice uses, on the CPU backend's Gloo
collectives.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "_multihost_worker.py")


def test_two_process_rendezvous_mesh_and_psum():
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    coord = f"127.0.0.1:{port}"

    # Each subprocess gets the repo alone on PYTHONPATH, the CPU
    # platform and 2 virtual CPU devices of its own.
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

    procs = [subprocess.Popen(
        [sys.executable, WORKER, coord, "2", str(pid)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env)
        for pid in range(2)]
    outs = []
    try:
        for p in procs:
            try:
                out, err = p.communicate(timeout=180)
            except subprocess.TimeoutExpired:
                raise AssertionError(
                    "rendezvous hung: worker never finished")
            assert p.returncode == 0, (
                f"worker failed rc={p.returncode}\n{err.decode()[-2000:]}")
            rec = json.loads(out.decode().splitlines()[-1])
            outs.append(rec)
    finally:
        # One worker failing fast must not orphan the other inside
        # JAX's multi-minute rendezvous retry loop.
        for p in procs:
            if p.poll() is None:
                p.kill()

    for rec in outs:
        assert rec["process_count"] == 2
        assert rec["global_devices"] == 4
        assert rec["mesh_shape"] == {"dp": 2, "tp": 2, "sp": 1}
        # All 16 ones reduced across both processes.
        assert rec["psum"] == 16.0
        assert rec["role"]["local_devices_in_mesh"] == 2
    # Exactly the coordinator process hosts mesh row 0 (the frontend).
    frontend = {rec["pid"]: rec["role"]["hosts_frontend"] for rec in outs}
    assert frontend == {0: True, 1: False}

    # Serving under the hybrid mesh: each process served its own dp
    # replica row (VERDICT r4 item 6) — distinct rows, identical tokens,
    # and both match the unsharded single-process oracle.
    assert sorted(rec["replica_row"] for rec in outs) == [0, 1]
    assert outs[0]["tokens"] == outs[1]["tokens"]
    from tests import _multihost_worker as mw
    from tpu_inference.config import EngineConfig, tiny_llama
    from tpu_inference.engine.engine import InferenceEngine

    oracle = InferenceEngine(tiny_llama(), EngineConfig(**mw.ENGINE_KW),
                             seed=0)
    want = oracle.generate(mw.PROMPTS, max_new_tokens=mw.MAX_NEW)
    assert outs[0]["tokens"] == want
