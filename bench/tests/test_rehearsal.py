"""bench/run.py end to end on the CPU at a tiny preset (its own manifest,
configuration and mixes under tests/rehearsal): the result line's keys,
the device fields (cpu, never a device's name), and the refusal of the
real command where there is no chip."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, REPO

REHEARSAL = os.path.join(BENCH, "tests", "rehearsal", "BENCHMARK.json")


def run(*args, manifest=None, timeout=600):
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), *args]
    if manifest:
        cmd += ["--manifest", manifest]
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    return subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)


@pytest.mark.parametrize("cell,trace,names", [
    ("tiny-mistral_tiny-chat", 0,
     {"ttft_mean_s", "gap_p99_s", "tpot_p50_s", "setup_s"}),
    ("tiny-mistral_tiny-closed", 0, {"tpot_p50_s", "out_tok_s", "setup_s"}),
    ("tiny-mistral_tiny-chat", 1, None),
    ("tiny-mistral_tiny-closed", 1, None),
])
def test_rehearsal_result_line(cell, trace, names):
    p = run("--workload", cell, "--seed", str(2**31 + 12345), "--seconds",
            "5", "--trace", str(trace), manifest=REHEARSAL)
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    dev = last["device"]
    assert dev["platform"] == "cpu" and dev["kind"] == "cpu"
    assert dev["count"] == 1 and "memory_peak_bytes" in dev
    if trace:
        assert dev["busy_s"] > 0 and dev["window_s"] >= dev["busy_s"]
        assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
        assert 0 < len(last["breakdown"]["device_ops"]) <= 10
        names = {"decode_batch_mean", "decode_step_ms",
                 "xla_compiles_in_window", "loop_enqueue_share", "loop_reap_share", "loop_other_share",
                 "loop_idle_share", "loop_swap_share"}
        closed_only = {"streams_decoding_mean", "clients_waiting_mean",
                       "ttft_mean_s.batch", "ttft_p90_s.batch",
                       "queue_wait_mean_ms.batch",
                       "queue_boundary_wait_ms.batch",
                       "queue_capacity_wait_ms.batch"}
        if cell.endswith("closed"):
            names |= closed_only
            got = {k: last["metrics"][k]["value"] for k in closed_only}
            # 3 clients, each waiting, decoding or turning round.
            assert 2.5 < (got["streams_decoding_mean"]
                          + got["clients_waiting_mean"]) <= 3.0
            assert got["queue_wait_mean_ms.batch"] == pytest.approx(
                got["queue_boundary_wait_ms.batch"]
                + got["queue_capacity_wait_ms.batch"], rel=0.02)
        else:
            names |= {"queue_wait_mean_ms", "ttft_p90_s"}
            assert not closed_only & set(last["metrics"])
        assert last["metrics"]["xla_compiles_in_window"]["value"] == 0
        # No chip, no peaks: roofline shares are absent, not made up.
        assert not any(k.endswith("_roofline") for k in last["metrics"])
        assert names <= set(last["metrics"])
    else:
        assert set(last["metrics"]) == names
    for m in last["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]
    compared = [json.loads(line)["compared"]
                for line in p.stdout.splitlines() if '"compared"' in line]
    assert any("logit_err_rms" in c for c in compared), \
        "every run prints each number compared beside its limit"
    # ... and again under the result line's last key and as the last
    # lines of stderr.
    assert list(last)[-1] == "compared"
    assert {k for c in compared for k in c} == set(last["compared"])
    tail = p.stderr.strip().splitlines()[-len(last["compared"]):]
    assert [t.split()[1].rstrip(":") for t in tail] == list(last["compared"])
    assert ("queue_drawn" in last["compared"]) == cell.endswith("closed")
    if cell.endswith("closed"):
        drawn, queued = last["compared"]["queue_drawn"]
        assert 0 < drawn < queued == 4 * 200
    if trace:
        # The rehearsal manifest reads five of the eleven phases.
        pct, whole = last["compared"]["loop_read_pct"]
        assert 0.0 < pct <= whole == 100.0


def test_the_real_command_refuses_without_a_chip():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    p = run("--workload", cell, "--seed", "1", "--seconds", "1",
            "--trace", "0", timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith('{"correct"')
                   for line in p.stdout.splitlines())


def test_an_unknown_cell_is_refused():
    p = run("--workload", "nope", "--seed", "1", "--seconds", "1",
            "--trace", "0", manifest=REHEARSAL)
    assert p.returncode != 0 and '"correct"' not in p.stdout
