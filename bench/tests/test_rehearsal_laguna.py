"""bench/run.py end to end on the CPU for the mixed-kind (Laguna)
configuration at its tiny preset (a manifest of its own,
BENCHMARK_laguna.json, beside the first rehearsal's): the counter-based
per-layer metrics the real cell adds are on a traced run's result line
(a pool a kind, pages released behind the window while sequences ran,
the routed pairs), and the trace's shares and times, which need a chip,
are not. Prompts are many windows (8 tokens) long, so the parity child
compares streams whose window-kind pages were released inside the run.
Then the planted faults of bench/planted_fault_mixed.py: each NOT
correct, on the CPU at float32."""

import json
import os
import subprocess
import sys

from conftest import BENCH, REPO
from test_rehearsal import run

MANIFEST = os.path.join(BENCH, "tests", "rehearsal", "BENCHMARK_laguna.json")
CELL = "tiny-laguna_tiny-code-mixed"


def last_line(trace):
    p = run("--workload", CELL, "--seed", str(2**31 + 3232), "--seconds",
            "6", "--trace", str(trace), manifest=MANIFEST)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_traced_run_reports_the_pools_and_the_routing():
    last = last_line(1)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["device"]["platform"] == "cpu"
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert 0.0 <= m["kv_window_pool_live_share"] <= 100.0
    assert 0.0 <= m["kv_full_pool_live_share"] <= 100.0
    assert m["kv_window_pages_released_per_s"] > 0.0
    assert m["moe_dropped_pairs"] == 0.0
    # 4 held experts of 16, top-3, near-uniform random routing.
    assert 0.3 < m["moe_local_pairs_per_token"] < 1.3
    assert 0.0 < m["moe_decode_distinct_experts"] <= 4.0
    assert m["preemptions_in_window"] == 0.0
    assert m["xla_compiles_in_window"] == 0 and m["decode_batch_mean"] > 1.0
    assert not any(k.endswith("_roofline") or k.endswith("_per_ktok")
                   for k in m), "no chip, no peaks: no share, no device time"


def test_untraced_run_reports_the_end_to_end_metrics():
    last = last_line(0)
    assert last["correct"] is True
    assert set(last["metrics"]) == {"tpot_p50_s", "out_tok_s", "setup_s"}


def test_every_planted_fault_reads_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "planted_fault_mixed.py"),
         "--manifest", MANIFEST, "--workload", CELL, "--seeds", "11"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    lines = [json.loads(x) for x in p.stdout.splitlines() if x[:1] == "{"]
    assert p.returncode == 0, (p.stderr[-2000:], lines)
    *faults, summary = lines
    assert summary == {"planted_fault": True, "ok": True}
    assert [f["fault"] for f in faults] == [
        "window_as_full", "full_as_window", "sliding_rope_on_full",
        "gate_left_out", "routed_zeroed"]
    assert all(f["rms"] > 10 * f["limit"]["rms"] for f in faults), faults
