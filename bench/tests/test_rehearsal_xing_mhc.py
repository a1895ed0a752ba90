"""bench/run.py end to end on the CPU for the Xing4.0 configuration at
its tiny preset (a manifest of its own, BENCHMARK_xing.json, beside the
first rehearsal's): four residual streams mixed by hyper-connections
around latent attention and expert layers that hold every expert, behind
shared prefixes the prefix cache serves. The counter-based per-layer
metrics the real cell lists are on a traced run's result line (the
largest row / column sum error of a mixing matrix, a token's pairs all
local, none dropped, the prefix-cache hits), and the trace's shares and
times, which need a chip, are not. Then the control (int8 weights) and
the planted faults of bench/planted_fault_xing_mhc.py: each NOT correct,
on the CPU at float32."""

import json
import os
import subprocess
import sys

from conftest import BENCH, REPO
from test_rehearsal import run

MANIFEST = os.path.join(BENCH, "tests", "rehearsal", "BENCHMARK_xing.json")
CELL = "tiny-xing_tiny-agent-tools"


def last_line(trace):
    p = run("--workload", CELL, "--seed", str(2**31 + 4747), "--seconds",
            "6", "--trace", str(trace), manifest=MANIFEST)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_traced_run_reports_the_counters_this_cell_lists():
    last = last_line(1)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["device"]["platform"] == "cpu"
    assert last["compared"]["probe"] == "refeed"
    assert last["compared"]["cached_tokens"] >= 48 - 16
    m = {k: v["value"] for k, v in last["metrics"].items()}
    # Twenty iterations leave the slowest token's columns a fraction of a
    # percent off; rows are exact.
    assert 0 < m["xing_mhc_row_sum_err_ppm"] < 50_000
    # Every expert is here: a token's four pairs are all local.
    assert m["moe_local_pairs_per_token"] == 4.0
    assert m["moe_dropped_pairs"] == 0
    assert 4.0 <= m["moe_decode_distinct_experts"] <= 16.0
    assert m["prefix_hit_share.batch"] > 30.0
    assert m["moe_gather_combine_programs"] > 0
    assert m["preemptions_in_window"] == 0
    assert m["xla_compiles_in_window"] == 0 and m["decode_batch_mean"] > 1.0
    assert not any(k.endswith("_roofline") or k.endswith("_per_ktok")
                   or k.endswith("_busy_share") for k in m), \
        "no chip, no peaks: no share, no device time"


def test_untraced_run_reports_the_end_to_end_metrics():
    last = last_line(0)
    assert last["correct"] is True
    assert set(last["metrics"]) == {"tpot_p50_s", "out_tok_s", "setup_s"}


def _child(script, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, script), "--manifest",
         MANIFEST, "--workload", CELL, "--seeds", "11", *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    return p, [json.loads(x) for x in p.stdout.splitlines() if x[:1] == "{"]


def test_the_control_reads_not_correct():
    p, lines = _child("parity.py", "--control")
    assert p.returncode == 0, p.stderr[-2000:]
    seed, summary = lines
    assert seed["control"] and seed["probe"] == "refeed"
    assert not seed["ok"] and not summary["ok"]
    assert seed["rms"] > 3 * seed["limit"]["rms"], seed


def test_every_planted_fault_reads_not_correct():
    p, lines = _child("planted_fault_xing_mhc.py")
    assert p.returncode == 0, (p.stderr[-2000:], lines)
    *faults, summary = lines
    assert summary == {"planted_fault": True, "ok": True}
    assert [f["fault"] for f in faults] == [
        "res_identity", "one_iteration", "post_unscaled", "unnormed",
        "first_tokens", "streams_averaged", "routed_zero", "wrong_fourth"]
    assert all(f["rms"] > 5 * f["limit"]["rms"] for f in faults), faults
