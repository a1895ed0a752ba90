"""bench/run.py end to end on the CPU for the SmallThinker configuration
at its tiny preset (a manifest of its own, BENCHMARK_smallthinker.json,
beside the first rehearsal's): the counter-based per-layer metrics the
real cell adds are on a traced run's result line (a token's pairs all
local, the rows the grouped kernels padded, the rows an expert gets at
decode, both pools' live share and what admission has booked of the
window kind's), and the trace's shares and times, which need a chip, are
not. The configuration names the probe ``kept``: ``correct`` compares the
rows the step programs sampled from. Prompts run from under the window
(8 tokens) to many windows and several chunks long. Then the control
(int8 weights) and the planted faults of
bench/planted_fault_smallthinker.py: each NOT correct, on the CPU at
float32."""

import json
import os
import subprocess
import sys

from conftest import BENCH, REPO
from test_rehearsal import run

MANIFEST = os.path.join(BENCH, "tests", "rehearsal",
                        "BENCHMARK_smallthinker.json")
CELL = "tiny-smallthinker_tiny-reason-wide"


def last_line(trace):
    p = run("--workload", CELL, "--seed", str(2**31 + 4343), "--seconds",
            "6", "--trace", str(trace), manifest=MANIFEST)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_traced_run_reports_the_experts_rows_and_both_pools():
    last = last_line(1)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["device"]["platform"] == "cpu"
    assert last["compared"]["probe"] == "kept"
    m = {k: v["value"] for k, v in last["metrics"].items()}
    # Every expert is here: a token's three pairs are all local, none is
    # dropped, and a decode step of ~3.5 lanes reaches 5-7 of 8 experts.
    assert m["moe_local_pairs_per_token"] == 3.0
    assert m["moe_dropped_pairs"] == 0
    assert 3.0 < m["moe_decode_distinct_experts"] <= 8.0
    assert 1.0 <= m["moe_decode_rows_per_expert"] < 4.0
    # Tiles of 16 rows for a handful of pairs an expert.
    assert 50.0 < m["moe_padded_row_share"] < 100.0
    assert 0.0 < m["kv_full_pool_live_share"] <= 100.0
    assert 0.0 < m["thinker_window_pool_live_share"] <= 100.0
    assert 0.0 <= m["thinker_window_pool_booked_share"] <= 100.0
    assert m["preemptions_in_window"] == 0
    assert m["xla_compiles_in_window"] == 0 and m["decode_batch_mean"] > 1.0
    assert not any(k.endswith("_roofline") or k.endswith("_per_ktok")
                   for k in m), "no chip, no peaks: no share, no device time"


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
    assert seed["control"] and seed["probe"] == "kept"
    assert not seed["ok"] and not summary["ok"]
    assert seed["rms"] > 3 * seed["limit"]["rms"], seed


def test_every_planted_fault_reads_not_correct():
    # The tiny configuration routes naturally (nothing pinned), so the
    # fault that only natural routing shows is asked for too.
    want = ["router_reads_h2", "silu_for_relu", "gates_uniform",
            "wrong_sixth", "next_expert", "rope_on_full", "window_as_full",
            "routed_zeroed", "softmax_over_all"]
    p, lines = _child("planted_fault_smallthinker.py", "--faults",
                      ",".join(want))
    assert p.returncode == 0, (p.stderr[-2000:], lines)
    *faults, summary = lines
    assert summary == {"planted_fault": True, "ok": True}
    assert [f["fault"] for f in faults] == want
    assert all(f["rms"] > 5 * f["limit"]["rms"] for f in faults), faults
