"""bench/run.py end to end on the CPU for the SambaY (Phi-4-mini-flash)
configuration at its tiny preset (a manifest of its own,
BENCHMARK_sambay.json, beside the first rehearsal's): the counter-based
per-layer metrics the real cell adds are on a traced run's result line
(the state slots, the positions the prefill programs ran their two halves
for), and the trace's shares and times, which need a chip, are not. The
configuration names the probe ``kept``: ``correct`` compares the rows the
step programs sampled from, the one probe that can judge a state a token
advances. Prompts are many windows (8 tokens) and several chunks long.
Then the control (int8 projections) and the planted faults of
bench/planted_fault_sambay.py: each NOT correct, on the CPU at float32."""

import json
import os
import subprocess
import sys

from conftest import BENCH, REPO
from test_rehearsal import run

MANIFEST = os.path.join(BENCH, "tests", "rehearsal", "BENCHMARK_sambay.json")
CELL = "tiny-sambay_tiny-reason-long"


def last_line(trace):
    p = run("--workload", CELL, "--seed", str(2**31 + 4040), "--seconds",
            "6", "--trace", str(trace), manifest=MANIFEST)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_traced_run_reports_the_state_slots_and_the_skip():
    last = last_line(1)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["device"]["platform"] == "cpu"
    assert last["compared"]["probe"] == "kept"
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert 0.0 < m["state_slots_live_share"] <= 100.0
    # One position a prompt chunk of tens of tokens, not every one.
    assert 0.0 < m["prefill_cross_positions_share"] < 15.0
    # The pools a kind: prompts many windows long release pages behind
    # the window, and nothing is preempted at this size.
    assert 0.0 < m["kv_full_pool_live_share"] <= 100.0
    assert 0.0 < m["kv_window_pool_live_share"] <= 100.0
    assert m["kv_window_pages_released_per_s"] > 0.0
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
    p, lines = _child("planted_fault_sambay.py")
    assert p.returncode == 0, (p.stderr[-2000:], lines)
    *faults, summary = lines
    assert summary == {"planted_fault": True, "ok": True}
    assert [f["fault"] for f in faults] == [
        "state_not_carried", "padded_advances", "masked_step_advances",
        "m_after_gate", "cross_reads_zeros", "window_as_full",
        "lam0_wrong_layer", "q1_with_k2"]
    assert all(f["rms"] > 5 * f["limit"]["rms"] for f in faults), faults
