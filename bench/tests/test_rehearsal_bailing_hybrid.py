"""bench/run.py end to end on the CPU for the Ling-3.0-flash configuration
at its tiny preset (a manifest of its own, BENCHMARK_ling.json, beside the
first rehearsal's): delta-rule layers with a state slot a sequence beside
one latent-attention layer, over experts routed within groups of which
the chip holds one. The counter-based per-layer metrics the real cell
lists are on a traced run's result line (the share of tokens whose chosen
groups reach this chip, the pairs a token sends here, none dropped, the
state slots' occupancy), and the trace's shares and times, which need a
chip, are not. Then the control (int8 projections) and the planted faults
of bench/planted_fault_bailing_hybrid.py: each NOT correct, on the CPU at
float32."""

import json
import os
import subprocess
import sys

from conftest import BENCH, REPO
from test_rehearsal import run

MANIFEST = os.path.join(BENCH, "tests", "rehearsal", "BENCHMARK_ling.json")
CELL = "tiny-ling_tiny-reason-tail"


def last_line(trace):
    p = run("--workload", CELL, "--seed", str(2**31 + 5151), "--seconds",
            "6", "--trace", str(trace), manifest=MANIFEST)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_traced_run_reports_the_counters_this_cell_lists():
    last = last_line(1)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["device"]["platform"] == "cpu"
    assert last["compared"]["probe"] == "kept"
    m = {k: v["value"] for k, v in last["metrics"].items()}
    # Two of four groups stay: about half the tokens reach this chip's
    # group (the served weights' routing is not uniform), and a token
    # that does brings a share of its four pairs.
    assert 0.0 < m["moe_group_reach_share"] <= 100.0
    assert 0.0 < m["moe_local_pairs_per_token"] <= 4.0
    assert m["moe_dropped_pairs"] == 0
    assert 0.0 < m["moe_decode_distinct_experts"] <= 4.0
    assert m["moe_expert_load_max_over_mean"] >= 1.0
    assert 0.0 < m["state_slots_live_share"] <= 100.0
    assert m["moe_gather_combine_programs"] > 0
    assert m["preemptions_in_window"] == 0 and m["kv_page_tokens"] == 16
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
    p, lines = _child("planted_fault_bailing_hybrid.py")
    assert p.returncode == 0, (p.stderr[-2000:], lines)
    *faults, summary = lines
    assert summary == {"planted_fault": True, "ok": True}
    assert [f["fault"] for f in faults] == [
        "state_not_carried", "padded_advances", "masked_step_advances",
        "decay_per_head", "no_erase", "conv_tail_lost",
        "group_limit_ignored", "gates_with_bias"]
    assert all(f["rms"] > 5 * f["limit"]["rms"] for f in faults), faults
