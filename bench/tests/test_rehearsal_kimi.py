"""bench/run.py end to end on the CPU for the latent-attention /
routed-expert configuration at its tiny preset (a manifest of its own,
BENCHMARK_kimi.json, beside the first rehearsal's): the counter-based
per-layer metrics the real cell adds are on a traced run's result line,
and the roofline shares, which need a chip's peaks, are not."""

import json
import os

from conftest import BENCH
from test_rehearsal import run

MANIFEST = os.path.join(BENCH, "tests", "rehearsal", "BENCHMARK_kimi.json")
CELL = "tiny-kimi_tiny-doc-reask"


def last_line(trace):
    p = run("--workload", CELL, "--seed", str(2**31 + 4321), "--seconds",
            "6", "--trace", str(trace), manifest=MANIFEST)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_traced_run_reports_the_routing_counters():
    last = last_line(1)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["device"]["platform"] == "cpu"
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert m["moe_dropped_pairs"] == 0.0
    # 8 of 16 experts are held, 4 chosen a token: 2 local pairs a token
    # under uniform routing.
    assert 1.0 < m["moe_local_pairs_per_token"] < 3.0
    assert m["moe_expert_load_max_over_mean"] >= 1.0
    assert m["prefix_hit_share.batch"] > 50.0
    assert 0.0 < m["moe_decode_distinct_experts"] <= 8.0
    assert m["xla_compiles_in_window"] == 0 and "out_tok_s.watch" not in m
    assert not any(k.endswith("_roofline") or k.startswith("prefill_ms_")
                   for k in m), "no chip, no peaks: no roofline share"


def test_untraced_run_reports_the_end_to_end_metrics():
    last = last_line(0)
    assert last["correct"] is True
    assert set(last["metrics"]) == {"tpot_p50_s", "out_tok_s", "setup_s"}
