"""bench/run.py end to end on the CPU for the looped (Ouro) configuration
at its tiny preset (a manifest of its own, BENCHMARK_ouro.json, beside the
first rehearsal's): the counter-based per-layer metrics the real cell adds
are on a traced run's result line, and the trace's shares and times, which
need a chip, are not. The pool is sized so that it, not the batch, admits:
4 clients book prompt + answer of a pool that holds three of them."""

import json
import os

from conftest import BENCH
from test_rehearsal import run

MANIFEST = os.path.join(BENCH, "tests", "rehearsal", "BENCHMARK_ouro.json")
CELL = "tiny-ouro_tiny-reason"


def last_line(trace):
    p = run("--workload", CELL, "--seed", str(2**31 + 3030), "--seconds",
            "6", "--trace", str(trace), manifest=MANIFEST)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_traced_run_reports_the_pool_and_the_queue():
    last = last_line(1)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["device"]["platform"] == "cpu"
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert m["kv_page_tokens"] == 16.0         # a CPU server's page
    assert m["preemptions_in_window"] == 0.0
    assert m["queue_capacity_wait_ms.batch"] >= 0.0
    assert m["xla_compiles_in_window"] == 0 and m["decode_batch_mean"] > 1.0
    assert not any(k.endswith("_roofline")
                   or k.startswith(("looped_", "prefill_ms_"))
                   for k in m), "no chip, no peaks: no share, no device time"


def test_untraced_run_reports_the_end_to_end_metrics():
    last = last_line(0)
    assert last["correct"] is True
    assert set(last["metrics"]) == {"tpot_p50_s", "out_tok_s", "setup_s"}
