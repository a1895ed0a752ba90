"""The per-layer metrics that read the program's own phase clock, queue
split, compile counter and boot gauges (PR 24): each new layer_metrics
file resolves to a reader that is there, ``metrics_value`` reads a gauge,
a program without the series (the parent commit) yields nothing and does
not raise, and a CPU rehearsal with them added to a COPY of the
rehearsal manifest prints them."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, REPO

NEW = {
    "loop_starved_share", "loop_stage_share", "loop_admit_share",
    "loop_prefix_lookup_share", "loop_deliver_share",
    "loop_device_wait_share", "loop_stalls_in_window", "heartbeat_ms",
    "queue_boundary_wait_ms", "queue_capacity_wait_ms",
    "xla_compiles_in_window", "boot_weights_s", "boot_warmup_s",
    "boot_ready_s",
}


def _root_entries():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [m for m in json.load(f)["per_layer"] if m["name"] in NEW]


def test_new_layer_metrics_resolve_to_readers():
    from manifest import Manifest

    man = Manifest(os.path.join(REPO, "BENCHMARK.json"))
    entries = _root_entries()
    assert {m["name"] for m in entries} == NEW
    cells = {c["name"] for c in man.data["workloads"]}
    for m in entries:
        spec = man.layer_metric(m["name"])
        assert spec["reader"] in ("metrics_delta", "metrics_value")
        assert callable(man.reader(spec["reader"]))
        assert set(m.get("workloads", cells)) <= cells
        assert m["layer"] in {x["layer"] for x in man.data["per_layer"]
                              if x["name"] not in NEW}


def test_metrics_value_reads_a_gauge_and_nothing_from_a_parent():
    from manifest import Manifest

    man = Manifest(os.path.join(REPO, "BENCHMARK.json"))
    read = man.reader("metrics_value")
    ctx = {"metrics_open": {"tpu_inf_boot_warmup_seconds": 17.5},
           "metrics_end": {"tpu_inf_boot_warmup_seconds": 17.5}}
    assert read(ctx, name="tpu_inf_boot_warmup_seconds") == 17.5
    assert read(ctx, name="tpu_inf_boot_warmup_seconds", scale=1000.0) \
        == 17500.0
    # A program that lacks the series (the parent commit): every new
    # metric reads None, none raises.
    parent = {"metrics_open": {"tpu_inf_steps_total": 1.0},
              "metrics_end": {"tpu_inf_steps_total": 9.0}}
    for m in _root_entries():
        spec = man.layer_metric(m["name"])
        assert man.reader(spec["reader"])(parent, **spec["args"]) is None


def test_shares_and_means_come_out_of_scrape_deltas():
    from manifest import Manifest

    man = Manifest(os.path.join(REPO, "BENCHMARK.json"))
    a = {"tpu_inf_loop_seconds_total": 100.0,
         "tpu_inf_loop_stage_seconds_total": 10.0,
         "tpu_inf_loop_heartbeat_seconds_total": 0.5,
         "tpu_inf_loop_heartbeats_total": 10.0,
         "tpu_inf_queue_boundary_wait_seconds_sum": 1.0,
         "tpu_inf_queue_boundary_wait_seconds_count": 10.0,
         "tpu_inf_xla_compiles_total": 16.0}
    b = {"tpu_inf_loop_seconds_total": 148.0,
         "tpu_inf_loop_stage_seconds_total": 14.8,
         "tpu_inf_loop_heartbeat_seconds_total": 0.51,
         "tpu_inf_loop_heartbeats_total": 15.0,
         "tpu_inf_queue_boundary_wait_seconds_sum": 10.0,
         "tpu_inf_queue_boundary_wait_seconds_count": 70.0,
         "tpu_inf_xla_compiles_total": 16.0}
    ctx = {"metrics_open": a, "metrics_end": b}

    def value(name):
        spec = man.layer_metric(name)
        return man.reader(spec["reader"])(ctx, **spec["args"])

    assert value("loop_stage_share") == pytest.approx(10.0)
    assert value("heartbeat_ms") == pytest.approx(2.0)
    assert value("queue_boundary_wait_ms") == pytest.approx(150.0)
    assert value("xla_compiles_in_window") == 0.0


def test_cpu_rehearsal_prints_the_new_metrics(tmp_path):
    """bench/tests/rehearsal/BENCHMARK.json is not edited: a copy of it
    gets the new per-layer entries (for its own cells) and runs."""
    with open(os.path.join(BENCH, "tests", "rehearsal",
                           "BENCHMARK.json")) as f:
        data = json.load(f)
    cell = "tiny-mistral_tiny-chat"
    for m in _root_entries():
        entry = dict(m)
        entry["moves"] = ("setup_s" if m["moves"] == "setup_s"
                          else "tpot_p50_s")
        entry["workloads"] = [cell]
        data["per_layer"].append(entry)
    manifest = tmp_path / "BENCHMARK.json"
    manifest.write_text(json.dumps(data))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(2**31 + 777), "--seconds", "5", "--trace", "1",
         "--manifest", str(manifest)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    got = last["metrics"]
    # A 5 s window may hold no heartbeat (one every 10 s): a mean over
    # zero beats is left out, as any reader with nothing to read is.
    assert NEW - {"heartbeat_ms"} <= set(got), sorted(NEW - set(got))
    shares = [got[n]["value"] for n in NEW if n.endswith("_share")]
    assert all(0.0 <= v <= 100.0 for v in shares)
    assert got["loop_device_wait_share"]["value"] > 0
    # The program's counter and the log lines ``correct`` counts agree.
    assert got["xla_compiles_in_window"]["value"] \
        == last["compared"]["compiles_in_window"][0] == 0
    assert got["boot_ready_s"]["value"] >= got["boot_warmup_s"]["value"] > 0
    assert got["queue_boundary_wait_ms"]["value"] >= 0
    # With the rehearsal manifest's own five shares (PR 35) the copy reads
    # all eleven phases of the loop's clock: the account is whole.
    assert last["compared"]["loop_read_pct"] == [pytest.approx(100.0,
                                                               abs=0.5), 100.0]
    phases = [got[n]["value"] for n in got
              if n.startswith("loop_") and n.endswith("_share")
              and n != "loop_starved_share"]
    assert len(phases) == 10 and sum(phases) <= 100.0 + 1e-6
