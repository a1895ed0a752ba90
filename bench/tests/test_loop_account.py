"""The engine loop's wall is read whole (PR 35): every phase the
program's clock keeps is some per-layer metric's numerator in every
cell, so a phase added later cannot go unread, and ``run.py`` says in a
traced run how much of the loop's wall the cell's metric files read."""

import json
import os
import sys

import pytest

import metrics as M
from conftest import REPO
from manifest import Manifest

TOTAL = "tpu_inf_loop_seconds_total"


@pytest.fixture(scope="module")
def man():
    return Manifest(os.path.join(REPO, "BENCHMARK.json"))


def loop_specs(man, cell):
    specs = [man.layer_metric(m["name"])
             for m in man.metrics_of("per_layer", cell)]
    return [s for s in specs if s.get("account", {}).get("name") == "loop"]


def test_every_phase_of_the_programs_clock_is_read_in_every_cell(man):
    sys.path.insert(0, REPO)
    from tpu_inference import telemetry

    families = set(telemetry.LOOP_FAMILIES.values())
    assert len(families) == 11
    for cell in (w["name"] for w in man.data["workloads"]):
        specs = loop_specs(man, cell)
        assert all(s["account"]["total"] == TOTAL for s in specs)
        read = [s["args"]["num"] for s in specs]
        assert sorted(read) == sorted(families), \
            f"{cell}: each phase once, none unread, none twice"


def test_the_new_shares_are_entries_of_every_cell(man):
    for phase, layer in (("enqueue", "engine (engine/engine.py)"),
                         ("reap", "scheduler (engine/scheduler.py)"),
                         ("other", "scheduler (engine/scheduler.py)"),
                         ("idle", "scheduler (engine/scheduler.py)"),
                         ("swap", "scheduler (engine/scheduler.py)")):
        entry = man._entry("per_layer", f"loop_{phase}_share")
        assert "workloads" not in entry and entry["layer"] == layer
        assert (entry["unit"], entry["better"], entry["moves"]) \
            == ("%", "lower", "tpot_p50_s")
        spec = man.layer_metric(entry["name"])
        assert spec["args"] == {
            "num": f"tpu_inf_loop_{phase}_seconds_total", "den": TOTAL,
            "scale": 100.0}


def test_accounts_read_says_what_share_of_the_total_was_read(man):
    cell = man.data["workloads"][3]["name"]
    specs = [man.layer_metric(m["name"])
             for m in man.metrics_of("per_layer", cell)]
    nums = [s["args"]["num"] for s in loop_specs(man, cell)]
    a = {TOTAL: 50.0, **{k: 1.0 for k in nums}}
    b = {TOTAL: 50.0 + 2.0 * len(nums), **{k: 3.0 for k in nums}}
    assert M.accounts_read(specs, a, b) == {"loop": pytest.approx(100.0)}
    # A phase the program keeps and no metric file reads shows as a hole.
    b[TOTAL] += 5.5
    got = M.accounts_read(specs, a, b)["loop"]
    assert got == pytest.approx(100.0 * 22.0 / 27.5)
    # A program without the clock (or a window of no wall): nothing said.
    assert M.accounts_read(specs, {}, {}) == {}
    assert M.accounts_read([{"reader": "steps", "args": {}}], a, b) == {}
