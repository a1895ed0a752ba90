"""The loop clock's second level is read whole (PR 37): every starved
part and every part of ``stage`` the program keeps is some per-layer
metric's numerator in every cell, under an account of its own, so
``run.py`` says ``starved_read_pct`` and ``stage_read_pct`` in a traced
run and a part added later cannot go unread."""

import os
import sys

import pytest

import metrics as M
from conftest import REPO
from manifest import Manifest

LOOP = "tpu_inf_loop_seconds_total"
TOTALS = {"starved": "tpu_inf_loop_starved_seconds_total",
          "stage": "tpu_inf_loop_stage_seconds_total"}
ENGINE = "engine (engine/engine.py)"
SCHEDULER = "scheduler (engine/scheduler.py)"


@pytest.fixture(scope="module")
def man():
    return Manifest(os.path.join(REPO, "BENCHMARK.json"))


@pytest.fixture(scope="module")
def families():
    sys.path.insert(0, REPO)
    from tpu_inference import telemetry
    return {"starved": telemetry.STARVED_FAMILIES,
            "stage": telemetry.STAGE_FAMILIES}


def specs_of(man, cell):
    return [man.layer_metric(m["name"])
            for m in man.metrics_of("per_layer", cell)]


def test_every_part_the_program_keeps_is_read_in_every_cell(man, families):
    assert len(families["starved"]) == 9 and len(families["stage"]) == 4
    for cell in (w["name"] for w in man.data["workloads"]):
        for name, total in TOTALS.items():
            acc = [s for s in specs_of(man, cell)
                   if s.get("account", {}).get("name") == name]
            assert all(s["account"]["total"] == total for s in acc)
            assert sorted(s["args"]["num"] for s in acc) \
                == sorted(families[name].values()), \
                f"{cell}: {name}: each part once, none unread, none twice"


def test_the_shares_are_entries_of_every_cell(man, families):
    names = [(f"starved_{p}_share", fam,
              ENGINE if p in ("stage", "enqueue") else SCHEDULER)
             for p, fam in families["starved"].items()]
    names += [(f"stage_{p}_share", fam, ENGINE)
              for p, fam in families["stage"].items()]
    names += [("loop_host_offcpu_share",
               "tpu_inf_loop_host_offcpu_seconds_total", SCHEDULER),
              ("stage_offcpu_share",
               "tpu_inf_loop_stage_offcpu_seconds_total", ENGINE)]
    assert len(names) == 15
    for name, family, layer in names:
        entry = man._entry("per_layer", name)
        assert "workloads" not in entry and entry["layer"] == layer
        assert (entry["unit"], entry["better"], entry["moves"],
                entry["source"]) == ("%", "lower", "tpot_p50_s",
                                     "program_span")
        spec = man.layer_metric(name)
        assert spec["reader"] == "metrics_delta"
        # Shares of the LOOP's wall: the parts of an account then sum to
        # the accepted share of their total (loop_starved_share,
        # loop_stage_share).
        assert spec["args"] == {"num": family, "den": LOOP, "scale": 100.0}


def test_the_parts_sum_to_the_accepted_share_and_are_said_read(man, families):
    cell = man.data["workloads"][3]["name"]
    specs = specs_of(man, cell)
    a = {LOOP: 100.0, **dict.fromkeys(TOTALS.values(), 1.0)}
    b = {LOOP: 148.0, TOTALS["starved"]: 1.0 + 9 * 0.5,
         TOTALS["stage"]: 1.0 + 4 * 1.5}
    for name, step in (("starved", 0.5), ("stage", 1.5)):
        for fam in families[name].values():
            a[fam], b[fam] = 2.0, 2.0 + step
    ctx = {"metrics_open": a, "metrics_end": b}
    read = man.reader("metrics_delta")
    for name, accepted in (("starved", "loop_starved_share"),
                           ("stage", "loop_stage_share")):
        parts = sum(read(ctx, **s["args"]) for s in specs
                    if s.get("account", {}).get("name") == name)
        whole = read(ctx, **man.layer_metric(accepted)["args"])
        assert parts == pytest.approx(whole)
    got = M.accounts_read(specs, a, b)
    assert got["starved"] == pytest.approx(100.0)
    assert got["stage"] == pytest.approx(100.0)
    assert got["loop"] < 100.0          # this window moved no phase family
    # A part the program keeps and no file reads shows as a hole.
    b[TOTALS["stage"]] += 2.0
    assert M.accounts_read(specs, a, b)["stage"] == pytest.approx(75.0)
    # An older server: the total is there, the parts are not. Said as 0,
    # and every share left out of the line.
    old = {k: v for k, v in b.items() if k in (LOOP, *TOTALS.values())}
    assert M.accounts_read(specs, a, old)["starved"] <= 0.0
    ctx = {"metrics_open": {}, "metrics_end": old}
    assert read(ctx, **man.layer_metric("starved_stage_share")["args"]) \
        is None
