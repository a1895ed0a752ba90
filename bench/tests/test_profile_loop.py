"""``readers/profile_loop.py`` on a recorded ``POST /debug/profile``
response: the loop clock's statement about the profiled seconds, beside
the device trace's idle share and the window's own /metrics deltas."""

import json
import os

import pytest

from conftest import BENCH, REPO
from manifest import Manifest, load_module

READ = load_module(os.path.join(BENCH, "readers", "profile_loop.py")).read
P = "tpu_inf_loop_"


def response():
    with open(os.path.join(BENCH, "tests", "data",
                           "profile_response.json")) as f:
        return json.load(f)


def ctx_of(profile, idle_share=0.30):
    loop = profile.get("loop") or {}
    # The window: 48 s holding the capture; outside it the host spends
    # half as long a dispatch as inside.
    a = {k: 10.0 for k in loop}
    b = {k: 10.0 + v for k, v in loop.items()}
    outside_dispatches = 900.0
    host_in = (loop.get(P + "seconds_total", 0.0)
               - loop.get(P + "idle_seconds_total", 0.0)
               - loop.get(P + "device_wait_seconds_total", 0.0))
    n_in = (loop.get("tpu_inf_decode_dispatches_total", 0.0)
            + loop.get("tpu_inf_prefill_dispatches_total", 0.0))
    if n_in:
        b[P + "seconds_total"] += 0.5 * host_in / n_in * outside_dispatches
        b["tpu_inf_decode_dispatches_total"] += outside_dispatches
    return {"profile": profile, "trace": {"idle_share_worst": idle_share},
            "metrics_open": a, "metrics_end": b}


def test_the_recorded_response_is_what_the_program_answers():
    loop = response()["loop"]
    assert {"dir", "seconds", "replica", "status"} <= set(response())
    parts = [v for k, v in loop.items() if k.startswith(P + "starved_")
             and k != P + "starved_seconds_total"]
    assert len(parts) == 9
    assert sum(parts) == pytest.approx(loop[P + "starved_seconds_total"])
    assert sum(v for k, v in loop.items() if k.startswith(P + "stage_")
               and not k.endswith("offcpu_seconds_total")
               and k != P + "stage_seconds_total") \
        == pytest.approx(loop[P + "stage_seconds_total"])


def test_the_three_readings():
    ctx = ctx_of(response())
    loop = ctx["profile"]["loop"]
    wall = loop["loop_wall_s"]
    starved = READ(ctx, what="capture_starved_share")
    assert starved == pytest.approx(
        100.0 * loop[P + "starved_seconds_total"] / wall)
    unclaimed = READ(ctx, what="capture_unclaimed_idle_share")
    assert unclaimed == pytest.approx(
        30.0 - starved - 100.0 * loop[P + "idle_seconds_total"] / wall)
    assert READ(ctx, what="tracer_host_stretch") == pytest.approx(2.0)
    with pytest.raises(ValueError):
        READ(ctx, what="no_such_reading")


def test_an_older_server_gives_nothing():
    """No ``loop`` in the response, or one without a family asked for:
    None, the metric is left out of the line. Never a guess."""
    old = {k: v for k, v in response().items() if k != "loop"}
    for what in ("capture_starved_share", "capture_unclaimed_idle_share",
                 "tracer_host_stretch"):
        assert READ(ctx_of(old), what=what) is None
        assert READ({"profile": None}, what=what) is None
    partial = response()
    del partial["loop"][P + "starved_seconds_total"]
    assert READ(ctx_of(partial), what="capture_starved_share") is None
    # The window's scrapes lack the families (a scrape of another
    # server): no stretch, the capture's own shares still stand.
    ctx = ctx_of(response())
    ctx["metrics_end"] = {}
    assert READ(ctx, what="tracer_host_stretch") is None
    assert READ(ctx, what="capture_starved_share") is not None
    # Nothing dispatched outside the capture: no rate to compare with.
    ctx = ctx_of(response())
    ctx["metrics_end"]["tpu_inf_decode_dispatches_total"] = \
        ctx["metrics_open"]["tpu_inf_decode_dispatches_total"] \
        + ctx["profile"]["loop"]["tpu_inf_decode_dispatches_total"]
    assert READ(ctx, what="tracer_host_stretch") is None


def test_the_three_metrics_are_entries_of_every_cell():
    man = Manifest(os.path.join(REPO, "BENCHMARK.json"))
    for name, layer, unit in (
            ("capture_starved_share", "device (TPU v5e)", "%"),
            ("capture_unclaimed_idle_share", "device (TPU v5e)", "%"),
            ("tracer_host_stretch", "engine (engine/engine.py)", "ratio")):
        entry = man._entry("per_layer", name)
        assert "workloads" not in entry
        assert (entry["layer"], entry["unit"], entry["moves"]) \
            == (layer, unit, "tpot_p50_s")
        spec = man.layer_metric(name)
        assert spec == {"reader": "profile_loop", "args": {"what": name}}
