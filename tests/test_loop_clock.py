"""The engine loop's phase clock (telemetry.LoopClock): phases are an
exclusive, complete partition of the loop's wall; starved time accrues
only with nothing in flight and work pending; a long visit is one stall
and one ``loop_stall`` event; with telemetry off it is the null object."""

import json
import threading
import time

import pytest

from tpu_inference import telemetry
from tpu_inference.telemetry import (HOST_PHASES, LOOP_FAMILIES, LOOP_PHASES,
                                     STAGE_FAMILIES, STAGE_PARTS,
                                     STARVED_FAMILIES, LoopClock, NULL_CLOCK,
                                     Registry)


class FakeTime:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


def _clock():
    ft = FakeTime()
    return LoopClock(now=ft), ft


def _clock_with_cpu():
    """A clock on a fake wall and a fake thread-CPU clock; ``visit``
    spends ``dt`` of wall in a phase, ``cpu`` of it on the CPU."""
    ft, cpu = FakeTime(), FakeTime()
    clock = LoopClock(now=ft, thread_time=cpu)

    def visit(phase, dt, on_cpu=None):
        clock.enter(phase)
        ft.tick(dt)
        cpu.tick(dt if on_cpu is None else on_cpu)

    return clock, ft, cpu, visit


def _benchmark_cells():
    """(cell, the metric files of its per-layer metrics) for every cell
    of BENCHMARK.json, as ``bench/manifest.py`` finds them by name."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    root = os.path.join(repo, manifest["paths"][0], "layer_metrics")

    def spec(name):
        for stem in (name, name.split(".")[0]):
            path = os.path.join(root, stem + ".json")
            if os.path.exists(path):
                with open(path) as f:
                    return json.load(f)
        raise AssertionError(f"no metric file for {name}")

    for cell in (w["name"] for w in manifest["workloads"]):
        yield cell, [spec(m["name"]) for m in manifest["per_layer"]
                     if "workloads" not in m or cell in m["workloads"]]


def _exported(clock):
    reg = Registry()
    clock.register(reg)
    return {m.name: m.collect_value() for m in reg.collect()}


def _stall_events(capsys):
    return [json.loads(line) for line in capsys.readouterr().err.splitlines()
            if '"loop_stall"' in line]


def test_phases_partition_the_wall_exactly():
    clock, ft = _clock()
    t_start = clock.start()
    visits = [("admit", 0.004), ("stage", 0.011), ("enqueue", 0.002),
              ("device_wait", 0.310), ("deliver", 0.003), ("reap", 0.0005),
              ("idle", 0.1), ("stage", 0.009), ("heartbeat", 0.0002),
              ("swap", 0.02), ("prefix_lookup", 0.001), ("other", 0.0)]
    for phase, dt in visits:
        assert clock.enter(phase) == ft.t      # enter returns the instant
        ft.tick(dt)
    clock.stop()
    wall = ft.t - t_start
    assert sum(clock.seconds.values()) == pytest.approx(wall, rel=1e-9)
    assert clock.total_s() == pytest.approx(wall, rel=1e-9)
    assert clock.seconds["stage"] == pytest.approx(0.020)
    assert clock.seconds["device_wait"] == pytest.approx(0.310)
    # Exported: one family per phase, and their sum, equal to 1e-6.
    values = _exported(clock)
    assert set(LOOP_FAMILIES.values()) <= set(values)
    assert len(LOOP_FAMILIES) == len(LOOP_PHASES) == 11
    total = values["tpu_inf_loop_seconds_total"]
    assert sum(values[f] for f in LOOP_FAMILIES.values()) == pytest.approx(
        total, rel=1e-6)
    assert total == pytest.approx(wall, rel=1e-6)


def test_nothing_accrues_outside_start_and_stop():
    """An engine driven directly (no scheduler loop) still gets instants
    from enter(), but no visit is left open to grow into a false stall."""
    clock, ft = _clock()
    assert clock.enter("stage") == ft.t
    ft.tick(50.0)
    assert clock.enter("other") == ft.t
    assert clock.total_s() == 0.0 and clock.stalls == 0
    clock.start()
    ft.tick(0.5)
    clock.stop()
    ft.tick(100.0)                       # between two runs of the loop
    clock.start()
    ft.tick(0.25)
    clock.stop()
    assert clock.total_s() == pytest.approx(0.75)
    assert clock.stalls == 0


def test_starved_needs_empty_queue_and_pending_work():
    clock, ft = _clock()
    clock.start()

    def visit(phase, dt):
        clock.enter(phase)
        ft.tick(dt)

    # No work known to the scheduler: host time is not starvation.
    clock.has_work = False
    visit("stage", 0.010)
    clock.enter("other")
    assert clock.starved_s == 0.0
    # Work pending, nothing in flight: stage and enqueue starve the
    # device ...
    clock.has_work = True
    visit("stage", 0.010)
    visit("enqueue", 0.002)
    clock.dispatched(1)                       # ... until this returns
    assert clock.starved_s == pytest.approx(0.012)
    assert clock.in_flight
    # In flight: host phases overlap the device, waiting on it is not
    # the host's doing either.
    ft.tick(0.001)
    visit("stage", 0.010)
    visit("device_wait", 0.300)
    clock.enter("other")
    assert clock.starved_s == pytest.approx(0.012)
    # Result observed, nothing else enqueued: deliver/reap starve again.
    clock.observed(1)
    assert not clock.in_flight
    visit("deliver", 0.004)
    visit("idle", 0.100)                      # idle never starves
    visit("device_wait", 0.050)               # nor waiting on the device
    clock.enter("other")
    assert clock.starved_s == pytest.approx(0.016)
    # An older program observed later does not un-observe a newer one.
    clock.dispatched(2)
    clock.observed(2)
    clock.observed(1)
    assert clock.observed_seq == 2 and not clock.in_flight
    clock.stop()
    assert clock.starved_s <= clock.total_s()


def test_starved_seconds_are_kept_by_phase_and_sum_to_the_total():
    """Dyadic walls, so every sum is exact: the nine cells sum to
    ``starved_s`` to the float, across a stop and a second start."""
    clock, ft, _, visit = _clock_with_cpu()
    assert tuple(clock.starved) == HOST_PHASES and len(HOST_PHASES) == 9
    clock.start()
    clock.has_work = True
    for phase, dt in (("admit", 0.5), ("prefix_lookup", 0.125),
                      ("stage", 2.0), ("enqueue", 0.25)):
        visit(phase, dt)
    clock.dispatched(1)                     # in flight: nothing starves
    visit("stage", 4.0)
    visit("device_wait", 8.0)
    clock.enter("other")
    clock.observed(1)
    for phase, dt in (("deliver", 0.0625), ("reap", 0.03125),
                      ("heartbeat", 1.0), ("swap", 16.0), ("idle", 32.0),
                      ("other", 0.015625)):
        visit(phase, dt)
    clock.stop()
    ft.tick(100.0)
    clock.start()
    clock.has_work = True
    visit("stage", 64.0)
    clock.stop()
    want = {"admit": 0.5, "prefix_lookup": 0.125, "stage": 66.0,
            "enqueue": 0.25, "deliver": 0.0625, "reap": 0.03125,
            "heartbeat": 1.0, "swap": 16.0, "other": 0.015625}
    assert clock.starved == want
    assert clock.starved_s == sum(want.values()) == 83.984375
    values = _exported(clock)
    assert [values[f] for f in STARVED_FAMILIES.values()] \
        == [want[p] for p in HOST_PHASES]
    assert sum(values[f] for f in STARVED_FAMILIES.values()) \
        == values["tpu_inf_loop_starved_seconds_total"]
    assert "tpu_inf_loop_starved_idle_seconds_total" not in values
    assert "tpu_inf_loop_starved_device_wait_seconds_total" not in values


def test_stage_parts_partition_the_stage_phase_exactly():
    """Every stage visit opens in ``rest``; a mark switches the part; a
    part left open when the phase changes is closed by the change; a mark
    outside a stage visit (or with the clock stopped) only tells the
    time. The parts sum to ``seconds['stage']`` to the float."""
    clock, ft, _, visit = _clock_with_cpu()
    assert clock.part("fill") == ft.t       # stopped: only the time
    assert not any(clock.stage_parts.values())
    clock.start()
    assert clock.part("put") == ft.t        # phase other: only the time
    ft.tick(1.0)
    visit("stage", 0.5)                     # unmarked: rest
    for part, dt in (("pages", 0.25), ("fill", 2.0), ("fill", 0.125),
                     ("put", 4.0)):
        assert clock.part(part) == ft.t
        ft.tick(dt)
    visit("enqueue", 8.0)                   # closes the open put
    assert clock.part("pages") == ft.t      # not in stage: ignored
    ft.tick(16.0)
    visit("stage", 0.0625)
    clock.part("pages")
    ft.tick(32.0)
    clock.stop()                            # ... and so does a stop
    ft.tick(100.0)
    clock.start()
    visit("stage", 64.0)                    # a second run: rest again
    clock.stop()
    assert clock.stage_parts == {"pages": 32.25, "fill": 2.125, "put": 4.0,
                                 "rest": 64.5625}
    assert tuple(clock.stage_parts) == STAGE_PARTS
    assert sum(clock.stage_parts.values()) == clock.seconds["stage"] \
        == 102.9375
    assert clock.seconds["enqueue"] == 24.0 and clock.seconds["other"] == 1.0
    values = _exported(clock)
    assert sum(values[f] for f in STAGE_FAMILIES.values()) \
        == values["tpu_inf_loop_stage_seconds_total"]


def test_host_seconds_off_the_cpu():
    """Wall minus the thread's own CPU time over the nine host phases,
    and over stage alone; idle and device_wait (blocked by nature) stay
    out."""
    clock, _, _, visit = _clock_with_cpu()
    clock.start()
    visit("stage", 4.0, on_cpu=3.0)
    visit("idle", 64.0, on_cpu=0.0)
    visit("device_wait", 32.0, on_cpu=0.5)
    visit("deliver", 2.0, on_cpu=2.0)
    visit("stage", 1.0, on_cpu=0.75)
    visit("reap", 0.5, on_cpu=0.25)
    clock.stop()
    assert clock.stage_offcpu_s == 1.25
    assert clock.host_offcpu_s == 1.5
    values = _exported(clock)
    assert values["tpu_inf_loop_stage_offcpu_seconds_total"] == 1.25
    assert values["tpu_inf_loop_host_offcpu_seconds_total"] == 1.5


def test_long_visit_is_one_stall_and_one_event(capsys):
    clock, ft = _clock()
    clock.start()
    clock.active, clock.waiting = 3, 2
    clock.dispatched(41)
    clock.enter("idle")
    ft.tick(30.0)                             # a long idle is no stall
    clock.enter("reap")
    ft.tick(0.9)                              # under the limit
    clock.enter("heartbeat")
    ft.tick(6.5)                              # the pause names itself
    clock.enter("other")
    clock.stop()
    assert clock.stalls == 1
    assert clock.stall_s == pytest.approx(6.5)
    events = _stall_events(capsys)
    assert len(events) == 1
    ev = events[0]
    assert ev["phase"] == "heartbeat" and ev["seconds"] == pytest.approx(6.5)
    assert ev["dispatch"] == 41 and ev["active"] == 3 and ev["waiting"] == 2
    values = _exported(clock)
    assert values["tpu_inf_loop_stalls_total"] == 1
    assert values["tpu_inf_loop_stall_seconds_total"] == pytest.approx(6.5)


def test_null_clock_when_telemetry_is_off(monkeypatch):
    monkeypatch.setenv("TPU_INF_TELEMETRY", "0")
    tel = telemetry.EngineTelemetry()
    assert tel.clock is NULL_CLOCK
    assert not [m for m in tel.registry.collect()
                if m.name.startswith("tpu_inf_loop_")]
    clock = tel.clock
    t0 = clock.start()
    clock.has_work = True                      # settable, kept nowhere
    assert clock.has_work is False
    t1 = clock.enter("stage")
    t_part = clock.part("fill")
    t2 = clock.dispatched(7)
    clock.observed(7)
    clock.stop()
    # It still tells the time: callers use it in place of a clock read.
    now = time.perf_counter()
    assert t0 <= t1 <= t_part <= t2 <= now and now - t0 < 5.0
    assert clock.phase is None and not clock.in_flight
    assert tel.loop_snapshot() == {}           # nothing kept, nothing said


def test_scheduler_loop_is_fully_partitioned(monkeypatch):
    """A real scheduler over a tiny engine: the exported phase families
    sum to the exported total, the loop's wall is covered, the engine's
    sites were visited, and dispatches were numbered and observed."""
    from tpu_inference.config import EngineConfig, tiny_llama
    from tpu_inference.engine.engine import InferenceEngine, Sequence
    from tpu_inference.engine.scheduler import EngineScheduler

    engine = InferenceEngine(tiny_llama(), EngineConfig(
        max_batch_size=4, num_pages=64, page_size=8, max_pages_per_seq=8,
        prefill_buckets=(16, 32), decode_steps_per_call=4))
    clock = engine.telemetry.clock
    sched = EngineScheduler(engine)
    done = threading.Event()
    left = [3]

    def on_finish(seq):
        left[0] -= 1
        if left[0] == 0:
            done.set()

    t0 = time.perf_counter()
    sched.start()
    for i in range(3):
        sched.submit(Sequence(request_id=i, prompt_tokens=[5, 6, 7, 8 + i],
                              max_new_tokens=12),
                     lambda s, t: None, on_finish)
    assert done.wait(120)
    sched.stop()
    wall = time.perf_counter() - t0
    assert clock.phase is None                 # stopped with the loop
    values = {m.name: m.collect_value()
              for m in engine.telemetry.registry.collect()
              if m.kind == "counter"}
    total = values["tpu_inf_loop_seconds_total"]
    assert sum(values[f] for f in LOOP_FAMILIES.values()) == pytest.approx(
        total, rel=1e-6)
    assert 0.5 * wall < total <= wall + 1e-3
    for phase in ("admit", "stage", "enqueue", "device_wait", "deliver",
                  "reap", "idle"):
        assert clock.seconds[phase] > 0, phase
    assert clock.dispatched_seq >= 4           # 1+ prefill, 3+ decode calls
    assert clock.observed_seq == clock.dispatched_seq
    assert 0 <= clock.starved_s <= total
    # The second level: the engine marked its staging, nothing is unread.
    assert sum(values[f] for f in STARVED_FAMILIES.values()) \
        == pytest.approx(values["tpu_inf_loop_starved_seconds_total"])
    assert sum(values[f] for f in STAGE_FAMILIES.values()) \
        == pytest.approx(clock.seconds["stage"], rel=1e-9)
    for part in ("pages", "fill", "put"):
        assert clock.stage_parts[part] > 0, part
    assert clock.stage_parts["rest"] < 0.25 * clock.seconds["stage"]
    assert clock.starved["stage"] > 0
    assert -1e-3 < clock.stage_offcpu_s <= clock.host_offcpu_s + 1e-3
    assert clock.host_offcpu_s < total


def test_every_phase_of_the_clock_is_read_by_the_benchmark_in_every_cell():
    """The tier-1 copy of bench/tests/test_loop_account.py's first case
    (that suite is not in the tier-1 run): each family of LOOP_FAMILIES
    is the numerator of exactly one per-layer metric of the loop's
    account in every cell of BENCHMARK.json, so a phase renamed, dropped
    or added here (``deliver`` among them) cannot go unread there."""
    families = sorted(LOOP_FAMILIES.values())
    assert len(families) == 11
    for cell, specs in _benchmark_cells():
        loop = [s for s in specs
                if s.get("account", {}).get("name") == "loop"]
        assert all(s["account"]["total"] == "tpu_inf_loop_seconds_total"
                   for s in loop)
        assert sorted(s["args"]["num"] for s in loop) == families, \
            f"{cell}: each phase once, none unread, none twice"


def test_every_part_of_starved_and_stage_is_read_in_every_cell():
    """The sibling of the case above for the clock's second level: every
    ``tpu_inf_loop_starved_*`` and ``tpu_inf_loop_stage_*`` family the
    program registers is the numerator of exactly one per-layer metric of
    its account (``starved`` / ``stage``) in every cell, and the two
    off-CPU families are read in every cell too."""
    registered = {m.name for m in
                  telemetry.EngineTelemetry(enabled=True).registry.collect()}
    totals = {"starved": "tpu_inf_loop_starved_seconds_total",
              "stage": "tpu_inf_loop_stage_seconds_total"}
    offcpu = {n for n in registered if n.endswith("_offcpu_seconds_total")}
    parts = {
        "starved": {n for n in registered - set(totals.values())
                    if n.startswith("tpu_inf_loop_starved_")},
        "stage": {n for n in registered - set(totals.values()) - offcpu
                  if n.startswith("tpu_inf_loop_stage_")}}
    assert parts["starved"] == set(STARVED_FAMILIES.values())
    assert parts["stage"] == set(STAGE_FAMILIES.values())
    assert len(parts["starved"]) == 9 and len(parts["stage"]) == 4
    assert len(offcpu) == 2
    for cell, specs in _benchmark_cells():
        for name, total in totals.items():
            acc = [s for s in specs
                   if s.get("account", {}).get("name") == name]
            assert all(s["account"]["total"] == total for s in acc)
            assert sorted(s["args"]["num"] for s in acc) \
                == sorted(parts[name]), f"{cell}: {name}, each part once"
        nums = [s["args"]["num"] for s in specs
                if s["reader"] == "metrics_delta"]
        assert offcpu <= set(nums), cell


def test_a_capture_reports_the_clock_over_its_own_seconds(tmp_path):
    """capture_jax_profile on the CPU: ``loop`` is the difference of
    every ``tpu_inf_loop_*`` family and the two dispatch counters between
    the trace's start and its stop, ``loop_wall_s`` those seconds."""
    tel = telemetry.EngineTelemetry(enabled=True)
    clock = tel.clock
    clock.start()
    clock.has_work = True
    stop = threading.Event()

    def loop():                                # a loop with no device
        while not stop.is_set():
            clock.enter("stage")
            clock.part("fill")
            time.sleep(0.002)
            tel.decode_dispatches.inc()
            clock.enter("idle")
            time.sleep(0.002)

    t = threading.Thread(target=loop)
    t.start()
    try:
        out = telemetry.capture_jax_profile(str(tmp_path), 3, 0.4, tel)
    finally:
        stop.set()
        t.join(30)
        clock.stop()
    assert not t.is_alive() and not telemetry.profile_capturing()
    assert (out["dir"], out["seconds"], out["replica"]) \
        == (str(tmp_path / "replica3"), 0.4, 3)
    loop_d = out["loop"]
    families = {family for family, _, _ in clock.families()}
    assert families == {m.name for m in tel.registry.collect()
                        if m.name.startswith("tpu_inf_loop_")} \
        - {"tpu_inf_loop_heartbeats_total"}    # the flight recorder's
    assert families >= (set(LOOP_FAMILIES.values())
                        | set(STARVED_FAMILIES.values())
                        | set(STAGE_FAMILIES.values())
                        | {"tpu_inf_loop_seconds_total",
                           "tpu_inf_loop_starved_seconds_total",
                           "tpu_inf_loop_host_offcpu_seconds_total",
                           "tpu_inf_loop_stage_offcpu_seconds_total"})
    assert set(loop_d) == families | {"tpu_inf_decode_dispatches_total",
                                      "tpu_inf_prefill_dispatches_total",
                                      "loop_wall_s"}
    assert 0.4 <= loop_d["loop_wall_s"] < 0.4 + 0.5
    # What the loop did in those seconds, and only that.
    total = loop_d["tpu_inf_loop_seconds_total"]
    assert 0.3 < total <= loop_d["loop_wall_s"] + 0.01
    assert loop_d["tpu_inf_loop_stage_fill_seconds_total"] > 0.1
    assert loop_d["tpu_inf_loop_starved_stage_seconds_total"] \
        == pytest.approx(loop_d["tpu_inf_loop_stage_seconds_total"])
    assert 10 < loop_d["tpu_inf_decode_dispatches_total"] < 150
    assert loop_d["tpu_inf_prefill_dispatches_total"] == 0
    json.dumps(out)                            # the response is JSON
