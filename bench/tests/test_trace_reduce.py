"""The reduction from a profiler trace to busy/idle, per-op and per-program
times, on a small recorded trace: tests/data/trace_events.json is the
first 120 ms of a 3 s profile of mistral-7b-int8_chat-steady on the v5e
(this PR's chip run), as ``trace_reduce.extract`` returns it."""

import json
import os

import pytest

import trace_reduce as TR

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def events():
    with open(os.path.join(HERE, "data", "trace_events.json")) as f:
        return json.load(f)


def test_short_name():
    hlo = ("%paged_attention.11 = bf16[16,8,4,128]{3,2,1,0:T(8,128)(2,1)} "
           "custom-call(s32[16,320]{1,0} %x), custom_call_target=\"tpu\"")
    assert TR.short_name(hlo) == "paged_attention.11_bf16_16_8_4_128_"
    assert TR.short_name("%while.25 = (s32[]{:T(128)}, bf16[2]) while(...)") \
        == "while.25"
    assert TR.short_name("fusion.3") == "fusion.3"


def test_self_time_takes_nested_ops_out():
    ev = [["while.1", 0, 100], ["fusion.1_f32_8_", 10, 20],
          ["kernel.2_bf16_4_", 40, 50], ["fusion.9_f32_8_", 120, 5]]
    by = TR._by_name(ev)
    assert by["while.1"] == [1, pytest.approx(30e-9)]
    assert by["kernel.2_bf16_4_"] == [1, pytest.approx(50e-9)]
    assert sum(v[1] for v in by.values()) == pytest.approx(105e-9)


def test_union_merges_overlaps():
    ev = [["a", 0, 10], ["b", 5, 10], ["c", 30, 5], ["d", 35, 1]]
    assert TR._union(ev) == [[0, 15], [30, 36]]


def test_recorded_trace_reduces(events):
    s = TR.summarise(events)
    chip = s["chips"]["/device:TPU:0"]
    ops = events["chips"]["/device:TPU:0"]
    span = (max(e[1] + e[2] for e in ops) - min(e[1] for e in ops)) / 1e9
    assert chip["span_s"] == pytest.approx(span)
    assert 0 < chip["busy_s"] <= chip["span_s"]
    # Busy is a union, never the plain sum of nested/overlapping events.
    assert chip["busy_s"] <= sum(e[2] for e in ops) / 1e9 + 1e-12
    assert s["busy_s"] == pytest.approx(chip["busy_s"])
    assert s["window_s"] == pytest.approx(span)
    assert s["idle_share_worst"] == pytest.approx(
        1 - chip["busy_s"] / chip["span_s"])
    names = [n for n, _ in s["device_ops"]]
    assert any(n.startswith("paged_attention") for n in names)
    secs = [v for _, v in s["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    # Per-op seconds are SELF times (a while loop minus its body), so
    # they add up to the busy time, not to the sum of nested durations.
    assert sum(v[1] for v in chip["ops"].values()) == pytest.approx(
        chip["busy_s"], rel=0.01)
    assert not any(n.startswith("while") for n in names[:3])


def test_programs_hold_their_ops(events):
    s = TR.summarise(events)
    assert s["modules"], "the recorded trace has XLA Modules events"
    decode = [m for m in s["modules"].values()
              if any(n.startswith("paged_attention") for n in m["ops"])]
    assert decode, "a decode program ran in the recorded slice"
    for m in s["modules"].values():
        assert m["runs"] >= 1 and m["seconds"] > 0
        assert len(m["starts"]) == m["runs"]
        # A program starts a hair before its first op.
        assert all(-1e-3 <= t <= s["window_s"] for t in m["starts"])


def test_idle_gaps_are_named_by_the_dispatching_thread(events):
    s = TR.summarise(events)
    assert s["idle_gaps"]
    thread = TR._dispatch_thread(events["host"])
    assert any(e[0].startswith("PjitFunction") or "Execute" in e[0]
               for e in thread)
    for name, sec in s["idle_gaps"]:
        assert sec > 0 and " " not in name and not name.startswith("$")


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(SystemExit):
        TR.summarise({"chips": {}, "modules": {}, "host": {}})
