#!/usr/bin/env python3
"""Profiler trace -> the summary the per-layer readers read.

    python3 bench/trace_reduce.py <file.xplane.pb> <summary.json> \
        [--events out.json]

Reads the trace file with ``jax.profiler.ProfileData`` (the only use of
JAX; the caller pins this process to the CPU) and reduces it in two steps
kept apart so the second can be tested on a small recorded trace
(tests/data/trace_events.json, written with ``--events``):

  extract(path)   -> {"chips": {plane: [[name, start_ns, dur_ns], ...]},
                      "modules": {plane: [[name, start_ns, dur_ns], ...]},
                      "host": {thread: [[name, start_ns, dur_ns], ...]}}
  summarise(ev)   -> busy seconds per chip (union of op intervals), the
                     traced span, time per op name, per program (module)
                     with the start of each of its runs, and the longest
                     idle gaps named by the innermost host frame of the
                     dispatching thread active at their middle.

A device plane is one named ``/device:TPU:<n>``; its ops are the events
of its ``XLA Ops`` line and its programs those of ``XLA Modules``. Off a
TPU (a CPU rehearsal) the XLA thunk events of the host's executor threads
stand in as one "chip", so the code path is exercised; run.py never
reports such a run as a device's.
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List


def short_name(hlo: str) -> str:
    """'%fusion.2 = bf16[8,128]{1,0:T(8,128)} fusion(...)' (the TPU
    plane's event names are whole HLO instructions) ->
    'fusion.2_bf16_8_128_': the op's name and its result's type and
    shape, in the characters a metric name may have."""
    name, sep, rest = hlo.partition(" = ")
    name = name.lstrip("%")
    if not sep:
        return name
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    if shape.startswith("("):
        return name
    shape = "".join(c if c.isalnum() else "_" for c in shape)
    return f"{name}_{shape}"


def extract(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    host: Dict[str, list] = {}
    cpu_ops: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chips[plane.name] = [
                        [short_name(e.name), e.start_ns, e.duration_ns]
                        for e in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] = [
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for i, line in enumerate(plane.lines):
                evs = [[e.name, e.start_ns, e.duration_ns]
                       for e in line.events]
                if any(n.startswith("$") for n, _, _ in evs[:50]):
                    host[f"{line.name or 'thread'}#{i}"] = evs
                elif line.name.startswith("tf_XLA"):
                    cpu_ops.extend(
                        ev for ev in evs
                        if ev[2] > 0 and not ev[0].startswith(
                            ("ThreadpoolListener", "ThunkExecutor", "end:")))
    if not chips and cpu_ops:
        chips["/host:CPU (rehearsal)"] = sorted(cpu_ops, key=lambda e: e[1])
    return {"chips": chips, "modules": modules, "host": host}


def _union(events: list) -> list:
    """Merged [start, end] intervals of events (sorted by start)."""
    merged: List[list] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], s + d)
        else:
            merged.append([s, s + d])
    return merged


def _self_times(events: list) -> list:
    """[name, start, self_ns]: an op's duration minus the ops nested in
    it. The XLA Ops line nests: a ``while`` (the scan over layers) spans
    every op of its body, so plain durations would count a layer's
    kernels twice, once under their own name and once under the loop's."""
    out, stack = [], []          # stack of [end, index into out]
    for n, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][2] -= d
        out.append([n, s, d])
        stack.append([s + d, len(out) - 1])
    return out


def _by_name(events: list) -> Dict[str, list]:
    """{name: [calls, self seconds]}."""
    out: Dict[str, list] = {}
    for n, _, d in _self_times(events):
        slot = out.setdefault(n, [0, 0.0])
        slot[0] += 1
        slot[1] += d / 1e9
    return out


def _dispatch_thread(host: dict) -> list:
    """The python-traced thread that launches device programs: the one
    with the most runtime events of a launch (PjitFunction(...), ...
    Execute ...) among its frames; the HTTP loop's thread has none."""
    if not host:
        return []

    def launches(thread: list) -> tuple:
        n = sum(1 for e in thread if not e[0].startswith("$")
                and (e[0].startswith("PjitFunction") or "Execute" in e[0]))
        return (n, len(thread))

    return max(host.values(), key=launches)


def _ops_by_module(ops: list, mods: list) -> Dict[str, dict]:
    """Each program (module) name -> its runs, seconds, the start of each
    run (seconds from the first op of the chip) and the ops that ran
    inside its runs' intervals."""
    import bisect

    ops = sorted(ops, key=lambda e: e[1])
    starts = [e[1] for e in ops]
    out: Dict[str, dict] = {}
    for name, s, d in mods:
        slot = out.setdefault(name, {"runs": 0, "seconds": 0.0,
                                     "starts": [], "ops": {}})
        slot["runs"] += 1
        slot["seconds"] += d / 1e9
        slot["starts"].append((s - starts[0]) / 1e9 if starts else 0.0)
        lo, hi = bisect.bisect_left(starts, s), bisect.bisect_right(
            starts, s + d)
        for n, _, od in _self_times(ops[lo:hi]):
            o = slot["ops"].setdefault(n, [0, 0.0])
            o[0] += 1
            o[1] += od / 1e9
    return out


class _Frames:
    """The python frames of one thread, for 'innermost frame at t'."""

    def __init__(self, thread: list):
        import numpy as np

        frames = [e for e in thread if e[0].startswith("$")]
        self.names = [e[0] for e in frames]
        self.start = np.array([e[1] for e in frames], dtype=np.float64)
        self.end = self.start + np.array([e[2] for e in frames],
                                         dtype=np.float64)

    def at(self, t: float) -> str:
        """Innermost traced frame covering instant t (latest start)."""
        import numpy as np

        idx = np.nonzero((self.start <= t) & (self.end >= t))[0]
        if not len(idx):
            return "_no_frame"
        best = idx[np.argmax(self.start[idx])]
        return self.names[best][1:].replace(" ", "_")


def summarise(ev: dict) -> dict:
    chips = {}
    for plane, events in ev["chips"].items():
        busy = _union(events)
        span0 = min(s for _, s, _ in events)
        span1 = max(s + d for _, s, d in events)
        chips[plane] = {
            "busy_s": sum(e - s for s, e in busy) / 1e9,
            "span_s": (span1 - span0) / 1e9, "span_ns": [span0, span1],
            "ops": _by_name(events),
            "gaps": [[a[1], b[0]] for a, b in zip(busy, busy[1:])],
        }
    if not chips:
        raise SystemExit("the trace holds no device operation")
    worst = max(chips.values(), key=lambda c: 1 - c["busy_s"] / c["span_s"])
    frames = _Frames(_dispatch_thread(ev["host"]))
    gaps: Dict[str, float] = {}
    for s, e in sorted(worst["gaps"], key=lambda g: g[0] - g[1])[:300]:
        name = frames.at((s + e) / 2)
        gaps[name] = gaps.get(name, 0.0) + (e - s) / 1e9
    ops: Dict[str, float] = {}
    for c in chips.values():
        for n, (_, sec) in c["ops"].items():
            ops[n] = ops.get(n, 0.0) + sec / len(chips)
    # Programs of the first chip that has them (under tensor parallelism
    # every chip runs the same programs side by side).
    modules: Dict[str, dict] = {}
    for plane, mods in ev["modules"].items():
        modules = _ops_by_module(ev["chips"].get(plane, []), mods)
        break
    for c in chips.values():
        del c["gaps"]
    top = lambda d: sorted(([k, v] for k, v in d.items()),  # noqa: E731
                           key=lambda kv: -kv[1])
    return {
        "busy_s": sum(c["busy_s"] for c in chips.values()) / len(chips),
        "window_s": max(c["span_s"] for c in chips.values()),
        "idle_share_worst": 1 - worst["busy_s"] / worst["span_s"],
        "chips": chips, "modules": modules,
        "device_ops": top(ops)[:40], "idle_gaps": top(gaps)[:20],
    }


def main(argv: list) -> int:
    ev = extract(argv[1])
    out = argv[2]
    if "--events" in argv:
        # The first 0.4 s of every line: enough to read names and shapes
        # by hand, small enough to bring back from the chip.
        t0 = min(e[1] for evs in ev["chips"].values() for e in evs)
        cut = {k: {n: [e for e in evs if t0 <= e[1] < t0 + 4e8]
                   for n, evs in group.items()}
               for k, group in ev.items()}
        with open(argv[argv.index("--events") + 1], "w") as f:
            json.dump(cut, f)
    with open(out, "w") as f:
        json.dump(summarise(ev), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
