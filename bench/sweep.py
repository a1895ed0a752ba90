#!/usr/bin/env python3
"""The knee sweep of an open-loop cell: several rates, a fresh server each.

    python3 bench/sweep.py --workload <cell> --rates 2,2.5,3,3.5,4 \
        --seconds 40 --seed 11

Run once, by hand, on the chip, when a steady cell is defined; the cell's
traffic file then gets 0.7 of the knee as its ``rate`` and PERF.md the
table. The knee is the highest rate at which at least 90% of the counted
requests meet the mix's ``ttft_limit_s`` and ``tpot_limit_s`` and the mean
number in flight over the window's last quarter is no more than over its
first by more than 4 requests or a quarter (a handful in flight swings by
that much from arrivals alone). Same paced traffic, warm lap and
arithmetic as run.py; no output check (run.py does that). Every rate gets
a server of its own: on a shared one the pool of an earlier, overloaded
window was still full in the next (PERF.md section 4).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import metrics as M  # noqa: E402
import traffic as T  # noqa: E402
from manifest import Manifest  # noqa: E402
from run import run_window  # noqa: E402
from server import Server  # noqa: E402


def in_flight(records: list, t: float) -> int:
    return sum(1 for r in records
               if r["sent_s"] is not None and r["sent_s"] <= t
               and not (r["done_s"] is not None and r["done_s"] <= t)
               and not (r["failed_s"] is not None and r["failed_s"] <= t))


def quarter(records: list, start: float, seconds: float) -> float:
    """Mean requests in flight over a quarter of the window."""
    ts = [(start + 0.25 * (k + 0.5) / 20) * seconds for k in range(20)]
    return sum(in_flight(records, t) for t in ts) / len(ts)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--manifest",
                    default=os.path.join(REPO, "BENCHMARK.json"))
    args = ap.parse_args()
    man = Manifest(args.manifest)
    cell = man.cell(args.workload)
    cfg = man.config(cell)
    traffic = T.load(man.traffic_path(cell))
    out_dir = os.path.join(REPO, ".bench_out", cell["name"] + ".sweep")
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        shutil.rmtree(out_dir, ignore_errors=True)
        server = Server(REPO, cfg["serving"]["flags"], out_dir)
        try:
            server.wait_ready()
            reqs = T.open_loop(traffic, args.seed + i, args.seconds, rate)
            win = run_window(server, traffic, reqs, args.seconds, False)
            kind = server.device()["kind"]
        finally:
            server.stop()
        recs, drain = win["records"], float(traffic["drain_s"])
        split = M.counted(recs, "open", args.seconds, drain)
        ok, n = split["ok"], len(split["ok"]) + len(split["failed"])
        met = sum(1 for r in ok if M.met_limits(
            r, traffic["ttft_limit_s"], traffic["tpot_limit_s"]))
        e2e = M.end_to_end(recs, split, args.seconds)
        tp = [x for x in (M.tpot(r) for r in ok) if x is not None]
        row = {"rate": rate, "device": kind, "attempted": n,
               "failed": e2e["failed"],
               "met_share_pct": 100.0 * met / max(n, 1),
               "ttft_mean_s": e2e["ttft_mean_s"],
               "ttft_p90_s": M.percentile([M.ttft(r) for r in ok], 90),
               "tpot_mean_s": M.mean(tp), "tpot_p50_s": e2e["tpot_p50_s"],
               "gap_p99_s": e2e["gap_p99_s"],
               "in_flight_open": quarter(recs, 0.0, args.seconds),
               "in_flight_close": quarter(recs, 0.75, args.seconds),
               "compiles": win["compiles_in_window"]}
        row["sustained"] = (
            row["met_share_pct"] >= 90.0
            and row["in_flight_close"] <= max(
                row["in_flight_open"] + 4, 1.25 * row["in_flight_open"]))
        rows.append(row)
        print(json.dumps(row), flush=True)
    good = [r["rate"] for r in rows if r["sustained"]]
    print(json.dumps({"knee": max(good) if good else None,
                      "rate_0.7": round(0.7 * max(good), 1) if good
                      else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
