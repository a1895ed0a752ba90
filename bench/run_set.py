#!/usr/bin/env python3
"""One cell on several seeds, one run after another, then the spread of
every end-to-end candidate: how a bound is set (builder's contract: the
spread is the distance between the first and third quartile, as
``statistics.quantiles(values, n=4)`` gives them, over the median).

    python3 bench/run_set.py --workload <cell> --seeds 1,2,3,4,5,6 \
        --seconds 48 [--out chiprun_out/<dir>]

By hand, on the chip. Every run is a fresh ``run.py`` process, as the
driver's are; each run's whole stdout is kept under ``--out``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    if args.out:
        os.makedirs(args.out, exist_ok=True)
    runs = []
    for seed in args.seeds.split(","):
        t0 = time.monotonic()
        p = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload",
             args.workload, "--seed", seed, "--seconds", args.seconds,
             "--trace", args.trace],
            capture_output=True, text=True)
        if args.out:
            with open(os.path.join(args.out, f"run_{seed}.out"), "w") as f:
                f.write(p.stdout + "\n--- stderr ---\n" + p.stderr[-4000:])
        lines = [json.loads(x) for x in p.stdout.splitlines()
                 if x.startswith("{")]
        row = {"seed": int(seed), "rc": p.returncode,
               "wall_s": round(time.monotonic() - t0, 1)}
        for line in lines:
            if "candidates" in line:
                row.update(line["candidates"])
            if "window" in line:
                row["window"] = line["window"]
            if "correct" in line:
                row["correct"] = line["correct"]
                row["result_metrics"] = {k: v["value"] for k, v
                                         in line["metrics"].items()}
            if "compared" in line and "logit_err_rms" in line["compared"]:
                row["parity"] = line["compared"]
        if p.returncode != 0:
            row["stderr"] = p.stderr[-1500:]
        runs.append(row)
        print(json.dumps(row), flush=True)
    good = [r for r in runs if r["rc"] == 0]
    if len(good) >= 3:
        keys = [k for k, v in good[0].items()
                if isinstance(v, float) and k != "wall_s"]
        for k in keys:
            vals = [r[k] for r in good if r.get(k) is not None]
            if len(vals) >= 3:
                print(json.dumps({"metric": k, "median":
                                  statistics.median(vals),
                                  "spread": spread(vals), "values": vals}),
                      flush=True)
    return 0 if len(good) == len(runs) else 1


if __name__ == "__main__":
    sys.exit(main())
