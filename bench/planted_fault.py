#!/usr/bin/env python3
"""A control of the comparison that decides ``correct`` for a routed-expert
configuration: ``parity.py``'s own run of a seed (same weights, streams,
engine path, limits and judge) with ONE fault planted in the program's
routed-expert path. Every fault must read NOT correct; a fault that reads
correct says the comparison does not see the routed part.

  routed_zero    the grouped experts return zero (only the shared expert
                 and attention are left)
  next_expert    every tile is computed with the next held expert's weights
  first_layer    every expert layer reads the first expert layer's weights
  gates_doubled  the gates are twice what the router gives

The faults replace functions of the program in this process only
(``tpu_inference.kernels.moe_experts.grouped_experts``,
``tpu_inference.models.deepseek_v3.route``); nothing of it is a program
option. One JSON line per (fault, seed), then a summary line whose ``ok``
is true when every reading was over a limit. Exit code 0 then, 1 otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import parity  # noqa: E402
from manifest import Manifest, load_module  # noqa: E402


def plant(fault: str):
    """Put the fault in; returns the function that takes it out."""
    from tpu_inference.kernels import moe_experts
    from tpu_inference.models import deepseek_v3

    grouped, route = moe_experts.grouped_experts, deepseek_v3.route

    def routed_zero(x, groups, wg, wu, wd, layer, **kw):
        y, done = grouped(x, groups, wg, wu, wd, layer, **kw)
        return y * 0.0, done

    def next_expert(x, groups, wg, wu, wd, layer, **kw):
        held = groups.counts.shape[0]
        wrong = groups._replace(tile_expert=(groups.tile_expert + 1) % held)
        return grouped(x, wrong, wg, wu, wd, layer, **kw)

    def first_layer(x, groups, wg, wu, wd, layer, **kw):
        return grouped(x, groups, wg, wu, wd, layer * 0, **kw)

    def gates_doubled(cfg, lp, x2):
        top, gates = route(cfg, lp, x2)
        return top, gates * 2.0

    if fault == "gates_doubled":
        deepseek_v3.route = gates_doubled
    else:
        moe_experts.grouped_experts = {"routed_zero": routed_zero,
                                       "next_expert": next_expert,
                                       "first_layer": first_layer}[fault]

    def restore():
        moe_experts.grouped_experts, deepseek_v3.route = grouped, route

    return restore


FAULTS = ("routed_zero", "next_expert", "first_layer", "gates_doubled")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--manifest", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--faults", default=",".join(FAULTS))
    args = ap.parse_args()

    man = Manifest(args.manifest)
    cell = man.cell(args.workload)
    cfg = man.config(cell)
    ref_mod = load_module(os.path.join(HERE, "references",
                                       cfg["reference"] + ".py"))
    srv = cfg["serving"]

    from tpu_inference.runtime import (enable_compile_cache,
                                       require_backend, select_platform)
    select_platform(srv["platform"], cpu_devices=max(4, cell["chips"]))
    enable_compile_cache()
    require_backend(srv["platform"])

    limit = cfg["parity"]["limit"]
    shared = cfg["parity"].get("shared_prefix", 0)
    all_over = True
    for fault in args.faults.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            restore = plant(fault)
            try:
                res = parity.one_seed(cfg, ref_mod, seed, control=False)
            finally:
                restore()
            res.pop("streams", None)
            res.update(fault=fault, limit=limit,
                       ok=parity.judge(res, limit, shared))
            all_over = all_over and not res["ok"]
            print(json.dumps(res), flush=True)
    print(json.dumps({"planted_fault": True, "ok": all_over}), flush=True)
    return 0 if all_over else 1


if __name__ == "__main__":
    sys.exit(main())
