#!/usr/bin/env python3
"""A control of the comparison that decides ``correct`` for a
configuration with several residual streams (manifold-constrained
hyper-connections): ``parity.py``'s own run of a seed (same weights,
streams, engine path, limits and judge) with ONE fault planted in the
program. Every fault must read NOT correct; one that reads correct says
the comparison does not see that part.

  res_identity     H_res is the identity (no stream reads another)
  one_iteration    one Sinkhorn iteration for the configured twenty
  post_unscaled    H_post = sigmoid(.), without the factor 2
  unnormed         the coefficient head reads the streams un-normed
  first_tokens     one token's coefficients for its whole chunk
  streams_averaged every stream is the streams' mean after every layer
  routed_zero      the grouped experts return zero
  wrong_fourth     the least of a token's four experts is off by one

The faults replace functions of the program in this process only
(``tpu_inference.models.hyper_connections``, ``.deepseek_v3``,
``tpu_inference.kernels.moe_experts``); nothing of it is a program
option. One JSON line per (fault, seed), then a summary line whose ``ok``
is true when every reading was over a limit. Exit code 0 then, 1
otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

FAULTS = ("res_identity", "one_iteration", "post_unscaled", "unnormed",
          "first_tokens", "streams_averaged", "routed_zero", "wrong_fourth")
# Not faults of the equations but of their PRECISION, run on request
# (``--faults coef_bf16,mix_bf16``) to learn whether ``correct`` sees it:
# the coefficients rounded to bfloat16 before the mixes use them; the
# mixes multiplied and summed in bfloat16.
PRECISION = ("coef_bf16", "mix_bf16")


def plant(fault: str):
    """Put the fault in; returns the function that takes it out."""
    import jax.numpy as jnp

    from tpu_inference.kernels import moe_experts
    from tpu_inference.models import deepseek_v3
    from tpu_inference.models import hyper_connections as mhc

    kept = [(mhc, "sinkhorn"), (mhc, "stream_scale"), (mhc, "coefficients"),
            (deepseek_v3, "_block_streams"), (deepseek_v3, "route"),
            (moe_experts, "grouped_experts"), (mhc, "_streams")]
    was = [getattr(mod, name) for mod, name in kept]
    sinkhorn, _, coefficients, block, route, grouped, streams = was

    def res_identity(m, iters, eps):
        return jnp.broadcast_to(jnp.eye(m.shape[0], dtype=m.dtype)[..., None],
                                m.shape)

    def one_iteration(m, iters, eps):
        return sinkhorn(m, 1, eps)

    def unnormed(x2, eps):
        return jnp.ones(x2.shape[:1], jnp.float32)

    def post_unscaled(cfg, lp, sublayer, x, **kw):
        coef, err = coefficients(cfg, lp, sublayer, x, **kw)
        n = cfg.hc_mult
        return coef.at[..., n:2 * n].multiply(0.5), err

    def first_tokens(cfg, lp, sublayer, x, **kw):
        coef, err = coefficients(cfg, lp, sublayer, x, **kw)
        return jnp.broadcast_to(coef[:, :1], coef.shape), err

    def coef_bf16(cfg, lp, sublayer, x, **kw):
        coef, err = coefficients(cfg, lp, sublayer, x, **kw)
        return coef.astype(jnp.bfloat16).astype(jnp.float32), err

    class Bf16Coefficients:
        """What the mixes index for a coefficient column, in bfloat16."""

        def __init__(self, coef):
            self.coef = coef.astype(jnp.bfloat16)

        def __getitem__(self, index):
            return self.coef[index]

    def mix_bf16(cfg, lp, sublayer, x, **kw):
        coef, err = coefficients(cfg, lp, sublayer, x, **kw)
        return Bf16Coefficients(coef), err

    def streams_bf16(cfg, x):
        return [s.astype(jnp.bfloat16) for s in streams(cfg, x)]

    def streams_averaged(cfg, *args):
        x, *rest = block(cfg, *args)
        return (mhc.fan_out(cfg, (mhc.read_out(cfg, x).astype(jnp.float32)
                                  / cfg.hc_mult).astype(x.dtype)), *rest)

    def routed_zero(x, groups, wg, wu, wd, layer, **kw):
        y, done = grouped(x, groups, wg, wu, wd, layer, **kw)
        return y * 0.0, done

    def wrong_fourth(cfg, lp, x2):
        top, gates = route(cfg, lp, x2)
        return top.at[:, -1].set((top[:, -1] + 1) % cfg.n_experts), gates

    mod, name, fn = {
        "res_identity": (mhc, "sinkhorn", res_identity),
        "one_iteration": (mhc, "sinkhorn", one_iteration),
        "unnormed": (mhc, "stream_scale", unnormed),
        "post_unscaled": (mhc, "coefficients", post_unscaled),
        "coef_bf16": (mhc, "coefficients", coef_bf16),
        "mix_bf16": (mhc, "coefficients", mix_bf16),
        "first_tokens": (mhc, "coefficients", first_tokens),
        "streams_averaged": (deepseek_v3, "_block_streams", streams_averaged),
        "routed_zero": (moe_experts, "grouped_experts", routed_zero),
        "wrong_fourth": (deepseek_v3, "route", wrong_fourth)}[fault]
    setattr(mod, name, fn)
    if fault == "mix_bf16":
        mhc._streams = streams_bf16

    def restore():
        for (mod, name), fn in zip(kept, was):
            setattr(mod, name, fn)

    return restore


def main() -> int:
    sys.path.insert(0, os.path.dirname(HERE))
    sys.path.insert(0, HERE)
    import parity
    from manifest import Manifest, load_module

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
