#!/usr/bin/env python3
"""``planted_fault.py`` for the SmallThinker configuration: ``parity.py``'s
own run of a seed with ONE fault planted in what this architecture adds
to the program. Every fault of ``FAULTS`` must read NOT correct.

  router_reads_h2      the router is fed the experts' input (the
                       post-attention norm) instead of the layer's
                       normed input
  silu_for_relu        the experts gate with silu
  gates_uniform        each of the six chosen experts weighs 1/6: the
                       token's own gates are not used
  wrong_sixth          the least of a token's six experts is the one
                       with the next index: a wrong top-k of one place
  next_expert          every tile of rows is computed with the next
                       expert's weights (``planted_fault.py``'s fault)
  rope_on_full         a full layer turns q and k with the window kind's
                       rope instead of none
  window_as_full       the window kind's layers attend over the whole
                       context (``planted_fault_mixed.py``'s fault: their
                       pages behind the window were released)
  routed_zeroed        the routed sum is left out (as ``planted_fault.py``'s
                       ``routed_zero``: with no shared expert, the layer
                       is its attention alone)

and one of ``NATURAL_ONLY``, asked for by name (``--faults``):

  softmax_over_all     the gates are the softmax over all 64 experts,
                       not renormalised over the six chosen. What it
                       moves is the share of the softmax's mass outside
                       the six: 64% at natural routing with logits of
                       spread 1, e^-19 or less behind the margin that
                       pins the real configuration's parity weights
                       (``assumed.weights.pinned``), where it reads
                       correct by construction. It is held where the
                       routing is natural: the tiny configuration's
                       rehearsal and tests/test_smallthinker.py, on the
                       CPU.

The faults replace functions of the program in this process only
(``tpu_inference.models.laguna.route`` / ``moe_ffn`` / ``attention``,
which ``models/smallthinker.py`` runs,
``tpu_inference.kernels.moe_experts.grouped_experts``, and the borrowed
one as its file says); nothing of it is a program option. Same arguments, lines and
exit code as ``planted_fault.py``, whose ``main`` this runs with the
faults below.
"""

from __future__ import annotations

import dataclasses

FAULTS = ("router_reads_h2", "silu_for_relu", "gates_uniform",
          "wrong_sixth", "next_expert", "rope_on_full", "window_as_full",
          "routed_zeroed")
NATURAL_ONLY = ("softmax_over_all",)


def plant(fault: str):
    """Put the fault in; returns the function that takes it out."""
    import planted_fault_mixed
    from tpu_inference.kernels import moe_experts
    from tpu_inference.models import laguna

    if fault == "window_as_full":
        return planted_fault_mixed.plant(fault)

    route, moe, attention = laguna.route, laguna.moe_ffn, laguna.attention
    grouped = moe_experts.grouped_experts

    def routed_zeroed(*args, **kw):
        y, done = grouped(*args, **kw)
        return y * 0.0, done

    def next_expert(x, groups, *args, **kw):
        held = groups.counts.shape[0]
        return grouped(x, groups._replace(
            tile_expert=(groups.tile_expert + 1) % held), *args, **kw)

    def with_cfg(fn, **changes):
        def planted(cfg, *args, **kw):
            return fn(dataclasses.replace(cfg, **changes), *args, **kw)
        return planted

    if fault == "router_reads_h2":
        # No early routing: ``moe_ffn`` then routes its own input.
        laguna.route = lambda cfg, lp, x2: None
    elif fault == "silu_for_relu":
        laguna.moe_ffn = with_cfg(moe, moe_act="silu")
    elif fault == "softmax_over_all":
        laguna.route = with_cfg(route, norm_topk_prob=False)
    elif fault == "gates_uniform":
        def uniform(cfg, lp, x2):
            top, gates = route(cfg, lp, x2)
            return top, gates * 0.0 + 1.0 / cfg.n_experts_per_tok
        laguna.route = uniform
    elif fault == "wrong_sixth":
        def wrong_sixth(cfg, lp, x2):
            top, gates = route(cfg, lp, x2)
            return top.at[:, -1].set((top[:, -1] + 1) % cfg.n_experts), gates
        laguna.route = wrong_sixth
    elif fault == "next_expert":
        moe_experts.grouped_experts = next_expert
    elif fault == "rope_on_full":
        laguna.attention = with_cfg(attention, nope_kinds=())
    elif fault == "routed_zeroed":
        moe_experts.grouped_experts = routed_zeroed
    else:
        raise ValueError(f"no fault {fault!r}: {FAULTS + NATURAL_ONLY}")

    def restore():
        laguna.route, laguna.moe_ffn, laguna.attention = (route, moe,
                                                          attention)
        moe_experts.grouped_experts = grouped

    return restore


if __name__ == "__main__":
    # Here and not at import: tests load this file for ``plant`` alone,
    # and bench/ on their path would shadow the repo's ``tests`` package.
    import os
    import sys

    HERE = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.dirname(HERE))
    sys.path.insert(0, HERE)
    import planted_fault

    planted_fault.plant, planted_fault.FAULTS = plant, FAULTS
    sys.exit(planted_fault.main())
