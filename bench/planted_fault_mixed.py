#!/usr/bin/env python3
"""``planted_fault.py`` for a configuration whose layers differ in kind
(Laguna): ``parity.py``'s own run of a seed with ONE fault planted in what
the mixed stack adds to the program. Every fault must read NOT correct.

  window_as_full       the window kind's layers attend over the whole
                       context (their pages behind the window were
                       released: they read what is left there)
  full_as_window       the full kind's layers see the last
                       ``sliding_window`` keys only
  sliding_rope_on_full a full layer turns every dim with the window
                       kind's plain rope instead of YaRN on its half
  gate_left_out        the per-head output gate is not applied
  routed_zeroed        the routed experts' part is left out (the shared
                       expert alone)

The faults replace functions of the program in this process only
(``tpu_inference.engine.engine.make_paged_attn``,
``tpu_inference.models.laguna.attention`` / ``moe_ffn``); nothing of it is
a program option. Same arguments, lines and exit code as
``planted_fault.py``, whose ``main`` this runs with the faults below.
"""

from __future__ import annotations

import dataclasses

FAULTS = ("window_as_full", "full_as_window", "sliding_rope_on_full",
          "gate_left_out", "routed_zeroed")


def plant(fault: str):
    """Put the fault in; returns the function that takes it out."""
    from tpu_inference.engine import engine
    from tpu_inference.models import laguna

    paged, attention, moe = (engine.make_paged_attn, laguna.attention,
                             laguna.moe_ffn)

    def swapped_window(cfg, *args, sliding_window=None, **kw):
        """A kind's call (``sliding_window`` given) with the other's."""
        if sliding_window is not None and (
                (sliding_window > 0) == (fault == "window_as_full")):
            sliding_window = cfg.sliding_window - sliding_window
        return paged(cfg, *args, sliding_window=sliding_window, **kw)

    def full_layer_with(changes):
        def planted(cfg, kind, *args):
            if kind == "full":
                cfg = dataclasses.replace(cfg, **changes(cfg))
            return attention(cfg, kind, *args)
        return planted

    def gate_left_out(cfg, *args):
        return attention(dataclasses.replace(cfg, attn_gate="none"), *args)

    def routed_zeroed(cfg, lp, experts, moe_layer, h, attn):
        _, stats = moe(cfg, lp, experts, moe_layer, h, attn)
        return laguna.swiglu(h, lp["ws_gate"], lp["ws_up"],
                             lp["ws_down"]), stats

    if fault in ("window_as_full", "full_as_window"):
        engine.make_paged_attn = swapped_window
    elif fault == "sliding_rope_on_full":
        laguna.attention = full_layer_with(lambda cfg: dict(
            rope_theta=cfg.window_rope_theta, rope_scaling=None,
            partial_rotary_factor=1.0))
    elif fault == "gate_left_out":
        laguna.attention = gate_left_out
    else:
        laguna.moe_ffn = routed_zeroed

    def restore():
        engine.make_paged_attn = paged
        laguna.attention, laguna.moe_ffn = attention, moe

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
