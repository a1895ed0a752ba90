#!/usr/bin/env python3
"""``planted_fault.py`` for a looped configuration (Ouro): ``parity.py``'s
own run of a seed with ONE fault planted in what the loop adds to the
program. Every fault must read NOT correct.

  pass_dropped         the stack runs one pass fewer
  slot_without_pass    every pass reads and writes the FIRST pass's KV
                       slots (slot = layer, not pass * layers + layer)
  no_norm_between      the final norm is applied once, after the last
                       pass, and not between passes
  output_norms_skipped the two norms on the branches' outputs are left out

The faults replace functions of the program in this process only
(``tpu_inference.models.ouro.forward_hidden``, ``ouro.rms_norm``,
``tpu_inference.models.llama.decoder_block``); nothing of it is a program
option. Same arguments, lines and exit code as ``planted_fault.py``, whose
``main`` this runs with the faults below.
"""

from __future__ import annotations

import dataclasses

FAULTS = ("pass_dropped", "slot_without_pass", "no_norm_between",
          "output_norms_skipped")


def plant(fault: str):
    """Put the fault in; returns the function that takes it out."""
    from tpu_inference.models import llama, ouro

    forward_hidden, block, norm = (ouro.forward_hidden, llama.decoder_block,
                                   ouro.rms_norm)

    def pass_dropped(params, cfg, *args):
        return forward_hidden(params, dataclasses.replace(
            cfg, loop_steps=cfg.loop_steps - 1), *args)

    def slot_without_pass(cfg, slot, *args):
        return block(cfg, slot % cfg.n_layers, *args)

    def no_norm_between(params, cfg, *args):
        ouro.rms_norm = lambda x, w, eps: x
        try:
            x, kv = forward_hidden(params, cfg, *args)
        finally:
            ouro.rms_norm = norm
        return norm(x, params["final_norm"], cfg.norm_eps), kv

    def output_norms_skipped(cfg, *args):
        return block(dataclasses.replace(cfg, sandwich_norm=False), *args)

    if fault in ("pass_dropped", "no_norm_between"):
        ouro.forward_hidden = {"pass_dropped": pass_dropped,
                               "no_norm_between": no_norm_between}[fault]
    else:
        llama.decoder_block = {
            "slot_without_pass": slot_without_pass,
            "output_norms_skipped": output_norms_skipped}[fault]

    def restore():
        ouro.forward_hidden, llama.decoder_block = forward_hidden, block

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
