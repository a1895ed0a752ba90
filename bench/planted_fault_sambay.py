#!/usr/bin/env python3
"""``planted_fault.py`` for a SambaY configuration (state-space layers with
a state slot a sequence, one full-attention KV slot that the cross layers
read, gated memory units, differential attention): ``parity.py``'s own run
of a seed with ONE fault planted in what that stack adds to the program.
Every fault must read NOT correct.

  state_not_carried    a prefill chunk behind the first starts from zeros
                       too: the state does not cross a chunk boundary
  padded_advances      the padded positions of a prefill bucket advance the
                       state (and end in the conv tail)
  masked_step_advances a decode step a lane is masked for advances its
                       state. (parity.py's streams are never masked, so
                       this fault comes with a scenario: every lane is
                       granted at most 3 of a call's 8 steps, which by
                       itself changes nothing, tests/test_sambay.py.)
  m_after_gate         the memory units get the middle scan's output AFTER
                       the z gate
  cross_reads_zeros    a cross layer attends over keys and values of its
                       own, which it does not have: zeros
  window_as_full       the window kind's layers attend over the whole
                       context (their pages behind the window were
                       released: they read what is left there)
  lam0_wrong_layer     differential attention's lam0 of a layer four
                       further down
  q1_with_k2           the query halves are paired with the other half's
                       keys

The faults replace functions of the program in this process only
(``tpu_inference.engine.engine``'s ``PagedState`` / ``make_paged_attn`` /
``_grant_decode_steps``, ``tpu_inference.models.sambay``'s ``ssm_mix`` /
``diff_attention`` / ``lam0`` / ``diff_queries``); nothing of it is a
program option. Same arguments, lines and exit code as
``planted_fault.py``, whose ``main`` this runs with the faults below.
"""

from __future__ import annotations

FAULTS = ("state_not_carried", "padded_advances", "masked_step_advances",
          "m_after_gate", "cross_reads_zeros", "window_as_full",
          "lam0_wrong_layer", "q1_with_k2")


def plant(fault: str):
    """Put the fault in; returns the function that takes it out."""
    import jax
    import jax.numpy as jnp

    from tpu_inference.engine import engine
    from tpu_inference.models import sambay

    was = dict(state=engine.PagedState, paged=engine.make_paged_attn,
               grant=engine.InferenceEngine._grant_decode_steps,
               ssm=sambay.ssm_mix, attention=sambay.diff_attention,
               lam0=sambay.lam0, queries=sambay.diff_queries)

    class State(engine.PagedState):
        def __init__(self, slots, valid, q_offset, *args, **kw):
            super().__init__(slots, valid, q_offset, *args, **kw)
            prefill = valid.shape[1] > 1
            if fault == "state_not_carried" and prefill:
                self.fresh = jnp.ones_like(self.fresh)
            if fault == "padded_advances" and prefill:
                self.lens = jnp.full_like(self.lens, valid.shape[1])
            if fault == "masked_step_advances" and not prefill:
                self.lens = jnp.ones_like(self.lens)
                self.slots_w = slots

    def short_grants(self, seq, k_steps, *args, **kw):
        return was["grant"](self, seq, min(k_steps, 3), *args, **kw)

    def m_after_gate(cfg, slot, lp, h, kv, attn):
        out, y, kv = was["ssm"](cfg, slot, lp, h, kv, attn)
        z = sambay.qdot(h, lp["w_in"])[..., cfg.d_inner:]
        return out, (y.astype(jnp.float32)
                     * jax.nn.silu(z)).astype(y.dtype), kv

    def cross_reads_zeros(cfg, kind, slot, l, ap, h, kv, attn):
        if kind != "cross":
            return was["attention"](cfg, kind, slot, l, ap, h, kv, attn)

        class Zeros:
            kinds = {"cross": lambda slot, q, k, v, kv: (
                jnp.zeros_like(q), kv)}
        return was["attention"](cfg, kind, slot, l, ap, h, kv, Zeros)

    def window_as_full(cfg, *args, sliding_window=None, **kw):
        return was["paged"](cfg, *args, sliding_window=sliding_window
                            and 0, **kw)

    def q1_with_k2(cfg, q, dtype):
        b, s, _ = q.shape
        half, rep, hd = cfg.n_kv_heads // 2, cfg.n_rep, cfg.head_dim
        out = was["queries"](cfg, q, dtype).reshape(b, s, half, 2, rep, 2, hd)
        return out[..., ::-1, :].reshape(b, s, cfg.n_heads, 2 * hd)

    if fault in ("state_not_carried", "padded_advances",
                 "masked_step_advances"):
        engine.PagedState = State
        if fault == "masked_step_advances":
            engine.InferenceEngine._grant_decode_steps = short_grants
    elif fault == "m_after_gate":
        sambay.ssm_mix = m_after_gate
    elif fault == "cross_reads_zeros":
        sambay.diff_attention = cross_reads_zeros
    elif fault == "window_as_full":
        engine.make_paged_attn = window_as_full
    elif fault == "lam0_wrong_layer":
        sambay.lam0 = lambda l: was["lam0"](l + 4)
    else:
        sambay.diff_queries = q1_with_k2

    def restore():
        engine.PagedState, engine.make_paged_attn = was["state"], was["paged"]
        engine.InferenceEngine._grant_decode_steps = was["grant"]
        sambay.ssm_mix, sambay.diff_attention = was["ssm"], was["attention"]
        sambay.lam0, sambay.diff_queries = was["lam0"], was["queries"]

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
