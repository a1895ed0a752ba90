#!/usr/bin/env python3
"""``planted_fault.py`` for a ``bailing_hybrid`` configuration (delta-rule
layers with a matrix state a head beside latent attention, over experts
routed within groups): ``parity.py``'s own run of a seed with ONE fault
planted in what that stack adds to the program. Every fault must read NOT
correct.

  state_not_carried    a prefill chunk behind the first starts from zeros
                       too: the state does not cross a chunk boundary
  padded_advances      the padded positions of a prefill bucket advance the
                       state (and end in the conv tail)
  masked_step_advances a decode step a lane is masked for advances its
                       state. (parity.py's streams are never masked, so
                       this fault comes with a scenario: every lane is
                       granted at most 3 of a call's 8 steps, which by
                       itself changes nothing, tests/test_bailing_hybrid.py.)
  decay_per_head       the decay is one scalar a head (the mean of its
                       channels') and not a channel's own
  no_erase             the ``- beta k k^T`` term is left out: the state is
                       a decayed sum of ``beta k v^T``
  conv_tail_lost       the convolution starts every call from zeros: its
                       last three inputs do not cross a call's boundary
  group_limit_ignored  the router takes the top k of ALL experts
  gates_with_bias      the gates are the chosen experts' scores WITH the
                       selection bias in, normalised

The faults replace functions of the program in this process only
(``tpu_inference.engine.engine``'s ``PagedState`` /
``_grant_decode_steps``, ``tpu_inference.models.deepseek_v3``'s
``group_limit`` / ``route``); nothing of it is a program option. Same
arguments, lines and exit code as ``planted_fault.py``, whose ``main``
this runs with the faults below.
"""

from __future__ import annotations

FAULTS = ("state_not_carried", "padded_advances", "masked_step_advances",
          "decay_per_head", "no_erase", "conv_tail_lost",
          "group_limit_ignored", "gates_with_bias")


def plant(fault: str):
    """Put the fault in; returns the function that takes it out."""
    import jax
    import jax.numpy as jnp

    from tpu_inference.engine import engine
    from tpu_inference.models import deepseek_v3

    was = dict(state=engine.PagedState,
               grant=engine.InferenceEngine._grant_decode_steps,
               limit=deepseek_v3.group_limit, route=deepseek_v3.route)

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

        def tail(self, layer, kv):
            tail = super().tail(layer, kv)
            return tail * 0 if fault == "conv_tail_lost" else tail

        def delta(self, layer, q, k, v, g, beta, kv):
            if fault == "decay_per_head":
                g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True),
                                     g.shape)
            if fault != "no_erase":
                return super().delta(layer, q, k, v, g, beta, kv)
            live = jnp.arange(q.shape[1])[None, :] < self.lens[:, None]
            s0 = jnp.where(self.fresh[:, None, None, None], 0.0,
                           kv.ssm_h[layer, self.slots])

            def step(s, t):
                q_t, k_t, v_t, g_t, b_t, on = t
                new = (s * jnp.exp(g_t)[..., None]
                       + (b_t[..., None] * k_t)[..., None]
                       * v_t[:, :, None, :])
                s = jnp.where(on[:, None, None, None], new, s)
                return s, jnp.einsum("bhk,bhkv->bhv", q_t, s)

            tm = lambda a: jnp.moveaxis(a.astype(jnp.float32), 1, 0)  # noqa
            s, o = jax.lax.scan(step, s0, (tm(q), tm(k), tm(v), tm(g),
                                           tm(beta),
                                           jnp.moveaxis(live, 1, 0)))
            return jnp.moveaxis(o, 0, 1), kv._replace(
                ssm_h=kv.ssm_h.at[layer, self.slots_w].set(s))

    def short_grants(self, seq, k_steps, *args, **kw):
        return was["grant"](self, seq, min(k_steps, 3), *args, **kw)

    def no_limit(cfg, ranked):
        return ranked, jnp.ones((ranked.shape[0], cfg.n_group), bool)

    def gates_with_bias(cfg, lp, x2):
        top, _ = was["route"](cfg, lp, x2)
        _, ranked = deepseek_v3.router_scores(cfg, lp, x2)
        g = jnp.take_along_axis(ranked, top, axis=1)
        return top, cfg.routed_scaling_factor * g / jnp.sum(
            g, axis=1, keepdims=True)

    if fault == "group_limit_ignored":
        deepseek_v3.group_limit = no_limit
    elif fault == "gates_with_bias":
        deepseek_v3.route = gates_with_bias
    else:
        engine.PagedState = State
        if fault == "masked_step_advances":
            engine.InferenceEngine._grant_decode_steps = short_grants

    def restore():
        engine.PagedState = was["state"]
        engine.InferenceEngine._grant_decode_steps = was["grant"]
        deepseek_v3.group_limit, deepseek_v3.route = (was["limit"],
                                                      was["route"])

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
