"""Speculative decoding: propose, target-model verify, exact acceptance.

**N-gram self-drafting** (``ngram_propose`` + ``verify_round``):
prompt-lookup decoding (Saxena 2023) — the host matches the sequence's
last N tokens against its own prompt+generated history and proposes the
continuation of the most recent match. No second model, no second KV
pool, no extra HBM; proposals are one-hot distributions, so greedy
acceptance degenerates to exact argmax match and sampled acceptance
stays distribution-exact (with p one-hot at d_i: accept iff
u < q_i(d_i); the rejection residual norm(max(q-p,0)) is q with d_i
zeroed, renormalized).

One spec round per device dispatch, all static shapes (SURVEY.md §7
hard part 6 — "variable acceptance lengths vs static shapes"):

1. **Propose** — on the host, between dispatches: up to gamma tokens
   d_1..d_gamma per slot.
2. **Verify** — the target model scores all gamma+1 positions in ONE
   forward: inputs [last, d_1..d_gamma] at positions ctx..ctx+gamma.
   This turns gamma sequential target steps into one MXU-friendly
   batched-matmul pass — the entire speedup.
3. **Accept** — standard rejection sampling against one-hot proposals:
   accept d_i with prob q_i(d_i); on first rejection emit a correction
   drawn from q_i with d_i zeroed; if all accepted, emit a bonus token
   from q_{gamma+1}.

Variable acceptance needs NO KV rollback in this engine: attention masks
the cache by per-sequence ``kv_len`` (= host ctx_len), so KV rows written
for rejected proposals are simply never attended to and get overwritten
when real tokens reach those positions.

Sampling filters (temperature, top-k, top-p) are applied to the target
distribution before the acceptance test, so speculation samples from
exactly the same filtered distribution as the plain decode path.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class VerifyRoundOut(NamedTuple):
    kv: object               # target KVPages
    emitted: jax.Array       # [B, gamma+1] int32, -1 padded
    n_accepted: jax.Array    # [B] int32 (proposals accepted, excl. final)


# The n-gram proposer scans at most this many trailing history tokens —
# matching is O(scan * n) numpy per sequence per round, and a match far
# behind a multi-thousand-token context rarely predicts the present.
NGRAM_SCAN_CAP = 8192


def ngram_propose(history, gamma: int, max_n: int,
                  min_n: int = 1) -> np.ndarray:
    """Prompt-lookup proposal (Saxena 2023): match the last n tokens of
    ``history`` (n from ``max_n`` down to ``min_n``) against the rest of
    the history and return up to ``gamma`` continuation tokens of the
    MOST RECENT match (recency wins: multi-turn echo repeats what was
    just said, not what opened the conversation).

    Pure numpy on the host — this runs inside the host bubble between
    device dispatches, proposing for every running slot per round.
    Returns an int32 array of length 0..gamma (empty = no match).
    """
    hist = np.asarray(history[-NGRAM_SCAN_CAP:], dtype=np.int32)
    length = len(hist)
    if gamma <= 0 or length < min_n + 1:
        return np.empty((0,), np.int32)
    for n in range(min(max_n, length - 1), min_n - 1, -1):
        pattern = hist[-n:]
        # Candidate starts 0..length-n-1: the match must end before the
        # final position so at least one continuation token exists (the
        # suffix matching itself proposes nothing).
        windows = np.lib.stride_tricks.sliding_window_view(
            hist[:-1], n)                         # [length-n, n]
        hits = np.nonzero((windows == pattern).all(axis=1))[0]
        if hits.size:
            start = int(hits[-1]) + n             # most recent match
            # The match hypothesis is "the stream repeats with period
            # length - start"; read the full γ proposal under it, tiling
            # past the end of history (a match one period from the end —
            # the repetition-loop steady state — would otherwise truncate
            # proposals to one period). For matches deep in the history
            # this indexes the plain continuation untiled.
            period = length - start
            idx = start + np.arange(gamma) % period
            return hist[idx].astype(np.int32, copy=True)
    return np.empty((0,), np.int32)


def _probs(logits: jax.Array, temperature: jax.Array, top_p: jax.Array,
           top_k: jax.Array) -> jax.Array:
    """The engine's actual sampling distribution per row (temperature +
    top-k + top-p filtered, renormalized); temperature<=0 = one-hot
    argmax. Using the *filtered* distributions for both p and q keeps
    rejection sampling exact w.r.t. what the non-spec path samples.
    logits [B, V] f32; temperature/top_p [B]; top_k [B] int32."""
    from tpu_inference.engine.sampling import apply_filters

    greedy = jax.nn.one_hot(jnp.argmax(logits, -1), logits.shape[-1],
                            dtype=jnp.float32)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = apply_filters(logits / temp, top_k, top_p)
    soft = jax.nn.softmax(scaled, axis=-1)
    return jnp.where((temperature <= 0.0)[:, None], greedy, soft)


def _sample_from(probs: jax.Array, key: jax.Array) -> jax.Array:
    """Categorical over probability rows (works for one-hot too)."""
    return jax.random.categorical(key, jnp.log(probs + 1e-30), axis=-1
                                  ).astype(jnp.int32)


def verify_round(engine, params, kv, tokens, ctx_lens, block_tables, cap,
                 active, drafts, n_prop, key, temperature, top_p, top_k,
                 rpen, rlast, window):
    """Verify-only spec round for host-proposed (one-hot) drafts — the
    device graph of speculation. Pure function of arrays; jitted
    by the engine with the KV pool donated, compiled once per ladder
    rung (the batch dim B is the rung; γ+1 is static).

    ``drafts`` [B, gamma] int32 host proposals, of which only the first
    ``n_prop[b]`` (0..gamma) are real — the rest are padding and forced
    rejections, so per-sequence adaptive γ lives INSIDE one compiled
    shape instead of multiplying graphs. Proposal probs are one-hot, so:
    greedy acceptance is exact argmax match (q one-hot at argmax: accept
    iff d_i == argmax); sampled acceptance is exact rejection sampling
    (accept with prob q_i(d_i); the correction draws from
    norm(max(q_i - onehot(d_i), 0)) = q_i with d_i zeroed).

    The repetition penalty COMPOSES here: position i's target
    distribution is penalized against the window rolled forward with
    d_1..d_i — exactly the window the sequential plain-decode path would
    hold if those drafts were its samples, and position i's row is only
    ever consumed when they were all accepted.

    No rollback: rejected/padded rows are dead KV (kv_len masking) and
    get overwritten by real tokens.
    Returns VerifyRoundOut; with n_prop==0 a round degenerates to one
    plain decode step (one forward, one emitted token).
    """
    from tpu_inference.engine.sampling import (apply_repeat_penalty,
                                               roll_window)

    ecfg = engine.engine_cfg
    # Active γ comes from the PROPOSAL width, not the config: the engine
    # compiles this graph at (every ladder rung) x (probe width 1, full
    # γ), so throttled lanes re-probe on a near-plain-cost narrow round
    # instead of paying the full verify width to learn they still don't
    # echo.
    gamma = drafts.shape[1]
    s_len = gamma + 1
    b = tokens.shape[0]
    vocab = engine.model_cfg.vocab_size

    # ------------------------------------------------------- verify
    tokens_in = jnp.concatenate([tokens[:, None], drafts], axis=1)
    ar = jnp.arange(s_len, dtype=jnp.int32)[None, :]
    positions = jnp.minimum(ctx_lens[:, None] + ar, ecfg.max_context - 1)
    valid = active[:, None] & (positions < cap[:, None])
    attn = engine._paged_attn(engine.model_cfg, block_tables, positions,
                              valid, q_offset=ctx_lens,
                              kv_len=ctx_lens + s_len)
    hidden, kv = engine.mod.forward_hidden(params, engine.model_cfg,
                                           tokens_in, positions, kv, attn)
    logits_all = engine.mod.unembed(params, engine.model_cfg, hidden)

    # Per-position penalty windows: window_i = base window rolled with
    # d_1..d_i (the state sequential decode would hold if those drafts
    # were its own samples — position i's row only matters when they
    # were all accepted, so this is exact, not approximate).
    def _roll(win, d):
        win = roll_window(win, d, active)
        return win, win
    _, rolled = jax.lax.scan(_roll, window, drafts.T)     # [g, B, W]
    win_seq = jnp.concatenate([window[None], rolled], axis=0)

    def _pen(logits_i, win_i):
        return apply_repeat_penalty(logits_i, win_i, rpen, rlast)
    logits_all = jax.vmap(_pen, in_axes=(1, 0), out_axes=1)(
        logits_all, win_seq)

    # All-greedy rounds (the byte-identity serving hot path) skip the
    # per-position [B, V] sort+softmax of the filtered branch entirely —
    # same lax.cond fast path sampling.sample takes. jnp.where alone
    # would still compute both branches.
    def _greedy_rows(_):
        return jax.nn.one_hot(jnp.argmax(logits_all, -1), vocab,
                              dtype=jnp.float32)

    def _filtered_rows(_):
        return jax.vmap(_probs, in_axes=(1, None, None, None),
                        out_axes=1)(logits_all, temperature, top_p,
                                    top_k)
    q_rows = jax.lax.cond(jnp.all(temperature <= 0.0), _greedy_rows,
                          _filtered_rows, None)           # [B, g+1, V]

    # ------------------------------------------------------- accept
    d_idx = drafts[..., None]                             # [B, g, 1]
    q_d = jnp.take_along_axis(q_rows[:, :gamma], d_idx, -1)[..., 0]
    u = jax.random.uniform(jax.random.fold_in(key, 7919), (b, gamma))
    slot_idx = jnp.arange(gamma, dtype=jnp.int32)[None, :]
    proposed = slot_idx < n_prop[:, None]
    # One-hot proposal: p_i(d_i) == 1, so the ratio test is u < q_i(d_i)
    # (greedy: q one-hot -> deterministic argmax match). Padded slots
    # force-reject so n_acc <= n_prop.
    accept = proposed & (u < q_d)                         # [B, g]
    acc_prefix = jnp.cumprod(accept.astype(jnp.int32), axis=1)
    n_acc = jnp.sum(acc_prefix, axis=1)                   # [B] 0..n_prop

    # Final token: at the first rejected PROPOSED position, draw from
    # the residual q with the rejected draft zeroed; with every proposal
    # accepted (n_acc == n_prop, padding included), the row at n_prop is
    # the model's genuine next-token distribution — the bonus draw.
    row = jax.vmap(lambda q, i: q[i])(q_rows, n_acc)      # [B, V]
    d_at = jax.vmap(lambda d, i: d[jnp.minimum(i, gamma - 1)])(
        drafts, n_acc)
    resid = jnp.maximum(row - jax.nn.one_hot(d_at, vocab,
                                             dtype=row.dtype), 0.0)
    resid_sum = jnp.sum(resid, axis=-1, keepdims=True)
    # Degenerate residual (q(d) ~ 1: the proposal is essentially surely
    # accepted, so this branch is unreachable in exact arithmetic —
    # guard anyway) falls back to q.
    corr_dist = jnp.where(resid_sum > 1e-12, resid / (resid_sum + 1e-30),
                          row)
    rejected_mid = n_acc < n_prop
    final_dist = jnp.where(rejected_mid[:, None], corr_dist, row)
    final_tok = _sample_from(final_dist, jax.random.fold_in(key, 104729))

    slot_all = jnp.arange(s_len, dtype=jnp.int32)[None, :]
    drafts_pad = jnp.concatenate(
        [drafts, jnp.zeros((b, 1), jnp.int32)], axis=1)
    emitted = jnp.where(slot_all < n_acc[:, None], drafts_pad, -1)
    emitted = jnp.where(slot_all == n_acc[:, None], final_tok[:, None],
                        emitted)
    emitted = jnp.where(active[:, None], emitted, -1)
    return VerifyRoundOut(kv=kv, emitted=emitted,
                          n_accepted=jnp.where(active, n_acc, 0))
