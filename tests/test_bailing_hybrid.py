"""The bailing_hybrid family (models/bailing_hybrid.py; config
``tiny-ling``: kda, kda, full, kda): delta-rule layers whose matrix state
a token ADVANCES, a state slot a sequence beside a latent pool of the
full layers alone, one query projection and a gate a head in the latent
layers, experts routed within groups of which this chip holds one.

The system is held to ``bench/references/bailing_hybrid.py`` (plain
float32, the recurrence a token at a time, no cache, no chunked form,
nothing imported from the program) on LOGITS, at every position the
engine kept (``EngineConfig.keep_logits``): the rows its own step
programs sampled from. Both sides are float32 at "highest" matmul
precision (tests/conftest.py), so what differs is the order of sums (the
chunked WY form against one recurrence, paged softmax against dense):
2e-4 of a logit spread of ~1 is a hundredfold what is read (~2e-6) and
two orders below what any planted fault reads (bench/tests).
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_inference.config import PRESETS, EngineConfig
from tpu_inference.engine.engine import (InferenceEngine, Sequence,
                                         model_columns, model_is, why_not)
from tpu_inference.models import bailing_hybrid as bh
from tpu_inference.models import deepseek_v3

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
TINY_FILE = "bench/tests/rehearsal/configs/tiny-ling.json"


def _load(path):
    spec = importlib.util.spec_from_file_location(
        os.path.basename(path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(os.path.join(REPO, "bench", "references", "bailing_hybrid.py"))
CFG = PRESETS["tiny-ling"]()
with open(os.path.join(REPO, TINY_FILE)) as _f:
    MODEL = json.load(_f)
SZ = REF.sizes(MODEL, CFG.n_layers)
ENGINE = dict(page_size=4, num_pages=128, max_pages_per_seq=32,
              max_batch_size=4, prefill_buckets=(8, 16),
              decode_steps_per_call=4, keep_logits=True,
              enable_prefix_cache=False)


@pytest.fixture(scope="module")
def weights():
    """The reference's own weights (gains away from 1, a selection bias
    that decides the held group's membership), widened to the tiny
    preset's float32."""
    w = REF.make_weights(SZ, 5)
    assert jax.tree.map(lambda a: a.shape, w) == bh.param_shapes(CFG)
    return w, jax.tree.map(lambda a: a.astype(jnp.float32), w)


def _engine(weights, **over):
    return InferenceEngine(CFG, EngineConfig(**dict(ENGINE, **over)),
                           params=weights[1],
                           pallas_interpret=over.get("attn_backend")
                           == "pallas")


def _seq(i, prompt, new=10):
    return Sequence(request_id=i, prompt_tokens=[int(t) for t in prompt],
                    max_new_tokens=new)


def _prompts(*lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, n) for n in lens]


def _worst(weights, seqs):
    """Largest distance of any kept row from the reference's logits of
    the same stream; every position from the last prompt token on must
    be among the kept ones."""
    worst = 0.0
    for s in seqs:
        stream = s.prompt_tokens + s.generated
        at = sorted(s.kept_logits)
        assert at[-1] == len(stream) - 2, (at, len(stream))
        ref = REF.logits(weights[0], SZ, stream, at)
        worst = max(worst, max(float(np.abs(ref[i] - s.kept_logits[p]).max())
                               for i, p in enumerate(at)))
    return worst


def _run(eng, seqs):
    while not all(s.done for s in seqs):
        eng.decode_steps()


def test_the_preset_is_the_cut_the_configuration_file_states():
    full = PRESETS["ling3-flash-ep8"]()
    full.validate()
    kinds = full.layer_types[:full.n_layers]
    assert (kinds.count("kda"), kinds.count("full")) == (11, 2)
    assert full.kind_layers("full") == (4, 10) and full.n_kv_slots == 2
    with open(os.path.join(REPO, "bench", "configs",
                           "ling3-flash-ep8-bf16.json")) as f:
        model = json.load(f)
    assert REF.layer_kinds(model, 13) == kinds
    # 10.81 GB of bfloat16, +- 1%.
    assert 2 * bh.param_count(full) == pytest.approx(10.81e9, rel=0.01)
    assert bh.param_count(full, active=True) < 0.2 * bh.param_count(full)
    # 2 MiB of matrix state + a 72 KB convolution tail a layer.
    assert full.state_bytes_per_seq() == 11 * (32 * 128 * 128 * 4
                                               + 3 * 12288 * 2)
    assert full.state_shapes() == ((3, 96, 128), (32, 128, 128))
    assert full.n_local_experts == 64 == full.n_experts // full.n_group
    assert REF.layer_kinds(MODEL, 4) == CFG.layer_types
    assert model_columns(full) == ("latent", "delta")
    assert model_is(full) == "latent + delta" and model_is(
        PRESETS["tiny-kimi"]()) == "latent"


def test_forward_matches_the_plain_reference(weights):
    toks = _prompts(40, seed=1)[0]
    lg, _ = bh.forward(weights[1], CFG, jnp.asarray(toks)[None],
                       jnp.arange(len(toks))[None], None,
                       bh.make_dense_attn(CFG, 1, len(toks)))
    ref = REF.logits(weights[0], SZ, list(toks), list(range(len(toks))))
    assert float(np.abs(np.asarray(lg[0]) - ref).max()) < TOL
    assert float(np.std(ref)) > 0.05


# The engine's two backends: under "pallas" (interpret mode here) a
# prefill chunk runs the chunk kernel and a decode step the one-token
# convolution and the one-token update (kernels/delta_rule.py), which
# advance the tails and the states where they lie in the pools.
BACKENDS = ["dense", "pallas"]


@pytest.mark.parametrize("backend", BACKENDS)
def test_engine_matches_the_reference_at_every_kept_position(weights,
                                                              backend):
    """Prefill in chunks (37 tokens: 16 + 16 + 5, the state crosses two
    chunk boundaries), a batched prefill with padded rows, then fused-K
    paged decode of all three: every kept row is the reference's."""
    eng = _engine(weights, attn_backend=backend)
    long = _seq(0, _prompts(37, seed=2)[0])
    eng.prefill(long)
    pair = [_seq(1 + i, p) for i, p in enumerate(_prompts(9, 14, seed=3))]
    eng.prefill_many(pair)
    _run(eng, [long, *pair])
    assert _worst(weights, [long, *pair]) < TOL
    stats = dict(zip(deepseek_v3.MOE_STATS, eng.aux_stats))
    assert stats["local_pairs"] == stats["computed_pairs"] > 0
    # The counter behind the experts': tokens whose chosen groups reach
    # this chip, never more than the routed tokens.
    assert 0 < eng.aux_stats[-1] <= stats["tokens"]


@pytest.mark.parametrize("backend", BACKENDS)
def test_alone_and_batched_agree(weights, backend):
    """Each prompt of a batched prefill reads what it reads alone: a
    padded position advances no state."""
    prompts = _prompts(9, 16, 11, seed=3)
    eng = _engine(weights, attn_backend=backend)
    batched = [_seq(i, p, 5) for i, p in enumerate(prompts)]
    eng.prefill_many(batched)
    _run(eng, batched)
    alone = _seq(10, prompts[0], 5)
    one = _engine(weights, attn_backend=backend)
    one.prefill(alone)
    _run(one, [alone])
    assert alone.generated == batched[0].generated
    for pos, row in alone.kept_logits.items():
        assert float(np.abs(row - batched[0].kept_logits[pos]).max()) < TOL


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_lane_allowed_fewer_steps_advances_no_further(weights, backend):
    """Inside one fused call of K = 4 a lane with 2 tokens left runs 2
    steps; the steps it is masked for write the trash slot."""
    eng = _engine(weights, attn_backend=backend)
    a, b = _seq(0, _prompts(12)[0], 3), _seq(1, _prompts(9, seed=4)[0], 11)
    eng.prefill_many([a, b])
    _run(eng, [a, b])
    assert len(a.generated) == 3 and len(b.generated) == 11
    assert _worst(weights, [a, b]) < TOL


def test_preempt_and_recompute_resume(weights):
    """A preempted sequence re-prefills prompt + generated from zeros (a
    chunk at position 0 reads a zero state, whatever its slot held)."""
    eng = _engine(weights)
    seq = _seq(0, _prompts(21, seed=6)[0], 12)
    other = _seq(1, _prompts(10, seed=7)[0], 12)
    eng.prefill(seq)
    eng.prefill(other)
    eng.decode_steps()
    resets = eng.state_slots.resets_total
    eng.preempt(seq)
    assert eng.state_slots.in_use == 1 and seq.resume_base == 5
    eng.decode_steps()                        # the other dirties nothing
    eng.prefill(seq)                          # takes the freed slot again
    assert eng.state_slots.resets_total == resets + 1
    _run(eng, [seq, other])
    assert _worst(weights, [seq, other]) < TOL


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_released_slot_leaks_nothing_into_its_next_owner(weights, backend):
    eng = _engine(weights, max_batch_size=2, attn_backend=backend)
    first = [_seq(i, p, 3) for i, p in enumerate(_prompts(9, 12, seed=8))]
    eng.prefill_many(first)
    held = {s.pages.state for s in first}
    assert held == {1, 2} and eng.state_slots.num_free == 0
    assert not eng.can_admit(_seq(9, _prompts(5)[0]))
    _run(eng, first)
    for s in first:
        eng.release(s)
    # The slots still hold the first owners' states: the next owner's
    # first chunk reads zeros in their place.
    assert float(jnp.abs(eng.kv.ssm_h[:, 1:]).max()) > 0
    third = _seq(2, _prompts(19, seed=9)[0], 6)
    assert eng.can_admit(third)
    eng.prefill(third)
    assert third.pages.state in held
    _run(eng, [third])
    assert _worst(weights, [third]) < TOL
    assert eng.state_slots.peak_in_use == 2


# ------------------------------------------------------------------ routing
def _plain_route(cfg, lp, x2):
    """Group-limited routing as plain loops over tokens and groups."""
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x2, np.float64)
                              @ np.asarray(lp["w_router"], np.float64))))
    ranked = s + np.asarray(lp["router_bias"], np.float64)[None]
    size = cfg.n_experts // cfg.n_group
    tops, gates = [], []
    for t in range(len(s)):
        score = [np.sort(ranked[t, g * size:(g + 1) * size])[-2:].sum()
                 for g in range(cfg.n_group)]
        stay = np.argsort(score)[-cfg.topk_group:]
        allowed = [e for g in stay for e in range(g * size, (g + 1) * size)]
        top = sorted(allowed, key=lambda e: -ranked[t, e])[
            :cfg.n_experts_per_tok]
        g = s[t, top]
        tops.append(top)
        gates.append(cfg.routed_scaling_factor * g / g.sum())
    return np.asarray(tops), np.asarray(gates)


def _router(cfg, seed=0, tokens=64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    lp = {"w_router": jax.random.normal(ks[0], (cfg.d_model, cfg.n_experts)),
          "router_bias": 0.3 * jax.random.normal(ks[1], (cfg.n_experts,))}
    return lp, jax.random.normal(ks[2], (tokens, cfg.d_model))


def test_group_limited_route_equals_plain_loops():
    lp, x = _router(CFG)
    top, gates = deepseek_v3.route(CFG, lp, x)
    want_top, want_gates = _plain_route(CFG, lp, x)
    assert (np.sort(np.asarray(top), 1) == np.sort(want_top, 1)).all()
    order = np.argsort(np.asarray(top), 1)
    worder = np.argsort(want_top, 1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(gates), order, 1),
        np.take_along_axis(want_gates, worder, 1), rtol=1e-5)
    # Every chosen expert lies in one of topk_group groups.
    size = CFG.n_experts // CFG.n_group
    assert all(len(set(row // size)) <= CFG.topk_group
               for row in np.asarray(top))
    # ... and the limit binds: without it some token chooses otherwise.
    free, _ = deepseek_v3.route(dataclasses.replace(
        CFG, n_group=1, topk_group=1), lp, x)
    assert (np.sort(np.asarray(free), 1) != np.sort(want_top, 1)).any()


def test_route_with_one_group_is_the_route_it_was():
    """``n_group`` 1 (Kimi, Xing): the function the parent commit had,
    bit for bit."""
    cfg = PRESETS["tiny-kimi"]()
    lp, x = _router(cfg, seed=1)

    def was(cfg, lp, x2):
        logits = jnp.dot(x2.astype(jnp.float32),
                         lp["w_router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, top_idx = jax.lax.top_k(scores + lp["router_bias"][None, :],
                                   cfg.n_experts_per_tok)
        gates = jnp.take_along_axis(scores, top_idx, axis=1)
        gates = gates / (jnp.sum(gates, axis=1, keepdims=True) + 1e-20)
        return top_idx, gates * cfg.routed_scaling_factor

    for got, want in zip(jax.jit(deepseek_v3.route, static_argnums=0)(
            cfg, lp, x), jax.jit(was, static_argnums=0)(cfg, lp, x)):
        assert (np.asarray(got) == np.asarray(want)).all()
    assert deepseek_v3.n_moe_stats(cfg) == len(deepseek_v3.MOE_STATS) + 8


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of the ``ep_size`` chips that share a layer (each
    holding one group) plus the shared expert ONCE are the layer with
    every expert on one chip."""
    key = jax.random.PRNGKey(3)
    whole = dataclasses.replace(CFG, ep_size=1)
    shapes = bh.param_shapes(whole)["moe"]
    lp = {k: (0.3 if k == "router_bias" else 0.05)
          * jax.random.normal(jax.random.fold_in(key, i), v[1:], jnp.float32)
          for i, (k, v) in enumerate(sorted(shapes.items()))}
    h = jax.random.normal(jax.random.fold_in(key, 99), (2, 9, CFG.d_model))

    class Attn:
        pallas = interpret = False

    def layer(cfg, first):
        held = slice(first, first + cfg.n_local_experts)
        experts = tuple(lp[k][None, held] for k in bh.EXPERT_STACKS)
        shared = {k: v for k, v in lp.items() if not k.startswith("we_")}
        y, stats = deepseek_v3.moe_ffn(cfg, shared, experts, 0, h, Attn)
        return np.asarray(y, np.float64), np.asarray(stats)

    uncut, _ = layer(whole, 0)
    only_shared = np.asarray(deepseek_v3.swiglu(
        h.reshape(-1, CFG.d_model), lp["ws_gate"], lp["ws_up"],
        lp["ws_down"]), np.float64).reshape(h.shape)
    total, pairs, reach = only_shared.copy(), 0, 0
    for rank in range(CFG.ep_size):
        cfg = dataclasses.replace(CFG, ep_rank=rank)
        y, stats = layer(cfg, rank * cfg.n_local_experts)
        total += y - only_shared
        pairs += stats[1]
        reach += stats[-1]
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    # Every pair lands on exactly one chip; a token reaches topk_group.
    assert pairs == h.shape[0] * h.shape[1] * CFG.n_experts_per_tok
    assert reach == h.shape[0] * h.shape[1] * CFG.topk_group


# ------------------------------------------------------------- what is refused
REFUSED = {
    "kv_quant": (dict(kv_quant="int8"), "kv_quant='int8' (the latent pool"),
    "host tier": (dict(host_cache_pages=8), "the host KV tier"),
    "int4": (dict(quant="int4"), "quant='int4' (the grouped expert kernels"),
    "ngram": (dict(num_speculative_tokens=2),
              "a rejected draft would already have advanced"),
    "role": (dict(role="prefill"), "role='prefill' (P/D handoff"),
    "hybrid": (dict(hybrid_prefill=True), "hybrid_prefill (a prefill chunk"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_refused_at_construction(weights, what):
    over, said = REFUSED[what]
    with pytest.raises(ValueError) as e:
        _engine(weights, keep_logits=False, **over)
    assert ("tiny-ling (latent attention and delta-rule layers) does not "
            "support") in str(e.value)
    assert said in str(e.value)


def test_refused_mesh_prefix_export_and_dense_forward(weights, capsys):
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2, 1),
                ("dp", "tp", "sp"))
    with pytest.raises(ValueError, match=r"tp / sp / pp > 1"):
        InferenceEngine(CFG, EngineConfig(**ENGINE), params=weights[1],
                        mesh=mesh)
    eng = _engine(weights, enable_prefix_cache=True)
    assert eng.prefix_cache is None
    assert "a snapshot of every delta-rule" in capsys.readouterr().out
    assert why_not("prefix", PRESETS["tiny-kimi"]()) is None
    seq = _seq(0, _prompts(9)[0])
    eng.prefill(seq)
    for call in (eng.export_sequence_kv, eng.export_sequence_kv_live,
                 eng.adopt_sequence):
        with pytest.raises(ValueError, match="KV export / adoption"):
            call(seq)
    with pytest.raises(ValueError, match="layers of mixed kinds"):
        eng.embed_many([[1, 2, 3]])


def test_int8_projections_leave_the_delta_rule_alone(weights):
    """``quant='int8'`` quantizes the projections by name and nothing of
    the delta rule's own; the rows move by int8's rounding and no
    further (the benchmark's control: bench/parity.py --control)."""
    from tpu_inference.models.quant import QuantizedArray
    eng = _engine(weights, quant="int8")
    kda = eng.params["kda"]
    assert all(isinstance(kda[k], QuantizedArray)
               for k in ("w_qkv", "w_f", "w_o"))
    assert not any(isinstance(kda[k], QuantizedArray) for k in (
        "conv_w", "a_log", "dt_bias", "w_beta", "w_head_gate", "o_norm"))
    assert isinstance(eng.params["full"]["wq"], QuantizedArray)
    seq = _seq(0, _prompts(21, seed=11)[0], 6)
    eng.prefill(seq)
    _run(eng, [seq])
    assert 10 * TOL < _worst(weights, [seq]) < 0.3


def test_state_slots_and_counters_on_metrics(weights):
    eng = _engine(weights)
    seqs = [_seq(i, p, 3) for i, p in enumerate(_prompts(20, 6))]
    eng.prefill(seqs[0])
    eng.prefill(seqs[1])
    _run(eng, seqs)
    from tpu_inference.telemetry import render_prometheus
    text = render_prometheus([({}, eng.telemetry.registry)])
    vals = {l.split()[0]: float(l.split()[1]) for l in text.splitlines()
            if l.startswith(("tpu_inf_state_", "tpu_inf_moe_"))
            and "{" not in l}
    assert vals["tpu_inf_state_slots_total"] == 4
    assert vals["tpu_inf_state_slots_in_use"] == 2
    assert vals["tpu_inf_state_slots_peak"] == 2
    assert vals["tpu_inf_state_resets_total"] == 2
    assert vals["tpu_inf_state_bytes_per_seq"] == 3 * (
        2 * 64 * 64 * 4 + 3 * 3 * 128 * 4) == CFG.state_bytes_per_seq()
    assert 0 < vals["tpu_inf_moe_group_reach_tokens_total"] \
        <= vals["tpu_inf_moe_tokens_total"]
    assert "tpu_inf_prefill_cross_positions_total" not in text
    info = eng.device_info()
    assert info["state_slots"] == 4
    assert info["state_bytes_per_slot"] == CFG.state_bytes_per_seq()
    cm = eng.telemetry.cost_model
    assert cm.state_bytes == CFG.state_bytes_per_seq() and cm.n_layers == 1
