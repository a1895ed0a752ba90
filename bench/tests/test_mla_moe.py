"""bench/roofline_mla_moe.py (bytes and flops from the configuration file)
and bench/readers/mla_moe.py (shares from a recorded trace summary beside
the client's records and the engine's ledger)."""

import json
import os

import pytest

import roofline_mla_moe as R
from conftest import BENCH
from manifest import load_module

READER = load_module(os.path.join(BENCH, "readers", "mla_moe.py"))
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def kimi(served=False):
    """The configuration file; without what it states of its served
    weights' routing (uniform routing's arithmetic) unless asked."""
    with open(os.path.join(BENCH, "configs", "kimi-k2-ep32-bf16.json")) as f:
        cfg = json.load(f)
    if not served:
        cfg["assumed"].pop("served_routing", None)
    return cfg


def test_counts_from_the_published_sizes():
    c = kimi()
    assert R.latent_dim(c) == 576 and R.held_experts(c) == 12
    assert R.all_experts(c) == 384 and R.expert_layers(c) == 6
    assert R.mla_attn_bytes(1000, c) == 1000 * 1152
    assert R.mla_attn_flops(1000, c) == 2 * 64 * (576 + 512) * 1000
    assert R.prefill_pairs(4, 10) == 4 * 10 + 10
    assert R.expert_params(c) == 3 * 7168 * 2048
    assert R.attn_params(c) == pytest.approx(101.14e6, rel=1e-3)
    # ISSUE's arithmetic: 3.06 GB of non-expert weights a step.
    assert R.non_expert_weight_bytes(c) == pytest.approx(3.06e9, rel=0.01)


def test_expected_distinct_experts():
    c = kimi()
    assert R.expected_distinct_experts(0, c) == 0
    assert R.expected_distinct_experts(1, c) == pytest.approx(12 * 8 / 384)
    c = dict(c, assumed={})          # uniform routing
    assert 5.5 < R.expected_distinct_experts(32, c) < 6.2
    assert R.expected_distinct_experts(10000, c) == pytest.approx(12)
    assert R.expected_local_pairs(32, c) == pytest.approx(8.0)
    step = R.decode_step_bytes(32, 32 * 9000, c)
    assert step == pytest.approx(
        R.non_expert_weight_bytes(c) + 6 * R.moe_layer_bytes(32, c)
        + 7 * 32 * 9000 * 1152)
    assert 7e9 < step < 10e9


def ctx(modules, ops, ledger=(), records=None):
    prof = {"start_s": 10.0, "seconds": 3.0, "start_unix": 1000.0,
            "end_unix": 1009.0}
    if records is None:
        # 32 streams of 8292 prompt tokens, each 100 tokens in at t = 10.
        records = [{"prompt_tokens": 8292,
                    "token_s": [5.0 + 0.05 * i for i in range(240)]}
                   for _ in range(32)]
    return {"config": kimi(), "peaks": PEAKS, "profile": prof,
            "records": records, "ledger": list(ledger),
            "trace": {"chips": {"c0": {"ops": ops}}, "modules": modules}}


DEC = "mla_decode_attention.3_bf16_32_1_64_512_"
PRE = "mla_prefill_attention.5_bf16_1_8_2048_512_"      # 256 token rows
GU = "moe_grouped_experts_gate_up.7_bf16_208_2048_"
DN = "moe_grouped_experts_down.9_f32_208_7168_"


def test_decode_shares():
    # 100 decode steps of 7 layers in 2.0 s of decode programs.
    mods = {"jit_tpu_inf_decode_k8": {
        "runs": 12, "seconds": 2.0, "starts": [0.1 * i for i in range(12)],
        "ops": {DEC: [700, 0.35], GU: [600, 0.5], DN: [600, 0.25]}}}
    c = ctx(mods, {DEC: [700, 0.35], GU: [600, 0.5], DN: [600, 0.25]})
    seqs, vis = READER._in_flight(c)
    assert seqs == [32] * 60 and 32 * 8392 < vis < 32 * 8460
    share = READER.read(c, "mla_decode_attn")
    least = max(vis * 1152 / 819e9, 2 * 64 * 1088 * vis / 197e12)
    assert share == pytest.approx(100 * 700 * least / 0.35)
    hbm = READER.read(c, "mla_moe_decode_hbm")
    assert hbm == pytest.approx(
        100 * R.decode_step_bytes(32, vis, kimi()) / 819e9 / (2.0 / 100))
    moe = READER.read(c, "moe_experts")
    # 100 steps x 6 expert layers, each reading the distinct held experts
    # expected at 32 tokens (5.9 of 12), all three matrices of each.
    assert moe == pytest.approx(
        100 * 600 * R.moe_layer_bytes(32, kimi()) / 819e9 / 0.75)
    assert R.moe_layer_bytes(32, kimi()) == pytest.approx(
        R.expected_distinct_experts(32, kimi()) * 3 * 7168 * 2048 * 2)
    assert 0 < share < 100 and 0 < hbm < 100 and 0 < moe < 100


def test_the_stated_served_routing_replaces_uniform_routing():
    c = kimi()
    uniform = dict(c, assumed={})
    assert R.hit_probability(uniform) == pytest.approx(8 / 384)
    assert R.local_pairs_per_token(uniform) == pytest.approx(0.25)
    stated = dict(c, assumed={"served_routing": {
        "decode_batch": 24.0, "distinct_held_experts": 3.6}})
    # The stated count comes back at the stated batch, less below it.
    assert R.expected_distinct_experts(24.0, stated) == pytest.approx(3.6)
    assert R.expected_distinct_experts(12.0, stated) == pytest.approx(
        12 * (1 - 0.7 ** 0.5))
    assert R.local_pairs_per_token(stated) == pytest.approx(
        12 * (1 - 0.7 ** (1 / 24.0)))
    assert (R.expected_distinct_experts(24, stated)
            < R.expected_distinct_experts(24, uniform))
    assert R.expected_distinct_experts(1000, stated) == pytest.approx(12)


def test_the_file_states_what_its_served_weights_reach():
    served = kimi(served=True)["assumed"]["served_routing"]
    assert 16 <= served["decode_batch"] <= 32
    assert 2.0 < served["distinct_held_experts"] < R.expected_distinct_experts(
        served["decode_batch"], kimi())          # under uniform routing's


def test_prefill_shares_match_ledger_records_to_the_profiles_runs():
    mods = {"jit_tpu_inf_prefill": {
        "runs": 2, "seconds": 0.30, "starts": [0.5, 1.9],
        "ops": {PRE: [14, 0.12], GU: [12, 0.02], DN: [12, 0.01]}}}
    ledger = [
        {"ts": 990.0, "kind": "prefill_chunk", "slots": 1,
         "chunk_tokens": 100, "kv_read_tokens": 100 * 8192 + 5050},
        {"ts": 1000.6, "kind": "prefill_chunk", "slots": 1,
         "chunk_tokens": 200, "kv_read_tokens": 200 * 8192 + 20100},
        {"ts": 1002.0, "kind": "prefill_chunk", "slots": 1,
         "chunk_tokens": 180, "kv_read_tokens": 180 * 8192 + 16290},
        {"ts": 1001.0, "kind": "decode", "slots": 32, "chunk_tokens": 0,
         "kv_read_tokens": 1}]
    c = ctx(mods, {PRE: [14, 0.12], GU: [12, 0.02], DN: [12, 0.01]}, ledger)
    assert READER._rows(PRE, kimi()) == 256
    assert READER.read(c, "mla_prefill_ms_per_ktok") == pytest.approx(
        1e6 * 0.30 / 380)
    pairs = 200 * 8192 + 20100 + 180 * 8192 + 16290
    assert READER.read(c, "mla_prefill_attn") == pytest.approx(
        100 * 7 * R.mla_attn_flops(pairs, kimi()) / 197e12 / 0.12)
    # A prefill's expert calls are in no decode program: not counted.
    assert READER.read(c, "moe_experts") is None


def test_expert_kernels_are_found_under_the_chips_op_names():
    """The v5e's trace calls the grouped kernels tpu_custom_call.<n>."""
    ops = {DEC: [700, 0.35], "tpu_custom_call.6_bf16_208_2048_": [600, 0.5],
           "tpu_custom_call.7_f32_208_7168_": [600, 0.25],
           "tpu_custom_call.9_f32_208_512_": [5, 9.0]}      # not theirs
    mods = {"jit_tpu_inf_decode_k8": {"runs": 12, "seconds": 2.0,
                                      "starts": [0.0] * 12, "ops": ops}}
    assert READER._expert_seconds(ops, kimi()) == 0.75
    pre = {"tpu_custom_call.6_bf16_896_2048_": [6, 0.4]}   # a prefill's
    mods["jit_tpu_inf_prefill"] = {"runs": 1, "seconds": 0.5,
                                   "starts": [0.0], "ops": pre}
    assert READER.read(ctx(mods, ops), "moe_experts") == pytest.approx(
        100 * 600 * R.moe_layer_bytes(32, kimi()) / 819e9 / 0.75)


def test_nothing_to_read_is_none():
    c = ctx({}, {"fusion.1_bf16_8_": [3, 0.1]})
    for what in ("mla_decode_attn", "mla_prefill_attn", "moe_experts",
                 "mla_moe_decode_hbm", "mla_prefill_ms_per_ktok"):
        assert READER.read(c, what) is None
    # Another family's configuration, or no chip: nothing, no raise.
    with open(os.path.join(BENCH, "configs", "mistral-7b-int8.json")) as f:
        other = dict(c, config=json.load(f))
    assert READER.read(other, "mla_decode_attn") is None
    assert READER.read(dict(c, peaks=None), "mla_decode_attn") is None
    with pytest.raises(ValueError):
        READER.read(ctx({}, {DEC: [1, 0.1]}), "nope")
