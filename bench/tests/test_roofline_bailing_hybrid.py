"""bench/roofline_bailing_hybrid.py (bytes and flops of a Ling-3.0-flash
configuration, from its file) and bench/readers/bailing_hybrid.py (shares
from a recorded trace summary beside the client's records, the engine's
ledger and the program's counters)."""

import json
import os

import pytest

import roofline_bailing_hybrid as R
from conftest import BENCH
from manifest import load_module

READER = load_module(os.path.join(BENCH, "readers", "bailing_hybrid.py"))
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
MIB = 1 << 20


def ling():
    with open(os.path.join(BENCH, "configs",
                           "ling3-flash-ep8-bf16.json")) as f:
        return json.load(f)


def test_counts_from_the_published_sizes():
    c = ling()
    kinds = R.kinds(c)
    assert len(kinds) == 13 and kinds.count("kda") == 11
    assert [i for i, k in enumerate(kinds) if k == "full"] == [4, 10]
    assert R.kda_heads(c) == 32 and R.kda_width(c) == 4096
    # A layer's state of one sequence: 32 x 128 x 128 float32 = 2 MiB,
    # and a 72 KB convolution tail: 2.17 MB a layer, 23.9 MB at 11.
    assert R.state_bytes_per_seq_layer(c) == 2 * MIB
    assert R.conv_tail_bytes_per_seq_layer(c) == 3 * 12288 * 2
    assert 11 * (2 * MIB + 73728) == 23879680
    # The one-token update of 96 lanes, one layer: the states in and out
    # beside 96 x 82 KB of operands.
    assert R.step_bytes(96, c) == 96 * (4 * MIB + (5 * 4096 + 32) * 4)
    # ISSUE 51's arithmetic: 4.4 GB of state in and out a step.
    assert R.decode_state_bytes(96, c) == pytest.approx(4.58e9, rel=0.01)
    # The mixers' parameters as the issue counts them.
    assert R.mixer_params(c, "kda") == pytest.approx(52.65e6, rel=2e-3)
    assert R.mixer_params(c, "full") == pytest.approx(31.97e6, rel=2e-3)
    assert R.expert_params(c) == 5_898_240
    whole = (R.non_expert_weight_bytes(c)
             + 12 * R.moe_read_bytes(64, c)
             + c["vocab_size"] * c["hidden_size"] * 2)      # the embedding
    assert whole == pytest.approx(10.81e9, rel=0.01)
    assert R.uniform_local_pairs_per_token(c) == 1.0
    assert R.uniform_group_reach_share(c) == 50.0
    # The chunked form: 172,032 FLOPs a token a head at blocks of 64.
    assert R.chunk_flops(1, c) == 32 * (8 * 64 * 128 + 6 * 128 * 128
                                        + 2 * 64 * 64)
    assert R.chunk_bytes(1024, 1, c) == 1024 * (5 * 4096 + 32) * 4 + 4 * MIB
    # A decode step at 96 lanes, 50 experts a layer, 2.5k tokens a lane:
    # 13.9 GB, ~17 ms at the HBM peak (4.6 GB of it state).
    step = R.decode_step_bytes(96, 50, 96 * 2500.0, c)
    assert 15e-3 < step / 819e9 < 19e-3


def ctx(modules, ledger=(), metrics=None, config=None):
    prof = {"start_s": 10.0, "seconds": 3.0, "start_unix": 1000.0,
            "end_unix": 1009.0}
    # 10 streams of 2000 prompt tokens, each 100 tokens in at t = 10.
    records = [{"prompt_tokens": 2000,
                "token_s": [5.0 + 0.05 * i for i in range(400)]}
               for _ in range(10)]
    return {"config": config or ling(), "peaks": PEAKS, "profile": prof,
            "records": records, "ledger": list(ledger), "seconds": 48.0,
            "cell": {"chips": 1}, "metrics_open": {},
            "metrics_end": metrics or {},
            "trace": {"chips": {"0": {"ops": {
                n: v for m in modules.values() for n, v in m["ops"].items()}}},
                "modules": modules}}


MLA_DEC = "mla_decode_attention.3_bf16_96_32_512_"
MLA_PRE = "mla_prefill_attention.2_bf16_1_1024_32_512_"
STEP_NAMED, STEP_IN_SCAN = "kda_step.4", "tpu_custom_call.11"
CHUNK_IN_SCAN = "tpu_custom_call.6"
EXP_UP = "tpu_custom_call.8_bf16_128_768_"
EXP_DOWN = "tpu_custom_call.9_f32_128_2560_"
COUNTERS = {"tpu_inf_moe_distinct_experts_total": 12 * 50 * 40.0,
            "tpu_inf_moe_decode_layer_steps_total": 12 * 50.0,
            "tpu_inf_moe_local_pairs_total": 5000.0,
            "tpu_inf_moe_tokens_total": 5000.0}


def test_the_delta_kernels_are_told_by_name_or_by_returning_a_pair():
    assert READER.is_delta(STEP_NAMED, "kda_step")
    assert READER.is_delta(STEP_IN_SCAN, "kda_step")
    assert READER.is_delta("kda_chunk_prefill.2", "kda_chunk_prefill")
    assert not READER.is_delta(EXP_UP, "kda_step")
    assert not READER.is_delta(MLA_DEC, "kda_step")
    assert not READER.is_delta("fusion.3", "kda_step")


def test_decode_readings():
    c = ling()
    # 50 decode steps: 100 latent calls, 550 one-token updates.
    ops = {MLA_DEC: [100, 0.004], STEP_NAMED: [50, 0.003],
           STEP_IN_SCAN: [500, 0.030], EXP_UP: [600, 0.020],
           EXP_DOWN: [600, 0.010], "fusion.9_bf16_96_2560_": [600, 0.2]}
    mods = {"jit_tpu_inf_decode_k8": {
        "runs": 7, "seconds": 0.6, "starts": [0.4 * i for i in range(7)],
        "ops": ops}}
    cx = ctx(mods, metrics=COUNTERS)
    seqs, vis = READER.X._in_flight(cx)
    assert seqs == [10] * 60 and 10 * 2100 < vis < 10 * 2200
    state = READER.read(cx, "delta_state_decode")
    assert state == pytest.approx(
        100 * 11 * 50 * R.step_bytes(10, c) / 819e9 / 0.033)
    assert 0 < state < 100
    hbm = READER.read(cx, "decode_hbm")
    assert hbm == pytest.approx(
        100 * R.decode_step_bytes(10, 40.0, vis, c) / 819e9
        / (0.6 / 50))
    experts = READER.read(cx, "moe_experts_decode")
    assert experts == pytest.approx(
        100 * 12 * 50 * R.moe_read_bytes(40.0, c) / 819e9 / 0.030)
    attn = READER.read(cx, "mla_decode_attn")
    assert attn == pytest.approx(
        100 * 100 * vis * 576 * 2 / 819e9 / 0.004)
    # No counters (the parent): the readings that need them are left out.
    assert READER.read(ctx(mods), "decode_hbm") is None
    # Another configuration: nothing to read.
    assert READER.read(ctx(mods, config={"model_type": "phi4flash"}),
                       "delta_state_decode") is None


def test_prefill_readings_match_the_ledger_to_the_profile():
    c = ling()
    ops = {MLA_PRE: [4, 0.002], CHUNK_IN_SCAN: [20, 0.010],
           "kda_chunk_prefill.1": [2, 0.001]}
    mods = {"jit_tpu_inf_prefill": {"runs": 2, "seconds": 0.040,
                                    "starts": [0.5, 1.5], "ops": ops}}
    ledger = [{"kind": "prefill_chunk", "ts": 1000.0 + t, "slots": 1,
               "chunk_tokens": n, "kv_read_tokens": n * (n + 1) // 2}
              for t, n in ((0.6, 1000), (1.6, 900))]
    cx = ctx(mods, ledger=ledger)
    assert READER.read(cx, "mla_prefill_ms_per_ktok") == pytest.approx(
        1e6 * 0.040 / 1900)
    share = READER.read(cx, "delta_chunk_prefill")
    least = max(R.chunk_bytes(1900, 2, c) / 819e9,
                R.chunk_flops(1900, c) / 197e12)
    assert share == pytest.approx(100 * 11 * least / 0.011)
    assert 0 < share < 100


def test_slots_live_share_reads_the_gauges():
    cx = ctx({}, metrics={"tpu_inf_state_slots_total": 96.0,
                          "tpu_inf_state_slots_peak": 72.0})
    assert READER.read(cx, "slots_live_share") == 75.0
    assert READER.read(ctx({}), "slots_live_share") is None
