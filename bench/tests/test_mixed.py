"""bench/roofline_mixed.py (bytes and flops of a configuration whose
layers differ in kind, from its file) and bench/readers/mixed.py (shares
and times from a recorded trace summary beside the client's records, the
engine's ledger and the pool gauges)."""

import json
import os

import pytest

import roofline_mixed as R
from conftest import BENCH
from manifest import load_module

READER = load_module(os.path.join(BENCH, "readers", "mixed.py"))
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def laguna():
    with open(os.path.join(BENCH, "configs",
                           "laguna-s-ep8-bf16.json")) as f:
        return json.load(f)


def test_counts_from_the_published_sizes():
    c = laguna()
    assert R.kinds(c) == ["full", "window", "window", "window"] * 3
    assert (R.layers_of(c, "full"), R.layers_of(c, "window")) == (3, 9)
    assert (R.heads_of(c, "full"), R.heads_of(c, "window")) == (48, 72)
    # K and V, 8 heads x 128, bfloat16: 4096 B a token a layer; 12 KB a
    # token in the full kind's pool, 36 KB (while inside the window) in
    # the window kind's.
    assert R.kv_bytes_per_token_layer(c) == 4096
    assert R.kv_bytes_per_token(c, "full") == 3 * 4096
    assert R.kv_bytes_per_token(c, "window") == 9 * 4096
    assert R.visible(5000, c, "full") == 5000
    assert R.visible(5000, c, "window") == 512
    assert R.visible(100, c, "window") == 100
    # ISSUE 32's count, by hand: full attention 3072 x 6144 x 2 + 2 x
    # 3072 x 1024 + 3072 x 48 = 44.19M, sliding 63.13M.
    assert R.attn_params(c, "full") == 2 * 3072 * 6144 + 2 * 3072 * 1024 \
        + 3072 * 48 == 44187648
    assert R.attn_params(c, "window") == 2 * 3072 * 9216 + 2 * 3072 * 1024 \
        + 3072 * 72 == 63135744
    assert R.expert_params(c) == 3 * 3072 * 1024
    assert (R.held_experts(c), R.all_experts(c), R.expert_layers(c)) == (
        32, 256, 11)
    # 1.93 GB of non-expert weights a step.
    n = (3 * 44187648 + 9 * 63135744 + 3 * 3072 * 12288
         + 11 * (3072 * 256 + 3 * 3072 * 1024) + 3072 * 12544)
    assert R.non_expert_weight_bytes(c) == 2 * n
    assert R.non_expert_weight_bytes(c) == pytest.approx(1.93e9, rel=2e-3)
    # Windowed pairs: a 1024-token chunk behind 4096 cached tokens.
    assert R.prefill_pairs(1024, 4096, c, "window") == 1024 * 512
    assert R.prefill_pairs(1024, 4096, c, "full") == (
        1024 * 4096 + 1024 * 1025 // 2)
    assert R.prefill_pairs(600, 0, c, "window") == (
        512 * 513 // 2 + 88 * 512)
    rec = {"chunk_tokens": 1024, "slots": 1,
           "kv_read_tokens": 1024 * 4096 + 1024 * 1025 // 2}
    assert R.ledger_prefill_pairs(rec, c, "window") == 1024 * 512
    assert R.ledger_prefill_pairs(rec, c, "full") == rec["kv_read_tokens"]
    assert R.attn_flops(1000, c, "window") == 4 * 72 * 128 * 1000


def test_the_expert_layer_under_the_stated_routing():
    c = laguna()
    served = c["assumed"]["served_routing"]
    # The file states what a decode step reaches at a batch: the hit
    # probability is the one that gives that count there.
    got = R.expected_distinct_experts(served["decode_batch"], c)
    assert got == pytest.approx(served["distinct_held_experts"])
    uniform = dict(c, assumed={})
    assert R.hit_probability(uniform) == 10 / 256
    assert R.local_pairs_per_token(uniform) == pytest.approx(1.25)
    # ISSUE 32: 32 x (1 - (1 - 10/256)^32) = 23.1 distinct held experts.
    assert R.expected_distinct_experts(32, uniform) == pytest.approx(
        23.06, abs=0.01)
    assert R.moe_layer_bytes(32, uniform) == pytest.approx(
        23.06 * 3 * 3072 * 1024 * 2, rel=1e-3)
    vis = {"full": 32 * 4600.0, "window": 32 * 512.0}
    step = R.decode_step_bytes(32, vis, uniform)
    assert step == pytest.approx(
        R.non_expert_weight_bytes(c) + 11 * R.moe_layer_bytes(32, uniform)
        + 3 * 32 * 4600 * 4096 + 9 * 32 * 512 * 4096)
    # ~11 ms at the HBM peak, as the issue's arithmetic has it.
    assert 9e-3 < step / 819e9 < 12e-3


def ctx(modules, ops, ledger=(), records=None, config=None, metrics=None):
    prof = {"start_s": 10.0, "seconds": 3.0, "start_unix": 1000.0,
            "end_unix": 1009.0}
    if records is None:
        # 10 streams of 2000 prompt tokens, each 100 tokens in at t = 10.
        records = [{"prompt_tokens": 2000,
                    "token_s": [5.0 + 0.05 * i for i in range(400)]}
                   for _ in range(10)]
    end = metrics or {}
    return {"config": config or laguna(), "peaks": PEAKS, "profile": prof,
            "records": records, "ledger": list(ledger), "seconds": 48.0,
            "cell": {"chips": 1}, "metrics_open": {}, "metrics_end": end,
            "trace": {"chips": {"c0": {"ops": ops}}, "modules": modules}}


DEC_F = "paged_attention.3_bf16_32_48_128_"
DEC_W = "tpu_custom_call.7_bf16_32_72_128_"      # in a scan inside a scan
PRE_F = "paged_prefill_attention.5_bf16_1_4_8_1536_128_"    # 1024 rows x 6
PRE_W = "tpu_custom_call.9_bf16_1_4_8_2304_128_"            # 1024 rows x 9
EXP_G = "tpu_custom_call.11_bf16_896_1024_"
EXP_D = "tpu_custom_call.12_f32_896_3072_"


def test_ops_are_told_apart_by_their_shapes():
    c = laguna()
    assert READER._decode_kind(DEC_F, c) == "full"
    assert READER._decode_kind(DEC_W, c) == "window"
    assert READER._decode_kind(PRE_F, c) is None
    assert READER._decode_kind("fusion.2_bf16_32_72_128_", c) is None
    assert READER._prefill_kind_rows(PRE_F, c) == ("full", 1024)
    assert READER._prefill_kind_rows(PRE_W, c) == ("window", 1024)
    assert READER._prefill_kind_rows(DEC_W, c) is None
    ops = {EXP_G: [10, 0.2], EXP_D: [10, 0.1], DEC_W: [5, 9.0],
           "fusion.1_bf16_896_1024_": [3, 7.0]}
    assert READER._expert_seconds(ops, c) == pytest.approx(0.3)


def test_decode_readings_by_kind():
    c = laguna()
    # 50 decode steps: 150 full-kind and 450 window-kind calls.
    ops = {DEC_F: [150, 0.060], DEC_W: [450, 0.045],
           EXP_G: [550, 0.20], EXP_D: [550, 0.10],
           "fusion.9_bf16_32_3072_": [600, 0.2]}
    mods = {"jit_tpu_inf_decode_k8": {
        "runs": 7, "seconds": 0.7, "starts": [0.4 * i for i in range(7)],
        "ops": ops},
        "jit_tpu_inf_prefill": {"runs": 1, "seconds": 0.5, "starts": [1.0],
                                "ops": {PRE_F: [3, 0.1], EXP_G: [11, 9.0]}}}
    cx = ctx(mods, ops)
    seqs, vis = READER._in_flight(cx)
    assert seqs == [10] * 60
    assert 10 * 2100 < vis["full"] < 10 * 2170
    assert vis["window"] == pytest.approx(10 * 512)
    full = READER.read(cx, "full_decode_attn")
    assert full == pytest.approx(
        100 * 150 * vis["full"] * 4096 / 819e9 / 0.060)
    window = READER.read(cx, "window_decode_attn")
    assert window == pytest.approx(100 * 450 * 5120 * 4096 / 819e9 / 0.045)
    hbm = READER.read(cx, "decode_hbm")
    assert hbm == pytest.approx(
        100 * R.decode_step_bytes(10, vis, c) / 819e9 / (0.7 / 50))
    experts = READER.read(cx, "moe_experts")
    assert experts == pytest.approx(
        100 * 11 * 50 * R.moe_layer_bytes(10, c) / 819e9 / 0.30)
    assert 0 < min(full, window, hbm, experts) and max(
        full, window, hbm, experts) < 100


def test_prefill_readings_match_ledger_records_to_the_profiles_runs():
    c = laguna()
    mods = {"jit_tpu_inf_prefill": {
        "runs": 2, "seconds": 0.30, "starts": [0.5, 1.9],
        "ops": {PRE_F: [6, 0.04], PRE_W: [18, 0.08]}}}
    ledger = [
        {"ts": 990.0, "kind": "prefill_chunk", "slots": 1,
         "chunk_tokens": 100, "kv_read_tokens": 5050},
        {"ts": 1000.6, "kind": "prefill_chunk", "slots": 1,
         "chunk_tokens": 1024,
         "kv_read_tokens": 1024 * 2048 + 1024 * 1025 // 2},
        {"ts": 1002.0, "kind": "prefill_chunk", "slots": 1,
         "chunk_tokens": 600, "kv_read_tokens": 600 * 601 // 2},
        {"ts": 1001.0, "kind": "decode", "slots": 10, "chunk_tokens": 0,
         "kv_read_tokens": 1}]
    cx = ctx(mods, {PRE_F: [6, 0.04], PRE_W: [18, 0.08]}, ledger)
    assert READER.read(cx, "prefill_ms_per_ktok") == pytest.approx(
        1e6 * 0.30 / 1624)
    pairs_full = 1024 * 2048 + 1024 * 1025 // 2 + 600 * 601 // 2
    pairs_win = 1024 * 512 + 512 * 513 // 2 + 88 * 512
    flops = 4 * 128 * (3 * 48 * pairs_full + 9 * 72 * pairs_win)
    assert READER.read(cx, "prefill_attn") == pytest.approx(
        100 * flops / 197e12 / 0.12)
    assert READER.read(cx, "decode_hbm") is None
    assert READER.read(cx, "window_decode_attn") is None


def test_pool_gauges():
    end = {"tpu_inf_kv_window_pages_total": 3136.0,
           "tpu_inf_kv_window_pages_in_use": 300.0,
           "tpu_inf_kv_window_pages_peak": 1100.0,
           "tpu_inf_kv_full_pages_total": 13000.0,
           "tpu_inf_kv_full_pages_in_use": 100.0,
           "tpu_inf_kv_full_pages_peak": 9100.0,
           "tpu_inf_kv_window_pages_released_total": 9600.0}
    cx = ctx({}, {}, metrics=end)
    cx["metrics_open"] = {"tpu_inf_kv_window_pages_released_total": 4800.0}
    assert READER.read(cx, "pool_live_share", "window") == pytest.approx(
        100 * 1100 / 3136)
    assert READER.read(cx, "pool_live_share", "full") == pytest.approx(70.0)
    assert READER.read(cx, "released_per_s") == pytest.approx(100.0)
    # On the CPU too (counters need no chip); a program without the
    # gauges (the parent commit) gives nothing and does not raise.
    assert READER.read(dict(cx, peaks=None), "released_per_s") == 100.0
    assert READER.read(ctx({}, {}), "pool_live_share", "full") is None
    assert READER.read(ctx({}, {}), "released_per_s") is None


def test_nothing_to_read_is_none():
    cx = ctx({}, {"fusion.1_bf16_8_": [3, 0.1]})
    whats = ("window_decode_attn", "full_decode_attn", "prefill_attn",
             "prefill_ms_per_ktok", "moe_experts", "decode_hbm")
    for what in whats:
        assert READER.read(cx, what) is None
    with open(os.path.join(BENCH, "configs", "mistral-7b-int8.json")) as f:
        other = dict(cx, config=json.load(f))
    for what in whats + ("released_per_s",):
        assert READER.read(other, what) is None
        assert READER.read(dict(cx, peaks=None), what) is None
    with pytest.raises(ValueError):
        READER.read(ctx({}, {DEC_F: [1, 0.1]}), "nope")
