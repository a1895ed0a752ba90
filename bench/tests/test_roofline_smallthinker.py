"""bench/roofline_smallthinker.py (bytes and flops of the SmallThinker
stage from its configuration file, against ISSUE 43's hand arithmetic)
and bench/readers/smallthinker.py (shares, rows and pools from a recorded
trace summary beside the client's records, the engine's ledger and the
program's counters)."""

import json
import os

import pytest

import roofline_mixed as RM
import roofline_smallthinker as R
from conftest import BENCH
from manifest import load_module

READER = load_module(os.path.join(BENCH, "readers", "smallthinker.py"))
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def thinker():
    with open(os.path.join(BENCH, "configs",
                           "smallthinker-21b-pp4-bf16.json")) as f:
        return json.load(f)


def test_counts_from_the_published_sizes():
    c = thinker()
    m = R.as_mixed(c)
    assert RM.kinds(m) == ["full", "window", "window", "window"] * 3
    assert (RM.layers_of(m, "full"), RM.layers_of(m, "window")) == (3, 9)
    assert RM.heads_of(m, "full") == RM.heads_of(m, "window") == 28
    # K and V, 4 heads x 128, bfloat16: 2 KB a token a layer; 6 KB a token
    # in the full kind's pool, 18 KB in the window kind's: 24 KB a token
    # below the window.
    assert RM.kv_bytes_per_token_layer(m) == 2048
    assert (R.kv_bytes_per_token(c, "full"),
            R.kv_bytes_per_token(c, "window")) == (6 * 1024, 18 * 1024)
    assert R.kv_bytes_per_token(c) == 24 * 1024
    assert RM.visible(5000, m, "window") == 4096
    assert RM.visible(900, m, "window") == RM.visible(900, m, "full") == 900
    # ISSUE 43's count, by hand: Wq 9.175 M, Wk + Wv 2 x 1.311 M, Wo
    # 9.175 M, router 0.164 M, norms 0.005 M = 21.14 M outside the
    # experts; 64 x 3 x 2560 x 768 = 377.49 M inside: 797 MB a layer.
    assert RM.attn_params(m, "full") == RM.attn_params(m, "window") == (
        2 * 2560 * 3584 + 2 * 2560 * 512) == 20_971_520
    assert R.expert_params(c) == 3 * 2560 * 768 == 5_898_240
    assert R.layer_params(c) == 20_971_520 + 163_840 + 5_120 + 377_487_360
    assert 2 * R.layer_params(c) == pytest.approx(797e6, rel=1e-3)
    # 12 layers + embedding + head: 5.561 B parameters, 11.12 GB.
    assert R.stage_params(c) == 5_561_448_960
    assert 2 * R.stage_params(c) == pytest.approx(11.12e9, rel=1e-3)
    # A real stage: 13 layers + one of embedding / head = 11.14 GB.
    assert 2 * (13 * R.layer_params(c) + 151936 * 2560) == pytest.approx(
        11.14e9, rel=1e-3)
    # The whole model: 21.5 B.
    assert 52 * R.layer_params(c) + 2 * 151936 * 2560 == pytest.approx(
        21.5e9, rel=2e-3)


def test_the_expert_layer_with_every_expert_here():
    c = thinker()
    # 63.9 of 64 at 64 lanes (1 - 0.906^64), 28.5 at 6 lanes... and the
    # pairs are all local whatever the routing.
    assert R.expected_distinct_experts(64, c) == pytest.approx(63.9, abs=0.06)
    assert R.expected_distinct_experts(1, c) == pytest.approx(6.0)
    assert R.moe_flops(64, c) == 2 * 64 * 6 * 5_898_240
    assert R.moe_read_bytes(64, c) == 64 * 5_898_240 * 2     # 755 MB
    assert R.moe_read_bytes(64, c) == pytest.approx(755e6, rel=1e-3)
    # The reckoned step of the issue: 12 x 755 MB of experts, 0.51 GB of
    # attention and router weights, the head, ~1.6 GB of KV at 64 lanes
    # of ~1 k. (The issue wrote the head as 0.39 GB: that is its 0.39 G
    # PARAMETERS, 0.78 GB of bfloat16; so ~12.0 GB and 14.6 ms at
    # 819 GB/s where it reckoned ~11.6 GB and 14 ms.)
    non = R.non_expert_weight_bytes(c)
    assert non == 2 * (12 * (20_971_520 + 163_840) + 2560 * 151936)
    assert non == pytest.approx(0.51e9 + 0.78e9, rel=0.01)
    vis = {"full": 64 * 1050.0, "window": 64 * 1050.0}
    step = R.decode_step_bytes(63.9, vis, c)
    assert step == pytest.approx(non + 12 * 63.9 * 5_898_240 * 2
                                 + 12 * 64 * 1050 * 2048)
    assert step == pytest.approx(12.0e9, rel=0.01)
    assert step / 819e9 == pytest.approx(0.0146, rel=0.01)
    # Past the window a window layer reads 4096 tokens, a full one all.
    far = R.decode_step_bytes(63.9, {"full": 6000.0, "window": 4096.0}, c)
    assert far - R.decode_step_bytes(63.9, {"full": 0.0, "window": 0.0},
                                     c) == (3 * 6000 + 9 * 4096) * 2048


def ctx(modules, ops, ledger=(), metrics=None, steps=None, config=None):
    prof = {"start_s": 10.0, "seconds": 3.0, "start_unix": 1000.0,
            "end_unix": 1009.0}
    # 64 streams of 600 prompt tokens, each 100 tokens in at t = 10.
    records = [{"prompt_tokens": 600,
                "token_s": [5.0 + 0.05 * i for i in range(400)]}
               for _ in range(64)]
    return {"config": config or thinker(), "peaks": PEAKS, "profile": prof,
            "records": records, "ledger": list(ledger), "seconds": 48.0,
            "cell": {"chips": 1}, "metrics_open": {},
            "metrics_end": metrics or {}, "steps": steps or {},
            "trace": {"chips": {"c0": {"ops": ops}}, "modules": modules}}


DEC = "tpu_custom_call.7_bf16_64_28_128_"            # either kind's
PRE = "tpu_custom_call.9_bf16_1_4_4_1792_128_"       # 1024 rows x 7
EXP_G = "tpu_custom_call.11_bf16_1024_768_"
EXP_D = "tpu_custom_call.12_f32_1024_2560_"
COUNTERS = {"tpu_inf_moe_distinct_experts_total": 63.5 * 1200,
            "tpu_inf_moe_decode_layer_steps_total": 1200.0,
            "tpu_inf_moe_tile_rows_total": 1024.0 * 1200,
            "tpu_inf_moe_computed_pairs_total": 384.0 * 1200}
STEPS = {"fleet": {"enabled": True, "kinds": {}, "rung_occupancy": {
    "64": {"dispatches": 100, "mean_slots": 63.5}}}}


def test_decode_readings():
    c = thinker()
    # 50 decode steps: 600 calls of the decode kernel, both kinds alike.
    ops = {DEC: [600, 0.090], EXP_G: [600, 0.40], EXP_D: [600, 0.20],
           "fusion.9_bf16_64_2560_": [600, 0.2]}
    mods = {"jit_tpu_inf_decode_k8": {
        "runs": 7, "seconds": 0.9, "starts": [0.4 * i for i in range(7)],
        "ops": ops}}
    cx = ctx(mods, ops, metrics=COUNTERS, steps=STEPS)
    m = R.as_mixed(c)
    seqs, vis = READER.X._in_flight(dict(cx, config=m))
    assert seqs == [64] * 60
    assert vis["full"] == vis["window"]          # all under the window
    attn = READER.read(cx, "decode_attn")
    assert attn == pytest.approx(
        100 * 50 * 12 * vis["full"] * 2048 / 819e9 / 0.090)
    experts = READER.read(cx, "moe_experts_decode")
    assert experts == pytest.approx(
        100 * 12 * 50 * (63.5 * 5_898_240 * 2 / 819e9) / 0.60)
    hbm = READER.read(cx, "decode_hbm")
    assert hbm == pytest.approx(
        100 * R.decode_step_bytes(63.5, vis, c) / 819e9 / (0.9 / 50))
    assert 0 < min(attn, experts, hbm) and max(attn, experts, hbm) < 100
    assert READER.read(cx, "rows_per_expert") == pytest.approx(6.0)
    assert READER.read(cx, "padded_row_share") == pytest.approx(62.5)
    # A program without the counters (the parent commit): nothing to
    # read, nothing raised; the attention share needs none.
    bare = ctx(mods, ops, steps=STEPS)
    for what in ("moe_experts_decode", "decode_hbm", "rows_per_expert",
                 "padded_row_share"):
        assert READER.read(bare, what) is None
    assert READER.read(bare, "decode_attn") == attn


def test_prefill_readings():
    mods = {"jit_tpu_inf_prefill": {
        "runs": 2, "seconds": 0.30, "starts": [0.5, 1.9],
        "ops": {PRE: [24, 0.05], EXP_G: [24, 0.09], EXP_D: [24, 0.06]}},
        "jit_tpu_inf_decode_k8": {
            "runs": 1, "seconds": 0.1, "starts": [1.0],
            "ops": {DEC: [96, 0.01], EXP_G: [96, 9.0]}}}
    ledger = [
        {"ts": 1000.6, "kind": "prefill_chunk", "slots": 1,
         "chunk_tokens": 1024, "kv_read_tokens": 1024 * 1025 // 2},
        {"ts": 1002.0, "kind": "prefill_chunk", "slots": 1,
         "chunk_tokens": 600, "kv_read_tokens": 600 * 601 // 2}]
    cx = ctx(mods, {}, ledger, metrics=COUNTERS)
    assert READER.read(cx, "prefill_ms_per_ktok") == pytest.approx(
        1e6 * 0.30 / 1624)
    # Real pairs: six a token, whatever rows the tiles were padded to;
    # the decode program's expert seconds stay out.
    assert READER.read(cx, "moe_experts_prefill") == pytest.approx(
        100 * (12 * 2 * 1624 * 6 * 5_898_240 / 197e12) / 0.15)
    pairs = 1024 * 1025 // 2 + 600 * 601 // 2
    assert READER.read(cx, "prefill_attn") == pytest.approx(
        100 * (12 * 4 * 28 * 128 * pairs / 197e12) / 0.05)


def test_pool_gauges_and_nothing_to_read():
    end = {"tpu_inf_kv_window_pages_total": 6900.0,
           "tpu_inf_kv_window_pages_peak": 5100.0,
           "tpu_inf_kv_window_pages_booked_peak": 5800.0,
           "tpu_inf_kv_full_pages_total": 6901.0,
           "tpu_inf_kv_full_pages_peak": 5200.0}
    cx = dict(ctx({}, {}, metrics=end), peaks=None)     # no chip needed
    assert READER.read(cx, "pool_live_share", "window") == pytest.approx(
        100 * 5100 / 6900)
    assert READER.read(cx, "pool_live_share", "full") == pytest.approx(
        100 * 5200 / 6901)
    assert READER.read(cx, "pool_booked_share", "window") == pytest.approx(
        100 * 5800 / 6900)
    empty = ctx({}, {"fusion.1_bf16_8_": [3, 0.1]})
    whats = ("decode_attn", "prefill_attn", "prefill_ms_per_ktok",
             "moe_experts_decode", "moe_experts_prefill", "decode_hbm",
             "rows_per_expert", "padded_row_share")
    for what in whats:
        assert READER.read(empty, what) is None
    assert READER.read(empty, "pool_booked_share", "window") is None
    with open(os.path.join(BENCH, "configs",
                           "laguna-s-ep8-bf16.json")) as f:
        other = dict(cx, config=json.load(f))
    for what in whats:
        assert READER.read(other, what) is None
    with pytest.raises(ValueError):
        READER.read(ctx({"m": {"runs": 1, "seconds": 1.0, "starts": [0.0],
                               "ops": {DEC: [12, 0.1]}}}, {},
                        metrics=COUNTERS), "nope")
