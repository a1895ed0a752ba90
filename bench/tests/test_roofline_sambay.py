"""bench/roofline_sambay.py (bytes and flops of a SambaY configuration,
from its file) and bench/readers/sambay.py (shares and times from a
recorded trace summary beside the client's records, the engine's ledger
and the program's gauges and counters)."""

import json
import os

import pytest

import roofline_sambay as R
from conftest import BENCH
from manifest import load_module

READER = load_module(os.path.join(BENCH, "readers", "sambay.py"))
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def phi4():
    with open(os.path.join(BENCH, "configs",
                           "phi4-mini-flash-bf16.json")) as f:
        return json.load(f)


def test_counts_from_the_published_sizes():
    c = phi4()
    kinds = R.kinds(c)
    assert [kinds.count(k) for k in ("ssm", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "ssm" and kinds[17] == "full"
    assert R.full_readers(c) == 8 and R.head_dim(c) == 64
    assert R.pool_heads(c) == (10, 128)
    assert R.scan_sizes(c) == (5120, 16, 4, 160)
    # K and V, 20 heads x 64, bfloat16: 5 KB a token a layer.
    assert R.kv_bytes_per_token_layer(c) == 5120
    assert R.visible(5000, c, "window") == 512
    assert R.visible(5000, c, "full") == 5000
    # ISSUE 40's count: 3.85 B parameters (norms elided here).
    assert R.weight_params(c) == pytest.approx(3.852e9, rel=1e-3)
    # A sequence's state: 9 x (16 x 5120 float32 + 3 x 5120 bfloat16).
    assert 9 * R.state_bytes_per_seq_layer(c) == 9 * 5120 * (64 + 6)
    assert R.decode_state_bytes(64, c) == 64 * 9 * 2 * 5120 * 70
    # The scan of a 1024-token chunk, one lane, one layer: x, dt, y a
    # channel (8 B), B and C (128 B a token), the state in and out.
    assert R.scan_bytes(1024, 1, c) == (
        1024 * (5120 * 8 + 128) + 2 * 5120 * 16 * 4)
    # Differential attention as the equations pay it: 6 H d a pair.
    assert R.attn_flops(1000, c) == 6 * 40 * 64 * 1000
    vis = {"full": 64 * 2100.0, "window": 64 * 512.0}
    step = R.decode_step_bytes(64, vis, c)
    assert step == pytest.approx(
        2 * R.weight_params(c) + 8 * 64 * 2100 * 5120
        + 8 * 64 * 512 * 5120 + R.decode_state_bytes(64, c))
    # ~18 ms at the HBM peak, as the issue's arithmetic has it.
    assert 17e-3 < step / 819e9 < 19e-3
    rec = {"chunk_tokens": 1024, "slots": 1,
           "kv_read_tokens": 1024 * 4096 + 1024 * 1025 // 2}
    assert R.ledger_prefill_pairs(rec, c, "window") == 1024 * 512
    assert R.ledger_prefill_pairs(rec, c, "full") == rec["kv_read_tokens"]


def ctx(modules, ledger=(), metrics=None, config=None):
    prof = {"start_s": 10.0, "seconds": 3.0, "start_unix": 1000.0,
            "end_unix": 1009.0}
    # 10 streams of 2000 prompt tokens, each 100 tokens in at t = 10.
    records = [{"prompt_tokens": 2000,
                "token_s": [5.0 + 0.05 * i for i in range(400)]}
               for _ in range(10)]
    return {"config": config or phi4(), "peaks": PEAKS, "profile": prof,
            "records": records, "ledger": list(ledger), "seconds": 48.0,
            "cell": {"chips": 1}, "metrics_open": {},
            "metrics_end": metrics or {},
            "trace": {"chips": {}, "modules": modules}}


DEC_W = "tpu_custom_call.7_bf16_64_40_128_"       # in the pairs' scan
DEC_F = "paged_attention.3_bf16_64_40_128_"
DEC_C = "tpu_custom_call.9_bf16_64_40_128_"
PRE_W = "tpu_custom_call.5_bf16_1_4_10_1024_128_"  # 1024 rows x 4
SCAN = "tpu_custom_call.4"                         # a pair: no shape
STATE = "fusion.12_f32_64_16_5120_"


def test_ops_are_told_apart():
    c = phi4()
    assert READER._is_decode(DEC_W, c) and READER._is_decode(DEC_F, c)
    assert not READER._is_decode("fusion.2_bf16_64_40_128_", c)
    assert not READER._is_decode("paged_attention.3_bf16_64_48_128_", c)
    assert READER._prefill_rows(PRE_W, c) == 1024
    assert READER._prefill_rows(DEC_W, c) is None
    assert READER._is_scan(SCAN) and READER._is_scan("selective_scan.2")
    assert not READER._is_scan(DEC_W) and not READER._is_scan("fusion.3")
    assert READER._is_state(STATE, c)
    assert READER._is_state("scatter.3_f32_9_65_16_5120_", c)
    assert not READER._is_state("fusion.1_bf16_64_16_5120_", c)


def test_decode_readings_tell_the_window_calls_by_their_count():
    c = phi4()
    # 50 decode steps: 400 window calls, 50 full, 350 cross.
    ops = {DEC_W: [400, 0.040], DEC_F: [50, 0.012], DEC_C: [350, 0.084],
           STATE: [450, 0.030], "fusion.9_bf16_64_2560_": [600, 0.2]}
    mods = {"jit_tpu_inf_decode_k8": {
        "runs": 7, "seconds": 1.0, "starts": [0.4 * i for i in range(7)],
        "ops": ops}}
    cx = ctx(mods)
    seqs, vis = READER._in_flight(cx)
    assert seqs == [10] * 60 and vis["window"] == pytest.approx(10 * 512)
    steps, secs, state_s, by_kind = READER._decode_programs(cx)
    assert (steps, secs, state_s) == (50, 1.0, 0.030)
    assert by_kind == {"window": [400, 0.040], "shared": [400, 0.096]}
    shared = READER.read(cx, "shared_decode_attn")
    assert shared == pytest.approx(
        100 * 400 * vis["full"] * 5120 / 819e9 / 0.096)
    window = READER.read(cx, "window_decode_attn")
    assert window == pytest.approx(100 * 400 * 5120 * 5120 / 819e9 / 0.040)
    state = READER.read(cx, "decode_state")
    assert state == pytest.approx(
        100 * 50 * R.decode_state_bytes(10, c) / 819e9 / 0.030)
    hbm = READER.read(cx, "decode_hbm")
    assert hbm == pytest.approx(
        100 * R.decode_step_bytes(10, vis, c) / 819e9 / (1.0 / 50))
    assert 0 < min(shared, window, state, hbm)
    assert max(shared, window, state, hbm) < 100
    # Calls that do not stand 8 : 1 : 7 are not told apart: nothing is
    # read, rather than one kind's time under the other's name.
    assert READER.read(ctx({"m": dict(mods["jit_tpu_inf_decode_k8"], ops=dict(
        ops, **{DEC_W: [392, 0.040]}))}), "window_decode_attn") is not None
    odd = dict(ops, **{DEC_W: [300, 0.040]})
    cx = ctx({"m": dict(mods["jit_tpu_inf_decode_k8"], ops=odd)})
    assert READER.read(cx, "window_decode_attn") is None


def test_prefill_readings_match_ledger_records_to_the_profiles_runs():
    c = phi4()
    mods = {"jit_tpu_inf_prefill": {
        "runs": 2, "seconds": 0.30, "starts": [0.5, 1.9],
        "ops": {PRE_W: [18, 0.08], SCAN: [18, 0.09],
                DEC_C: [14, 0.001]}}}
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
    cx = ctx(mods, ledger)
    assert READER.read(cx, "prefill_ms_per_ktok") == pytest.approx(
        1e6 * 0.30 / 1624)
    pairs_full = 1024 * 2048 + 1024 * 1025 // 2 + 600 * 601 // 2
    pairs_win = 1024 * 512 + 512 * 513 // 2 + 88 * 512
    flops = 6 * 40 * 64 * (pairs_full + 8 * pairs_win)
    assert READER.read(cx, "prefill_attn") == pytest.approx(
        100 * flops / 197e12 / 0.08)
    assert READER.read(cx, "scan_prefill") == pytest.approx(
        100 * 9 * R.scan_bytes(1624, 2, c) / 819e9 / 0.09)
    assert READER.read(cx, "decode_hbm") is None


def test_gauges_and_counters():
    end = {"tpu_inf_state_slots_total": 64.0,
           "tpu_inf_state_slots_peak": 48.0,
           "tpu_inf_prefill_positions_total": 300000.0,
           "tpu_inf_prefill_cross_positions_total": 260.0}
    cx = ctx({}, metrics=end)
    cx["metrics_open"] = {"tpu_inf_prefill_positions_total": 100000.0,
                          "tpu_inf_prefill_cross_positions_total": 60.0}
    assert READER.read(cx, "slots_live_share") == 75.0
    assert READER.read(cx, "cross_positions_share") == pytest.approx(0.1)
    # The pools a kind, as readers/mixed.py reads Laguna's.
    pools = {"tpu_inf_kv_full_pages_total": 20000.0,
             "tpu_inf_kv_full_pages_peak": 9000.0,
             "tpu_inf_kv_window_pages_total": 6272.0,
             "tpu_inf_kv_window_pages_peak": 3136.0,
             "tpu_inf_kv_window_pages_released_total": 5000.0}
    px = ctx({}, metrics=pools)
    px["metrics_open"] = {"tpu_inf_kv_window_pages_released_total": 200.0}
    assert READER.read(px, "pool_live_share", kind="full") == 45.0
    assert READER.read(px, "pool_live_share", kind="window") == 50.0
    assert READER.read(px, "released_per_s") == 4800.0 / px["seconds"]
    assert READER.read(ctx({}), "pool_live_share", kind="full") is None
    assert READER.read(ctx({}), "released_per_s") is None
    # On the CPU too (counters need no chip); a program without them (the
    # parent commit) gives nothing and does not raise.
    assert READER.read(dict(cx, peaks=None), "slots_live_share") == 75.0
    assert READER.read(ctx({}), "slots_live_share") is None
    assert READER.read(ctx({}), "cross_positions_share") is None


def test_nothing_to_read_is_none():
    cx = ctx({"m": {"runs": 1, "seconds": 1.0, "starts": [0.0],
                    "ops": {"fusion.1_bf16_8_": [3, 0.1]}}})
    whats = ("shared_decode_attn", "window_decode_attn", "prefill_attn",
             "prefill_ms_per_ktok", "scan_prefill", "decode_state",
             "decode_hbm")
    for what in whats:
        assert READER.read(cx, what) is None
    with open(os.path.join(BENCH, "configs", "mistral-7b-int8.json")) as f:
        other = dict(cx, config=json.load(f))
    for what in whats + ("slots_live_share", "cross_positions_share",
                         "released_per_s"):
        assert READER.read(other, what) is None
    assert READER.read(other, "pool_live_share", kind="full") is None


def test_every_reading_of_the_cell_names_the_reader():
    """What only this cell has is an entry of its own, whose file names
    the reader; what every architecture has is the shared entry, and the
    configuration's ``readings`` names the reader (PR 50)."""
    from manifest import Manifest

    man = Manifest(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    cell = man.cell("phi4-mini-flash-bf16_reason-long-batch")
    cfg = man.config(cell)
    listed = {m["name"]: m for m in man.metrics_of("per_layer", cell["name"])}
    own = [m for m in listed.values() if m.get("workloads") == [cell["name"]]]
    assert sorted(m["name"] for m in own) == [
        "prefill_cross_positions_share", "ssm_decode_state_roofline",
        "ssm_scan_prefill_roofline", "state_slots_live_share"]
    assert sorted(cfg["readings"]) == [
        "decode_attn_roofline", "decode_hbm_roofline",
        "kv_full_pool_live_share", "kv_window_pages_released_per_s",
        "kv_window_pool_live_share", "prefill_attn_roofline",
        "prefill_ms_per_ktok", "window_decode_attn_roofline"]
    shared = [m for name, m in listed.items()
              if name.split(".")[0] in cfg["readings"]]
    assert len(shared) == 8
    for m in own + shared:
        assert man.layer_metric(m["name"], cfg)["reader"] == "sambay"
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["better"] == "higher"
    # The counter's delta is everybody's reader, under everybody's name.
    assert listed["preemptions_in_window"]["moves"] == "out_tok_s"
    assert man.layer_metric("preemptions_in_window", cfg)["reader"] \
        == "metrics_delta"
