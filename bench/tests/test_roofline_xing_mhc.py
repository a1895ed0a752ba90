"""bench/roofline_xing_mhc.py (bytes and flops of a hyper-connection and
of the Xing4.0 stage's decode step from its configuration file, against
ISSUE 47's hand arithmetic) and bench/readers/xing_mhc.py (shares and
rows from a recorded trace summary beside the client's records, the
engine's ledger and the program's counters); and the accepted readings
this cell lists as they are (readers/mla_moe.py), at this
configuration's sizes."""

import json
import os

import pytest

import roofline_mla_moe as M
import roofline_xing_mhc as R
from conftest import BENCH
from manifest import load_module

READER = load_module(os.path.join(BENCH, "readers", "xing_mhc.py"))
MLA = load_module(os.path.join(BENCH, "readers", "mla_moe.py"))
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def xing():
    with open(os.path.join(BENCH, "configs",
                           "xing4-29b-pp6-bf16.json")) as f:
        return json.load(f)


def test_counts_from_the_published_sizes():
    c = xing()
    assert (R.streams(c), R.n_coeff(c), R.wide(c), R.sublayers(c)) == (
        4, 24, 14336, 14)
    # ISSUE 47's count, by hand: attention 28.41 M a layer; an expert
    # 11.01 M; 64 of them, the shared one and the router 744.3 M a layer
    # with the attention.
    assert M.attn_params(c) == (3584 * 768 + 768 * 6144 + 3584 * 576
                                + 512 * 8192 + 4096 * 3584) == 28_409_856
    assert M.expert_params(c) == 3 * 3584 * 1024 == 11_010_048
    assert (M.held_experts(c), M.all_experts(c), M.expert_layers(c)) == (
        64, 64, 6)
    assert 28_409_856 + 65 * 11_010_048 + 3584 * 64 == pytest.approx(
        744.3e6, rel=1e-3)
    # A latent entry: 576 values counted (640 stored), 7 layers.
    assert M.mla_attn_bytes(1, c) == 576 * 2
    # A hyper-connection: phi 14336 x 24 bfloat16 + 27 float32; a token
    # moves 2 x 14336 + 3584 values of 2 bytes through one: 64.5 KB, 14
    # times a forward pass: 0.9 MB a token, 925 MB a 1024-token chunk.
    assert R.mhc_weight_bytes(c) == 14336 * 24 * 2 + 27 * 4
    assert R.mhc_stream_bytes(1, c) == (2 * 14336 + 3584) * 2 == 64_512
    assert 14 * R.mhc_stream_bytes(1024, c) == pytest.approx(925e6, rel=1e-3)
    # ~120 MB of stream traffic a sublayer a chunk in the program's own
    # form was the issue's guess; the perfect form's is 66 MB.
    assert R.mhc_stream_bytes(1024, c) == pytest.approx(66e6, rel=1e-2)
    # Arithmetic: the head's matmul is 2 x 14336 x 24 = 0.69 MFLOP a
    # token; the mixes 2 x 14336 x (1 + 1 + 4 + 1); the projection 20 x
    # (32 + 24 + 8) = 1280 flops.
    assert R.mhc_flops(1, c) == (2 * 14336 * 24 + 2 * 14336 * 7 + 1280)
    # Non-expert weights a decode step reads: 7 x 28.41 M of attention,
    # the dense SwiGLU 99.09 M, 6 x (router 0.23 M + shared 11.01 M), the
    # head 469.8 M, and 14 hyper-connections: 1.676 GB (the issue's
    # "1.7 GB of dense weights and head").
    non = R.non_expert_weight_bytes(c)
    assert non == 2 * (7 * 28_409_856 + 3 * 3584 * 9216
                       + 6 * (3584 * 64 + 11_010_048) + 3584 * 131072) + \
        14 * (14336 * 24 * 2 + 108)
    assert non == pytest.approx(1.68e9, rel=0.01)
    # The reckoned step: ~59 of 64 experts x 6 layers (7.8 GB), 64 lanes
    # of ~5.6 k latents (the issue counted the stored 640: 3.2 GB; 576
    # are 2.9 GB): ~12.4 GB, 15 ms at the v5e's 819 GB/s.
    step = R.decode_step_bytes(59.0, 64 * 5600.0, c)
    assert step == pytest.approx(
        non + 6 * 59 * 11_010_048 * 2 + 7 * 64 * 5600 * 1152)
    assert step == pytest.approx(12.4e9, rel=0.01)
    assert step / 819e9 == pytest.approx(0.0151, rel=0.01)
    assert R.moe_read_bytes(64, c) == 64 * 11_010_048 * 2
    assert R.moe_flops(64, c) == 2 * 64 * 4 * 11_010_048


def ctx(modules, ops, ledger=(), metrics=None, steps=None, config=None,
        busy_s=1.0):
    prof = {"start_s": 10.0, "seconds": 3.0, "start_unix": 1000.0,
            "end_unix": 1009.0}
    # 64 streams of 5000 prompt tokens, each 100 tokens in at t = 10.
    records = [{"prompt_tokens": 5000,
                "token_s": [5.0 + 0.05 * i for i in range(400)]}
               for _ in range(64)]
    return {"config": config or xing(), "peaks": PEAKS, "profile": prof,
            "records": records, "ledger": list(ledger), "seconds": 48.0,
            "cell": {"chips": 1}, "metrics_open": {},
            "metrics_end": metrics or {}, "steps": steps or {},
            "trace": {"chips": {"c0": {"ops": ops}}, "modules": modules,
                      "busy_s": busy_s}}


DEC = "mla_decode_attention.7_bf16_64_32_512_"
PRE = "mla_prefill_attention.9_bf16_1_1024_32_512_"
EXP_G = "tpu_custom_call.11_bf16_1024_1024_"
EXP_D = "tpu_custom_call.12_f32_1024_3584_"
HEAD = "fusion.835_f32_24_1024_"                 # the head's matmul
JOIN = "pad_maximum_fusion.17_bf16_1_1024_14336_"     # the re-assembly
SINK = "multiply_divide_fusion.14_f32_16_1024_"
COUNTERS = {"tpu_inf_moe_distinct_experts_total": 59.0 * 600,
            "tpu_inf_moe_decode_layer_steps_total": 600.0}
STEPS = {"fleet": {"enabled": True, "kinds": {}, "rung_occupancy": {
    "64": {"dispatches": 100, "mean_slots": 59.0}}}}


@pytest.mark.parametrize("name,told", [
    (HEAD, True), (JOIN, True), (SINK, True),
    ("fusion.6073_f32_16_64_", True), ("pad_maximum_fusion.27_f32_4_4_64_",
                                       True),
    ("maximum_bitcast_fusion.53_f32_1_4_64_", True),
    ("broadcast_add_fusion.244_f32_4_1024_", True),
    ("maximum_bitcast_fusion.54_f32_64_1_24_", True),
    ("fusion.5_bf16_64_1_14336_", True),
    # Not a hyper-connection's: a hidden-wide result, the kernels, a
    # tuple result (no shape in its name), an int array of that shape.
    ("fusion.9_bf16_64_3584_", False), (DEC, False), (EXP_D, False),
    ("bitcast_divide_fusion.5156", False), ("fusion.3_s32_4_64_", False),
    ("fusion.77_f32_64_4_", False), ("convolution.3_f32_64_131072_", False),
])
def test_which_ops_the_trace_can_tell(name, told):
    assert READER.is_mhc(name, xing()) is told


def test_decode_readings():
    c = xing()
    # 50 decode steps: 350 calls of the decode kernel (7 layers).
    ops = {DEC: [350, 0.200], EXP_G: [300, 0.40], EXP_D: [300, 0.20],
           HEAD.replace("1024", "64"): [700, 0.004],
           JOIN.replace("1_1024", "64_1"): [700, 0.006],
           "fusion.9_bf16_64_3584_": [700, 0.2]}
    mods = {"jit_tpu_inf_decode_k8": {
        "runs": 7, "seconds": 1.0, "starts": [0.4 * i for i in range(7)],
        "ops": ops}}
    cx = ctx(mods, ops, metrics=COUNTERS, steps=STEPS, busy_s=1.25)
    seqs, vis = MLA._in_flight(cx)
    assert seqs == [64] * 60 and vis == pytest.approx(64 * 5130.0, rel=0.01)
    experts = READER.read(cx, "moe_experts_decode")
    assert experts == pytest.approx(
        100 * 6 * 50 * (59 * 11_010_048 * 2 / 819e9) / 0.60)
    hbm = READER.read(cx, "decode_hbm")
    assert hbm == pytest.approx(
        100 * R.decode_step_bytes(59.0, vis, c) / 819e9 / (1.0 / 50))
    assert READER.read(cx, "mhc_busy_share") == pytest.approx(
        100 * 0.010 / 1.25)
    # The accepted readings this cell lists, at its sizes: 32 heads.
    attn = MLA.read(cx, "mla_decode_attn")
    assert attn == pytest.approx(100 * 350 * vis * 1152 / 819e9 / 0.200)
    assert 0 < min(attn, experts, hbm) and max(attn, experts, hbm) < 100
    # A program without the counters: nothing to read, nothing raised.
    bare = ctx(mods, ops, steps=STEPS)
    for what in ("moe_experts_decode", "decode_hbm"):
        assert READER.read(bare, what) is None


def test_prefill_readings():
    mods = {"jit_tpu_inf_prefill": {
        "runs": 2, "seconds": 0.30, "starts": [0.5, 1.9],
        "ops": {PRE: [14, 0.05], EXP_G: [12, 0.09], HEAD: [28, 0.004],
                JOIN: [28, 0.008], SINK: [28, 0.001]}},
        "jit_tpu_inf_decode_k8": {
            "runs": 1, "seconds": 0.1, "starts": [1.0],
            "ops": {DEC: [56, 0.01],
                    HEAD.replace("1024", "64"): [112, 9.0]}}}
    ledger = [
        {"ts": 1000.6, "kind": "prefill_chunk", "slots": 1,
         "chunk_tokens": 1024, "kv_read_tokens": 1024 * 4096 + 1024 * 1025 // 2},
        {"ts": 1002.0, "kind": "prefill_chunk", "slots": 1,
         "chunk_tokens": 600, "kv_read_tokens": 600 * 4096 + 600 * 601 // 2}]
    cx = ctx(mods, {PRE: [14, 0.05]}, ledger, metrics=COUNTERS)
    # The decode program's ops stay out; the told ops' 13 ms over the
    # prompt tokens the chunks held, not their buckets' rows.
    assert READER.read(cx, "mhc_prefill_us_per_ktok") == pytest.approx(
        1e9 * 0.013 / 1624)
    assert MLA.read(cx, "mla_prefill_ms_per_ktok") == pytest.approx(
        1e6 * 0.30 / 1624)
    pairs = sum(r["kv_read_tokens"] for r in ledger)
    assert MLA.read(cx, "mla_prefill_attn") == pytest.approx(
        100 * (7 * 2 * 32 * (576 + 512) * pairs / 197e12) / 0.05)


def test_nothing_to_read():
    empty = ctx({}, {"fusion.1_bf16_8_": [3, 0.1]})
    whats = ("mhc_busy_share", "mhc_prefill_us_per_ktok", "moe_experts_decode",
             "decode_hbm")
    for what in whats:
        assert READER.read(empty, what) is None
    # Another configuration (one residual stream), and no chip.
    with open(os.path.join(BENCH, "configs",
                           "kimi-k2-ep32-bf16.json")) as f:
        other = ctx({}, {HEAD: [1, 1.0]}, metrics=COUNTERS, steps=STEPS,
                    config=json.load(f))
    for what in whats:
        assert READER.read(other, what) is None
    off_chip = dict(ctx({}, {HEAD: [1, 1.0]}, metrics=COUNTERS,
                        steps=STEPS), peaks=None)
    for what in whats:
        assert READER.read(off_chip, what) is None
    with pytest.raises(ValueError):
        READER.read(ctx({"m": {"runs": 1, "seconds": 1.0, "starts": [0.0],
                               "ops": {DEC: [7, 0.1]}}}, {},
                        metrics=COUNTERS), "nope")
