"""bench/roofline_looped.py (bytes and flops of a looped configuration,
from its file) and bench/readers/looped.py (shares and times from a
recorded trace summary beside the client's records and the engine's
ledger)."""

import json
import os

import pytest

import roofline_looped as L
from conftest import BENCH
from manifest import load_module

READER = load_module(os.path.join(BENCH, "readers", "looped.py"))
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def ouro():
    with open(os.path.join(BENCH, "configs", "ouro-2.6b-bf16.json")) as f:
        return json.load(f)


def test_counts_from_the_published_sizes():
    c = ouro()
    assert L.passes(c) == 4 and L.layer_applications(c) == 192
    # By hand: 4 x 2048^2 + 3 x 2048 x 5632 parameters a layer, 2 bytes.
    assert L.layer_weight_bytes(c) == 2 * 51380224
    assert L.head_weight_bytes(c) == 2 * 2048 * 49152
    # 192 slots x K and V x 16 heads x 128 x 2 bytes = 1.5 MiB a token.
    assert L.kv_bytes_per_token(c) == 192 * 2 * 16 * 128 * 2 == 1572864
    assert L.layer_pass_floor_us(c, 819e9) == pytest.approx(125.47, abs=0.01)
    # ISSUE 30's arithmetic: 4 x 4.93 GB + 0.20 GB of weights a step, and
    # 3.9k visible tokens x 1.5 MiB beside them.
    assert L.decode_step_bytes(0, c) == pytest.approx(19.93e9, rel=1e-3)
    assert L.decode_step_bytes(3900, c) - L.decode_step_bytes(0, c) \
        == 3900 * 1572864
    # One pair costs 4 x 16 x 128 flops in each of the 192 applications.
    assert L.prefill_attn_flops(1000, c) == 192 * 4 * 16 * 128 * 1000


def ctx(modules, ops, ledger=(), records=None, config=None):
    prof = {"start_s": 10.0, "seconds": 3.0, "start_unix": 1000.0,
            "end_unix": 1009.0}
    if records is None:
        # 10 streams of 130 prompt tokens, each 100 tokens in at t = 10.
        records = [{"prompt_tokens": 130,
                    "token_s": [5.0 + 0.05 * i for i in range(400)]}
                   for _ in range(10)]
    return {"config": config or ouro(), "peaks": PEAKS, "profile": prof,
            "records": records, "ledger": list(ledger),
            "cell": {"chips": 1},
            "trace": {"chips": {"c0": {"ops": ops}}, "modules": modules}}


DEC = "paged_attention.3_bf16_12_16_128_"
PRE = "paged_prefill_attention.5_bf16_1_2_16_128_128_"    # 256 token rows


def test_decode_readings():
    # 50 decode steps of 192 layer applications in 2.4 s of decode programs.
    ops = {DEC: [9600, 0.30], "fusion.9_bf16_12_2048_": [9600, 1.5]}
    mods = {"jit_tpu_inf_decode_k8": {
        "runs": 7, "seconds": 2.4, "starts": [0.4 * i for i in range(7)],
        "ops": ops},
        "jit_tpu_inf_prefill": {"runs": 1, "seconds": 0.5, "starts": [1.0],
                                "ops": {PRE: [192, 0.1]}}}
    c = ctx(mods, ops)
    assert READER.read(c, "looped_layer_pass_us") == pytest.approx(
        1e6 * 2.4 / 9600)                       # 250 us an application
    vis = READER.K._mean_visible_context(c)
    assert 10 * 230 < vis < 10 * 300            # 130 + 100..160 tokens each
    share = READER.read(c, "looped_decode_hbm")
    assert share == pytest.approx(
        100 * (192 * 2 * 51380224 + 2 * 2048 * 49152 + vis * 1572864)
        / 819e9 / (2.4 / 50))
    assert 50 < share < 100


def test_prefill_readings_match_ledger_records_to_the_profiles_runs():
    mods = {"jit_tpu_inf_prefill": {
        "runs": 2, "seconds": 0.30, "starts": [0.5, 1.9],
        "ops": {PRE: [384, 0.12]}}}
    ledger = [
        {"ts": 990.0, "kind": "prefill_chunk", "slots": 1,
         "chunk_tokens": 100, "kv_read_tokens": 5050},
        {"ts": 1000.6, "kind": "prefill_chunk", "slots": 1,
         "chunk_tokens": 200, "kv_read_tokens": 20100},
        {"ts": 1002.0, "kind": "prefill_chunk", "slots": 1,
         "chunk_tokens": 180, "kv_read_tokens": 16290},
        {"ts": 1001.0, "kind": "decode", "slots": 10, "chunk_tokens": 0,
         "kv_read_tokens": 1}]
    c = ctx(mods, {PRE: [384, 0.12]}, ledger)
    assert READER.read(c, "looped_prefill_ms_per_ktok") == pytest.approx(
        1e6 * 0.30 / 380)
    assert READER.read(c, "looped_prefill_attn") == pytest.approx(
        100 * 192 * 4 * 16 * 128 * (20100 + 16290) / 197e12 / 0.12)
    # No decode program in this trace.
    assert READER.read(c, "looped_decode_hbm") is None
    assert READER.read(c, "looped_layer_pass_us") is None


def test_nothing_to_read_is_none():
    c = ctx({}, {"fusion.1_bf16_8_": [3, 0.1]})
    whats = ("looped_decode_hbm", "looped_layer_pass_us",
             "looped_prefill_attn", "looped_prefill_ms_per_ktok")
    for what in whats:
        assert READER.read(c, what) is None
    # Another family's configuration, or no chip: nothing, no raise.
    with open(os.path.join(BENCH, "configs", "mistral-7b-int8.json")) as f:
        other = dict(c, config=json.load(f))
    for what in whats:
        assert READER.read(other, what) is None
        assert READER.read(dict(c, peaks=None), what) is None
    with pytest.raises(ValueError):
        READER.read(ctx({}, {DEC: [1, 0.1]}), "nope")
