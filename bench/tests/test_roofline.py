"""bench/roofline.py: bytes and flops from published sizes."""

import json
import os

import pytest

import roofline as R
from conftest import BENCH


def cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_mistral_weight_bytes():
    m = cfg("mistral-7b-int8")
    per_layer = 4096 * (4096 + 2 * 1024) + 4096 * 4096 + 3 * 4096 * 14336
    assert R.matmul_params_per_layer(m) == per_layer
    n = 32 * per_layer + 4096 * 32000
    int8 = R.weight_bytes_per_step(m, "int8")
    assert n < int8 < 1.01 * n                 # codes + a sliver of scales
    assert R.weight_bytes_per_step(m, "int8", chips=4) == pytest.approx(
        int8 / 4)
    with pytest.raises(ValueError):
        R.weight_bytes_per_step(m, "int3")


def test_kv_and_window():
    m, q = cfg("mistral-7b-int8"), cfg("qwen2-7b-int8")
    assert R.kv_bytes_per_token_layer(m) == 2 * 8 * 128 * 2
    assert R.kv_bytes_per_token_layer(q) == 2 * 4 * 128 * 2
    assert R.window(m) == 4096 and R.window(q) == 0
    assert R.visible(5000, m) == 4096 and R.visible(5000, q) == 5000
    assert R.decode_attn_bytes(1000, m) == 1000 * 4096
    assert R.decode_attn_flops(1000, m) == 4 * 32 * 128 * 1000


def test_prefill_flops_are_causal_and_windowed():
    q = cfg("qwen2-7b-int8")
    # 4 new tokens after 10 cached: keys seen 11 + 12 + 13 + 14
    assert R.prefill_attn_keys(4, 10, q) == 50
    assert R.prefill_attn_flops(50, q) == 4 * 28 * 128 * 50
    m = cfg("mistral-7b-int8")
    assert R.prefill_attn_keys(2, 4095, m) == 4096 + 4096


def test_ledger_record_keys_are_recounted_with_the_window():
    m, q = cfg("mistral-7b-int8"), cfg("qwen2-7b-int8")
    # The engine's own count: chunk * offset + chunk * (chunk + 1) / 2.
    rec = {"chunk_tokens": 512, "slots": 1,
           "kv_read_tokens": 512 * 4000 + 512 * 513 // 2}
    assert R.ledger_prefill_keys(rec, q) == rec["kv_read_tokens"]
    assert R.ledger_prefill_keys(rec, m) == R.prefill_attn_keys(512, 4000, m)
    assert R.ledger_prefill_keys(rec, m) < rec["kv_read_tokens"]
    batch = {"chunk_tokens": 300, "slots": 3, "kv_read_tokens": 17000}
    assert R.ledger_prefill_keys(batch, m) == 17000
