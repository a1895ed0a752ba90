"""The readers that set a trace beside the engine's step ledger, and the
harness's hold on the one trace file that is its own."""

import json
import os
import time

import pytest

import run as RUN
from conftest import BENCH
from manifest import Manifest, load_module

KERNELS = load_module(os.path.join(BENCH, "readers", "kernels.py"))
PREFILL = "paged_prefill_attention"


def mistral():
    with open(os.path.join(BENCH, "configs", "mistral-7b-int8.json")) as f:
        return json.load(f)


def rec(ts, chunk, offset=0, slots=1, kind="prefill_chunk"):
    return {"ts": ts, "kind": kind, "slots": slots, "chunk_tokens": chunk,
            "kv_read_tokens": chunk * offset + chunk * (chunk + 1) // 2}


def ctx_of(modules, ledger, t0=1000.0):
    return {"config": mistral(), "ledger": ledger,
            "trace": {"modules": modules},
            "profile": {"start_unix": t0, "end_unix": t0 + 9.0,
                        "seconds": 3.0}}


def module(rows, starts, seconds):
    # bf16[1, rows/128, 8 kv heads, 128 * 4 grouped queries, 128]
    op = f"{PREFILL}.11_bf16_1_{rows // 128}_8_512_128_"
    return {"runs": len(starts), "seconds": seconds, "starts": starts,
            "ops": {op: [32 * len(starts), 0.01]}}


def test_rows_come_from_the_kernel_result_shape():
    m = mistral()
    assert KERNELS._rows(f"{PREFILL}.11_bf16_1_8_8_512_128_", m) == 1024
    assert KERNELS._rows(f"{PREFILL}.11_bf16_1_1_8_256_128_", m) == 64
    assert KERNELS._rows(f"{PREFILL}.11", m) is None


def test_prefill_work_is_that_of_the_dispatches_the_profile_holds():
    # The profile holds three runs (1024, 256, 1024 rows). The ledger has
    # the whole run's dispatches; those before and after the profile must
    # not be counted, whatever the window's totals are.
    mods = {"a": module(1024, [0.30, 2.10], 0.30),
            "b": module(256, [1.20], 0.04),
            "decode": {"runs": 9, "seconds": 2.0, "starts": [0.0] * 9,
                       "ops": {"paged_attention.11_bf16_8_8_4_128_":
                               [288, 0.7]}}}
    ledger = [rec(990.0, 700), rec(1000.2, 1000), rec(1001.25, 900),
              rec(1001.9, 200), rec(1002.8, 180), rec(1003.7, 1024),
              rec(1004.6, 300), rec(1001.0, 0, kind="decode")]
    # 900 does not fit 256 rows; of (200, 180, 1024) / (1000, ...) only
    # one stretch fits every run: 1024-row, 256-row, 1024-row.
    tokens, keys, secs = KERNELS._prefill_in_profile(ctx_of(mods, ledger),
                                                     PREFILL)
    assert tokens == 900 + 200 + 180 or tokens == 200 + 180 + 1024
    assert tokens == 200 + 180 + 1024      # 900 > 256 rows rules the first out
    assert secs == pytest.approx(0.34)
    assert keys == sum(c * (c + 1) // 2 for c in (200, 180, 1024))
    ms = KERNELS.read(dict(ctx_of(mods, ledger), peaks={"flops_bf16": 1e12},
                           cell={"chips": 1}),
                      "prefill_ms_per_ktok", kernel=PREFILL)
    assert ms == pytest.approx(1e6 * 0.34 / 1404)


def test_equal_rows_are_told_apart_by_their_instants():
    # A document's chunks: all 512 rows. Only timing says which ones.
    mods = {"a": module(512, [0.2, 0.9, 2.6], 0.5)}
    ledger = [rec(1000.0 + t, 512, offset=512 * i) for i, t in
              enumerate([0.1, 0.4, 1.1, 2.8, 3.3, 4.9])]
    tokens, keys, _ = KERNELS._prefill_in_profile(ctx_of(mods, ledger),
                                                  PREFILL)
    assert tokens == 3 * 512
    want = sum(512 * 512 * i + 512 * 513 // 2 for i in (1, 2, 3))
    assert keys == want, "the stretch at +0.4, +1.1, +2.8 keeps even lags"


def test_a_pattern_that_repeats_is_taken_nearest_the_profile_call():
    # Closed-loop documents repeat their chunk pattern every 5 s: both
    # stretches are equally even; the one at the profile call is meant.
    mods = {"a": module(1024, [0.1, 0.8, 1.5], 0.6)}
    ledger = ([rec(995.3 + t, 1024) for t in (0.0, 0.7, 1.4)]
              + [rec(1000.3 + t, 1000) for t in (0.0, 0.7, 1.4)]
              + [rec(1005.3 + t, 900) for t in (0.0, 0.7, 1.4)])
    tokens, _, _ = KERNELS._prefill_in_profile(ctx_of(mods, ledger), PREFILL)
    assert tokens == 3000
    far = [rec(r["ts"] + 20.0, r["chunk_tokens"]) for r in ledger]
    assert KERNELS._prefill_in_profile(ctx_of(mods, far), PREFILL) is None


def test_no_ledger_or_no_prefill_run_reads_nothing():
    mods = {"a": module(512, [0.2], 0.1)}
    assert KERNELS._prefill_in_profile(ctx_of(mods, []), PREFILL) is None
    assert KERNELS._prefill_in_profile(
        ctx_of({}, [rec(1001.0, 100)]), PREFILL) is None


def test_only_this_runs_own_trace_is_taken(tmp_path):
    d = tmp_path / "replica0" / "plugins" / "profile"
    now = time.time()

    def trace(session, age_s):
        p = d / session / "host.xplane.pb"
        p.parent.mkdir(parents=True)
        p.write_bytes(b"x")
        os.utime(p, (now - age_s, now - age_s))
        return str(p)

    stale = trace("yesterday", 86400.0)
    prof = {"dir": str(tmp_path / "replica0"), "seconds": 3.0,
            "start_unix": now - 8.0, "end_unix": now - 1.0}
    with pytest.raises(RUN.BenchFailure):
        RUN.own_trace(prof)                      # nothing new: fail
    mine = trace("mine", 3.0)
    assert RUN.own_trace(prof) == mine
    other = trace("somebody_else", 2.0)
    with pytest.raises(RUN.BenchFailure):
        RUN.own_trace(prof)                      # two new: whose is whose?
    # Afterwards only what this run's call wrote in its session goes.
    beside = d / "mine" / "host.trace.json.gz"
    beside.write_bytes(b"x")
    os.utime(beside, (now - 3.0, now - 3.0))
    older = d / "mine" / "kept.txt"
    older.write_bytes(b"x")
    os.utime(older, (now - 500.0, now - 500.0))
    RUN.remove_own_session(mine, prof)
    assert not os.path.exists(mine) and not beside.exists()
    assert older.exists() and os.path.exists(stale) and os.path.exists(other)
    older.unlink()
    RUN.remove_own_session(other, dict(prof, start_unix=now - 7.0))
    assert not (d / "somebody_else").exists() and (d / "yesterday").exists()


def test_a_split_metric_reads_its_readings_file():
    man = Manifest(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    a = man.layer_metric("prefill_ms_per_ktok.steady")
    assert a == man.layer_metric("prefill_ms_per_ktok.batch")
    assert a["args"]["what"] == "prefill_ms_per_ktok"
    assert man.layer_metric("ttft_mean_s.watch")["reader"] == "client_stat"
