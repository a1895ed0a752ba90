"""chip_smoke.py's own control flow, at a tiny preset on the CPU.

The smoke exists to run on the chip; what can break it between chip
runs is its plumbing: the CLI flags it passes, the /healthz and /metrics
fields it reads, the NDJSON checks, the child protocol, the exit codes.
These tests drive the same phase functions with the settings dict
steered to a tiny model on the CPU (``platform: "cpu"`` is the device
assertion the phases then hold the servers to) — the script itself has
no option for that. The children share the tests' compile cache.

Also here: the guard that the subprocess fleet's router never
initialises a JAX backend (on a TPU host that would take the chip from
its own workers).
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

# tiny-mistral: 2 layers, window 64 — the long request (200 tokens)
# crosses the window like the real one crosses 4096. Warm-up off keeps
# the compile count to what the requests touch.
TINY = dict(
    chip_smoke.SETTINGS, model="tiny-mistral", platform="cpu",
    attn_backend="dense", warmup=False,
    sizing=["--max-batch-size", "16", "--num-pages", "256",
            "--host-cache-pages", "0"],
    max_pages_per_seq=16, bucket_prompts=[20, 100], long_prompt=200,
    burst=12, burst_tokens=48, max_tokens=6, boot_timeout_s=300,
    parity_layers=2, parity_prompts=[30, 150], seed=0)


@pytest.fixture(autouse=True)
def _logs_in_tmp(tmp_path, monkeypatch):
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))


def test_serve_phase_tiny():
    rec = chip_smoke.phase_serve(TINY)
    assert rec["ok"] and rec["device"] == {"platform": "cpu", "kind": "cpu"}
    assert rec["rung_switches"] >= 1
    assert rec["long_request_tokens"] == 206
    assert (rec["max_batch_size"], rec["num_pages"]) == (16, 256)
    assert rec["ladder"] == [8, 16]


def test_parity_phase_tiny():
    rec = chip_smoke.run_child(TINY, "parity")
    assert rec["ok"] and rec["layers"] == 2
    # prefill-written and decode-written positions of both prompts, and
    # every greedy token, were held to the reference.
    assert rec["checks"] == 2 * (2 + TINY["parity_decode_steps"] + 1)
    assert rec["logit_err_rms"] <= TINY["parity_tol"]["rms"]
    assert rec["token_gap_max"] == 0.0
    assert rec["device"]["platform"] == "cpu"


def test_parity_phase_catches_a_wrong_model():
    """The comparison is not vacuous: a reference on other weights (a
    different seed) is far outside the tolerance."""
    import numpy as np

    got = [([1, 2], {3: np.zeros(8, np.float32)})]
    ref = np.ones((1, 5, 8), np.float32) * np.arange(8)
    with pytest.raises(chip_smoke.SmokeFailure, match="logit error rms"):
        chip_smoke._compare(got, ref, [[0, 0, 0, 0]],
                            tol=chip_smoke.SETTINGS["parity_tol"])


def test_fleet_phase_tiny():
    rec = chip_smoke.phase_fleet(TINY)
    assert rec["ok"] and rec["worker_chips"] == ["0"]
    assert rec["served"] == [2]
    assert len(rec["worker_pids"]) == 1


def test_pallas_off_the_tpu_fails_the_phase():
    """No quiet slow path: asked for the Pallas kernels on a machine
    without a TPU, the server refuses to start (it does not fall to
    interpret mode), and the phase fails with it."""
    with pytest.raises(chip_smoke.SmokeFailure, match="needs a TPU"):
        chip_smoke.phase_serve(dict(TINY, attn_backend="pallas"))


def test_result_device_needs_one_device():
    phases = [{"phase": "serve", "device": {"platform": "tpu", "kind": "a"}},
              {"phase": "parity",
               "device": {"platform": "tpu", "kind": "a", "count": 1}}]
    assert chip_smoke.result_device(phases) == {
        "platform": "tpu", "kind": "a", "count": 1}
    phases[0]["device"]["kind"] = "b"
    with pytest.raises(chip_smoke.SmokeFailure, match="different devices"):
        chip_smoke.result_device(phases)


def _run_smoke(cwd, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_without_a_tpu_the_first_child_fails_and_nothing_else_starts(
        tmp_path):
    """The contract's refusal: no accelerator -> non-zero exit, no result
    line, and no phase after the first (--platform tpu pins the TPU even
    where the environment says cpu)."""
    work = tmp_path / "checkout"
    work.mkdir()
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), work)
    os.symlink(os.path.join(ROOT, "tpu_inference"), work / "tpu_inference")
    p = _run_smoke(work)
    assert p.returncode != 0
    assert p.stdout.strip() == ""           # no phase line, no result line
    assert "serve: server exited" in p.stderr
    logs = os.listdir(work / "chiprun_out" / "chip_smoke")
    assert logs == ["serve.log"]            # parity and fleet never began


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    p = _run_smoke(tmp_path, timeout=60)
    assert p.returncode != 0 and p.stdout.strip() == ""


ROUTER_GUARD = r"""
import json, sys, threading
from jax._src import xla_bridge
from tpu_inference.engine.engine import Sequence
from tpu_inference.server.http import build_server

srv = build_server(model="tiny-llama", warmup=False, dp=1, platform="cpu",
                   server_overrides=dict(fleet="subprocess"),
                   sizing=dict(max_batch_size=2, num_pages=32,
                               decode_ladder="off", target_ctx=0,
                               batch_cap=32),
                   page_size=8, max_pages_per_seq=4, prefill_buckets=(16,))
srv.group.start()
done, toks = threading.Event(), []
srv.group.submit(Sequence(request_id=1, prompt_tokens=[1, 2, 3],
                          max_new_tokens=4),
                 lambda s, t: toks.append(t), lambda s: done.set())
assert done.wait(120), "request never finished"
hz = srv.group.health_snapshot()
assert "tpu_inf_build_info" in srv.group.prometheus_text()
out = {"initialized": xla_bridge.backends_are_initialized(),
       "tokens": len(toks), "worker": hz["replicas"][0]["device"]}
srv.group.stop(drain=False)
print(json.dumps(out))
"""


def test_fleet_router_never_initialises_a_backend():
    """One process per chip: after building the fleet, booting a worker,
    serving a request through it and reading its health, the ROUTER's
    process still has no JAX backend — the worker (which does, and says
    so in its hello) was started after the router built its envelope."""
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", ROUTER_GUARD], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["initialized"] is False
    assert out["tokens"] == 4
    assert out["worker"]["platform"] == "cpu"
    assert out["worker"]["max_batch_size"] == 2     # the sizing ask arrived
    assert out["worker"]["visible_chips"] == "0"    # its chip, by spawn env


def test_latent_kernel_check_in_interpret_mode():
    """The smoke's latent-attention kernel check (run on the chip at
    Kimi-K2's shapes, at layer 1 of a 3-layer stacked pool) at small
    shapes through the interpreter: the same code, within its tolerance;
    a check that reads the wrong layer is off by the whole spread."""
    errs = chip_smoke._latent_kernel_errors(
        TINY, heads=4, rank=128, rope=16, ctx=(20, 0, 300, 0, 700, 41),
        chunk=64, interpret=True)
    assert set(errs) == {"latent_decode", "latent_prefill"}
    assert max(errs.values()) <= chip_smoke.SETTINGS["latent_kernel_tol"]


@pytest.mark.parametrize("check", ["gqa", "latent"])
def test_wide_page_kernel_checks_in_interpret_mode(check):
    """The smoke's two checks at the 64-token page that 'auto' gives a
    pool under 32 KB a 16-token page (run on the chip at [P, 64, 4, 128],
    a window of 4096 and none, and at [P, 64, 640]) at small shapes
    through the interpreter: the same code, the 16-token cases'
    tolerances."""
    if check == "gqa":
        errs = chip_smoke._mixed_kernel_errors(
            TINY, d=16, kv_heads=2, kinds=((8, 96), (8, 0)), slots=4,
            lanes=4, ctx=300, rows=64, page=64, interpret=True)
        assert set(errs) == {"h8w96_decode", "h8w96_prefill", "h8w0_decode",
                             "h8w0_prefill"}
        tol = chip_smoke.SETTINGS["kernel_tol"]
    else:
        errs = chip_smoke._latent_kernel_errors(
            TINY, heads=4, rank=128, rope=16, ctx=(20, 0, 300, 0, 700, 41),
            chunk=64, page=64, interpret=True)
        assert set(errs) == {"latent_decode", "latent_prefill"}
        tol = chip_smoke.SETTINGS["latent_kernel_tol"]
    assert max(errs.values()) <= tol


def test_mha_kernel_check_in_interpret_mode():
    """The smoke's check of the two GQA kernels at an MHA shape (run on
    the chip at Ouro-2.6B's: 16 heads x 128, a 16-slot pool, 12 lanes of
    ~330 tokens, 384 prefill rows) at a small shape through the
    interpreter: the same code, within the kernels' tolerance."""
    errs = chip_smoke._mha_kernel_errors(
        TINY, heads=4, d=32, slots=6, lanes=5, ctx=70, rows=128,
        interpret=True)
    assert set(errs) == {"mha_decode", "mha_prefill"}
    assert max(errs.values()) <= chip_smoke.SETTINGS["kernel_tol"]


def test_mixed_kernel_check_in_interpret_mode():
    """The smoke's mixed-kind kernel check (run on the chip at
    Laguna-S-2.1's: 72 heads over a window of 512 and 48 over everything,
    on 8 KV heads x 128) at a small shape through the interpreter: the
    same code, a window and no window, n_rep 3 and 2, the pages behind a
    window the trash page."""
    errs = chip_smoke._mixed_kernel_errors(
        TINY, d=32, kv_heads=3, kinds=((9, 24), (6, 0)), slots=4, lanes=5,
        ctx=90, rows=64, interpret=True)
    assert set(errs) == {"h9w24_decode", "h9w24_prefill", "h6w0_decode",
                         "h6w0_prefill"}
    assert max(errs.values()) <= chip_smoke.SETTINGS["kernel_tol"]


def test_all_held_expert_and_nope_window_checks_in_interpret_mode():
    """The smoke's checks for a stage that holds every expert of its
    layers (run on the chip at SmallThinker-21B's: 64 held, top-6, relu,
    no shared expert; 28 query heads on 4 KV heads over a window of 4096
    and over everything) at the tiny preset through the interpreter."""
    errs = chip_smoke._routed_expert_errors(
        TINY, preset="tiny-smallthinker", tokens=(8, 64), idle=3,
        interpret=True)
    assert set(errs) == {"routed_8", "routed_64", "planted_zero",
                         "planted_next_expert", "planted_layer_0"}
    chip_smoke.check_routed_experts(errs,
                                    chip_smoke.SETTINGS["routed_expert_tol"])
    errs = chip_smoke._mixed_kernel_errors(
        TINY, d=16, kv_heads=2, kinds=((8, 32), (8, 0)), slots=4, lanes=4,
        ctx=90, rows=64, interpret=True)
    assert set(errs) == {"h8w32_decode", "h8w32_prefill", "h8w0_decode",
                         "h8w0_prefill"}
    assert max(errs.values()) <= chip_smoke.SETTINGS["kernel_tol"]


def test_sambay_kernel_check_in_interpret_mode():
    """The smoke's SambaY check (run on the chip at Phi-4-mini-flash's:
    a scan 5120 wide with 16 states; 40 padded query heads on 10 pair
    heads x 128) at a small shape through the interpreter: the scan
    kernel with a whole row, one that ends inside a block and an idle
    one, and the GQA kernels at n_rep 4 on an odd count of heads."""
    errs = chip_smoke._sambay_kernel_errors(
        TINY, d_inner=1024, n_state=8, rows=32, interpret=True,
        pairs=dict(d=32, kv_heads=5, kinds=((20, 24), (20, 0)), slots=3,
                   lanes=4, ctx=70, rows=32))
    assert set(errs) == {"scan_y", "scan_h", "pair_h20w24_decode",
                         "pair_h20w24_prefill", "pair_h20w0_decode",
                         "pair_h20w0_prefill"}
    assert max(errs.values()) <= chip_smoke.SETTINGS["kernel_tol"]


def test_delta_rule_check_in_interpret_mode():
    """The smoke's delta-rule check (run on the chip at Ling-3.0-flash's:
    32 heads of a 128 x 128 state, a 1024-token chunk) at a small shape
    through the interpreter: the chunk kernel with a whole row, one that
    ends inside a block and an idle one, half the channels at the gate's
    bound, a second chunk from the states the first left, and the
    one-token update."""
    errs = chip_smoke._delta_rule_errors(TINY, heads=2, d=64, rows=128,
                                         slots=5, time_it=False,
                                         interpret=True)
    assert set(errs) == {"chunk_o", "chunk_s", "next_chunk_o",
                         "next_chunk_s", "step_o", "step_s"}
    assert max(errs.values()) <= chip_smoke.SETTINGS["delta_rule_tol"]


def test_kda_tail_step_check_in_interpret_mode():
    """The smoke's check of a delta-rule layer's one-token convolution
    (run on the chip at cell 10's shapes: 96 lanes, 97 slots, 11 layers,
    12,288 channels, timed) at a few lanes and heads through the
    interpreter, untimed: idle and fresh lanes among the valid ones, the
    tails the XLA form's bit for bit, x to float32's rounding."""
    errs = chip_smoke._kda_tail_step_check(TINY, lanes=16, layers=2,
                                           heads=2, reps=0, interpret=True)
    assert errs == {"tail_err": 0, "x_err": 0.0}


def test_kv_rows_write_check_in_interpret_mode():
    """The smoke's check of a decode step's K / V write into merged-row
    pools (run on the chip at cell 7's shapes, 64 lanes, timed) at a few
    lanes and pages through the interpreter, untimed: the kernel's pools
    are the scatter's, bit for bit."""
    errs = chip_smoke._kv_rows_write_check(TINY, pages=12, lanes=8, reps=0,
                                           interpret=True)
    assert errs == {"window_err": 0, "full_err": 0}


def test_routed_expert_check_in_interpret_mode():
    """The smoke's routed-expert check (run on the chip at Kimi-K2's
    widths, layer 1 of a 2-layer stack) at the tiny preset through the
    interpreter: the layer is within the tolerance, rows without a token
    route nowhere, and each planted fault reads far over it."""
    errs = chip_smoke._routed_expert_errors(
        TINY, preset="tiny-kimi", tokens=(8, 64), idle=3, interpret=True)
    assert set(errs) == {"routed_8", "routed_64", "planted_zero",
                         "planted_next_expert", "planted_layer_0"}
    chip_smoke.check_routed_experts(errs,
                                    chip_smoke.SETTINGS["routed_expert_tol"])


def test_hyper_connection_check_on_the_cpu():
    """The smoke's check of one hyper-connection (run on the chip at
    Xing4.0's widths, 64 and 1024 rows) at the tiny preset: the program's
    functions are the equations in float32; coefficients rounded to
    bfloat16 and a projection stopped after one iteration are not."""
    errs = chip_smoke._hyper_connection_errors(TINY, preset="tiny-xing",
                                               rows=(96,))
    assert set(errs) == {"coef_96", "pre_mix_96", "post_mix_96",
                         "sum_err_ppm_96", "planted_coef_bf16",
                         "planted_one_iteration"}
    chip_smoke.check_hyper_connection(
        errs, chip_smoke.SETTINGS["hyper_connection_tol"],
        chip_smoke.SETTINGS["hyper_connection_f32_tol"])
    assert max(errs["pre_mix_96"], errs["post_mix_96"]) < 1e-4   # float32


def test_spilled_routing_check_in_interpret_mode():
    """The smoke's check of a decode rung whose routing spills (run on
    the chip at Kimi-K2's and Laguna's widths, 32 lanes) at tiny-laguna's
    32-token bucket through the interpreter (4 of 16 held, top-3: two
    rounds of 7 tiles): the check itself refuses a routing that fills
    one round or keeps every token's rows in one; the layer, gathering
    in each round, is within the tolerance; the planted faults are not."""
    errs = chip_smoke._routed_expert_errors(
        TINY, preset="tiny-laguna", tokens=(32,), idle=3, interpret=True,
        spill=True)
    assert set(errs) == {"spilled_32", "planted_zero",
                         "planted_next_expert", "planted_layer_0"}
    chip_smoke.check_routed_experts(errs,
                                    chip_smoke.SETTINGS["routed_expert_tol"])
    # A bucket too small to spill its experts' tiles is refused.
    with pytest.raises(chip_smoke.SmokeFailure, match="cannot spill"):
        chip_smoke._routed_expert_errors(
            TINY, preset="tiny-laguna", tokens=(8,), idle=3, interpret=True,
            spill=True)


def test_combine_costs_in_interpret_mode():
    """The microbenchmark ``moe_experts.SCATTERED_ROW_COST`` is set from
    (run on the chip at Laguna's and Kimi's widths) at a tiny preset
    through the interpreter: both combines and both forms of the layer
    run on one layout, the constant is what it was afterwards, and the
    reading has the keys the constant's comment quotes. (The times are
    the CPU's: no device number.)"""
    from tpu_inference.kernels import moe_experts

    kept = moe_experts.SCATTERED_ROW_COST
    out = chip_smoke._combine_costs(TINY, shapes=(("tiny-laguna", 32),),
                                    reps=2, interpret=True)
    assert moe_experts.SCATTERED_ROW_COST == kept
    assert set(out) == {"tiny-laguna_32"}
    got = out["tiny-laguna_32"]
    assert (got["rows_round"], got["pairs"], got["rounds"]) == (112, 96, 2)
    assert set(got) == {"rows_round", "pairs", "rounds", "scatter_us",
                        "gather_us", "row_cost", "layer_scatter_us",
                        "layer_gather_us"}
