"""HBM-aware auto-sizing (engine/autosize.py) — pure arithmetic, no
devices needed. Pins the sizing decisions VERDICT r3 asked for: a 1B
model on a 16 GB chip must serve well above batch 8, an 8B bf16 model
must refuse to pretend it fits, and int8 levers must buy the expected
capacity."""

import jax.numpy as jnp
import pytest

from tpu_inference.config import ModelConfig, tiny_llama, tiny_mixtral
from tpu_inference.engine import autosize


def llama_1b():
    return ModelConfig(name="llama-1b", family="llama", vocab_size=32000,
                       d_model=2048, n_layers=22, n_heads=32, n_kv_heads=4,
                       d_ff=5632, max_seq_len=2048, dtype=jnp.bfloat16)


def llama_8b():
    return ModelConfig(name="llama-8b", family="llama", vocab_size=128256,
                       d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
                       d_ff=14336, max_seq_len=8192, dtype=jnp.bfloat16)


def test_param_estimate_close_to_known_sizes():
    # TinyLlama-1.1B and Llama-3-8B: estimates within 10% of the names.
    assert 0.9e9 < autosize.estimate_param_count(llama_1b()) < 1.3e9
    assert 7e9 < autosize.estimate_param_count(llama_8b()) < 9e9


def test_moe_params_count_all_experts():
    dense = autosize.estimate_param_count(tiny_llama())
    moe = autosize.estimate_param_count(tiny_mixtral())
    assert moe > dense * 1.5  # 4 experts' FFNs vs 1


def test_1b_on_v5e_serves_well_above_batch_8():
    sz = autosize.auto_size(llama_1b(), hbm_bytes=16e9)
    assert sz.max_batch_size >= 16        # the VERDICT r3 complaint
    assert sz.max_batch_size <= 32        # default cap
    assert sz.num_pages > 512             # pool sized by HBM, not default
    # Residents actually fit inside the stated budget.
    assert (sz.weight_bytes_per_chip + sz.kv_pool_bytes_per_chip
            < 0.85 * 16e9)


def test_8b_bf16_refuses_single_v5e():
    with pytest.raises(ValueError, match="int8"):
        autosize.auto_size(llama_8b(), hbm_bytes=16e9)


def test_8b_int8_fits_single_v5e():
    sz = autosize.auto_size(llama_8b(), hbm_bytes=16e9, quant="int8",
                            kv_quant="int8")
    assert sz.max_batch_size >= 8
    assert (sz.weight_bytes_per_chip + sz.kv_pool_bytes_per_chip
            < 0.85 * 16e9)


def test_8b_bf16_fits_with_tp4():
    sz = autosize.auto_size(llama_8b(), hbm_bytes=16e9, tp=4)
    assert sz.max_batch_size >= 8


def test_int8_kv_roughly_doubles_pool_tokens():
    a = autosize.auto_size(llama_8b(), hbm_bytes=16e9, quant="int8")
    b = autosize.auto_size(llama_8b(), hbm_bytes=16e9, quant="int8",
                           kv_quant="int8")
    assert b.num_pages > 1.8 * a.num_pages


def test_pool_floor_one_full_sequence():
    # A budget too small for even one max-length sequence must raise,
    # not deadlock admission later.
    with pytest.raises(ValueError, match="pages"):
        autosize.auto_size(llama_1b(), hbm_bytes=3.5e9,
                           max_pages_per_seq=4096)


def test_target_ctx_shapes_batch():
    wide = autosize.auto_size(llama_1b(), hbm_bytes=16e9, batch_cap=512,
                              target_ctx=512)
    narrow = autosize.auto_size(llama_1b(), hbm_bytes=16e9, batch_cap=512,
                                target_ctx=2048)
    assert wide.max_batch_size > narrow.max_batch_size


def test_swa_model_batches_by_window_not_context():
    """Behind-window eviction caps live KV at ~window tokens, so auto
    sizing serves a bigger batch for an SWA model than for the same
    architecture with full attention."""
    import dataclasses

    from tpu_inference.config import PRESETS

    mistral = PRESETS["mistral-7b"]()
    full = dataclasses.replace(mistral, sliding_window=0)
    # Long-context serving geometry (target ctx 8192 > the 4096 window):
    # full attention must budget the whole context per sequence, SWA
    # only the window.
    kw = dict(hbm_bytes=16e9, quant="int8", kv_quant="int8",
              max_pages_per_seq=1024, batch_cap=256)
    swa_sz = autosize.auto_size(mistral, **kw)
    full_sz = autosize.auto_size(full, **kw)
    assert swa_sz.max_batch_size > full_sz.max_batch_size
    assert swa_sz.target_ctx <= mistral.sliding_window + 32


def test_swa_clamp_is_the_same_with_and_without_speculation():
    """Speculation keeps behind-window eviction on, so the sizer knows
    nothing of it: ``auto_size`` takes no such argument, and a server
    that speculates is given the batch and the pool of one that does
    not."""
    import inspect

    from tpu_inference.config import PRESETS, EngineConfig

    assert "speculative" not in inspect.signature(
        autosize.auto_size).parameters
    mistral = PRESETS["mistral-7b"]()
    req = dict(max_batch_size="auto", num_pages="auto", decode_ladder="off",
               target_ctx=0, batch_cap=256)
    sized = [autosize.resolve_sizing(
        mistral, EngineConfig(quant="int8", kv_quant="int8",
                              max_pages_per_seq=1024,
                              num_speculative_tokens=gamma),
        dict(req), hbm_bytes=16e9) for gamma in (0, 3)]
    assert (sized[0].max_batch_size, sized[0].num_pages) == (
        sized[1].max_batch_size, sized[1].num_pages)
    clamped = autosize.auto_size(mistral, hbm_bytes=16e9, quant="int8",
                                 kv_quant="int8", max_pages_per_seq=1024,
                                 batch_cap=256)
    assert sized[1].max_batch_size == clamped.max_batch_size


def test_decode_ladder_rungs_shapes():
    """The compiled-graph ladder: doubling rungs from 8 strictly below
    the top, plus the top itself; tops at or under the base collapse to
    the single legacy rung."""
    assert autosize.decode_ladder_rungs(32) == (8, 16, 32)
    assert autosize.decode_ladder_rungs(64) == (8, 16, 32, 64)
    assert autosize.decode_ladder_rungs(24) == (8, 16, 24)
    assert autosize.decode_ladder_rungs(8) == (8,)
    assert autosize.decode_ladder_rungs(4) == (4,)
    with pytest.raises(ValueError, match="positive"):
        autosize.decode_ladder_rungs(0)


def test_ladder_from_auto_sizing_is_engine_valid():
    """The ladder derived from an auto-sized top must pass the engine's
    validation shape: strictly increasing, ending at the top."""
    sz = autosize.auto_size(llama_1b(), hbm_bytes=16e9)
    rungs = autosize.decode_ladder_rungs(sz.max_batch_size)
    assert rungs[-1] == sz.max_batch_size
    assert list(rungs) == sorted(set(rungs))
    assert len(rungs) >= 2                # a 1B/v5e top is 16+ (above)


class _Dev:
    """Stand-in for a jax device (chip_spec reads three attributes)."""

    def __init__(self, platform, kind, bytes_limit=None):
        self.platform, self.device_kind = platform, kind
        self._limit = bytes_limit

    def memory_stats(self):
        return {"bytes_limit": self._limit} if self._limit else None


def test_chip_spec_v5e_published_peaks():
    """ONE table, keyed by device_kind, holding the published figures:
    the v5e's bf16 peak is 197 TFLOP/s (393 is its int8 figure)."""
    v5e = autosize.chip_spec(_Dev("tpu", "TPU v5 lite"))
    assert v5e.peak_bf16_flops == 197e12
    assert v5e.peak_int8_ops == 393e12
    assert v5e.hbm_bytes == 16e9 and v5e.hbm_bw == 819e9


def test_chip_spec_unknown_tpu_raises():
    """An unknown TPU kind is an error, never rated as a v5e."""
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        autosize.chip_spec(_Dev("tpu", "TPU v99"))


def test_chip_spec_cpu_has_no_peak():
    """On the CPU there is nothing to rate against: no spec (so the MFU
    gauge and roofline shares are absent, "not measured"), and 'auto'
    sizing refuses instead of sizing as some chip."""
    assert autosize.chip_spec(_Dev("cpu", "cpu")) is None
    assert autosize.chip_spec() is None          # the tests' own backend
    with pytest.raises(ValueError, match="explicit sizes"):
        autosize.detect_hbm_bytes(_Dev("cpu", "cpu"))


def test_detect_hbm_prefers_the_devices_own_figure():
    """memory_stats()["bytes_limit"] where the backend reports it, the
    table otherwise."""
    own = 16_909_336_576
    assert autosize.detect_hbm_bytes(
        _Dev("tpu", "TPU v5 lite", bytes_limit=own)) == own
    assert autosize.detect_hbm_bytes(_Dev("tpu", "TPU v5 lite")) == 16e9


def test_resolve_sizing_auto_and_explicit():
    """The CLI's sizing ask resolves against an HBM figure (in the
    process that owns the device): 'auto' fills batch, pool and the
    ladder below the batch; explicit sizes pass through with their
    ladder; a bad explicit ladder is a usage error up front."""
    import types

    from tpu_inference.config import PRESETS, EngineConfig

    args = types.SimpleNamespace(max_batch_size="auto", num_pages="auto",
                                 decode_ladder="auto", target_ctx=0,
                                 batch_cap=32)
    req = autosize.sizing_request(args)
    ecfg = autosize.resolve_sizing(
        PRESETS["mistral-7b"](), EngineConfig(quant="int8"), req,
        hbm_bytes=16e9)
    assert ecfg.max_batch_size == 32 and ecfg.num_pages > 2000
    assert ecfg.decode_ladder == (8, 16, 32)
    args.max_batch_size, args.num_pages = 16, 300
    ecfg = autosize.resolve_sizing(
        PRESETS["mistral-7b"](), EngineConfig(),
        autosize.sizing_request(args))
    assert (ecfg.max_batch_size, ecfg.num_pages) == (16, 300)
    assert ecfg.decode_ladder == (8, 16)
    args.decode_ladder = "8,12"
    with pytest.raises(ValueError, match="end at max_batch_size"):
        autosize.sizing_request(args)
    assert autosize.resolve_sizing(None, ecfg, None) is ecfg   # no ask


def test_int_or_auto_argparse_type():
    import argparse

    assert autosize.int_or_auto("auto") == "auto"
    assert autosize.int_or_auto("16") == 16
    with pytest.raises(argparse.ArgumentTypeError, match="auto"):
        autosize.int_or_auto("8x")


def test_resolve_sizing_args_noop_on_ints():
    """No 'auto' -> no model resolution, no device probe: the values
    pass through untouched (the CLI fast path)."""
    import types

    args = types.SimpleNamespace(max_batch_size=8, num_pages=512)
    assert autosize.resolve_sizing_args(args) == (8, 512)


@pytest.mark.parametrize("target_ctx,batch_cap,lanes", [
    (2560, 96, 96), (2560, 128, 98), (16384, 96, 45)])
def test_a_state_a_sequence_beside_one_latent_pool(target_ctx, batch_cap,
                                                   lanes, capsys):
    """Delta-rule layers beside a latent pool (ling3-flash-ep8): a lane
    costs its 23.9 MB state slot and ``target_ctx`` tokens of latent
    pages, the trash slot one state more; the pool gets what the slots
    leave. At the cell's flags the STATE sets the batch: 96 lanes hold
    2.3 GB of states beside 0.7 GB of pages."""
    from tpu_inference.config import PRESETS, EngineConfig

    mcfg = PRESETS["ling3-flash-ep8"]()
    state = mcfg.state_bytes_per_seq()
    assert state == 23_879_680
    ecfg = autosize.resolve_sizing(
        mcfg, EngineConfig(quant="none", attn_backend="pallas",
                           max_pages_per_seq=1168),
        dict(max_batch_size="auto", num_pages="auto", decode_ladder="auto",
             target_ctx=target_ctx, batch_cap=batch_cap), hbm_bytes=16.91e9)
    said = capsys.readouterr().err
    assert ecfg.max_batch_size == lanes and ecfg.page_size == 64
    assert f"state_slots={lanes} state_bytes_per_slot={state}" in said
    kv_tok = autosize.kv_bytes_per_token(mcfg)
    assert kv_tok == 2 * 640 * 2           # two latent layers, 640 wide
    held = (ecfg.num_pages * 64 * kv_tok + (lanes + 1) * state
            + autosize.weight_bytes(mcfg))
    assert held < 0.85 * 16.91e9 and (ecfg.num_pages - 1) * 64 \
        >= lanes * target_ctx
    # A model with no state a sequence is sized as it was.
    sz = autosize.auto_size(PRESETS["kimi-k2-ep32"](), hbm_bytes=16.91e9,
                            quant="none", page_size=64,
                            max_pages_per_seq=168, target_ctx=2048)
    assert sz.kv_pool_bytes_per_chip == sz.num_pages * 64 * sz.kv_bytes_per_token
