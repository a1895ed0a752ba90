"""No step program slices one layer's pool out of the stacked KV pool.

``kv.k[layer_idx]`` under the model's scan over layers is a
``dynamic_slice`` whose result is a buffer of its own when a Pallas
kernel is its consumer: on the chip that was a copy of one layer's WHOLE
pool (~100 MB for a 7B model) twice a layer in front of every kernel
call, the largest single item of the device's time (PERF.md, PR 25). The
kernels take the stacked pool and the layer index instead. This guard
runs on the CPU: it traces the graphs ``engine.warmup()`` itself
dispatches — batched prefill, fused-K and 1-step decode, hybrid — of a
tiny engine on the Pallas backend, walks every equation of their jaxprs
(into scan / pjit / shard_map bodies), and fails if one yields an array
shaped like one layer's pool. tests/test_tpu_compile.py asks the chip's
compiler the same of its own HLO.
"""

import jax
import pytest

from tpu_inference import config as cfgs
from tpu_inference.engine.engine import InferenceEngine


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations carry
    (scan / while / cond / pjit / shard_map bodies), but not the body of
    a Pallas kernel: its refs are the blocks it was handed."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("preset,kv_quant", [
    ("tiny-llama", "none"), ("tiny-llama", "int8"),
    # A looped stack: the pool's leading dim is pass x layer slots and the
    # kernels are handed slot pass * L + l, still as an operand.
    ("tiny-ouro", "none")])
def test_no_step_program_slices_a_layer_of_the_pool(preset, kv_quant):
    ecfg = cfgs.EngineConfig(
        page_size=8, num_pages=48, max_pages_per_seq=6, max_batch_size=4,
        prefill_buckets=(16, 32), decode_steps_per_call=4,
        hybrid_prefill=True, kv_quant=kv_quant, attn_backend="pallas")
    eng = InferenceEngine(cfgs.PRESETS[preset](vocab_size=256), ecfg,
                          seed=0, pallas_interpret=True)
    # One layer of the code pool, with and without the unit layer dim a
    # dynamic_slice leaves. (A quantized pool's SCALES are sliced per
    # layer on purpose, 1% of the bytes: engine.make_paged_attn.)
    one_layer = tuple(eng.kv.k.shape[1:])
    banned = {one_layer, (1,) + one_layer}

    traced = {}

    def recording(jitted):
        def call(*args):
            traced.setdefault(jitted.__name__,
                              jitted.trace(*args).jaxpr.jaxpr)
            return jitted(*args)
        call.__name__ = jitted.__name__
        return call

    for name in ("_prefill_jit", "_decode_multi_jit", "_decode_one_jit",
                 "_hybrid_jit"):
        setattr(eng, name, recording(getattr(eng, name)))
    eng.warmup()

    assert sorted(traced) == ["tpu_inf_decode_1", "tpu_inf_decode_k4",
                              "tpu_inf_hybrid", "tpu_inf_prefill"]
    for program, jaxpr in traced.items():
        kernels = 0
        for eqn in _equations(jaxpr):
            kernels += eqn.primitive.name == "pallas_call"
            for var in eqn.outvars:
                assert tuple(var.aval.shape) not in banned, (
                    f"{program}: {eqn.primitive.name} yields one layer's "
                    f"pool {var.aval.shape} {var.aval.dtype}")
        assert kernels, f"{program} holds no Pallas kernel"
