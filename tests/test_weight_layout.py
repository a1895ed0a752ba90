"""The stored orientation of the weight stacks that feed attention.

Weights enter the engine as published, ``[.., K, N]``; the stacks named
by ``models/quant.py STORED_TRANSPOSED`` are stored ``[.., N, K]``,
swapped once in the constructor, and say so in their type
(``QuantizedArray.transposed``, ``Transposed``). Held here, for every
family's tiny preset:

* an engine built from published ``params`` stores each listed leaf
  swapped and every other leaf as it came, leaves the caller's arrays
  alone, and ``published`` gives the caller's tree back;
* ``qdot`` on a stored leaf is ``qdot`` on the published one, bit for bit
  on bfloat16 operands (to float32's last bit on float32 ones);
* greedy and seeded token streams equal those of an engine whose list
  is emptied: only the storage moved;
* under a mesh a swapped leaf's dims keep their axes;
* the gauge says how many stacks were swapped.

What the chip compiler makes of the stored orientation (no whole-stack
copy in a step program) is tests/test_tpu_compile.py's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_inference.config import PRESETS, EngineConfig, ParallelConfig
from tpu_inference.engine.engine import InferenceEngine
from tpu_inference.models import quant
from tpu_inference.models.quant import QuantizedArray, Transposed
from tpu_inference.models.registry import get_model_fns

FAMILIES = ["tiny-llama", "tiny-mixtral", "tiny-gpt2", "tiny-kimi",
            "tiny-ouro", "tiny-laguna", "tiny-sambay"]
CASES = ([(m, q) for m in FAMILIES for q in ("none", "int8")]
         + [("tiny-llama", "int4")])
ENGINE = dict(page_size=4, num_pages=96, max_pages_per_seq=24,
              max_batch_size=3, prefill_buckets=(8, 32),
              enable_prefix_cache=False, decode_steps_per_call=4)


def _published(name, mode):
    cfg = PRESETS[name]()
    params = get_model_fns(cfg).init_params(cfg, jax.random.PRNGKey(3))
    return cfg, quant.quantize_params(params, mode)


def _leaves(tree):
    return jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, (QuantizedArray, Transposed)))[0]


@pytest.mark.parametrize("name,mode", CASES)
def test_listed_leaves_are_stored_transposed_and_nothing_else(name, mode):
    cfg, params = _published(name, mode)
    eng = InferenceEngine(cfg, EngineConfig(quant=mode, **ENGINE),
                          params=params)
    listed = quant.STORED_TRANSPOSED.get(cfg.family, frozenset())
    n = 0
    for (path, was), (_, now) in zip(_leaves(params), _leaves(eng.params)):
        where = jax.tree_util.keystr(path)
        if path[-1].key in listed:
            n += 1
            assert quant.is_transposed(now), where
            a, b = jax.tree.leaves(was)[0], jax.tree.leaves(now)[0]
            assert b.shape == a.shape[:-2] + a.shape[:-3:-1], where
            np.testing.assert_array_equal(np.asarray(jnp.swapaxes(a, -1, -2)),
                                          np.asarray(b))
            if isinstance(now, QuantizedArray):     # the scale stays
                assert now.scale is was.scale, where
        else:
            assert not quant.is_transposed(now), where
            assert all(x is y for x, y in zip(jax.tree.leaves(was),
                                              jax.tree.leaves(now))), where
    assert n == len([1 for path, _ in _leaves(params)
                     if path[-1].key in listed])
    assert eng.telemetry.weight_stacks_transposed.collect_value() == n
    # The caller's arrays are the caller's still, and come back.
    for (path, was), (_, back) in zip(_leaves(params),
                                      _leaves(quant.published(eng.params))):
        assert type(back) is type(was)
        for x, y in zip(jax.tree.leaves(was), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("mode", ["none", "int8", "int4"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_qdot_on_a_stored_leaf_is_qdot_on_the_published_one(mode, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    w = (0.02 * jax.random.normal(k1, (3, 256, 384), jnp.float32)
         ).astype(dtype)
    x = jax.random.normal(k2, (2, 5, 256), jnp.float32).astype(dtype)
    leaf = w if mode == "none" else quant.quantize_array(w, mode)
    stored, n = quant.store_transposed({"wq": leaf}, "llama")
    assert n == 1 and quant.is_transposed(stored["wq"])
    if mode == "int4":              # packed along the contraction dim
        assert stored["wq"].q.shape == (3, 384, 128)
        assert stored["wq"].scale.shape == (3, 2, 384)

    @jax.jit
    def layers(x, stack):           # one layer at a time, as the scan does
        return jax.lax.map(lambda lw: quant.qdot(x, lw), stack)

    a, b = np.asarray(layers(x, leaf)), np.asarray(layers(x, stored["wq"]))
    if dtype == jnp.bfloat16 and mode != "int4":
        np.testing.assert_array_equal(a, b)
    else:
        # A float32 contraction (the grouped int4 path contracts in
        # float32 off the TPU) sums in another order when XLA:CPU takes
        # its right-hand side transposed: the last bit of a float32.
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-6)
    # ... and a second swap is refused by the type: nothing to do.
    again, n = quant.store_transposed(stored, "llama")
    assert n == 0 and again["wq"] is stored["wq"]


def _streams(cfg, mode, params):
    eng = InferenceEngine(cfg, EngineConfig(quant=mode, **ENGINE),
                          params=params, seed=5)
    rng = np.random.default_rng(11)
    prompts = [[int(t) for t in rng.integers(3, 200, n)] for n in (19, 6, 27)]
    return (eng, eng.generate(prompts, 10),
            eng.generate(prompts, 10, temperature=0.9, top_p=0.95))


@pytest.mark.parametrize("name,mode", CASES)
def test_streams_equal_an_engine_that_stores_as_published(name, mode,
                                                         monkeypatch):
    cfg, params = _published(name, mode)
    eng, greedy, seeded = _streams(cfg, mode, params)
    monkeypatch.setattr(quant, "STORED_TRANSPOSED", {})
    plain, greedy0, seeded0 = _streams(cfg, mode, params)
    assert plain.telemetry.weight_stacks_transposed.collect_value() == 0
    assert not any(quant.is_transposed(x) for _, x in _leaves(plain.params))
    assert greedy == greedy0
    assert seeded == seeded0
    listed = quant.STORED_TRANSPOSED.get(cfg.family)   # (emptied here)
    assert listed is None


@pytest.mark.parametrize("mode", ["none", "int8", "int4"])
def test_a_swapped_leaf_keeps_its_axes_under_a_mesh(mode):
    from jax.sharding import PartitionSpec as P

    from tpu_inference.parallel.mesh import build_mesh
    from tpu_inference.parallel.shardings import param_shardings

    cfg = PRESETS["tiny-llama"]()
    mesh = build_mesh(ParallelConfig(tp=2), devices=jax.devices()[:2])
    eng = InferenceEngine(cfg, EngineConfig(quant=mode, **ENGINE), seed=0,
                          mesh=mesh)
    for name in ("wq", "wk", "wv"):
        leaf = eng.params["blocks"][name]
        assert quant.is_transposed(leaf)
        w = jax.tree.leaves(leaf)[0]
        # published [L, K, N] shards N over tp: stored [L, N, K] does too
        assert w.sharding.spec == P(None, "tp", None), name
        shard = w.addressable_shards[0].data.shape
        assert shard == (w.shape[0], w.shape[1] // 2, w.shape[2]), name
    wo = jax.tree.leaves(eng.params["blocks"]["wo"])[0]
    assert wo.sharding.spec == P(None, "tp", None)     # as published
    # The specs for a stored tree are the engine's own placement.
    want = param_shardings(cfg, mesh, eng.params)
    for (path, x), s in zip(
            jax.tree_util.tree_flatten_with_path(eng.params)[0],
            jax.tree.leaves(want)):
        assert x.sharding.is_equivalent_to(s, x.ndim), \
            jax.tree_util.keystr(path)
    assert eng.telemetry.weight_stacks_transposed.collect_value() == 3
    out = eng.generate([[5, 9, 14, 3, 8]], 6)
    one = InferenceEngine(cfg, EngineConfig(quant=mode, **ENGINE), seed=0)
    assert out == one.generate([[5, 9, 14, 3, 8]], 6)
