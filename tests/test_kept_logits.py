"""``EngineConfig.keep_logits`` / ``Sequence.kept_logits`` (what
bench/probes/kept.py reads): every program that samples hands the float32
row it sampled from out beside the token, and the engine files it under
the position it follows, for every family's step programs. Held here for
each model file through what needs no second implementation:

* a greedy token is the argmax of the row kept for the position before it;
* the rows do not depend on how the steps were cut into programs (a prompt
  prefilled whole or in chunks, decode fused 4 steps a call or 1): the
  prefill program's row, the chunked prefill's and the fused scan's are
  the same function of the stream;
* the last ``4 x decode_steps_per_call`` positions stay;
* with the flag off a program has no such output and a sequence no rows:
  the decode program's outputs are the four they were.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_inference.config import PRESETS, EngineConfig
from tpu_inference.engine.engine import InferenceEngine, Sequence

MODELS = ["tiny-llama", "tiny-mixtral", "tiny-gpt2", "tiny-kimi",
          "tiny-ouro", "tiny-laguna"]
ENGINE = dict(page_size=4, num_pages=96, max_pages_per_seq=24,
              max_batch_size=2, prefill_buckets=(8, 32),
              enable_prefix_cache=False)


def _cfg(name):
    return dataclasses.replace(PRESETS[name](), dtype=jnp.float32)


def _run(name, params=None, **over):
    eng = InferenceEngine(_cfg(name), EngineConfig(**dict(ENGINE, **over)),
                          params=params, seed=3)
    rng = np.random.default_rng(7)
    seqs = [Sequence(request_id=i, max_new_tokens=7, prompt_tokens=[
        int(t) for t in rng.integers(3, 200, n)]) for i, n in enumerate(
            (19, 6))]
    eng.prefill(seqs[0])
    eng.prefill_many(seqs[1:])
    while not all(s.done for s in seqs):
        eng.decode_steps()
    return eng, seqs


@pytest.mark.parametrize("name", MODELS)
def test_rows_are_what_the_programs_sampled_from(name):
    eng, seqs = _run(name, keep_logits=True, decode_steps_per_call=4)
    for s in seqs:
        n, stream = len(s.prompt_tokens), s.prompt_tokens + s.generated
        assert sorted(s.kept_logits) == list(range(n - 1, len(stream) - 1))
        for p, row in s.kept_logits.items():
            assert row.dtype == np.float32
            assert row.shape == (eng.model_cfg.vocab_size,)
            assert int(np.argmax(row)) == stream[p + 1]
    # Another cut of the same steps into programs: the prompt in chunks
    # of 8, one decode step a call.
    _, again = _run(name, params=eng.params, keep_logits=True,
                    decode_steps_per_call=1, prefill_buckets=(8,))
    for a, b in zip(seqs, again):
        assert a.generated == b.generated
        # (one step a call keeps the last 4 positions)
        assert sorted(b.kept_logits) == sorted(a.kept_logits)[-4:]
        for p, row in b.kept_logits.items():
            assert float(np.abs(row - a.kept_logits[p]).max()) < 2e-4


def test_off_by_default_and_no_output_of_the_programs():
    eng, seqs = _run("tiny-llama")
    assert all(s.kept_logits is None for s in seqs)
    on, _ = _run("tiny-llama", params=eng.params, keep_logits=True)
    operand = jnp.zeros((2, eng._decode_layout.width), jnp.int32)

    def outputs(e):
        low = e._decode_multi_jit.lower(e.params, e.kv, e._base_key, operand)
        return [a.shape for a in jax.tree.leaves(low.out_info)]

    vocab, k = eng.model_cfg.vocab_size, eng.engine_cfg.decode_steps_per_call
    kept = (k, 2, vocab)
    assert kept in outputs(on) and kept not in outputs(eng)
    assert len(outputs(on)) == len(outputs(eng)) + 1


@pytest.mark.parametrize("over,said", [
    (dict(num_speculative_tokens=2),
     "keep_logits does not support: speculative decoding"),
    (dict(role="decode"), "a handed-off sequence carries no kept rows"),
])
def test_refused_with_speculation_and_roles(over, said):
    with pytest.raises(ValueError, match=said):
        InferenceEngine(_cfg("tiny-llama"), EngineConfig(**dict(
            ENGINE, keep_logits=True, **over)))
