#!/usr/bin/env python3
"""How ``correct`` is decided: the program's engine against the plain
float32 reference, at the configuration's published widths with depth cut
to what fits beside the reference. A child process of ``run.py`` (it
imports JAX and owns the chip while it runs; the server has exited).

For each seed: weights are made HERE from the seed (``references/``),
handed to the program's ``InferenceEngine`` (which quantises and shards
them by its own code), and token streams from the seed go through the
engine's real path: prefill (batched, or chunked past the largest
bucket), fused-K paged decode of all streams together, and, where the
configuration shares prefixes, a prefix-cache hit. Logits are read off
the pool the engine wrote (one query over the sequence's own pages, as
``chip_smoke.py``'s parity does) at the last prompt position (right after
prefill) and at the last decoded position, and compared with the reference's logits of the
same stream, as shares of that position's logit spread. The engine's
greedy tokens are judged on the reference's logits.

``--control`` builds the engine in the precision below the one the
configuration states (its ``parity.control`` overrides: int4 weights for
int8): every number it prints must then be OVER the limit.

One JSON line per seed, then one summary line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))          # the checkout: tpu_inference
sys.path.insert(0, HERE)

from manifest import Manifest, load_module  # noqa: E402


def check_sizes(model: dict, mcfg) -> list:
    """The program's preset against the published keys: exact."""
    pairs = [("hidden_size", mcfg.d_model),
             ("intermediate_size", mcfg.d_ff),
             ("num_hidden_layers", mcfg.n_layers),
             ("num_attention_heads", mcfg.n_heads),
             ("num_key_value_heads", mcfg.n_kv_heads),
             ("vocab_size", mcfg.vocab_size),
             ("rope_theta", mcfg.rope_theta),
             ("rms_norm_eps", mcfg.norm_eps),
             ("sliding_window", mcfg.sliding_window or None),
             ("attention_bias", mcfg.qkv_bias),
             ("tie_word_embeddings", mcfg.tie_embeddings)]
    model = dict(model)
    if not model.get("use_sliding_window", True):
        model["sliding_window"] = None
    return [f"{k}: file {model.get(k)!r} != program {v!r}"
            for k, v in pairs if (model.get(k) or None) != (v or None)]


def make_probe(eng):
    """One query at position p over the sequence's own pages, built of
    the engine's own parts (its paged attention, model forward and
    unembed): logits [V] off the pool the serving graphs wrote."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def probe(params, kv, token, pos, block_table):
        attn = eng._paged_attn(eng.model_cfg, block_table, pos[:, None],
                               jnp.ones((1, 1), bool), q_offset=pos,
                               kv_len=pos + 1)
        hidden, kv = eng.mod.forward_hidden(
            params, eng.model_cfg, token[:, None], pos[:, None], kv, attn)
        return kv, eng.mod.unembed(params, eng.model_cfg, hidden[:, 0])

    jitted = jax.jit(probe, donate_argnums=(1,))

    def at(seq, p: int):
        stream = seq.prompt_tokens + seq.generated
        table = jnp.asarray(eng._block_table_array(seq.pages))[None]
        eng.kv, lg = jitted(eng.params, eng.kv,
                            jnp.asarray([stream[p]], jnp.int32),
                            jnp.asarray([p], jnp.int32), table)
        return np.asarray(lg[0], np.float32)

    return at


def run_streams(eng, prompts, decode_steps: int):
    """Prefill every stream, read the logits at each one's last prompt
    position (every page it reads was written by the prefill graphs; read
    NOW, because a sliding window frees the pages a later token no longer
    needs), decode all streams together, read the logits at the last
    decoded position (prefill and the decode graph's writes). Returns the
    sequences and {stream: {position: logits}}."""
    from tpu_inference.engine.engine import Sequence

    probe = make_probe(eng)
    seqs = [Sequence(request_id=i, prompt_tokens=list(p),
                     max_new_tokens=4 * decode_steps)
            for i, p in enumerate(prompts)]
    got = {}
    for i, s in enumerate(seqs):
        eng.prefill(s)
        n = len(s.prompt_tokens)
        got[i] = {n - 1: probe(s, n - 1)}
    while any(len(s.generated) < decode_steps + 1 for s in seqs):
        eng.decode_steps()
    for i, s in enumerate(seqs):
        p = len(s.prompt_tokens) + decode_steps - 1
        got[i][p] = probe(s, p)
    return seqs, got


def compare(ref_mod, w32, sz, seqs, got, decode_steps: int) -> dict:
    import numpy as np

    rms, peak, gap, streams = [], [], [], []
    for i, s in enumerate(seqs):
        n = len(s.prompt_tokens)
        stream = s.prompt_tokens + s.generated[:decode_steps + 1]
        at = list(range(n - 1, n + decode_steps))
        ref = ref_mod.logits(w32, sz, stream[:n + decode_steps], at)
        for p, lg in got[i].items():
            r = ref[p - (n - 1)]
            if not np.isfinite(lg).all():
                return {"rms": float("inf"), "max": float("inf"),
                        "token_gap": float("inf")}
            d = (lg - r) / np.std(r)
            rms.append(float(np.sqrt(np.mean(d * d))))
            peak.append(float(np.max(np.abs(d))))
            streams.append([n, p, round(rms[-1], 5), round(peak[-1], 5)])
        for j, tok in enumerate(s.generated[:decode_steps + 1]):
            r = ref[j]
            gap.append(float((r.max() - r[tok]) / np.std(r)))
    return {"rms": max(rms), "max": max(peak), "token_gap": max(gap),
            "rms_mean": sum(rms) / len(rms), "streams": streams}


def one_seed(cfg: dict, ref_mod, seed: int, control: bool) -> dict:
    import gc

    import jax
    import numpy as np

    from tpu_inference.config import PRESETS, EngineConfig
    from tpu_inference.engine.engine import InferenceEngine

    par = cfg["parity"]
    mcfg = PRESETS[cfg["serving"]["preset"]]()
    wrong = check_sizes(cfg, mcfg)
    mcfg = dataclasses.replace(mcfg, n_layers=par["layers"])
    sz = ref_mod.sizes(cfg, par["layers"])
    quant = cfg["serving"]["quant"]
    eng_kw = dict(par["engine"], quant=quant,
                  attn_backend=cfg["serving"]["attn_backend"])
    if control:
        eng_kw.update(par["control"])
    weights = ref_mod.make_weights(sz, seed)
    eng = InferenceEngine(mcfg, EngineConfig(**eng_kw), params=weights,
                          seed=seed & 0x7FFFFFFF)
    rng = np.random.default_rng([int(seed), 7])
    shared = [int(t) for t in rng.integers(0, sz["vocab"],
                                           par.get("shared_prefix", 0))]
    prompts = [shared + [int(t) for t in rng.integers(0, sz["vocab"], n)]
               for n in par["prompts"]]
    k = par["decode_steps"]
    seqs, got = run_streams(eng, prompts, k)
    cached = None
    if shared:
        # Publish the streams' pages, then a new stream that starts with
        # the shared prefix: its prefill must come from the cache.
        for s in seqs:
            eng.release(s)
        tail = [int(t) for t in rng.integers(0, sz["vocab"],
                                             par["prompts"][0] + 17)]
        hit, hit_got = run_streams(eng, [shared + tail], k)
        cached = hit[0].cached_tokens
        got[len(seqs)] = hit_got[0]
        seqs = seqs + hit
    del eng
    gc.collect()
    w32 = ref_mod.reference_weights(weights, quant)
    res = compare(ref_mod, w32, sz, seqs, got, k)
    res.update(seed=seed, sizes_wrong=wrong, cached_tokens=cached,
               control=control)
    return res


def judge(res: dict, limit: dict, shared_prefix: int) -> bool:
    ok = (res["rms"] <= limit["rms"] and res["max"] <= limit["max"]
          and res["token_gap"] <= limit["max"] and not res["sizes_wrong"])
    if shared_prefix:
        ok = ok and (res["cached_tokens"] or 0) >= shared_prefix - 16
    return ok


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--manifest", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()

    man = Manifest(args.manifest)
    cell = man.cell(args.workload)
    cfg = man.config(cell)
    ref_mod = load_module(os.path.join(HERE, "references",
                                       cfg["reference"] + ".py"))
    srv = cfg["serving"]

    from tpu_inference.runtime import (enable_compile_cache,
                                       require_backend, select_platform)
    select_platform(srv["platform"], cpu_devices=max(4, cell["chips"]))
    enable_compile_cache()
    require_backend(srv["platform"])
    import jax

    dev = jax.devices()[0]
    limit = cfg["parity"]["limit"]
    all_ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        res = one_seed(cfg, ref_mod, seed, args.control)
        res["ok"] = judge(res, limit, cfg["parity"].get("shared_prefix", 0))
        res["limit"] = limit
        res["seconds"] = round(time.monotonic() - t0, 2)
        all_ok = all_ok and res["ok"]
        print(json.dumps(res), flush=True)
    print(json.dumps({"parity": True, "ok": all_ok, "control": args.control,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
