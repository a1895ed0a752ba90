"""Mosaic-validate the window-aware Pallas kernels on the real chip.

Interpret-mode Pallas and virtual CPU meshes never exercise the Mosaic
compiler, so this runs each windowed kernel and SP attention wrapper
NON-interpret at small shapes against the dense window-masked oracle and
writes one JSON artifact. (The main path's kernels at real widths are
covered without it: tests/test_tpu_compile.py compiles them for a v5e,
chip_smoke.py's parity phase checks their numbers on the chip.)

Checks (each timed; first run includes the Mosaic/XLA compile):
  swa_decode    paged_attention(sliding_window=W, interpret=False)
  swa_decode8   same on the int8 KV pool (in-kernel dequant + window)
  swa_prefill   paged_prefill_attention(sliding_window=W, interpret=False)
  swa_prefill8  same on the int8 pool
  ring_swa      windowed ring attention over a 1-device mesh (shard_map
                compiles on the TPU backend; axis size is what the
                hardware offers)
  ulysses_swa   windowed Ulysses over the same mesh

Usage (on the chip):  python benchmarks/mosaic_validate.py [--out PATH]
Exit 0 iff every check passes. One process; refuses to run without a
TPU (on the CPU there is no Mosaic to validate against).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/mosaic.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from tpu_inference.engine import kv_cache as kvc
    from tpu_inference.kernels.paged_attention import paged_attention
    from tpu_inference.kernels.prefill_attention import (
        paged_prefill_attention)
    from tpu_inference.kernels.ring_attention import ring_attention
    from tpu_inference.kernels.ulysses_attention import ulysses_attention
    from tpu_inference.models import common

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(f"mosaic_validate needs a TPU; jax runs on "
                         f"{platform!r}")
    rec = {"platform": platform, "device_kind": jax.devices()[0].device_kind,
           "checks": {}, "ok": True}
    rng = np.random.default_rng(23)

    # Shared pool geometry: TPU-tile-friendly head dim, window crossing
    # page boundaries, ragged kv lens shorter and longer than the window.
    page, mp, hq, hkv, d, window = 8, 6, 4, 2, 128, 11
    b = 3
    n_pages = 32
    kv_lens = np.array([5, 17, 41], np.int32)
    # The kernels take the engine's stacked pool [L, P, page, Hkv, D]
    # and the layer to read (a quantized pool's scales: that layer's).
    # Three layers of different contents; every check reads the last.
    layers, layer = 3, 2
    k_pool = rng.standard_normal(
        (layers, n_pages, page, hkv, d)).astype(np.float32)
    v_pool = rng.standard_normal(
        (layers, n_pages, page, hkv, d)).astype(np.float32)
    bt = rng.permutation(np.arange(1, 1 + b * mp)).reshape(b, mp).astype(
        np.int32)

    def check(name, fn):
        t0 = time.perf_counter()
        try:
            err = fn()
            dt = time.perf_counter() - t0
            rec["checks"][name] = {"ok": err is None, "wall_s": round(dt, 2),
                                   **({"error": err} if err else {})}
            if err:
                rec["ok"] = False
            print(f"[mosaic] {name}: {'ok' if not err else 'FAIL'} "
                  f"({dt:.1f}s){'' if not err else ' ' + err}")
        except Exception as e:                        # noqa: BLE001
            dt = time.perf_counter() - t0
            rec["checks"][name] = {"ok": False, "wall_s": round(dt, 2),
                                   "error": f"{type(e).__name__}: {e}"}
            rec["ok"] = False
            print(f"[mosaic] {name}: RAISED ({dt:.1f}s) "
                  f"{type(e).__name__}: {e}")

    def decode_ref(kp, vp, q):
        outs = []
        for i in range(b):
            n = int(kv_lens[i])
            fk = np.concatenate([kp[bt[i, j]] for j in range(mp)])[:n]
            fv = np.concatenate([vp[bt[i, j]] for j in range(mp)])[:n]
            outs.append(np.asarray(common.dense_causal_attention(
                jnp.asarray(q[i][None, None]), jnp.asarray(fk[None]),
                jnp.asarray(fv[None]), q_offset=n - 1, kv_len=n,
                sliding_window=window))[0, 0])
        return np.stack(outs)

    q1 = rng.standard_normal((b, hq, d)).astype(np.float32)

    def swa_decode():
        got = paged_attention(jnp.asarray(q1), jnp.asarray(k_pool),
                              jnp.asarray(v_pool), layer, jnp.asarray(bt),
                              jnp.asarray(kv_lens), None, None,
                              sliding_window=window, interpret=False)
        want = decode_ref(k_pool[layer], v_pool[layer], q1)
        if not np.allclose(np.asarray(got), want, rtol=2e-2, atol=2e-2):
            return f"max abs err {np.abs(np.asarray(got) - want).max():.2e}"
        return None

    def swa_decode8():
        kq, ks = kvc.quantize_kv(jnp.asarray(k_pool))
        vq, vs = kvc.quantize_kv(jnp.asarray(v_pool))
        got = paged_attention(jnp.asarray(q1), kq, vq, layer,
                              jnp.asarray(bt), jnp.asarray(kv_lens),
                              ks[layer], vs[layer],
                              sliding_window=window, interpret=False)
        kd = np.asarray(kq, np.float32) * np.asarray(ks)[..., None]
        vd = np.asarray(vq, np.float32) * np.asarray(vs)[..., None]
        want = decode_ref(kd[layer], vd[layer], q1)
        if not np.allclose(np.asarray(got), want, rtol=5e-2, atol=5e-2):
            return f"max abs err {np.abs(np.asarray(got) - want).max():.2e}"
        return None

    def swa_decode4():
        # int4 nibble-packed pool: proves the in-kernel integer
        # unpack (shift/mask/select + lane-dim concat) lowers through
        # Mosaic, not just interpret mode.
        kq, ks = kvc.quantize_kv_int4(jnp.asarray(k_pool))
        vq, vs = kvc.quantize_kv_int4(jnp.asarray(v_pool))
        got = paged_attention(jnp.asarray(q1), kq, vq, layer,
                              jnp.asarray(bt), jnp.asarray(kv_lens),
                              ks[layer], vs[layer],
                              sliding_window=window, interpret=False)
        kd = np.asarray(kvc.unpack_int4_kv(kq), np.float32) \
            * np.asarray(ks)[..., None]
        vd = np.asarray(kvc.unpack_int4_kv(vq), np.float32) \
            * np.asarray(vs)[..., None]
        want = decode_ref(kd[layer], vd[layer], q1)
        if not np.allclose(np.asarray(got), want, rtol=5e-2, atol=5e-2):
            return f"max abs err {np.abs(np.asarray(got) - want).max():.2e}"
        return None

    s = 24
    q_off = np.array([0, 16, 8], np.int32)
    pf_lens = (q_off + s).astype(np.int32)
    mp_pf = 8
    n_pages_pf = 64
    k_pf = rng.standard_normal(
        (layers, n_pages_pf, page, hkv, d)).astype(np.float32)
    v_pf = rng.standard_normal(
        (layers, n_pages_pf, page, hkv, d)).astype(np.float32)
    bt_pf = rng.permutation(np.arange(1, 1 + b * mp_pf)).reshape(
        b, mp_pf).astype(np.int32)
    qs = rng.standard_normal((b, s, hq, d)).astype(np.float32)

    def prefill_ref(kp, vp):
        outs = []
        for i in range(b):
            n = int(pf_lens[i])
            fk = np.concatenate([kp[bt_pf[i, j]] for j in range(mp_pf)])[:n]
            fv = np.concatenate([vp[bt_pf[i, j]] for j in range(mp_pf)])[:n]
            outs.append(np.asarray(common.dense_causal_attention(
                jnp.asarray(qs[i][None]), jnp.asarray(fk[None]),
                jnp.asarray(fv[None]), q_offset=int(q_off[i]), kv_len=n,
                sliding_window=window))[0])
        return np.stack(outs)

    def swa_prefill():
        got = paged_prefill_attention(
            jnp.asarray(qs), jnp.asarray(k_pf), jnp.asarray(v_pf), layer,
            jnp.asarray(bt_pf), jnp.asarray(pf_lens), jnp.asarray(q_off),
            None, None, block_q=8, sliding_window=window, interpret=False)
        want = prefill_ref(k_pf[layer], v_pf[layer])
        if not np.allclose(np.asarray(got), want, rtol=2e-2, atol=2e-2):
            return f"max abs err {np.abs(np.asarray(got) - want).max():.2e}"
        return None

    def swa_prefill8():
        kq, ks = kvc.quantize_kv(jnp.asarray(k_pf))
        vq, vs = kvc.quantize_kv(jnp.asarray(v_pf))
        got = paged_prefill_attention(
            jnp.asarray(qs), kq, vq, layer, jnp.asarray(bt_pf),
            jnp.asarray(pf_lens), jnp.asarray(q_off), ks[layer], vs[layer],
            block_q=8, sliding_window=window, interpret=False)
        kd = np.asarray(kq, np.float32) * np.asarray(ks)[..., None]
        vd = np.asarray(vq, np.float32) * np.asarray(vs)[..., None]
        want = prefill_ref(kd[layer], vd[layer])
        if not np.allclose(np.asarray(got), want, rtol=5e-2, atol=5e-2):
            return f"max abs err {np.abs(np.asarray(got) - want).max():.2e}"
        return None

    # SP wrappers: shard_map compiles on this backend over the devices the
    # hardware offers (with one chip the collective is degenerate, but the
    # windowed local bodies still lower via XLA).
    # Axis capped at 2 (a divisor of hkv=2, Ulysses' contract); sequence
    # length fixed well above the window so the mask always binds — a
    # dropped window term fails numerically, not just at lowering.
    from jax.sharding import Mesh

    ndev = len(jax.devices())
    sp_n = 2 if ndev >= 2 else 1
    mesh = Mesh(np.array(jax.devices()[:sp_n]), ("sp",))
    sl = max(32, 8 * sp_n)
    qsp = jnp.asarray(rng.standard_normal((1, sl, 4, d)), jnp.float32)
    ksp = jnp.asarray(rng.standard_normal((1, sl, 2, d)), jnp.float32)
    vsp = jnp.asarray(rng.standard_normal((1, sl, 2, d)), jnp.float32)
    want_sp = None

    def sp_ref():
        nonlocal want_sp
        if want_sp is None:
            want_sp = np.asarray(common.dense_causal_attention(
                qsp, ksp, vsp, sliding_window=window))
        return want_sp

    def ring_swa():
        got = ring_attention(qsp, ksp, vsp, mesh=mesh, sliding_window=window)
        if not np.allclose(np.asarray(got), sp_ref(), rtol=2e-2, atol=2e-2):
            return "mismatch vs dense oracle"
        return None

    def ulysses_swa():
        got = ulysses_attention(qsp, ksp, vsp, mesh=mesh,
                                sliding_window=window)
        if not np.allclose(np.asarray(got), sp_ref(), rtol=2e-2, atol=2e-2):
            return "mismatch vs dense oracle"
        return None

    check("swa_decode", swa_decode)
    check("swa_decode8", swa_decode8)
    check("swa_decode4", swa_decode4)
    check("swa_prefill", swa_prefill)
    check("swa_prefill8", swa_prefill8)
    check("ring_swa", ring_swa)
    check("ulysses_swa", ulysses_swa)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps({"mosaic_ok": rec["ok"], "platform": platform,
                      "n_checks": len(rec["checks"])}))
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
