"""Weight-only int8/int4 quantization for the HBM-bound decode path.

TPU decode at serving batch sizes is bandwidth-bound: every step re-reads
the full weight set from HBM (BASELINE.md roofline), so storing matmul
weights as int8 with a per-output-channel scale halves weight traffic —
and int4 with group-wise scales halves it again. XLA folds the int->bf16
convert into the matmul fusion, so HBM sees one narrow read and the MXU
still runs a bf16 contraction against full-precision activations.

Design:
- ``QuantizedArray`` is a registered pytree dataclass ``{q, scale}``.
  int8: per-*output*-channel scale — the contraction dim (axis -2 of
  every weight in this codebase's [in, out] convention) is reduced to 1
  in ``scale``. int4: the contraction dim is split into groups of
  ``GROUP_SIZE`` and the scale is per (group, output channel) — 4-bit
  cells are too coarse for one whole-column scale (the GPTQ/AWQ
  group-quant recipe). Registered as a pytree node it survives
  ``lax.scan`` over stacked layer weights and tree-mapped sharding.
- int4 codes are stored PACKED, two per int8 byte along the contraction
  dim (rows 2i, 2i+1 -> low, high nibble). Sub-byte (S4) arrays never
  persist across a jit boundary: a packed byte array is the portable
  representation (checkpoints, host copies and every backend agree on
  its layout). The arithmetic-shift unpack is elementwise and fuses into the
  matmul read; HBM still sees half of int8's weight bytes. Invariant:
  a grouped scale (G > 1) always pairs with packed codes.
- ``qdot`` / ``qeinsum`` are drop-in contraction helpers the model
  forwards call for every weight matmul; they accept plain arrays too, so
  quantization stays a load-time decision (EngineConfig.quant) rather
  than a model-code fork. The two paths are discriminated by the scale's
  group count alone: G == 1 scales the contraction *output* (exact
  because the scale is constant along the contracted axis), G > 1 runs a
  grouped contraction and folds the per-group partial sums.
- STORED orientation. Weights are published, initialised, quantized,
  loaded and sharded as ``[.., K, N]`` (contraction dim second to last).
  The engine, once, when it takes its weights, stores the stacks that
  feed attention (``STORED_TRANSPOSED``, one entry a family) swapped,
  ``[.., N, K]``: row-major with the contraction dim minor-most is the
  layout the chip compiler reads a layer's matrix in when the
  activation has few rows, and a stack stored the other way round is
  copied whole in front of the layer loop, once a dispatch. A swapped
  leaf says so in its TYPE (``QuantizedArray.transposed``, a static
  field; ``Transposed``, a one-child node, for a plain stack), so it
  rides ``lax.scan`` and tree maps like any leaf and ``qdot`` contracts
  it on its last dim. No model file names a layout.
- Under tensor parallelism GSPMD shards the grouped partials like any
  einsum; for G == 1 it may place the all-reduce before or after the
  scale — both are exact.

The reference has no quantization tier (it has no model code at all,
SURVEY.md §0); this implements the serving-side capability its external
Ollama endpoint provided (Ollama serves quantized GGUF models — the
reference's `mistral` was a 4-bit variant by default, which is exactly
the int4 tier here).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

QUANT_MODES = ("none", "int8", "int4")

# int4 group size along the contraction dim (GPTQ/AWQ-style). Contraction
# dims not divisible by it fall back to one group per column (exact for
# the tiny test models whose dims are below the group size anyway).
GROUP_SIZE = 128

# Params-tree leaf names eligible for quantization: the large matmul
# weights. Norm scales, biases, embeddings (gather tables), positional
# tables, and the MoE router (tiny, routing-precision-sensitive) stay in
# the model dtype.
QUANT_KEYS = frozenset({
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head",
    "w_qkv", "w_proj", "w_fc", "w_out",
    # models/deepseek_v3.py: query / latent down- and up-projections,
    # the shared expert, the routed experts (int8 only: the grouped
    # kernels widen in VMEM). ``wkv_b`` stays in the model dtype: it is
    # used absorbed, as two per-head einsums, not through qdot.
    "wq_a", "wq_b", "wkv_a", "ws_gate", "ws_up", "ws_down",
    "we_gate", "we_up", "we_down",
    # models/sambay.py: the projections (the fused MLP, the mixers' in /
    # out, the attention's query and output). The scan's own parameters
    # (conv taps, W_x, W_dt and its bias, A_log, D) stay in their dtype.
    "w1", "w2", "w_in", "w_q", "w_o",
    # models/bailing_hybrid.py: the delta-rule layers' projections (the
    # fused q / k / v, the decay's, the output's; ``wq`` / ``wkv_a`` /
    # ``wo`` of its latent layers are above). The gate's own parameters
    # (A_log, dt_bias), the convolution's taps and the head-wide beta and
    # gate projections stay in their dtype.
    "w_f",
})

# The stacks the engine stores transposed, ``[.., N, K]`` (see the module
# docstring), by family and leaf name: those the v5e compiler's HLO of
# the decode programs shows copied whole in front of the layer loop
# (``benchmarks/aot_rehearsal.py``'s ``param_copies``; the table is in
# PERF.md section 5), read off the HLO and not off a model's name. A
# family with no entry stores every leaf as published.
STORED_TRANSPOSED = {
    "llama": frozenset({"wq", "wk", "wv"}),
    "ouro": frozenset({"wq", "wk", "wv"}),
    "laguna": frozenset({"wq", "wk", "wv"}),
    "deepseek_v3": frozenset({"wq_b", "wkv_b"}),
    "sambay": frozenset({"w_q"}),
    "smallthinker": frozenset({"wq", "wk", "wv"}),
    "bailing_hybrid": frozenset({"wq", "wkv_b"}),
}


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantizedArray:
    """Narrow-int weight + f32 scale.

    int8: scale [..., 1, out] (axis -2 reduced). int4: scale
    [..., G, out] with G groups along the contraction dim.
    ``transposed`` (static: part of the tree's structure): ``q`` is
    stored ``[..., out, in]``; the scale is as above either way.
    """

    q: jax.Array
    scale: jax.Array
    transposed: bool = dataclasses.field(default=False,
                                         metadata=dict(static=True))

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype

    @property
    def size(self):
        return self.q.size

    @property
    def ndim(self):
        return self.q.ndim


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Transposed:
    """A plain weight stored ``[..., out, in]``: the published
    ``[..., in, out]`` with its last two dims swapped. A square stack's
    shape cannot say which it is, so the type does."""

    w: jax.Array

    @property
    def shape(self):
        return self.w.shape

    @property
    def dtype(self):
        return self.w.dtype

    @property
    def size(self):
        return self.w.size

    @property
    def ndim(self):
        return self.w.ndim


def _contract_dtype(act_dtype):
    """Contraction dtype for the grouped (int4) paths. bf16 on TPU (MXU
    native); f32 elsewhere — XLA:CPU's batched-dot thunk cannot execute
    bf16 x bf16 -> f32 (the backend is static at trace time, so this is
    a compile-time constant, not a traced branch)."""
    if act_dtype == jnp.bfloat16 and jax.default_backend() != "tpu":
        return jnp.float32
    return act_dtype


def _groups_for(in_dim: int, mode: str) -> int:
    """Scale groups along the contraction dim for a quant mode."""
    if mode == "int8" or in_dim % GROUP_SIZE:
        return 1
    return in_dim // GROUP_SIZE


def pack_int4(codes: jax.Array) -> jax.Array:
    """int8 codes [..., in, out] (values in [-7, 7]) -> packed int8
    [..., in // 2, out]: row 2i in the low nibble, row 2i+1 in the high."""
    *lead, in_dim, out = codes.shape
    pairs = codes.reshape(*lead, in_dim // 2, 2, out)
    lo, hi = pairs[..., 0, :], pairs[..., 1, :]
    return (lo & jnp.int8(0x0F)) | (hi << 4)


def unpack_int4(packed: jax.Array, transposed: bool = False) -> jax.Array:
    """Packed int8 [..., in // 2, out] -> sign-extended int8 codes
    [..., in, out] (``transposed``: [..., out, in // 2] -> [..., out,
    in]; the pairs lie along the contraction dim either way). Two
    arithmetic shifts per nibble — elementwise, so XLA fuses the unpack
    into the consuming matmul's operand read."""
    lo = (packed << 4) >> 4                      # sign-extend low nibble
    hi = packed >> 4                             # arithmetic: sign-extends
    if transposed:
        *lead, out, half = packed.shape
        return jnp.stack([lo, hi], axis=-1).reshape(*lead, out, 2 * half)
    *lead, half, out = packed.shape
    return jnp.stack([lo, hi], axis=-2).reshape(*lead, 2 * half, out)


def quantize_array(w: jax.Array, mode: str = "int8") -> QuantizedArray:
    """Symmetric narrow-int quantization along the contraction dim
    (axis -2): int8 per output channel, int4 per (group, channel).

    int4 with grouped scales returns PACKED codes (see module docstring);
    the no-group fallback (contraction dim not divisible by GROUP_SIZE —
    tiny test models) keeps one code per byte with a per-column scale,
    which the G == 1 contraction path handles exactly."""
    wf = w.astype(jnp.float32)
    if mode == "int4":
        in_dim, out = w.shape[-2], w.shape[-1]
        ngrp = _groups_for(in_dim, mode)
        wg = wf.reshape(w.shape[:-2] + (ngrp, in_dim // ngrp, out))
        amax = jnp.max(jnp.abs(wg), axis=-2, keepdims=True)
        scale = jnp.maximum(amax, 1e-8) / 7.0
        q = jnp.clip(jnp.round(wg / scale), -7, 7).astype(jnp.int8)
        q = q.reshape(w.shape)
        if ngrp > 1:
            q = pack_int4(q)
        return QuantizedArray(q=q, scale=scale[..., 0, :])  # [..., G, out]
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return QuantizedArray(q=q, scale=scale)


def dequantize(w: QuantizedArray, dtype=jnp.float32) -> jax.Array:
    """The weight in the published orientation, [..., in, out]."""
    w = published(w)
    ngrp = w.scale.shape[-2]
    if ngrp == 1:
        return (w.q.astype(jnp.float32) * w.scale).astype(dtype)
    codes = unpack_int4(w.q)
    in_dim, out = codes.shape[-2], codes.shape[-1]
    wg = codes.reshape(codes.shape[:-2] + (ngrp, in_dim // ngrp, out))
    full = wg.astype(jnp.float32) * w.scale[..., :, None, :]
    return full.reshape(codes.shape).astype(dtype)


def _dot_stored(x: jax.Array, w: jax.Array, transposed: bool) -> jax.Array:
    """x [..., in] against one layer's matrix as it is stored ([in, out],
    or [out, in] ``transposed``: contracted on its last dim), f32 out."""
    if transposed:
        return jax.lax.dot_general(
            x, w, (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def qdot(x: jax.Array, w: Any) -> jax.Array:
    """``x @ w`` with f32 accumulation; w may be a QuantizedArray, and
    stored transposed (its type says so: see the module docstring).

    x: [..., in]; w: [in, out] as published. Returns f32 [..., out].
    """
    if isinstance(w, Transposed):
        return _dot_stored(x, w.w, True)
    if isinstance(w, QuantizedArray):
        ngrp = w.scale.shape[-2]
        if ngrp == 1:
            y = _dot_stored(x, w.q.astype(x.dtype), w.transposed)
            return y * w.scale[..., 0, :]
        # Grouped (int4): unpack the nibble-packed codes (fuses into the
        # operand read), contract each group separately, fold the
        # per-group partials with their own scales. HBM still reads only
        # the packed 4-bit codes + the small scale table.
        codes = unpack_int4(w.q, w.transposed)
        ct = _contract_dtype(x.dtype)
        if w.transposed:                   # [out, in]: groups along the last
            out, gsz = codes.shape[-2], codes.shape[-1] // ngrp
            eq, qg = "...gi,ogi->...go", codes.reshape(out, ngrp, gsz)
        else:
            out, gsz = codes.shape[-1], codes.shape[-2] // ngrp
            eq, qg = "...gi,gio->...go", codes.reshape(ngrp, gsz, out)
        xg = x.reshape(x.shape[:-1] + (ngrp, gsz)).astype(ct)
        y = jnp.einsum(eq, xg, qg.astype(ct),
                       preferred_element_type=jnp.float32)
        return jnp.sum(y * w.scale, axis=-2)
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def split_heads(w: Any, heads: int) -> jax.Array:
    """One layer's plain ``[in, heads * d]`` matrix as ``[in, heads, d]``,
    for a per-head einsum (models/deepseek_v3.py's absorbed ``wkv_b``),
    whichever way it is stored. A ``Transposed`` one is swapped back in
    name only: the compiler takes a transpose as a change of layout, so
    the loop reads the stored bytes as it read its own copy of the
    published stack."""
    if isinstance(w, Transposed):
        w = jnp.swapaxes(w.w, -1, -2)
    return w.reshape(w.shape[0], heads, -1)


def qeinsum(eq: str, a: jax.Array, w: Any) -> jax.Array:
    """``einsum(eq, a, w)`` where w may be quantized.

    Valid for contractions whose output ends with w's output (last) axis
    and preserves w's leading batch axes (the MoE expert einsums
    'ecd,edf->ecf' and 'ecf,efd->ecd'): the [..., 1, out] scale then
    broadcasts against the result directly; grouped (int4) scales fold
    per-group partial contractions of the same two patterns.
    """
    if isinstance(w, QuantizedArray):
        ngrp = w.scale.shape[-2]
        if ngrp == 1:
            y = jnp.einsum(eq, a, w.q.astype(a.dtype),
                           preferred_element_type=jnp.float32)
            return y * w.scale
        assert eq in ("ecd,edf->ecf", "ecf,efd->ecd"), (
            f"grouped qeinsum supports the MoE expert contractions, "
            f"got {eq!r}")
        codes = unpack_int4(w.q)
        gsz = codes.shape[-2] // ngrp
        ct = _contract_dtype(a.dtype)
        a4 = a.reshape(a.shape[:-1] + (ngrp, gsz)).astype(ct)  # [E,C,G,g]
        q4 = codes.reshape(codes.shape[0], ngrp, gsz,
                           codes.shape[-1]).astype(ct)    # [E, G, g, out]
        y = jnp.einsum("ecgi,egio->egco", a4, q4,
                       preferred_element_type=jnp.float32)
        return jnp.sum(y * w.scale[:, :, None, :], axis=1)
    return jnp.einsum(eq, a, w, preferred_element_type=jnp.float32)


def _leaf_name(path) -> str:
    last = path[-1]
    return last.key if hasattr(last, "key") else str(last)


def _is_weight(x) -> bool:
    return isinstance(x, (QuantizedArray, Transposed))


def is_transposed(leaf) -> bool:
    """Whether a params leaf is stored ``[.., N, K]``: its type says."""
    return isinstance(leaf, Transposed) or getattr(leaf, "transposed", False)


def transposed_spec(spec, ndim: int):
    """A PartitionSpec of a published ``[.., K, N]`` weight, for the same
    weight stored ``[.., N, K]``: each dim keeps its mesh axis."""
    entries = list(spec) + [None] * (ndim - len(spec))
    entries[-1], entries[-2] = entries[-2], entries[-1]
    return type(spec)(*entries)


def store_transposed(params: dict, family: str, owned: bool = False):
    """The engine's one step from published to stored orientation:
    swap the last two dims of every ``STORED_TRANSPOSED[family]`` leaf of
    ``params`` on the device (a jitted transpose a distinct shape; a
    sharded leaf's dims keep their mesh axes) and say so in the leaf's
    type. Quantization has run before, on the published orientation: the
    codes move, the scale does not. With ``owned`` (the caller made the
    arrays and holds no other use of them) each swapped input is freed
    at once, so the peak is one leaf over the weights. Returns (params,
    the number of stacks swapped). Abstract trees work too
    (``jax.eval_shape``: benchmarks/aot_rehearsal.py takes the stored
    shapes from here, as the engine does)."""
    from jax.sharding import NamedSharding

    names = STORED_TRANSPOSED.get(family, frozenset())
    swaps: dict = {}
    n = 0

    def swap(x):
        sh = getattr(x, "sharding", None)   # (None on an abstract leaf)
        out = (NamedSharding(sh.mesh, transposed_spec(sh.spec, x.ndim))
               if isinstance(sh, NamedSharding) else None)
        if out not in swaps:
            def tpu_inf_store_transposed(a):
                return jnp.swapaxes(a, -1, -2)
            swaps[out] = jax.jit(tpu_inf_store_transposed,
                                 out_shardings=out)
        y = swaps[out](x)
        if owned:
            x.delete()
        return y

    def store(path, leaf):
        nonlocal n
        if _leaf_name(path) not in names or is_transposed(leaf):
            return leaf
        n += 1
        if isinstance(leaf, QuantizedArray):
            return QuantizedArray(q=swap(leaf.q), scale=leaf.scale,
                                  transposed=True)
        return Transposed(swap(leaf))

    stored = jax.tree_util.tree_map_with_path(store, params,
                                              is_leaf=_is_weight)
    return stored, n


def published(tree: Any) -> Any:
    """``tree`` (a params tree, or one leaf) with every weight the engine
    stores transposed back in the published ``[.., K, N]`` orientation:
    for whoever reads ``engine.params`` as a checkpoint would hold it."""
    def back(leaf):
        if isinstance(leaf, Transposed):
            return jnp.swapaxes(leaf.w, -1, -2)
        if isinstance(leaf, QuantizedArray) and leaf.transposed:
            return QuantizedArray(q=jnp.swapaxes(leaf.q, -1, -2),
                                  scale=leaf.scale)
        return leaf

    return jax.tree.map(back, tree, is_leaf=_is_weight)


def quantize_params(params: dict, mode: str = "int8") -> dict:
    """Quantize the matmul weights of a params pytree (QUANT_KEYS leaves).

    Runs on device (jitted per distinct leaf shape); sharded inputs
    produce q/scale with layouts GSPMD derives from the input sharding —
    re-apply ``parallel.shardings.shard_params`` afterwards for the
    canonical placement.
    """
    if mode == "none":
        return params
    if mode not in QUANT_MODES:
        raise ValueError(f"unknown quant mode {mode!r}; one of {QUANT_MODES}")
    import functools
    quant_jit = jax.jit(functools.partial(quantize_array, mode=mode))

    def maybe_quant(path, leaf):
        if _leaf_name(path) in QUANT_KEYS:
            return quant_jit(leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(maybe_quant, params)


def init_quantized_params(model_cfg, seed: int = 0,
                          mode: str = "int8") -> dict:
    """Random init + quantize ONE LEAF AT A TIME.

    ``build_model`` then ``quantize_params`` peaks at the full
    model-dtype tree plus the quantized copy — an 8B-dims engine would
    OOM a 16 GB chip it comfortably serves int8. Here each QUANT_KEYS
    leaf is initialized and quantized inside a single jit (XLA frees the
    full-precision intermediate on exit), so peak device memory is
    ~quantized-model-sized plus one full-precision leaf.

    Leaf VALUES differ from build_model's (independent per-leaf keys);
    random-init weights carry no meaning, so only shapes, dtypes, and
    determinism-per-seed matter. Norm-scale leaves are ones (as in every
    family's init_params); everything else draws the same 0.02-std
    normal.
    """
    if mode == "none":
        raise ValueError("init_quantized_params needs a quant mode; use "
                         "build_model for full-precision init")
    if mode not in QUANT_MODES:
        raise ValueError(f"unknown quant mode {mode!r}; one of {QUANT_MODES}")
    from tpu_inference.models.registry import get_model_fns

    mod = get_model_fns(model_cfg)
    shapes = jax.eval_shape(
        lambda k: mod.init_params(model_cfg, k), jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    key = jax.random.PRNGKey(seed)

    out = []
    for path, sds in leaves:
        name = _leaf_name(path)
        key, sub = jax.random.split(key)
        if name in QUANT_KEYS:
            out.append(jax.jit(
                lambda k, s=sds: quantize_array(
                    (0.02 * jax.random.normal(k, s.shape, jnp.float32)
                     ).astype(s.dtype), mode))(sub))
        elif "norm" in name:
            out.append(jnp.ones(sds.shape, sds.dtype))
        else:
            out.append(jax.jit(
                lambda k, s=sds: (0.02 * jax.random.normal(
                    k, s.shape, jnp.float32)).astype(s.dtype))(sub))
    return jax.tree_util.tree_unflatten(treedef, out)
