"""Paged KV cache: an HBM block pool with per-sequence block tables.

The reference delegates all KV management to its external Ollama server
(SURVEY.md §0); this is the TPU-native equivalent of vLLM's PagedAttention
memory model, re-designed for XLA's static-shape world:

- Device side, per layer: one pool array ``[L, P, page, Hkv, D]`` for K and V.
  Page 0 is a reserved **trash page**: padded / inactive token slots write
  there, so every scatter has a valid static target and no branching.
- Sequences address the pool through **block tables** ``[B, max_pages]``
  (int32 page ids, 0-filled), recomputed on the host and shipped each step —
  tiny arrays, so host->device traffic stays negligible.
- Writes are flat scatters (token -> page*page_size + offset); reads gather a
  sequence's pages into a contiguous [B, max_pages*page, Hkv, D] view for the
  dense-reference attention path. The Pallas decode kernel (kernels/) reads
  pages directly from HBM instead of materializing the gather.

Host side, ``PageAllocator`` is a free-list with refcounts so shared prompt
prefixes can map the same physical pages (copy-on-write is unnecessary for
inference: pages are append-only within a sequence).
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from tpu_inference import integrity
from tpu_inference.telemetry import named_program
from tpu_inference.config import EngineConfig, ModelConfig


class KVPages(NamedTuple):
    """Device-side KV pool. k, v: [L, num_pages, page_size, Hkv, head_dim].

    With int8 KV quantization (EngineConfig.kv_quant), k/v hold int8
    codes and ``k_scale``/``v_scale`` hold per-(token, kv-head) f32
    scales ``[L, num_pages, page_size, Hkv]`` — symmetric quantization
    over the head_dim axis, the standard KV-cache scheme. Decode HBM
    traffic for the KV working set halves vs bf16; dequantization
    happens on the consumer side (in-kernel for Pallas, at gather for
    the dense path). ``None`` scales = unquantized pool.

    With int4 (kv_quant="int4") k/v hold **uint8 nibble-packed** codes
    ``[..., head_dim // 2]`` — byte i carries code i (low nibble) and
    code i + head_dim/2 (high nibble), so unpacking is a concat, never
    an interleave — with the same per-(token, head) scale pools. KV HBM
    traffic quarters vs bf16. The mode is carried by the pool DTYPE
    (uint8 = packed int4, int8 = int8), which stays static under jit —
    a bool field here would become a traced pytree leaf inside the
    decode-step carry.

    A LATENT pool (DeepSeek-V3 / Kimi-K2 attention, ``latent_width``
    below) is ``k`` alone, ``[L, num_pages, page_size, W]``: one entry
    per token per layer for all heads, ``v`` None. ``aux`` is an int32
    vector of counters the MODEL defines and adds to inside any graph
    (its family module's ``n_aux_stats``; models/deepseek_v3.py: routing
    counts); the decode graphs emit it behind the step's tokens and
    clear it, so a prefill's counts leave with the next readback and no
    graph gains an output or a sync of its own.

    A model whose layers differ in kind (``ModelConfig.layer_types``)
    has a pool a kind: ``k`` / ``v`` are the FULL kind's, ``[n_full,
    P_full, ...]``, and ``wk`` / ``wv`` the WINDOW kind's, ``[n_window,
    P_window, ...]``, each with its own allocator and its own block
    table a sequence (``KindPages``). None for a model of one kind.

    A model with layers of a state kind (``config.STATE_KINDS``: "ssm",
    a selective scan, or "kda", a delta rule) also holds a state a
    SEQUENCE, which a token advances and no page table addresses, in
    ``conv`` and ``ssm_h``, each ``[n_layers_of_the_kind, S + 1, ...]``
    with the rest as ``ModelConfig.state_shapes`` says: the
    convolution's last inputs in the model dtype (ssm ``[d_conv - 1,
    d_inner]``; kda ``[d_conv - 1, 3 x heads, d]``, q, k and v side by
    side, a head a row) and the recurrent state in float32 (ssm
    ``[d_state, d_inner]``, state-major so that d_inner lies on the
    lanes; kda ``[heads, d_k, d_v]``, a matrix a head). S state slots are
    handed out by ``StateSlots``; slot 0 is the TRASH slot, as page 0 is the trash
    page: a lane that advances nothing in a call writes there. Such a
    model's page pools are a pool a kind (``k`` / ``v`` / ``wk`` / ``wv``,
    Phi-4) or ONE latent pool of its "full" layers (``k`` alone,
    Ling-3.0).

    (Fields beside the pools, not a mapping by kind. PR 40 kept them on
    the ground that the kinds' states differ in shape, indexing and
    allocator; PR 51 brought a second state shape and decided again:
    the two shapes differ ONLY in what lies behind ``[layers, slots]``.
    Both are indexed ``[layer's place among its kind, slot]``, handed out
    by the one ``StateSlots``, read and written by the one
    ``engine.PagedState``, and a model has one state kind, so the two
    fields hold either and ``state_shapes`` is the one place that knows
    which. A mapping by kind would have one entry a model.)
    """

    k: jax.Array
    v: Optional[jax.Array]
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None
    aux: Optional[jax.Array] = None
    wk: Optional[jax.Array] = None
    wv: Optional[jax.Array] = None
    conv: Optional[jax.Array] = None
    ssm_h: Optional[jax.Array] = None

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def latent(self) -> bool:
        return self.v is None

    @property
    def packed_int4(self) -> bool:
        return self.k.dtype == jnp.uint8


def latent_width(model_cfg: ModelConfig) -> int:
    """Stored width of one latent entry: kv_lora_rank + qk_rope_head_dim
    rounded up to the chip's 128 lanes (576 -> 640; the tail stays zero).
    A 576-wide pool would be kept page-dim-minor by the v5e compiler and
    copied whole in front of every kernel call
    (kernels/mla_attention.py)."""
    return -(-model_cfg.latent_dim // 128) * 128


def alloc_latent_pages(model_cfg: ModelConfig, engine_cfg: EngineConfig,
                       dtype=None) -> KVPages:
    """A latent pool (of the "full" layers alone where the model has
    other kinds), with the model's counter vector beside it where its
    family module asks for one (``n_aux_stats``) and the state slots
    where it has layers of a state kind."""
    from tpu_inference.models.registry import family_fn

    n_aux = family_fn(model_cfg, "n_aux_stats")
    if engine_cfg.kv_quant != "none":
        raise ValueError(
            f"{model_cfg.name}: kv_quant={engine_cfg.kv_quant!r} is not "
            "implemented for a latent (MLA) pool; use kv_quant='none'")
    shape = (model_cfg.n_kv_slots, engine_cfg.num_pages,
             engine_cfg.page_size, latent_width(model_cfg))
    pool = jax.jit(lambda: jnp.zeros(shape, dtype or model_cfg.dtype))()
    return KVPages(k=pool, v=None, aux=jnp.zeros(
        (n_aux(model_cfg),), jnp.int32) if n_aux else None,
        **alloc_state_slots(model_cfg, engine_cfg))


def write_latent(kv: KVPages, layer_idx: jax.Array, entry: jax.Array,
                 slots: jax.Array) -> KVPages:
    """Scatter latent entries [B, S, R + Dr] into the pool at ``slots``."""
    L, P, pg, W = kv.k.shape
    entry = jnp.pad(entry.astype(kv.k.dtype),
                    ((0, 0), (0, 0), (0, W - entry.shape[-1])))
    flat = kv.k.reshape(L, P * pg, W).at[layer_idx, slots.reshape(-1)].set(
        entry.reshape(-1, W))
    return kv._replace(k=flat.reshape(L, P, pg, W))


class KindPages(list):
    """A sequence's pages where the model has a pool a kind: the list
    itself is the FULL kind's block table (what ``Sequence.pages`` is
    for every model), ``window`` the WINDOW kind's, indexed by position
    like the other, with 0 (the trash page) where a page behind the
    window was released."""

    def __init__(self, full=(), window=(), state: int = 0):
        super().__init__(full)
        self.window: List[int] = list(window)
        # The sequence's state slot (StateSlots; 0: the model has none).
        self.state = state


def written_ahead_tokens(engine_cfg: EngineConfig) -> int:
    """Tokens a sequence writes before a behind-window release catches
    up: a prefill chunk, or the decode steps granted ahead."""
    return max(engine_cfg.chunk_tokens_cap,
               engine_cfg.decode_steps_per_call
               * max(1, engine_cfg.decode_pipeline_depth))


def window_span_pages(model_cfg: ModelConfig,
                      engine_cfg: EngineConfig) -> int:
    """The most window-kind pages one sequence holds: the window, the
    tokens written before a release catches up, a page of misalignment
    at each end."""
    span = -(-(model_cfg.sliding_window + written_ahead_tokens(engine_cfg))
             // engine_cfg.page_size) + 2
    return min(span, engine_cfg.max_pages_per_seq)


def num_window_pages(model_cfg: ModelConfig, engine_cfg: EngineConfig) -> int:
    """Pages of the window kind's pool (0: the model has one kind):
    ``engine_cfg.num_window_pages``, or every lane's span + trash."""
    if "window" not in model_cfg.layer_types[:model_cfg.n_layers]:
        return 0
    return engine_cfg.num_window_pages or (
        engine_cfg.max_batch_size * window_span_pages(model_cfg, engine_cfg)
        + 1)


class StateSlots:
    """Host-side allocator of per-sequence state slots: a sequence holds
    one from admission to release. Slot 0 is the trash slot and never
    handed out."""

    def __init__(self, num_slots: int):
        self.num_slots = num_slots            # trash slot included
        self._free: List[int] = list(range(num_slots - 1, 0, -1))
        self.peak_in_use = 0
        self.resets_total = 0     # first chunks: states started from zeros

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.num_slots - 1 - len(self._free)

    def allocate(self) -> int:
        if not self._free:
            raise MemoryError("state slots exhausted")
        slot = self._free.pop()
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return slot

    def free(self, slot: int) -> None:
        if slot:
            assert slot not in self._free, f"double free of state slot {slot}"
            self._free.append(slot)


def num_state_slots(model_cfg: ModelConfig, engine_cfg: EngineConfig) -> int:
    """State slots of a model with layers of a state kind, trash slot
    included (0: the model has none): one a lane."""
    if not model_cfg.state_kind:
        return 0
    return engine_cfg.max_batch_size + 1


def alloc_state_slots(model_cfg: ModelConfig,
                      engine_cfg: EngineConfig) -> dict:
    """The ``conv`` / ``ssm_h`` fields of a model with layers of a state
    kind ({}: it has none): zeros, ``ModelConfig.state_shapes`` behind
    ``[layers of the kind, slots]``."""
    n_slots = num_state_slots(model_cfg, engine_cfg)
    if not n_slots:
        return {}
    lead = (len(model_cfg.kind_layers(model_cfg.state_kind)), n_slots)
    tail, state = model_cfg.state_shapes()

    def zeros(shape, dt):
        return jax.jit(lambda: jnp.zeros(lead + shape, dt))()

    return dict(conv=zeros(tail, model_cfg.dtype),
                ssm_h=zeros(state, jnp.float32))


def alloc_kind_pages(model_cfg: ModelConfig, engine_cfg: EngineConfig,
                     dtype=None) -> KVPages:
    """A pool a kind (and the state-space layers' slots, where the model
    has any), with the model's counter vector beside them."""
    from tpu_inference.models.registry import family_fn

    if engine_cfg.kv_quant != "none":
        raise ValueError(
            f"{model_cfg.name}: kv_quant={engine_cfg.kv_quant!r} is not "
            "implemented for per-kind pools; use kv_quant='none'")
    dtype = dtype or model_cfg.dtype
    tail = (engine_cfg.page_size, model_cfg.pool_kv_heads,
            model_cfg.pool_head_dim)
    if model_cfg.pool_rows_merged:
        tail = (tail[0] * tail[1], tail[2])

    def zeros(shape, dt=dtype):
        return jax.jit(lambda: jnp.zeros(shape, dt))()

    def pool(kind, pages):
        return zeros((len(model_cfg.kind_layers(kind)), pages) + tail)

    n_aux = family_fn(model_cfg, "n_aux_stats")
    n_win = num_window_pages(model_cfg, engine_cfg)
    state = alloc_state_slots(model_cfg, engine_cfg)
    return KVPages(
        k=pool("full", engine_cfg.num_pages),
        v=pool("full", engine_cfg.num_pages),
        wk=pool("window", n_win), wv=pool("window", n_win),
        aux=jnp.zeros((n_aux(model_cfg),), jnp.int32) if n_aux else None,
        **state)


def alloc_kv_pages(model_cfg: ModelConfig, engine_cfg: EngineConfig,
                   dtype=None, sharding=None,
                   scale_sharding=None) -> KVPages:
    """Allocate the pool; with ``sharding`` each chip materializes only its
    shard (never the full replicated pool — at 70B scale that would OOM)."""
    if model_cfg.latent_dim:
        assert sharding is None, "a latent pool is not sharded"
        return alloc_latent_pages(model_cfg, engine_cfg, dtype)
    if model_cfg.layer_types:
        assert sharding is None, "per-kind pools are not sharded"
        return alloc_kind_pages(model_cfg, engine_cfg, dtype)
    shape = (model_cfg.n_kv_slots, engine_cfg.num_pages,
             engine_cfg.page_size, model_cfg.n_kv_heads, model_cfg.head_dim)
    dtype = dtype or model_cfg.dtype
    if engine_cfg.kv_quant not in ("none", "int8", "int4"):
        raise ValueError(f"unknown kv_quant mode {engine_cfg.kv_quant!r}; "
                         "one of ('none', 'int8', 'int4')")
    if engine_cfg.kv_quant == "int4" and model_cfg.head_dim % 2:
        raise ValueError("kv_quant='int4' needs an even head_dim to "
                         f"nibble-pack, got {model_cfg.head_dim}")
    if engine_cfg.kv_quant != "none":
        code_dtype = (jnp.uint8 if engine_cfg.kv_quant == "int4"
                      else jnp.int8)
        code_shape = (shape[:-1] + (shape[-1] // 2,)
                      if engine_cfg.kv_quant == "int4" else shape)
        zeros = jax.jit(lambda: jnp.zeros(code_shape, code_dtype),
                        out_shardings=sharding)
        szeros = jax.jit(lambda: jnp.zeros(shape[:-1], jnp.float32),
                         out_shardings=scale_sharding)
        return KVPages(k=zeros(), v=zeros(), k_scale=szeros(),
                       v_scale=szeros())
    zeros = jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sharding)
    return KVPages(k=zeros(), v=zeros())


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(token, head) symmetric int8 over head_dim.

    x: [B, S, Hkv, D] -> (codes int8 [B,S,Hkv,D], scale f32 [B,S,Hkv]).
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_kv_int4(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(token, head) symmetric int4 over head_dim, nibble-packed.

    x: [B, S, Hkv, D] -> (packed uint8 [B,S,Hkv,D//2], scale f32
    [B,S,Hkv]). Codes live in [-7, 7]; byte i = code i (low nibble) |
    code i+D/2 (high nibble) so unpack is a concat along D.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 7.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -7, 7).astype(jnp.int32)
    half = x.shape[-1] // 2
    lo, hi = q[..., :half], q[..., half:]
    packed = ((hi << 4) | (lo & 0xF)) & 0xFF
    return packed.astype(jnp.uint8), scale


def unpack_int4_kv(packed: jax.Array) -> jax.Array:
    """uint8 nibble-packed codes [..., D//2] -> int32 codes [..., D].

    Pure integer ops (compare/select sign extension, no bitcasts), so it
    lowers both through XLA (dense gather path) and Mosaic (in-kernel
    dequant in the paged decode/prefill kernels).
    """
    p = packed.astype(jnp.int32)
    lo = p & 0xF
    hi = (p >> 4) & 0xF
    lo = lo - jnp.where(lo > 7, 16, 0)
    hi = hi - jnp.where(hi > 7, 16, 0)
    return jnp.concatenate([lo, hi], axis=-1)


def slot_mapping(block_tables: jax.Array, positions: jax.Array,
                 valid: jax.Array, page_size: int) -> jax.Array:
    """Map absolute token positions to flat pool slots.

    block_tables: [B, max_pages]; positions: [B, S]; valid: [B, S] bool.
    Invalid tokens map to slot 0 (the trash page). Returns [B, S] int32.
    """
    page_of_pos = positions // page_size                     # [B, S]
    page_ids = jnp.take_along_axis(block_tables, page_of_pos, axis=1)
    slots = page_ids * page_size + positions % page_size
    return jnp.where(valid, slots, 0).astype(jnp.int32)


def write_kv(kv: KVPages, layer_idx: jax.Array, k_new: jax.Array,
             v_new: jax.Array, slots: jax.Array) -> KVPages:
    """Scatter new K/V ([B, S, Hkv, D]) into the pool at flat ``slots`` [B,S].

    Quantized pools quantize on the way in (codes + per-token-head scale
    scatter to the same flat slots)."""
    L, P, pg, H, D = kv.k.shape
    flat = slots.reshape(-1)
    if kv.quantized:
        qfn = quantize_kv_int4 if kv.packed_int4 else quantize_kv
        k_new, ks = qfn(k_new)
        v_new, vs = qfn(v_new)
        ksf = kv.k_scale.reshape(L, P * pg, H)
        vsf = kv.v_scale.reshape(L, P * pg, H)
        ksf = ksf.at[layer_idx, flat].set(ks.reshape(-1, H))
        vsf = vsf.at[layer_idx, flat].set(vs.reshape(-1, H))
        k_scale = ksf.reshape(L, P, pg, H)
        v_scale = vsf.reshape(L, P, pg, H)
    else:
        k_scale, v_scale = kv.k_scale, kv.v_scale
    kf = kv.k.reshape(L, P * pg, H, D)
    vf = kv.v.reshape(L, P * pg, H, D)
    kf = kf.at[layer_idx, flat].set(k_new.reshape(-1, H, D).astype(kv.k.dtype))
    vf = vf.at[layer_idx, flat].set(v_new.reshape(-1, H, D).astype(kv.v.dtype))
    return KVPages(k=kf.reshape(L, P, pg, H, D), v=vf.reshape(L, P, pg, H, D),
                   k_scale=k_scale, v_scale=v_scale)


def write_kv_rows(pool: jax.Array, layer_idx: jax.Array, new: jax.Array,
                  starts: jax.Array) -> jax.Array:
    """write_kv for ONE pool allocated with merged rows (``[L, P, page *
    H, D]``, ModelConfig.pool_rows_merged): window w of ``new`` [W, rows,
    D] goes to rows ``starts[w] ..`` of layer ``layer_idx``'s pages laid
    end to end. A window is one token's ``[H, D]`` (a decode step) or one
    page's ``[page * H, D]`` (a prefill chunk: a sixteenth of the
    windows, each a whole tile; the chip runs a scatter a window at a
    time)."""
    L, P, R, D = pool.shape
    at = starts.reshape(-1)
    idx = jnp.stack([jnp.broadcast_to(layer_idx, at.shape), at], axis=1)
    dims = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2), inserted_window_dims=(0,),
        scatter_dims_to_operand_dims=(0, 1))
    flat = jax.lax.scatter(pool.reshape(L, P * R, D), idx.astype(jnp.int32),
                           new.astype(pool.dtype), dims)
    return flat.reshape(L, P, R, D)


def decode_write_path(model_cfg: ModelConfig, pallas: bool) -> str:
    """How a decode step's K / V reach the pool, fixed when its program is
    built: "kernel" (kernels/kv_rows_write.py: one call a layer, every
    lane's copy in flight together) where the pool has merged rows and
    the Pallas backend reads it, else "scatter" (``write_kv_rows`` /
    ``write_kv``: the kernel's reference, and every other write's path).
    /healthz ``device.kv_decode_write`` and the [autosize] line say it."""
    return "kernel" if model_cfg.pool_rows_merged and pallas else "scatter"


def kda_tail_step_path(model_cfg: ModelConfig, pallas: bool) -> str:
    """How a decode step passes a delta-rule layer's convolution, fixed
    when its program is built ("" for a model with no such layer):
    "kernel" (kernels/delta_rule.kda_tail_step: each lane's tail read,
    used and advanced where it lies) under the Pallas backend, else
    "xla" (a gather of the tails, the taps, a scatter: the kernel's
    reference, and a prefill chunk's path). /healthz
    ``device.kda_tail_step`` and the [autosize] line say it."""
    if model_cfg.state_kind != "kda":
        return ""
    return "kernel" if pallas else "xla"


def page_starts(block_tables: jax.Array, first_pos: jax.Array,
                n_valid: jax.Array, n_pages: int, page_size: int,
                rows: int) -> jax.Array:
    """Row starts [B, n_pages] of the pages a chunk of ``n_pages`` whole
    pages writes from position ``first_pos`` [B] (a multiple of the page
    size) on; a page with no valid token (``n_valid`` [B] of the chunk's
    are) is the trash page."""
    j = jnp.arange(n_pages)[None, :]
    at = jnp.minimum(first_pos[:, None] // page_size + j,
                     block_tables.shape[1] - 1)
    pages = jnp.take_along_axis(block_tables, at, axis=1)
    return jnp.where(j * page_size < n_valid[:, None], pages, 0) * rows


def gather_kv(kv: KVPages, layer_idx: jax.Array,
              block_tables: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Gather each sequence's pages into contiguous
    [B, max_pages*pg, H, head_dim].

    ``d_pool`` is the pool's trailing dim as STORED — head_dim, except
    head_dim/2 for packed-int4 pools (two nibbles per byte; the kernels'
    d_pool convention) — so the gather below is [B, max_pages*pg, H,
    d_pool] until unpack_int4_kv doubles it back to head_dim.
    Quantized pools dequantize after the gather (f32 out — the dense
    attention path computes in f32 anyway)."""
    b, mp = block_tables.shape
    _, _, pg, H, d_pool = kv.k.shape
    k = kv.k[layer_idx][block_tables].reshape(b, mp * pg, H, d_pool)
    v = kv.v[layer_idx][block_tables].reshape(b, mp * pg, H, d_pool)
    if kv.packed_int4:
        k, v = unpack_int4_kv(k), unpack_int4_kv(v)
    if kv.quantized:
        ks = kv.k_scale[layer_idx][block_tables].reshape(b, mp * pg, H)
        vs = kv.v_scale[layer_idx][block_tables].reshape(b, mp * pg, H)
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]
    return k, v


class PageAllocator:
    """Host-side free-list allocator with refcounts (prefix sharing).

    Page 0 is reserved as the trash page and never allocated. The engine's
    admission control (SURVEY.md §5 "Failure detection": OOM-safe admission)
    asks ``can_allocate`` before scheduling a sequence.
    """

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._refs = [0] * num_pages
        self._cached = [False] * num_pages
        # Pages held ONLY by the prefix cache (refs == 1 and cached):
        # reclaimable capacity. Kept as an O(1) counter updated on the
        # engine thread so metrics scrapes from other threads read a
        # GIL-atomic int instead of iterating a mutating dict.
        self.evictable_count = 0
        # Optional observer fired on every evictability flip —
        # (page, became_evictable) — at exactly the points the counter
        # moves. The prefix cache uses it to keep an evictable-ordered
        # structure, so evict() pops victims in O(evicted) instead of
        # scanning the whole (mostly share-pinned) LRU table.
        self.on_evictable = None
        # Lifetime alloc/free churn counters, exported by telemetry as
        # tpu_inf_kv_page_{allocs,frees}_total (read-through, so the
        # allocator itself never imports the metrics layer). Plain ints:
        # engine-thread writes, GIL-atomic reads from scrape threads.
        self.pages_allocated_total = 0
        self.pages_freed_total = 0
        # The most pages ever out at once (a gauge: how full the pool
        # has been, whenever it is scraped).
        self.peak_in_use = 0

    @property
    def num_free(self) -> int:
        return len(self._free)

    def _flip_evictable(self, page: int, up: bool) -> None:
        self.evictable_count += 1 if up else -1
        if self.on_evictable is not None:
            self.on_evictable(page, up)

    def mark_cached(self, page: int) -> None:
        """Flag a page as prefix-cache-held (cache owns one of its refs)."""
        assert self._refs[page] > 0 and not self._cached[page]
        self._cached[page] = True
        if self._refs[page] == 1:
            self._flip_evictable(page, True)

    def unmark_cached(self, page: int) -> None:
        assert self._cached[page]
        self._cached[page] = False
        if self._refs[page] == 1:
            self._flip_evictable(page, False)

    def can_allocate(self, n: int) -> bool:
        return len(self._free) >= n

    def allocate(self, n: int = 1) -> List[int]:
        if len(self._free) < n:
            raise MemoryError(f"KV pool exhausted: need {n}, have {len(self._free)}")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        self.pages_allocated_total += n
        self.peak_in_use = max(self.peak_in_use,
                               self.num_pages - 1 - len(self._free))
        return pages

    def share(self, page: int) -> int:
        """Increment refcount for a prefix-shared page."""
        assert self._refs[page] > 0
        self._refs[page] += 1
        if self._cached[page] and self._refs[page] == 2:
            self._flip_evictable(page, False)  # no longer sole-referenced
        return page

    def refcount(self, page: int) -> int:
        return self._refs[page]

    def free(self, pages: List[int]) -> None:
        for p in pages:
            if p == 0:
                continue
            assert self._refs[p] > 0, f"double free of page {p}"
            self._refs[p] -= 1
            if self._refs[p] == 0:
                self._free.append(p)
                self.pages_freed_total += 1
            elif self._refs[p] == 1 and self._cached[p]:
                self._flip_evictable(p, True)  # cache is now sole holder


def pages_needed(n_tokens: int, page_size: int,
                 already: int = 0) -> int:
    """Pages to add so a sequence of ``already`` tokens can hold n_tokens more."""
    total = -(-(already + n_tokens) // page_size)
    have = -(-already // page_size)
    return max(0, total - have)


# ---------------------------------------------------------------------------
# Host tier: device<->host page copies (tiered KV cache, README "Tiered
# KV cache"). Evicted prefix-cache pages demote to host RAM instead of
# being dropped, and promote back into freshly allocated device pages
# when a returning prompt needs them — device<->host copies are cheap
# relative to re-prefilling the tokens they hold.
# ---------------------------------------------------------------------------


class HostKVPage(NamedTuple):
    """Host copy of ONE pool page, in the pool's stored layout: k/v are
    ``[L, page_size, Hkv, d_pool]`` in the pool dtype (bf16, int8 codes,
    or uint8 nibble-packed int4 — the copy is layout-agnostic, so every
    quantization mode round-trips bit-exactly), scales ``[L, page_size,
    Hkv]`` f32 or None for unquantized pools."""

    k: np.ndarray
    v: np.ndarray
    k_scale: Optional[np.ndarray] = None
    v_scale: Optional[np.ndarray] = None

    @property
    def nbytes(self) -> int:
        n = self.k.nbytes + self.v.nbytes
        if self.k_scale is not None:
            n += self.k_scale.nbytes + self.v_scale.nbytes
        return n


# Fixed gather/scatter width: every swap pads its page-index vector to
# a multiple of this and runs in SWAP_CHUNK-page groups, so XLA compiles
# exactly ONE gather and ONE scatter graph per pool dtype — a variable
# width would pay a fresh compile mid-serving the first time each batch
# size appears (pad slots target page 0, the trash page).
SWAP_CHUNK = 8


def _chunk_indices(pages: List[int]):
    """Yield SWAP_CHUNK-wide int32 index arrays covering ``pages``,
    zero-padded (trash page) at the tail."""
    for at in range(0, len(pages), SWAP_CHUNK):
        group = pages[at:at + SWAP_CHUNK]
        idx = np.zeros((SWAP_CHUNK,), np.int32)
        idx[:len(group)] = group
        yield len(group), idx


def _gather_pool(pool: jax.Array, idx: jax.Array) -> jax.Array:
    """Page gather for the device->host copy: pool[:, idx] (one named
    program per pool shape, not an anonymous gather per slice)."""
    return pool[:, idx]


_offload_jit = jax.jit(named_program("tpu_inf_kv_offload", _gather_pool))


def offload_pages(kv: KVPages, pages: List[int]) -> List[HostKVPage]:
    """Copy ``pages`` out of the device pool into host memory.

    All chunk gathers are dispatched first and fetched with ONE
    device_get (one stream sync for the whole batch), then split per
    page so each HostKVPage owns its bytes. Blocks until any in-flight
    dispatch that last donated the pool has settled — correct by
    construction, and the eviction path that calls this was about to
    reuse the pages anyway."""
    n = len(pages)
    if n == 0:
        return []
    chunks = []
    for count, idx_np in _chunk_indices(pages):
        idx = jnp.asarray(idx_np)
        arrs = [_offload_jit(kv.k, idx), _offload_jit(kv.v, idx)]
        if kv.quantized:
            arrs += [_offload_jit(kv.k_scale, idx),
                     _offload_jit(kv.v_scale, idx)]
        chunks.append((count, arrs))
    host = jax.device_get([arrs for _, arrs in chunks])
    out: List[HostKVPage] = []
    for (count, _), fetched in zip(chunks, host):
        k, v = fetched[0], fetched[1]
        ks, vs = (fetched[2], fetched[3]) if kv.quantized else (None, None)
        # .copy(): the per-page slices must not pin the padded buffer.
        out.extend(
            HostKVPage(k[:, i].copy(), v[:, i].copy(),
                       ks[:, i].copy() if ks is not None else None,
                       vs[:, i].copy() if vs is not None else None)
            for i in range(count))
    return out


def _scatter_pool(pool: jax.Array, idx: jax.Array,
                  data: jax.Array) -> jax.Array:
    """In-place (donated) page scatter: pool[:, idx] = data. Padding rows
    target page 0 (trash), so duplicate trash indices are harmless."""
    return pool.at[:, idx].set(data)


_restore_jit = jax.jit(named_program("tpu_inf_kv_restore", _scatter_pool),
                       donate_argnums=(0,))


def restore_pages(kv: KVPages, pages: List[int],
                  host_pages: List[HostKVPage]) -> KVPages:
    """Scatter host page copies back into the device pool at freshly
    allocated page ids. Non-blocking: the scatters are dispatched async
    (donated pool, same stream), so a following prefill chains behind
    them on device and decode lanes staged through the dispatch-ahead
    pipeline never stall on the swap-in."""
    n = len(pages)
    if n == 0:
        return kv
    assert n == len(host_pages)
    k, v = kv.k, kv.v
    k_scale, v_scale = kv.k_scale, kv.v_scale
    at = 0
    for count, idx_np in _chunk_indices(pages):
        group = host_pages[at:at + count]
        at += count
        idx = jnp.asarray(idx_np)

        def _bulk(host_attr, pool):
            first = getattr(group[0], host_attr)
            data = np.zeros((first.shape[0], SWAP_CHUNK) + first.shape[1:],
                            first.dtype)
            for i, hp in enumerate(group):
                data[:, i] = getattr(hp, host_attr)
            return _restore_jit(pool, idx, jnp.asarray(data))

        k = _bulk("k", k)
        v = _bulk("v", v)
        if kv.quantized:
            k_scale = _bulk("k_scale", k_scale)
            v_scale = _bulk("v_scale", v_scale)
    return KVPages(k=k, v=v, k_scale=k_scale, v_scale=v_scale)


class HostPagePool:
    """Capacity accounting for the host-RAM KV tier (the actual page
    bytes live in the prefix cache's host-tier table; this tracks how
    many pages they may occupy and the lifetime churn counters exported
    by telemetry). Host side only — no device state."""

    def __init__(self, capacity_pages: int):
        self.capacity = max(0, int(capacity_pages))
        self.used = 0
        self.bytes_resident = 0
        # Lifetime churn (read-through telemetry counters).
        self.offloaded_total = 0          # pages demoted device -> host
        self.restored_total = 0           # pages promoted host -> device
        self.evicted_total = 0            # second-tier (host LRU) drops
        self.imported_total = 0           # pages migrated in (fleet drain)
        self.offload_bytes_total = 0
        self.restore_bytes_total = 0
        self.import_bytes_total = 0
        # Cumulative host wall spent in device<->host swap batches
        # (engine-reported), per direction — the tier's total swap cost
        # without histogram math, surfaced in /healthz host_cache.
        self.swap_out_s_total = 0.0
        self.swap_in_s_total = 0.0

    def note_swap_wall(self, direction: str, seconds: float) -> None:
        """Accumulate one swap batch's host wall ("out" = demote
        device->host, "in" = promote host->device)."""
        if direction == "out":
            self.swap_out_s_total += seconds
        else:
            self.swap_in_s_total += seconds

    def can_hold(self, n: int = 1) -> bool:
        return self.used + n <= self.capacity

    @property
    def free(self) -> int:
        return self.capacity - self.used

    def note_offload(self, nbytes: int) -> None:
        self.used += 1
        self.bytes_resident += nbytes
        self.offloaded_total += 1
        self.offload_bytes_total += nbytes

    def note_restore(self, nbytes: int) -> None:
        self.used -= 1
        self.bytes_resident -= nbytes
        self.restored_total += 1
        self.restore_bytes_total += nbytes

    def note_evict(self, nbytes: int) -> None:
        self.used -= 1
        self.bytes_resident -= nbytes
        self.evicted_total += 1

    def note_import(self, nbytes: int) -> None:
        """A page migrated IN from another replica's drain export (fleet
        KV migration): occupies capacity like a demote, but counted
        separately — imports are warmth received, not local churn."""
        self.used += 1
        self.bytes_resident += nbytes
        self.imported_total += 1
        self.import_bytes_total += nbytes

    def readmit(self, nbytes: int) -> bool:
        """Undo one note_restore for an entry a failed swap-in returns:
        reverses the restore counters, then re-admits the entry IF the
        capacity an intervening demote may have claimed still allows it
        (False = caller must drop the entry; the RAM cap always wins)."""
        self.restored_total -= 1
        self.restore_bytes_total -= nbytes
        if not self.can_hold(1):
            self.evicted_total += 1
            return False
        self.used += 1
        self.bytes_resident += nbytes
        return True


# ---------------------------------------------------------------------------
# Migration wire format (README "Process fleet"): HostKVPage batches
# serialized for the fleet's drain-time KV migration channel. The layout
# is the host tier's stored layout verbatim — pool-dtype k/v blocks plus
# optional f32 scales — so any kv_quant mode round-trips bit-exactly and
# an imported page is indistinguishable from a locally demoted one.
# ---------------------------------------------------------------------------


def _np_dtype(name: str) -> np.dtype:
    """np.dtype by name, reaching into ml_dtypes for bfloat16 (numpy
    only knows it once ml_dtypes registered it — jax imports do that,
    but a standalone deserializer must not rely on import order)."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def serialize_host_pages_parts(pages: List[HostKVPage]) -> List[bytes]:
    """The blob of :func:`serialize_host_pages` as its constituent
    buffers — ``[u32 header_len + json header, page buffers...]`` in
    stream order. The zero-copy plane writes these straight into an
    arena slab (RegionWriter.alloc_parts) so the payload is copied
    exactly once, into shared memory; the relay plane joins them into
    one frame blob. The embedded digest is chained across the parts —
    no intermediate body concatenation on either plane."""
    import json
    import struct

    if not pages:
        return [struct.pack(">I", 2) + b"{}"]
    first = pages[0]
    meta = {
        "n": len(pages),
        "k_dtype": np.dtype(first.k.dtype).name,
        "k_shape": list(first.k.shape),
        "scaled": first.k_scale is not None,
    }
    if meta["scaled"]:
        meta["scale_dtype"] = np.dtype(first.k_scale.dtype).name
        meta["scale_shape"] = list(first.k_scale.shape)
    parts = []
    for hp in pages:
        parts.append(np.ascontiguousarray(hp.k).tobytes())
        parts.append(np.ascontiguousarray(hp.v).tobytes())
        if meta["scaled"]:
            parts.append(np.ascontiguousarray(hp.k_scale).tobytes())
            parts.append(np.ascontiguousarray(hp.v_scale).tobytes())
    # Per-blob digest (README "Failure model"): CRC-32C over the raw
    # page bytes, carried inside the header so every adopt/import path
    # can verify end-to-end — across processes, sockets, and any future
    # storage hop — independent of the frame-level checksum.
    crc = 0
    for p in parts:
        crc = integrity.crc32c(p, crc)
    meta["crc32c"] = crc
    header = json.dumps(meta).encode()
    return [struct.pack(">I", len(header)) + header] + parts


def serialize_host_pages(pages: List[HostKVPage]) -> bytes:
    """Pack host page copies into one binary blob:
    ``[u32 header_len][json header][raw k|v|k_scale|v_scale per page]``.
    All pages in a batch come from one pool, so shapes/dtypes are
    batch-constant and live once in the header."""
    return b"".join(serialize_host_pages_parts(pages))


def deserialize_host_pages(blob: bytes,
                           copy: bool = True) -> List[HostKVPage]:
    """Inverse of :func:`serialize_host_pages`. Each returned page owns
    its bytes (copies out of the blob), so the caller may drop the blob
    and the pages live independently in the host tier.

    ``copy=False`` returns read-only page views over the blob instead
    (each array's ``.base`` keeps the blob alive) — the one-shot adopt
    path hands them straight to the device restore and never needs an
    owning copy, which at multi-MiB handoff blobs is the difference
    between one memcpy of the payload and two."""
    import json
    import struct

    if len(blob) < 4:
        raise integrity.KVIntegrityError(
            f"KV blob truncated ({len(blob)} bytes)")
    (hlen,) = struct.unpack(">I", blob[:4])
    if 4 + hlen > len(blob):
        raise integrity.KVIntegrityError(
            f"KV blob header overruns blob ({hlen} > {len(blob) - 4})")
    try:
        meta = json.loads(blob[4:4 + hlen].decode())
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise integrity.KVIntegrityError(
            f"KV blob header unparseable: {e}") from None
    if not meta:
        return []
    want = meta.get("crc32c")
    if want is not None:
        got = integrity.crc32c(blob[4 + hlen:])
        if got != want:
            raise integrity.KVIntegrityError(
                "KV blob digest mismatch "
                f"(want 0x{want:08x} got 0x{got:08x})")
    k_dtype = _np_dtype(meta["k_dtype"])
    k_shape = tuple(meta["k_shape"])
    k_size = int(np.prod(k_shape)) * k_dtype.itemsize
    scaled = meta.get("scaled", False)
    if scaled:
        s_dtype = _np_dtype(meta["scale_dtype"])
        s_shape = tuple(meta["scale_shape"])
        s_size = int(np.prod(s_shape)) * s_dtype.itemsize
    at = 4 + hlen
    out: List[HostKVPage] = []

    def take(n, dtype, shape):
        nonlocal at
        arr = np.frombuffer(blob, dtype=dtype, count=int(np.prod(shape)),
                            offset=at).reshape(shape)
        if copy:
            arr = arr.copy()
        at += n
        return arr

    for _ in range(meta["n"]):
        k = take(k_size, k_dtype, k_shape)
        v = take(k_size, k_dtype, k_shape)
        ks = vs = None
        if scaled:
            ks = take(s_size, s_dtype, s_shape)
            vs = take(s_size, s_dtype, s_shape)
        out.append(HostKVPage(k, v, ks, vs))
    return out


def verify_host_pages_blob(blob: bytes) -> Optional[str]:
    """Structural + digest check WITHOUT materializing pages — the
    router's cheap gate before forwarding a handoff/migrate blob to a
    destination worker. Returns None when sound, else the rejection
    reason. A pre-digest blob (no ``crc32c`` in its header) passes the
    structure check only."""
    import json
    import struct

    if not blob:
        return None
    if len(blob) < 4:
        return f"KV blob truncated ({len(blob)} bytes)"
    (hlen,) = struct.unpack(">I", blob[:4])
    if 4 + hlen > len(blob):
        return f"KV blob header overruns blob ({hlen} > {len(blob) - 4})"
    try:
        meta = json.loads(blob[4:4 + hlen].decode())
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        return f"KV blob header unparseable: {e}"
    want = meta.get("crc32c") if meta else None
    if want is not None:
        got = integrity.crc32c(blob[4 + hlen:])
        if got != want:
            return ("KV blob digest mismatch "
                    f"(want 0x{want:08x} got 0x{got:08x})")
    return None
