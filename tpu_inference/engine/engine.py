"""The inference engine: bucketed prefill + batched decode as two XLA graphs.

TPU-first structure (SURVEY.md §7, hard parts 2-3):
- **Two compiled graphs**, not one: ``prefill`` (one sequence, prompt padded
  to a static bucket) and ``decode`` (fixed max-batch, one token per active
  slot). Every shape is static; prompt-length variation is handled by a small
  set of buckets, batch variation by validity masks — zero recompiles in
  steady state.
- **KV buffers are donated** (``donate_argnums``) so the pool is updated in
  place in HBM instead of being double-buffered.
- Attention inside the graphs goes through the injected AttentionFn: the
  dense gather-based reference here, or the Pallas paged kernel
  (kernels/paged_attention.py) on TPU.
- The host never blocks per token on device_get of logits: decode returns
  sampled token ids ([B] int32), the only per-step host transfer.

The reference repo has no engine (it load-tests an external server,
SURVEY.md §0); capability parity is defined by BASELINE.json configs 1-4.
"""

from __future__ import annotations

import collections
import dataclasses
import random as _chaos_random
import threading
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_inference import telemetry
from tpu_inference.config import (EngineConfig, ModelConfig,
                                  validate_spec_config)
from tpu_inference.engine import kv_cache as kvc
from tpu_inference.engine import staging
from tpu_inference.engine.autosize import resolve_page_size
from tpu_inference.engine.kv_cache import KVPages, PageAllocator
from tpu_inference.engine.sampling import (
    PENALTY_WINDOW,
    SamplingParams,
    roll_window,
    sample,
)
from tpu_inference.engine.speculative import NGRAM_SCAN_CAP, ngram_propose
from tpu_inference.models.quant import store_transposed
from tpu_inference.models.registry import (build_model, family_fn,
                                          get_model_fns)


def make_latent_attn(cfg: ModelConfig, page_size: int,
                     block_tables: jax.Array, positions: jax.Array,
                     valid: jax.Array, q_offset: jax.Array,
                     kv_len: jax.Array, attn_backend: str = "dense",
                     interpret: bool = False):
    """make_paged_attn for a latent (MLA) pool: the AttentionFn's second
    shape (models/common.py). ``attn(layer, q [B,S,H,R+Dr], entry
    [B,S,R+Dr], None, kv)`` writes the entries into the pool, then
    attends in the absorbed form over the pages — the chunk's own tokens
    and any cached prefix alike — and returns the weighted latents
    [B,S,H,R]. The kernels get the stacked pool and the layer index
    (kernels/mla_attention.py). ``attn.pallas`` / ``attn.interpret`` tell
    the model's expert layer which grouped matmul to run
    (kernels/moe_experts.py), ``attn.valid`` which rows hold a token."""
    from tpu_inference.kernels import mla_attention as mla

    rank, scale = cfg.kv_lora_rank, family_fn(cfg, "softmax_scale")(cfg)
    pallas = attn_backend == "pallas"
    state = None
    if cfg.state_kind:
        # Layers of a state kind beside the latent ones: the lane's
        # STATE SLOT rides behind its block table (make_kind_attn).
        state = PagedState(block_tables[:, -1], valid, q_offset, pallas,
                           interpret)
        block_tables = block_tables[:, :-1]

    def attn(layer_idx, q, entry, v, kv: KVPages):
        del v
        slots = kvc.slot_mapping(block_tables, positions, valid, page_size)
        kv = kvc.write_latent(kv, layer_idx, entry, slots)
        if pallas and q.shape[1] == 1:
            # A lane whose token is padding (an idle lane of the rung)
            # reads nothing: its rows are thrown away.
            out = mla.mla_decode_attention(
                q[:, 0], kv.k, layer_idx, block_tables,
                jnp.where(valid[:, 0], kv_len, 0), rank=rank, scale=scale,
                interpret=interpret)[:, None]
        elif pallas:
            out = mla.mla_prefill_attention(
                q, kv.k, layer_idx, block_tables, kv_len, q_offset,
                rank=rank, scale=scale, interpret=interpret)
        else:
            out = mla.mla_attention_dense(
                q, kv.k, layer_idx, block_tables, kv_len, q_offset,
                rank=rank, scale=scale)
        return out, kv

    attn.pallas, attn.interpret, attn.valid = pallas, interpret, valid
    if state is not None:
        attn.state = state
    return attn


def make_paged_attn(cfg: ModelConfig, page_size: int, block_tables: jax.Array,
                    positions: jax.Array, valid: jax.Array,
                    q_offset: jax.Array, kv_len: jax.Array,
                    attn_backend: str = "dense", mesh: Optional[Any] = None,
                    sp_mode: Optional[str] = None, interpret: bool = False,
                    sliding_window: Optional[int] = None,
                    write: bool = True, **kind_args):
    """AttentionFn that writes new K/V into the paged pool then attends.

    block_tables [B, MP]; positions/valid [B, S]; q_offset/kv_len [B].
    ``sliding_window`` overrides ``cfg.sliding_window`` (one kind of a
    model whose layers differ: make_kind_attn, which also takes
    ``kind_args``). ``write=False``: a kind that READS another's pool
    (it is handed no K / V and scatters nothing).

    The Pallas kernels are handed the STACKED pool ``kv.k`` / ``kv.v``
    ``[L, P, page, Hkv, D]`` whole, with ``layer_idx`` as a scalar
    operand: the layer is picked inside the kernel's page DMAs.
    ``layer_idx`` is the traced index of the model's scan over layers, so
    ``kv.k[layer_idx]`` would be a dynamic_slice that XLA materializes —
    a copy of one layer's whole pool in front of every kernel call.
    (A quantized pool's scales, 1% of its bytes, are still sliced per
    layer: ``_paged_call``.)

    With a mesh, the Pallas kernels are shard_map-wrapped over the
    ``tp`` axis: q shards on the query-head dim and the KV pool on the
    kv-head dim (parallel/shardings.py keeps them aligned), so each chip
    streams only its own head shard's pages — attention output is
    head-local and needs no collective; the following wo matmul's
    all-reduce (placed by GSPMD) combines chips as usual.

    ``sp_mode``: sequence-parallel prefill — the chunk's self-attention
    runs sequence-sharded over the mesh's ``sp`` axis, composed with tp
    head sharding. "ring" rotates K/V shards by ppermute over ICI
    (kernels/ring_attention.py, O((S/n)²) memory); "ulysses" re-shards
    via two all-to-alls and attends full-sequence per head group
    (kernels/ulysses_attention.py, fewer collective hops, needs head
    counts divisible by sp). Valid only for a fresh full-prompt chunk
    (no cached prefix); the engine routes eligible prefills here. Both
    kernels apply ``cfg.sliding_window`` when set, so SWA models (Mistral)
    compose with sequence parallelism.

    ``interpret``: run the Pallas kernels in interpret mode (tests on the
    CPU); the serving path compiles them (False).
    """
    from tpu_inference.models.common import dense_causal_attention

    if cfg.latent_dim:
        return make_latent_attn(cfg, page_size, block_tables, positions,
                                valid, q_offset, kv_len,
                                attn_backend=attn_backend,
                                interpret=interpret)
    if cfg.layer_types and sliding_window is None:
        return make_kind_attn(cfg, page_size, block_tables, positions, valid,
                              q_offset, kv_len, attn_backend=attn_backend,
                              interpret=interpret, **kind_args)
    window = cfg.sliding_window if sliding_window is None else sliding_window

    def _sp_prefill(q, k, v):
        from functools import partial as _partial

        from jax.sharding import PartitionSpec as P

        if sp_mode == "ulysses":
            from tpu_inference.kernels.ulysses_attention import (
                ulysses_attention_local as sp_local)
        else:
            from tpu_inference.kernels.ring_attention import (
                ring_attention_local as sp_local)

        spec = P(None, "sp", "tp", None)       # [B, S, H, D]: seq × heads
        return jax.shard_map(
            _partial(sp_local, axis_name="sp",
                     sliding_window=window),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)

    def _paged_call(kernel, kv: KVPages, layer_idx, lead_args, lead_specs,
                    head_spec):
        """Call a paged kernel on the STACKED pool with the layer index
        as an operand: the kernel addresses layer ``layer_idx``'s pages
        itself, so no ``kv.k[layer_idx]`` slice — a copy of one layer's
        whole pool, since a pallas_call operand is a buffer of its own —
        is made in front of it. ``kernel(*lead, layer, k, v[, ks, vs])``.
        The scales of a quantized pool ARE sliced here (1% of the codes'
        bytes; the stacked scale pool's device layout is not the
        row-major one a kernel operand needs, so XLA would re-lay-out all
        of it: kernels/paged_attention.py). Under a mesh the call is
        shard_mapped over tp: q/out shard on heads (``head_spec``), the
        pools on the kv-head dim."""
        args = [*lead_args, layer_idx, kv.k, kv.v]
        if kv.quantized:
            args += [kv.k_scale[layer_idx], kv.v_scale[layer_idx]]
        if mesh is None:
            return kernel(*args)
        from jax.sharding import PartitionSpec as P
        pool_p = P(None, None, None, "tp", None)       # [L, P, pg, Hkv, D]
        specs = [head_spec, *lead_specs, P(), pool_p, pool_p]
        if kv.quantized:
            scale_p = P(None, None, "tp")              # [P, pg, Hkv]
            specs += [scale_p, scale_p]
        return jax.shard_map(
            kernel, mesh=mesh, in_specs=tuple(specs), out_specs=head_spec,
            check_vma=False)(*args)

    def _pallas_decode(q1, kv: KVPages, layer_idx):
        from jax.sharding import PartitionSpec as P

        from tpu_inference.kernels.paged_attention import paged_attention

        def kernel(q_, bt_, kl_, layer_, k_, v_, ks_=None, vs_=None):
            return paged_attention(q_, k_, v_, layer_, bt_, kl_, ks_, vs_,
                                   interpret=interpret,
                                   sliding_window=window)

        # A lane whose token is padding (an idle lane of the rung) reads
        # nothing: its rows are thrown away.
        return _paged_call(kernel, kv, layer_idx,
                           lead_args=(q1, block_tables,
                                      jnp.where(valid[:, 0], kv_len, 0)),
                           lead_specs=(P(), P()),
                           head_spec=P(None, "tp", None))   # [B, H*, D]

    def _pallas_prefill(q, kv: KVPages, layer_idx):
        from jax.sharding import PartitionSpec as P

        from tpu_inference.kernels.prefill_attention import (
            paged_prefill_attention)

        def kernel(q_, bt_, kl_, qo_, layer_, k_, v_, ks_=None, vs_=None):
            return paged_prefill_attention(
                q_, k_, v_, layer_, bt_, kl_, qo_, ks_, vs_,
                interpret=interpret, sliding_window=window)

        return _paged_call(kernel, kv, layer_idx,
                           lead_args=(q, block_tables, kv_len, q_offset),
                           lead_specs=(P(), P(), P()),
                           head_spec=P(None, None, "tp", None))  # [B,S,H*,D]

    def attn(layer_idx, q, k, v, kv: KVPages):
        if write:
            slots = kvc.slot_mapping(block_tables, positions, valid,
                                     page_size)
            kv = kvc.write_kv(kv, layer_idx, k, v, slots)
        if attn_backend == "pallas" and q.shape[1] == 1:
            return _pallas_decode(q[:, 0], kv, layer_idx)[:, None], kv
        if sp_mode and q.shape[1] > 1:
            # Fresh full-prompt chunk: attention is pure self-attention
            # over (q, k, v) — no need to read back through the pool.
            return _sp_prefill(q, k, v), kv
        if attn_backend == "pallas" and q.shape[1] > 1:
            # Flash prefill over pool pages: O(S·page) memory, no gather
            # (window-aware when cfg.sliding_window is set: each query
            # block touches O(block+window) pages).
            return _pallas_prefill(q, kv, layer_idx), kv
        k_all, v_all = kvc.gather_kv(kv, layer_idx, block_tables)
        out = dense_causal_attention(q, k_all, v_all, q_offset=q_offset,
                                     kv_len=kv_len,
                                     sliding_window=window)
        return out, kv

    return attn


class PagedState:
    """``attn.state`` over the state slots (kv_cache.KVPages.conv /
    .ssm_h): what a layer of a state kind (config.STATE_KINDS) reads a
    lane's state through and writes it back through. ONE rule for
    everything that must not advance a state, said here: a position that
    is not ``valid`` (a padded position of a prefill, a masked step of a
    fused decode call, an idle lane) advances nothing — the model's scan
    reads ``dt = 0`` there, the delta rule a decay of 1 and a beta of 0,
    and both keep the conv tail (``lens`` counts a lane's valid
    positions) — and a lane with NO valid position writes to slot 0, the
    trash slot, because the row it was staged with may be stale and its
    slot somebody else's by now. A lane whose call starts at position 0
    (a prompt's first chunk, a recompute-resume's) reads zeros.

    A state-space layer (models/sambay.py) calls ``read`` / ``scan`` /
    ``write``; a delta-rule layer (models/bailing_hybrid.py) ``tail`` /
    ``put_tail`` for the convolution's (``conv_step`` where a call is
    one token a lane) and ``delta`` for the matrix state; the kernels
    advance both where they lie in the pools."""

    def __init__(self, slots, valid, q_offset, pallas: bool,
                 interpret: bool):
        self.lens = jnp.sum(valid, axis=1).astype(jnp.int32)
        self.slots = slots
        self.slots_w = jnp.where(self.lens > 0, slots, 0)
        self.fresh = q_offset == 0
        self.pallas, self.interpret = pallas, interpret

    def tail(self, layer, kv: KVPages):
        """Each lane's tail [B, taps - 1, channels] (the pool holds a
        delta-rule layer's channels a head a row)."""
        tail = kv.conv[layer, self.slots]
        return jnp.where(~self.fresh[:, None, None],
                         tail.reshape(tail.shape[:2] + (-1,)), 0)

    def put_tail(self, layer, tail, kv: KVPages) -> KVPages:
        return kv._replace(conv=kv.conv.at[layer, self.slots_w].set(
            tail.astype(kv.conv.dtype).reshape(
                tail.shape[:1] + kv.conv.shape[2:])))

    def conv_step(self, layer, qkv, conv_w, kv: KVPages):
        """A delta-rule layer's convolution over ONE token a lane, qkv
        [B, 1, C] -> (x [B, 1, C] float32, kv): the kernel reads, uses
        and advances each lane's tail where it lies in ``kv.conv``
        (kernels/delta_rule.kda_tail_step); off its backend, the
        model's taps over ``tail`` / ``put_tail``."""
        if not self.pallas:
            from tpu_inference.models.bailing_hybrid import conv_taps

            return conv_taps(self, layer, qkv, conv_w, kv)
        from tpu_inference.kernels import delta_rule as dr

        x, pool = dr.kda_tail_step(
            kv.conv, layer, self.slots, self.slots_w, self.lens, self.fresh,
            qkv[:, 0], conv_w, interpret=self.interpret)
        return x[:, None], kv._replace(conv=pool)

    def read(self, layer, kv: KVPages):
        keep = ~self.fresh[:, None, None]
        tail, h = kv.conv[layer, self.slots], kv.ssm_h[layer, self.slots]
        return jnp.where(keep, tail, 0), jnp.where(keep, h, 0.0)

    def write(self, layer, tail, h, kv: KVPages) -> KVPages:
        return kv._replace(
            conv=kv.conv.at[layer, self.slots_w].set(
                tail.astype(kv.conv.dtype)),
            ssm_h=kv.ssm_h.at[layer, self.slots_w].set(h))

    def scan(self, x, dt, b, c, a_t, d_skip, h0):
        """A chunk's selective scan: the kernel, or off the kernel's
        backend the same function as a ``lax.scan``."""
        from tpu_inference.kernels import selective_scan as ss

        if self.pallas:
            return ss.selective_scan(x, dt, b, c, a_t, d_skip, h0,
                                     self.lens, interpret=self.interpret)
        return ss.selective_scan_reference(x, dt, b, c, a_t, d_skip, h0,
                                           self.lens)

    def delta(self, layer, q, k, v, g, beta, kv: KVPages):
        """The delta rule over q, k, g [B, S, H, d], v [B, S, H, d], beta
        [B, S, H] -> (o [B, S, H, d] float32, kv): the kernels advance
        each lane's matrix state in place in ``kv.ssm_h``
        (kernels/delta_rule.py: a chunk in blocks, or one token); off
        their backend the recurrence runs on gathered states."""
        from tpu_inference.kernels import delta_rule as dr

        b, s, h, d = q.shape
        if not self.pallas:
            s0 = jnp.where(self.fresh[:, None, None, None], 0.0,
                           kv.ssm_h[layer, self.slots])
            o, st = dr.kda_recurrence(q, k, v, g, beta, s0, self.lens)
            return o, kv._replace(
                ssm_h=kv.ssm_h.at[layer, self.slots_w].set(st))
        if s == 1:
            flat = lambda a: a.reshape(b, h * d)               # noqa: E731
            o, pool = dr.kda_step(
                kv.ssm_h, layer, self.slots, self.slots_w, flat(q), flat(k),
                flat(v), flat(g), beta[:, 0], n_heads=h,
                interpret=self.interpret)
        else:
            flat = lambda a: a.reshape(b, s, h * d)            # noqa: E731
            o, pool = dr.kda_chunk_prefill(
                kv.ssm_h, layer, self.slots, self.slots_w, self.fresh,
                flat(q), flat(k), flat(v), flat(g), beta, self.lens,
                n_heads=h, interpret=self.interpret)
        return o.reshape(b, s, h, d), kv._replace(ssm_h=pool)


def make_kind_attn(cfg: ModelConfig, page_size: int, block_tables: jax.Array,
                   positions: jax.Array, valid: jax.Array,
                   q_offset: jax.Array, kv_len: jax.Array,
                   attn_backend: str = "dense", interpret: bool = False,
                   cross_at: Optional[jax.Array] = None):
    """make_paged_attn for a model whose layers differ in kind
    (``cfg.layer_types``): one AttentionFn a kind under ``attn.kinds``,
    each over its own pool (``kv.k`` / ``kv.v`` full, ``kv.wk`` /
    ``kv.wv`` window) and its own block table, with the kind's window
    static. ``block_tables`` is [B, 2 * MP]: the full kind's table, then
    the window kind's; where the model has state-space layers one more
    column behind them holds the lane's STATE SLOT, and ``attn.state``
    (PagedState) reads and writes it. The layer index a kind's function
    takes is the layer's place among its kind (its slot in that pool).
    A "cross" kind reads the full kind's table and pool, SLOT 0 of it
    whatever its own place among the cross layers (the one full layer's:
    a place past the pool's one slot is an address outside it, which the
    chip's kernels halt on), and writes nothing; with ``cross_at`` [B] (a prefill that runs the cross layers
    for one position a row) its one query sits at ``q_offset +
    cross_at``. ``attn.pallas`` / ``attn.interpret`` / ``attn.valid`` as
    make_latent_attn's."""
    kinds = cfg.layer_types[:cfg.n_layers]
    stateful = "ssm" in kinds
    mp = (block_tables.shape[1] - stateful) // 2
    pallas = attn_backend == "pallas"

    def of(table, window, pool_k, pool_v, write=True, at=None):
        pos, ok, off, length = positions, valid, q_offset, kv_len
        if at is not None:
            off = q_offset + at
            pos, ok = off[:, None], jnp.ones((off.shape[0], 1), bool)
            length = off + 1
        inner = make_paged_attn(
            cfg, page_size, table, pos, ok, off, length,
            attn_backend=attn_backend, interpret=interpret,
            sliding_window=window,
            write=write and not cfg.pool_rows_merged)

        def kind_attn(slot, q, k, v, kv: KVPages):
            slot = slot if write else 0
            out, sub = inner(slot, q, k, v, KVPages(
                k=getattr(kv, pool_k), v=getattr(kv, pool_v)))
            if not write:
                return out, kv
            return out, kv._replace(**{pool_k: sub.k, pool_v: sub.v})

        def merged_attn(slot, q, k, v, kv: KVPages):
            """The same over pools allocated with merged rows
            (ModelConfig.pool_rows_merged): written here, a window a
            token, and read through a five-dim VIEW (the kernels merge
            the rows again: the two reshapes fold away)."""
            pk, pv = getattr(kv, pool_k), getattr(kv, pool_v)
            slot = slot if write else 0
            if write:
                b, s, h, d = k.shape
                if s % page_size:
                    # (a decode step) a window a token
                    starts = kvc.slot_mapping(table, pos, ok, page_size) * h
                    shape = (b * s, h, d)
                else:
                    # A prefill chunk starts on a page's edge (__init__
                    # holds the buckets to it): a window a PAGE. Rows of
                    # a last page behind the chunk's valid tokens get
                    # padding's K / V; nothing reads a row at or past
                    # kv_len, and the token that comes to sit there
                    # writes it first.
                    starts = kvc.page_starts(
                        table, off, jnp.sum(ok, axis=1), s // page_size,
                        page_size, page_size * h)
                    shape = (b * s // page_size, page_size * h, d)
                if s == 1 and kvc.decode_write_path(cfg, pallas) == "kernel":
                    # One token a lane, so no two windows share a page
                    # (n-gram verification's several tokens a lane would).
                    from tpu_inference.kernels.kv_rows_write import (
                        kv_rows_write)

                    pk, pv = kv_rows_write(
                        pk, pv, slot, k.reshape(shape), v.reshape(shape),
                        starts.reshape(-1), interpret=interpret)
                else:
                    pk = kvc.write_kv_rows(pk, slot, k.reshape(shape), starts)
                    pv = kvc.write_kv_rows(pv, slot, v.reshape(shape), starts)
                kv = kv._replace(**{pool_k: pk, pool_v: pv})
            view = pk.shape[:2] + (page_size, cfg.pool_kv_heads,
                                   cfg.pool_head_dim)
            out, _ = inner(slot, q, None, None, KVPages(
                k=pk.reshape(view), v=pv.reshape(view)))
            return out, kv

        return merged_attn if cfg.pool_rows_merged else kind_attn

    def attn(*_):
        raise TypeError("a stack of mixed kinds calls attn.kinds[kind]")

    attn.kinds = {
        "full": of(block_tables[:, :mp], 0, "k", "v"),
        "window": of(block_tables[:, mp:2 * mp], cfg.sliding_window,
                     "wk", "wv")}
    if "cross" in kinds:
        attn.kinds["cross"] = of(block_tables[:, :mp], 0, "k", "v",
                                 write=False, at=cross_at)
    if stateful:
        attn.state = PagedState(block_tables[:, 2 * mp], valid, q_offset,
                                pallas, interpret)
    attn.pallas, attn.interpret, attn.valid = pallas, interpret, valid
    return attn


# Why each is refused, keyed by what the model is: "latent" (a latent
# pool), "looped" (a looped stack), "kinds" (a stack of mixed kinds with a
# pool a kind), "state" (state-space layers: a state a sequence, which a
# token advances; such a model has kinds too, and this column speaks for
# them), "delta" (delta-rule layers: a matrix state a head a sequence; a
# state kind too, config.STATE_KINDS, with reasons of its own). A model
# can be several at once (delta-rule layers beside a latent pool:
# "latent" and "delta"): every column that speaks for it gives its
# reason (``why_not``).
_WHY_NOT = {
    "tp": {
        "latent": "no param shardings, no sharded latent pool, no expert "
                  "exchange: parallel/shardings.py",
        "looped": "no param shardings for the output norms and the exit "
                  "gate: parallel/shardings.py",
        "kinds": "no param shardings for parameters stacked per kind, no "
                 "sharded per-kind pools, no expert exchange: "
                 "parallel/shardings.py",
        "state": "no param shardings for parameters stacked per kind, no "
                 "sharded per-kind pools or state slots: "
                 "parallel/shardings.py (pipeline stages: "
                 "parallel/pipeline.pp_forward runs the llama family)",
        "delta": "no param shardings for parameters stacked per kind, no "
                 "sharded state slots (a head's matrix state would shard "
                 "with its head): parallel/shardings.py (pipeline stages: "
                 "parallel/pipeline.pp_forward runs the llama family)"},
    "kv_quant": {
        "latent": "the latent pool is stored in the model dtype",
        "looped": "no test holds a quantized pool of pass x layer slots "
                  "to the reference",
        "kinds": "per-kind pools are stored in the model dtype: "
                 "kv_cache.alloc_kind_pages",
        "state": "per-kind pools are stored in the model dtype and the "
                 "scan's state in float32: kv_cache.alloc_kind_pages",
        "delta": "the delta rule's matrix state is float32 and its "
                 "convolution tail the model dtype: "
                 "kv_cache.alloc_state_slots"},
    "host": {
        "latent": "offload / restore / serialize assume K and V pools",
        "looped": "a page of pass x layer slots is tens of MiB to copy "
                  "out at every eviction, untested",
        "kinds": "offload / restore copy one pool; a page of the full "
                 "kind has no window-kind twin to restore",
        "state": "offload / restore copy one pool's pages; a restored "
                 "prefix would need the scan's state at its end, which "
                 "no page holds",
        "delta": "a restored prefix would need the delta rule's state at "
                 "its end, which no page holds"},
    "role": {
        "latent": "P/D handoff serializes K and V pages",
        "looped": "P/D handoff of pages of pass x layer slots is untested",
        "kinds": "P/D handoff serializes one pool's pages, not a table a "
                 "kind",
        "state": "P/D handoff serializes one pool's pages: neither a "
                 "table a kind nor the sequence's state slot "
                 "(export_sequence_kv* / adopt_sequence refuse too)",
        "delta": "a handed-off sequence would need its state slot (a "
                 "matrix a head a delta-rule layer) beside its latent "
                 "pages: nothing exports or adopts it "
                 "(export_sequence_kv* / adopt_sequence refuse too)"},
    "quant": {
        "latent": "the grouped expert kernels take bf16 or int8 weights",
        "kinds": "the grouped expert kernels take bf16 or int8 weights",
        "state": "no test holds int4 projections around a recurrence to "
                 "the reference (int8 quantizes the projections and "
                 "leaves the scan's own parameters, A_log, D, the dt "
                 "and conv weights, as they are)",
        "delta": "no test holds int4 projections around a recurrence to "
                 "the reference (int8 quantizes the projections and "
                 "leaves the delta rule's own parameters, A_log, dt_bias, "
                 "the conv, beta and gate weights, as they are)"},
    "spec": {
        "state": "a rejected draft would already have advanced the "
                 "sequence's state, and no snapshot is kept to go back "
                 "to",
        "delta": "a rejected draft would already have advanced the "
                 "sequence's matrix states, and no snapshot is kept to "
                 "go back to"},
    "hybrid": {
        "state": "a prefill chunk and the decode lanes in one program "
                 "both scatter into the state slots: untested",
        "delta": "a prefill chunk and the decode lanes in one program "
                 "both advance the state slots in place: untested"},
    # Not an error: the cache is left off with this line (a one-kind
    # window model does the same, __init__ below).
    "prefix": {
        "kinds": "a hit would need the full kind's pages of the prefix "
                 "AND the window kind's last sliding_window tokens before "
                 "its end, which are released while a sequence runs",
        "state": "a hit would need a snapshot of every state-space "
                 "layer's state at the prefix's end (and the window "
                 "kind's last sliding_window tokens before it): none is "
                 "kept",
        "delta": "a hit would need a snapshot of every delta-rule "
                 "layer's matrix state and convolution tail at the "
                 "prefix's end: none is kept"},
}

_WHAT_IT_IS = {"latent": "latent attention", "looped": "a looped stack",
               "kinds": "layers of mixed kinds",
               "state": "state-space layers", "delta": "delta-rule layers"}


def model_columns(model_cfg: ModelConfig) -> tuple:
    """The columns of ``_WHY_NOT`` that speak for this model (none: a
    plain stack, nothing is refused for what it is)."""
    kinds = model_cfg.layer_types[:model_cfg.n_layers]
    return tuple(c for c, on in (
        ("latent", model_cfg.latent_dim), ("looped", model_cfg.loop_steps > 1),
        ("state", model_cfg.state_kind == "ssm"),
        ("delta", model_cfg.state_kind == "kda"),
        ("kinds", kinds and not model_cfg.state_kind)) if on)


def model_is(model_cfg: ModelConfig) -> Optional[str]:
    """``model_columns`` in words: "state", "latent + delta", ... (None:
    a plain stack)."""
    return " + ".join(model_columns(model_cfg)) or None


def why_not(what: str, model_cfg: ModelConfig) -> Optional[str]:
    """Row ``what`` of ``_WHY_NOT`` for this model: the reason of every
    column that speaks for it and has one (None: no column does)."""
    return "; ".join(_WHY_NOT[what][c] for c in model_columns(model_cfg)
                     if c in _WHY_NOT[what]) or None


def _refuse_unsupported(model_cfg: ModelConfig, engine_cfg: EngineConfig,
                        mesh) -> None:
    """What a latent pool (the latent-attention / routed-expert family),
    a looped stack (``loop_steps`` > 1), a stack of mixed kinds
    (``layer_types``) or one with state-space layers does not run yet,
    said at construction and not at the first request."""
    what = []
    spec = engine_cfg.num_speculative_tokens > 0
    if engine_cfg.keep_logits:
        # Whatever the model is: the programs that file rows are the
        # prefill and the fused-K decode.
        if spec:
            what.append("speculative decoding (the speculative rounds "
                        "sample inside programs that keep no rows)")
        if engine_cfg.role != "mixed":
            what.append(f"role={engine_cfg.role!r} (a handed-off sequence "
                        "carries no kept rows)")
        if what:
            raise ValueError("keep_logits does not support: "
                             + "; ".join(what))
    is_ = model_columns(model_cfg)
    if not is_:
        return
    why = {k: why_not(k, model_cfg) for k in _WHY_NOT}
    if model_cfg.early_exit_threshold < 1.0:
        what.append(f"early_exit_threshold={model_cfg.early_exit_threshold}"
                    " < 1 (per-token depth: every token runs every pass)")
    if mesh is not None and any(int(mesh.shape.get(ax, 1)) > 1
                                for ax in ("tp", "sp", "pp")):
        what.append(f"tp / sp / pp > 1 ({why['tp']})"
                    if model_cfg.state_kind else f"tp / sp > 1 ({why['tp']})")
    if engine_cfg.kv_quant != "none":
        what.append(f"kv_quant={engine_cfg.kv_quant!r} ({why['kv_quant']})")
    if spec:
        what.append("speculative decoding"
                    + (f" ({why['spec']})" if why["spec"] else ""))
    if engine_cfg.host_cache_pages:
        what.append(f"the host KV tier (host_cache_pages > 0: {why['host']})")
    if why["quant"] and engine_cfg.quant == "int4":
        what.append(f"quant='int4' ({why['quant']})")
    if why["hybrid"] and engine_cfg.hybrid_prefill:
        what.append(f"hybrid_prefill ({why['hybrid']})")
    if engine_cfg.role != "mixed":
        what.append(f"role={engine_cfg.role!r} ({why['role']})")
    if what:
        raise ValueError(
            f"{model_cfg.name} ({' and '.join(_WHAT_IT_IS[c] for c in is_)}) "
            "does not support: " + "; ".join(what))


def _extend(result: Dict[int, List[int]], more: Dict[int, List[int]]
            ) -> Dict[int, List[int]]:
    """Append ``more``'s per-request tokens to ``result``'s, in place."""
    for rid, toks in more.items():
        result.setdefault(rid, []).extend(toks)
    return result


class ChaosStepError(RuntimeError):
    """Injected engine-step failure (EngineConfig.chaos_step_failure_rate).

    A distinct type so supervision tests can tell injected faults from
    real engine bugs; the scheduler treats both identically (any step
    exception feeds the replica health machine)."""


@dataclasses.dataclass
class Sequence:
    """Host-side state for one running sequence (one decode slot)."""

    request_id: int
    prompt_tokens: List[int]
    max_new_tokens: int
    temperature: float = 0.0
    top_p: float = 1.0
    top_k: Optional[int] = None            # None = engine default
    seed: Optional[int] = None             # None = engine-global key stream
    # Ollama repetition penalty (1.0 = off; window clamps to
    # sampling.PENALTY_WINDOW). Ignored under speculative decoding
    # (rejection sampling needs the unmodified target distribution).
    repeat_penalty: float = 1.0
    repeat_last_n: int = 64
    eos_token_id: Optional[int] = None
    # Filled by the engine:
    slot: int = -1
    pages: List[int] = dataclasses.field(default_factory=list)
    # Bumped whenever ``pages`` is wholesale-replaced (each prefill
    # setup): part of the staging-buffer block-table key, so a preempted
    # sequence resumed into the same slot with a same-length page list
    # can never alias a stale cached row (engine._stage_batch).
    pages_version: int = 0
    ctx_len: int = 0                       # tokens currently in KV
    # SWA eviction cursor: pages[:evicted_pages] (pages.window[:...] where
    # the model has a pool a kind) are behind the window, freed, and
    # zeroed (engine._evict_behind_window).
    evicted_pages: int = 0
    cached_tokens: int = 0                 # prefix-cache hit length
    # Tiered KV cache (README "Tiered KV cache"): device pages restored
    # from the host-RAM tier for this request's prefill (swap-in), and
    # whether the queue-wait prefetch already ran for it. prefix_digests
    # carries the prompt's chain hashes computed ONCE (by the router's
    # scoring pass, or lazily at first engine use) so route -> admit ->
    # publish costs one hash pass per request, not three.
    host_restored_pages: int = 0
    host_prefetched: bool = False
    prefix_digests: Optional[List[bytes]] = None
    # Resume-stream digests (prompt + pre-preemption generated tokens),
    # kept SEPARATE from prefix_digests so failover clones and router
    # reuse never see a resume-polluted list; cleared at each preemption
    # (the stream and truncation window change there and only there).
    resume_digests: Optional[List[bytes]] = None
    # Preemption / recompute-resume state (admission="optimistic"):
    # preemptions counts evictions so far (the starvation guard compares
    # it against preempt_max_per_request); resume_base is the number of
    # generated tokens present at the last (re)prefill, so the resume
    # prefill computes prompt + generated[:resume_base] and decode
    # continues from there. admit_idx orders running sequences by
    # admission recency (victim selection preempts the newest first).
    preemptions: int = 0
    resume_base: int = 0
    admit_idx: int = -1
    # Incremental multi-chunk prefill state (prefill_begin/prefill_step).
    prefill_prompt: Optional[List[int]] = None
    prefill_offset: int = 0
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    finish_reason: str = ""
    # Set (under the scheduler lock) by EngineScheduler._finish so the
    # terminal path runs exactly once even when the shutdown force-
    # finish races a slow engine thread's own reap.
    reaped: bool = False
    # Timing (server metrics; SURVEY.md §5 observability).
    enqueue_time: float = 0.0
    prefill_start: float = 0.0
    first_token_time: float = 0.0
    finish_time: float = 0.0
    # End-to-end tracing (telemetry.py): trace_id is the client-visible
    # request id propagated from HTTP ingress (X-Request-Id) into
    # structured logs and response metadata; attempt counts failover
    # resubmissions (server/replicas.py) so a resubmitted span is marked.
    trace_id: str = ""
    attempt: int = 0
    # Priority class (README "Elastic fleet"): interactive requests
    # outrank batch/background at admission AND in the waiting queue
    # (config.class_rank); lower classes absorb overload via deferral
    # and watermark preemption instead of a fleet-wide 429.
    priority_class: str = "interactive"
    # Routing span (server/replicas.py): which dp replica this attempt
    # was dispatched to and how many cached prefix pages the router
    # counted on at decision time (-1/0 when submitted scheduler-direct).
    routed_replica: int = -1
    route_hit_pages: int = 0
    # Of route_hit_pages, how many were host-tier (warm but needing a
    # swap-in) at decision time — the router's third temperature.
    route_host_hit_pages: int = 0
    # Pages the router pulled from the fleet KV fabric into this
    # replica's host tier before dispatch (README "KV fabric") — the
    # fourth temperature: warmth another replica prefilled.
    route_fabric_hit_pages: int = 0
    # The first admission pass that saw this request waiting (perf
    # counter; 0 = none yet): splits queue wait into waiting for the
    # running dispatch to come back and waiting for capacity.
    admit_seen_time: float = 0.0
    # Adaptive-γ state for n-gram speculation (README
    # "Speculative decoding"): current per-sequence γ (-1 = engine
    # default, 0 = throttled), EWMA acceptance rate, and the countdown
    # until a throttled sequence re-probes. Survives preemption /
    # recompute-resume — the stream's echo statistics don't change when
    # its KV pages do.
    # The EWMA starts mildly optimistic (not 1.0): a fresh echo-free
    # stream throttles after ~3 rejected rounds instead of ~5, and an
    # echoic one pulls toward 1 just as fast.
    spec_gamma: int = -1
    spec_accept_ewma: float = 0.5
    spec_probe_countdown: int = 0
    # Consecutive failed probes back the probe interval off (doubling,
    # capped at 8x spec_probe_every), so a stream that never echoes
    # pays a vanishing fraction of its rounds re-checking.
    spec_probe_interval: int = 0
    # P/D disaggregation (README "P/D disaggregation"). Outbound: a
    # prefill-role worker sets handoff_after_prefill so the scheduler
    # emits the settled prefill (KV pages incl. the partial final page
    # + stream state) as a live handoff instead of decoding it locally.
    # Inbound: adopt_kv = (host_pages, ctx_len) carries a received
    # handoff; admission restores the pages straight into fresh device
    # pages and resumes DECODE — no prefill dispatch, zero recomputed
    # tokens (engine.adopt_sequence).
    handoff_after_prefill: bool = False
    adopt_kv: Optional[tuple] = None
    # Set by adopt_sequence: this attempt resumed from a live KV
    # handoff (no prefill dispatch ran) — the tracing layer emits a
    # handoff_adopt span in place of the prefill span, and the SLO
    # tracker skips its TTFT (the client's first token streamed from
    # the prefill worker, not here).
    adopted: bool = False
    # Per-request speculative-round exposure:
    # rounds this sequence proposed in and positions accepted —
    # surfaced as attrs on the request's decode span so a trace shows
    # where speculation paid off without a span per round.
    spec_rounds: int = 0
    spec_accepted_toks: int = 0
    # EngineConfig.keep_logits: position -> the float32 row [V] the step
    # program handed to ``sample`` for the token after that position (the
    # last 4 x decode_steps_per_call positions). None: not kept.
    kept_logits: Optional[Dict[int, np.ndarray]] = None

    @property
    def last_token(self) -> int:
        return self.generated[-1] if self.generated else self.prompt_tokens[-1]


class InferenceEngine:
    """Owns device state (params, KV pool) and the compiled step functions."""

    def __init__(self, model_cfg: ModelConfig, engine_cfg: EngineConfig,
                 params: Optional[dict] = None, seed: int = 0,
                 attn_backend: Optional[str] = None,
                 shard_fn: Optional[Callable[[dict], dict]] = None,
                 mesh: Optional[Any] = None,
                 pallas_interpret: bool = False):
        model_cfg.validate()
        t_boot = time.perf_counter()
        self.model_cfg = model_cfg
        self.engine_cfg = engine_cfg
        _refuse_unsupported(model_cfg, engine_cfg, mesh)
        self.mod = get_model_fns(model_cfg)
        # Resolve the attention backend: constructor arg wins, then
        # EngineConfig; "auto" = the Pallas paged kernels on a TPU, the
        # dense gather path elsewhere. The kernels compile for the chip
        # or not at all: off a TPU, "pallas" is an error unless the
        # caller asks for interpret mode by name (``pallas_interpret``,
        # a constructor argument only — tests use it; no serving entry
        # point can reach it, so a server never quietly serves from the
        # interpreter).
        on_tpu = jax.default_backend() == "tpu"
        backend = attn_backend or engine_cfg.attn_backend
        if backend == "auto":
            backend = "pallas" if on_tpu else "dense"
        if backend not in ("dense", "pallas"):
            raise ValueError(f"unknown attn_backend {backend!r}; "
                             "expected 'auto', 'dense' or 'pallas'")
        if backend == "pallas" and not (on_tpu or pallas_interpret):
            raise ValueError(
                "attn_backend='pallas' needs a TPU (jax backend is "
                f"{jax.default_backend()!r}); use 'dense' here, or "
                "InferenceEngine(..., pallas_interpret=True) to run the "
                "kernels in interpret mode for a test")
        self._pallas_interpret = pallas_interpret
        # An engine built directly (no resolve_sizing before it) settles
        # the tokens of a page here, before anything reads them: a no-op
        # on a config that names them.
        engine_cfg = self.engine_cfg = resolve_page_size(
            model_cfg, engine_cfg, pallas=backend == "pallas",
            tp=mesh.shape.get("tp", 1) if mesh is not None else 1)
        # Validate mesh compatibility BEFORE materializing params —
        # at 70B scale a post-init failure wastes minutes (or OOMs).
        if mesh is not None:
            from tpu_inference.parallel import shardings as _shd
            _shd.validate_tp(model_cfg, mesh.shape.get("tp", 1))
        def maybe_quantize(p):
            # Weight-only int8: halves the per-step HBM weight read that
            # bounds decode throughput (BASELINE.md roofline). Runs on
            # device; shard_params below re-canonicalizes placements.
            if engine_cfg.quant == "none":
                return p
            from tpu_inference.models.quant import quantize_params
            return quantize_params(p, engine_cfg.quant)

        built = params is None      # the arrays are this engine's alone
        if params is None:
            if engine_cfg.quant != "none":
                # Leaf-by-leaf init+quantize: peak device memory stays
                # ~quantized-model-sized (8B random-init int8 fits one
                # 16 GB chip; init-everything-then-quantize would OOM
                # at the full-precision peak).
                from tpu_inference.models.quant import init_quantized_params
                params = init_quantized_params(model_cfg, seed,
                                               engine_cfg.quant)
            else:
                # Under a mesh, straight into the sharded layout: the
                # unsharded model may not fit the first chip.
                params, _ = build_model(
                    model_cfg, seed=seed,
                    shardings=(_shd.param_shardings(model_cfg, mesh)
                               if mesh is not None and shard_fn is None
                               else None))
        if shard_fn is not None:
            params = shard_fn(params)
        params = maybe_quantize(params)  # no-op on already-quantized leaves
        self.mesh = mesh
        kv_sh = kv_scale_sh = None
        if mesh is not None:
            # Declarative TP/EP: annotate weights + KV pool, let GSPMD place
            # the ICI collectives. The jitted graphs pick the shardings up
            # from their inputs; donated KV keeps its sharding step to step.
            from tpu_inference.parallel import shardings as shd
            params = shd.shard_params(params, model_cfg, mesh)
            kv_sh = shd.kv_sharding(mesh)
            kv_scale_sh = shd.kv_scale_sharding(mesh)
        # Weights enter as published, [.., K, N]; the stacks that feed
        # attention are STORED [.., N, K] (models/quant.py
        # STORED_TRANSPOSED), swapped here once, and contracted on their
        # last dim: the layout every decode program would otherwise copy
        # them into once a dispatch. Whoever reads ``engine.params`` as a
        # checkpoint holds it goes through ``quant.published``.
        self.params, n_swapped = store_transposed(params, model_cfg.family,
                                                  owned=built)
        del params
        # Boot phases are host walls: no sync is added to time them, so
        # whatever the device still owes on the weights when their last
        # program is enqueued lands in the phases after (warm-up ends
        # in the boot's one block_until_ready).
        t_weights = time.perf_counter()
        self.n_params = int(sum(x.size
                                for x in jax.tree.leaves(self.params)))
        # Resident bytes of the (possibly quantized) weights — global
        # logical size, independent of sharding. Reported by /api/ps and
        # used by bench.py's hbm_util roofline math.
        self.weight_bytes = int(sum(x.nbytes
                                    for x in jax.tree.leaves(self.params)))
        self.attn_backend = backend
        self.kv = kvc.alloc_kv_pages(model_cfg, engine_cfg, sharding=kv_sh,
                                     scale_sharding=kv_scale_sh)
        # Where the pool sits, read once off the array itself: every
        # dispatch donates self.kv, so other threads (health, hello)
        # must never touch the array to ask.
        self._devices = sorted(self.kv.k.devices(), key=lambda d: d.id)
        # What the model counts on the device (``kv.aux``; a family's
        # ``n_aux_stats``), summed off the decode readbacks: expert
        # routing (models/deepseek_v3.py MOE_STATS + one slot per held
        # expert), the positions a prefill ran (models/sambay.py
        # AUX_STATS). None for a family that counts nothing. The slots
        # a family names under ``aux_max_slots`` hold the largest value
        # seen, the others sums.
        self.aux_stats = (np.zeros(self.kv.aux.shape, np.int64)
                          if self.kv.aux is not None else None)
        max_slots = family_fn(model_cfg, "aux_max_slots")
        self._aux_max_slots = list(max_slots(model_cfg)) if max_slots else []
        t_pool = time.perf_counter()
        self.allocator = PageAllocator(engine_cfg.num_pages)
        # A model whose layers differ in kind has a second pool, for its
        # window layers, with an allocator and a block table a sequence
        # of its own (kvc.KindPages); ``allocator`` is then the full
        # kind's. None: the model has one kind.
        n_win = kvc.num_window_pages(model_cfg, engine_cfg)
        self.win_allocator = PageAllocator(n_win) if n_win else None
        self.window_span = (kvc.window_span_pages(model_cfg, engine_cfg)
                            if n_win else 0)
        self.window_pages_released = 0    # behind-window frees, lifetime
        # Pages a kind that admission held back at its last pass
        # (pages_booked) and the most it has at once since boot: stored
        # by the engine loop, so that a metrics scrape reads two arrays
        # and never walks the live sequences from its own thread.
        self.pages_booked_seen = np.zeros(2, np.int64)
        self.pages_booked_peak = np.zeros(2, np.int64)
        # State-space layers: a state slot a sequence, from admission to
        # release (kvc.StateSlots; ``seq.pages.state``). It rides behind
        # the block tables, one more column of a row: lanes move between
        # dispatches (_compact_slots) and the state must not.
        if model_cfg.pool_rows_merged and any(
                b % engine_cfg.page_size
                for b in (*engine_cfg.prefill_buckets,
                          engine_cfg.chunk_tokens_cap)):
            raise ValueError(
                f"{model_cfg.name}: prefill buckets and the chunk cap have "
                f"to be multiples of the page size ({engine_cfg.page_size}"
                "): a chunk's K / V are written a whole page at a time")
        n_state = kvc.num_state_slots(model_cfg, engine_cfg)
        self.state_slots = kvc.StateSlots(n_state) if n_state else None
        self.keep_logits = engine_cfg.keep_logits
        # Step-phase telemetry (telemetry.py): dispatch/bubble histograms
        # + read-through page/param gauges. TPU_INF_TELEMETRY=0 swaps in
        # no-op metrics (the overhead-comparison arm).
        self.telemetry = telemetry.EngineTelemetry(self)
        if self.state_slots is not None:
            self.telemetry.bind_state(self)
        if self.aux_stats is not None and model_cfg.n_experts:
            self.telemetry.bind_moe(self)
        # Boot phases (gauges set once; a caller that loaded a
        # checkpoint itself adds its load time to the first).
        self.boot_s = {"weights": t_weights - t_boot,
                       "pool": t_pool - t_weights}
        self.telemetry.boot_weights_s.set(self.boot_s["weights"])
        self.telemetry.boot_pool_s.set(self.boot_s["pool"])
        self.telemetry.weight_stacks_transposed.set(n_swapped)
        # Monotone dispatch number of the step programs (the ledger's
        # ``seq``, the tpu_inf/dispatch annotation's ``seq``), and the
        # prefill chunks enqueued whose result no readback has covered
        # yet: (seq, enqueue instant, stalled decode lanes?).
        self._dispatch_seq = 0
        self._unsettled: List[Tuple[int, float, bool]] = []
        self._device_free_at = 0.0        # instant of the last readback
        # Host-side bubble tracking: perf_counter at the end of the last
        # decode dispatch, None when the decode streak broke (idle batch
        # or an interleaved prefill) so cross-idle gaps never count.
        self._last_decode_end: Optional[float] = None
        # Fault injection, copied out of the frozen config so tests and
        # the /debug/chaos endpoint can arm/disarm per replica at runtime.
        self.chaos_step_failure_rate = engine_cfg.chaos_step_failure_rate
        self.chaos_step_wedge_s = engine_cfg.chaos_step_wedge_s
        # Admission mode (README "Admission & preemption"): "reserve"
        # charges worst case at admission; "optimistic" charges prompt +
        # headroom and relies on watermark-driven preemption +
        # recompute-resume as the exhaustion safety net.
        if engine_cfg.admission not in ("reserve", "optimistic"):
            raise ValueError(f"unknown admission mode "
                             f"{engine_cfg.admission!r}; "
                             "one of ('reserve', 'optimistic')")
        self.admission = engine_cfg.admission
        self.preemptions_total = 0        # sequences evicted for pressure
        self.resumes_total = 0            # recompute-resume prefills
        self.swap_in_resumes = 0          # resumes that restored KV pages
        self.hybrid_steps_total = 0       # fused prefill+decode dispatches
        # KV page migration (README "Process fleet"): pages/bytes this
        # engine exported at drain time and imported from a sibling
        # replica's drain. Plain ints (GIL-atomic reads from scrape
        # threads), exported read-through by bind_engine.
        self.migrate_out_pages = 0
        self.migrate_out_bytes = 0
        self.migrate_in_pages = 0
        self.migrate_in_bytes = 0
        # P/D disaggregation (README "P/D disaggregation"): the worker
        # phase role this engine serves (specializes warmup below), and
        # the live-handoff churn — settled prefills exported to a decode
        # worker, and handed-off sequences adopted here (KV restored,
        # decode resumed, nothing recomputed).
        from tpu_inference.config import WORKER_ROLES
        if engine_cfg.role not in WORKER_ROLES:
            raise ValueError(f"unknown engine role {engine_cfg.role!r}; "
                             f"one of {WORKER_ROLES}")
        self.role = engine_cfg.role
        self.handoffs_out = 0
        self.handoff_out_pages = 0
        self.adoptions_in = 0
        # Handoffs this worker RECEIVED but could not adopt (malformed/
        # truncated blob, pool shortfall at admission) — they fell back
        # to recompute-resume. Folded into the fleet's
        # tpu_inf_pd_handoff_recomputes_total so the metric's contract
        # ("every non-clean handoff") holds for worker-side failures
        # too, not just the router-side stale-blob/no-adopter paths.
        self.adopt_fallbacks = 0
        # Byzantine transport (README "Failure model"): KV blobs whose
        # embedded CRC-32C digest failed verification on an adopt or
        # import path — rejected and counted here, never adopted. The
        # worker folds this into healthz and the fleet sums it into
        # tpu_inf_kv_integrity_rejections_total.
        self.kv_integrity_rejections = 0
        # Fleet KV fabric publish (README "KV fabric"): when armed (the
        # worker's boot() or the in-process group sets fabric_publish to
        # a callable taking [(digest, HostKVPage)]), _publish_to_cache
        # also offloads the settled prefix run and ships it to the
        # router's fabric pool, so a prefix prefilled here warms every
        # replica. _fabric_published is a bounded dedup set so steady
        # traffic over the same system prompt doesn't re-serialize the
        # same pages every release.
        self.fabric_publish = None
        self.fabric_publish_min_pages = 1
        self._fabric_published: "collections.OrderedDict[bytes, None]" = \
            collections.OrderedDict()
        self.fabric_published_pages = 0
        # Cross-thread migration imports (the worker's import-kv RPC
        # lands on an RPC thread; the host tier is engine-thread only):
        # queued here, applied by the scheduler loop before admission so
        # an import acked before its request's submit is visible to that
        # request's prefill. Each entry is (entries, done_event).
        self._pending_imports: List[tuple] = []
        self._pending_imports_lock = threading.Lock()
        self._admit_counter = 0           # admission recency for victims
        # Sequences preempted since the caller last collected them; the
        # scheduler requeues these at the head of its wait queue.
        self._preempted_out: List[Sequence] = []
        # chaos_page_pressure holds REAL pages out of the pool so the
        # exhaustion/preemption paths run deterministically on CPU.
        self._pressure_pages: List[int] = []
        self.chaos_page_pressure = 0
        # Cross-thread arm/disarm requests (the /debug/chaos handler
        # runs on an aiohttp thread; the allocator is engine-thread
        # only): a plain GIL-atomic store, applied by the engine loop.
        self._pressure_target: Optional[int] = None
        if engine_cfg.chaos_page_pressure > 0:
            self.set_page_pressure(engine_cfg.chaos_page_pressure)
        # Speculative decoding (README "Speculative decoding") is n-gram
        # verification, on when num_speculative_tokens > 0: the host
        # proposes from each sequence's own history (prompt lookup) and
        # one verify-only program accepts. No second model and no second
        # pool, so the ladder, the host tier, SWA eviction and the
        # repetition penalty all stay active.
        self.spec_enabled = engine_cfg.num_speculative_tokens > 0
        if self.spec_enabled:
            validate_spec_config(engine_cfg.num_speculative_tokens,
                                 engine_cfg.ngram_window)
        self.prefix_cache = None
        # The window only binds when the serving context can exceed it
        # (ADVICE r4): with max_context <= window no query ever looks
        # back past the window, eviction would never free a page, and
        # behavior is identical to full attention — so the prefix cache
        # stays safe and the SWA exclusions don't apply.
        swa_binds = bool(model_cfg.sliding_window) and (
            engine_cfg.max_context > model_cfg.sliding_window)
        self.host_pool = None
        if engine_cfg.enable_prefix_cache and (
                self.win_allocator is not None
                or self.state_slots is not None):
            print(f"[engine] {model_cfg.name}: prefix cache disabled — "
                  + why_not("prefix", model_cfg))
        elif engine_cfg.enable_prefix_cache and not swa_binds:
            # SWA models run WITHOUT the prefix cache (vLLM makes the
            # same exclusion): behind-window pages are evicted while a
            # sequence runs (_evict_behind_window), and a cached prefix
            # with holes would hand garbage KV to a shorter follow-up
            # request whose own window lands inside the evicted region.
            from tpu_inference.engine.prefix_cache import PrefixCache
            if engine_cfg.host_cache_pages > 0:
                # Host-RAM second tier: evicted pages demote instead of
                # being dropped (README "Tiered KV cache").
                self.host_pool = kvc.HostPagePool(
                    engine_cfg.host_cache_pages)
                self.telemetry.bind_host_pool(self.host_pool)
            self.prefix_cache = PrefixCache(self.allocator,
                                            engine_cfg.page_size,
                                            host_pool=self.host_pool,
                                            offload_fn=self._offload_pages)
            self.prefix_cache.bind_telemetry(self.telemetry)
        elif engine_cfg.enable_prefix_cache:
            print(f"[engine] {model_cfg.name}: prefix cache disabled — "
                  f"sliding_window={model_cfg.sliding_window} evicts "
                  "behind-window pages, which doesn't compose with "
                  "cached prefixes (multi-turn requests re-prefill)")
        self.max_pages = engine_cfg.max_pages_per_seq
        # Width of a block-table row: a table a kind, side by side, and
        # behind them the state slot where the model has one.
        self.bt_width = (self.max_pages * (1 if self.win_allocator is None
                                           else 2)
                         + (self.state_slots is not None))
        # Cold-start evidence (device_info): wall seconds of the last
        # warmup() and how many graphs it ran.
        self.warmup_s = 0.0
        self.warmup_graphs = 0
        # ... and how many of them sum their expert layers' rows by a
        # gather (a family with grouped experts: kernels/moe_experts.py).
        self.gather_combine_programs = 0
        # Step programs take their small operands as ONE packed int32
        # array a dispatch (engine/staging.py), put where the program
        # expects it: replicated over the mesh, or the default device.
        self._operand_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._operand_sharding = NamedSharding(mesh, PartitionSpec())
        self._decode_layout = staging.decode_layout(self.bt_width)
        self._prefill_layouts: Dict[int, staging.PackedLayout] = {}
        # A dispatch's sampling key is fold_in(base key, its step
        # number), folded INSIDE the program from the number that rides
        # in the packed operand; the base key lives on the device.
        self._base_key = jax.device_put(jax.random.PRNGKey(seed),
                                        self._operand_sharding)
        self._step_count = 0
        # Batch ladder (README "Batch ladder"): the decode graphs are
        # compiled at every rung; dispatch uses the smallest rung that
        # covers the occupied slots. The slot array is always top-rung
        # sized — rung moves never relocate KV (block tables are host
        # state shipped per dispatch), only which compiled graph runs.
        from tpu_inference.engine.autosize import validate_ladder
        ladder = validate_ladder(engine_cfg.ladder_rungs,
                                 engine_cfg.max_batch_size)
        self.ladder = ladder
        self.decode_rung = ladder[0]      # rung of the latest dispatch
        self.rung_peak = ladder[0]        # highest rung reached
        self.rung_switches_total = 0      # dispatches at a changed rung
        # Step-ledger scratch (telemetry.py StepLedger; README
        # "Performance attribution"): compile-event detection per rung /
        # prefill bucket, the staged bubble/staging micros the next
        # ledger push consumes, and the KV-swap byte-counter watermark
        # that turns cumulative swap counters into per-record deltas.
        self._rungs_seen: set = set()
        self._prefill_buckets_seen: set = set()
        self._pending_bubble = 0.0
        self._last_staging_s = 0.0
        self._last_swap_bytes_total = 0.0
        self._last_compile_event = False
        # Host staging reuse (the per-dispatch bubble shrinker): one
        # persistent packed operand a rung, refreshed incrementally. The
        # device gets a copy — device_put aliases numpy memory on CPU,
        # and the rows mutate next step while a dispatch may still read.
        self._stage_reuse = engine_cfg.stage_host_reuse
        self._stage_bufs: Dict[int, dict] = {}
        self.slots: List[Optional[Sequence]] = [None] * engine_cfg.max_batch_size
        # Dispatch-ahead decode pipeline (decode_steps_pipelined).
        self._inflight: List[dict] = []
        # Embeddings graph (built on first /api/embeddings use).
        self._embed_jit = None
        self._embed_lock = threading.Lock()

        k_fused = max(1, engine_cfg.decode_steps_per_call)
        # The compiled programs are thin wrappers (_prefill_packed,
        # _decode_packed, _hybrid_packed): unpack the operand, fold the
        # key, call the bodies below.
        self._prefill_jit = jax.jit(
            telemetry.named_program("tpu_inf_prefill", self._prefill_packed),
            donate_argnums=(1,))
        self._decode_multi_jit = jax.jit(
            telemetry.named_program("tpu_inf_decode_k" + str(k_fused),
                                    self._decode_packed),
            donate_argnums=(1,))
        # Hybrid prefill-decode steps (EngineConfig.hybrid_prefill): one
        # fused dispatch advances a [1, S] prefill chunk AND the [B]
        # K-step decode scan on the shared (page-disjoint) pool. One
        # graph per prefill bucket; the decode half keeps the fused-K
        # shape, so compile count matches the serial path's.
        self._hybrid_jit = jax.jit(
            telemetry.named_program("tpu_inf_hybrid", self._hybrid_packed),
            donate_argnums=(1,))
        # Single-step decode graph: a 1-iteration scan, so a token leaves
        # the device every step instead of every K — the scheduler's
        # latency mode uses it when the batch is nearly empty (streaming
        # smoothness; fused K-step calls would still run K forwards for
        # one visible token). With K == 1 the fused graph IS the 1-step
        # graph; aliasing keeps one compile cache so warmup covers both
        # routes.
        if engine_cfg.decode_steps_per_call <= 1:
            self._decode_one_jit = self._decode_multi_jit
        else:
            self._decode_one_jit = jax.jit(
                telemetry.named_program(
                    "tpu_inf_decode_1",
                    partial(self._decode_packed, k_steps=1)),
                donate_argnums=(1,))
        # Deeper than 1, the fused-K and hybrid programs take the newest
        # in-flight call's carry (final tokens, final window) as two more
        # operands and fold it in the graph; with nothing in flight they
        # are handed this one, which no lane reads (``carried`` all 0).
        # The one-step program only runs on a drained pipeline.
        self._null_carry: Dict[int, tuple] = {}
        if engine_cfg.decode_pipeline_depth > 1:
            for b in ladder:
                self._null_carry[b] = jax.device_put(
                    (np.zeros((b,), np.int32),
                     np.full((b, PENALTY_WINDOW), -1, np.int32)),
                    self._operand_sharding)
        # Sequence-parallel prefill (ring attention over the sp axis) for
        # fresh full-prompt chunks on an sp>1 mesh.
        self.sp = 1 if mesh is None else int(mesh.shape.get("sp", 1))
        # Compiled prefill lane counts (pad-to-size keeps XLA graph count
        # bounded at 2 per bucket).
        self._prefill_batch_sizes = sorted(
            {1, max(1, engine_cfg.max_prefill_batch)})
        if self.sp > 1:
            if engine_cfg.sp_attn not in ("ring", "ulysses"):
                raise ValueError(f"sp_attn={engine_cfg.sp_attn!r}: "
                                 "one of ('ring', 'ulysses')")
            if engine_cfg.sp_attn == "ulysses":
                tp = int(mesh.shape.get("tp", 1))
                if (model_cfg.n_heads % (tp * self.sp)
                        or model_cfg.n_kv_heads % (tp * self.sp)):
                    raise ValueError(
                        f"sp_attn='ulysses' needs n_heads "
                        f"({model_cfg.n_heads}) and n_kv_heads "
                        f"({model_cfg.n_kv_heads}) divisible by tp*sp "
                        f"({tp}*{self.sp}); use sp_attn='ring'")
            self._prefill_sp_jit = jax.jit(
                telemetry.named_program(
                    "tpu_inf_prefill_sp",
                    partial(self._prefill_packed,
                            sp_mode=engine_cfg.sp_attn)),
                donate_argnums=(1,))

        # Speculation's accounting: positions proposed and accepted,
        # verify rounds dispatched, rounds that degraded to the plain
        # fused-K graph (no slot proposed), and per-sequence γ=0 throttle
        # events (the adaptive-γ "spec never loses" lever).
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_rounds_total = 0
        self.spec_fallback_rounds = 0
        self.spec_throttles_total = 0
        # Behind-window page eviction (SWA): a running sequence holds
        # O(window) KV pages instead of O(context). It composes with
        # speculation: the verify queries sit at positions >= ctx, whose
        # windows start at or after plain decode's. Off when the window
        # can't bind (swa_binds above): there would never be a
        # behind-window page to free.
        self.swa_evict = swa_binds and self.prefix_cache is None
        if self.spec_enabled:
            self.telemetry.bind_spec(self)
            from tpu_inference.engine.speculative import verify_round
            self._verify_jit = jax.jit(
                telemetry.named_program("tpu_inf_spec_verify",
                                        partial(verify_round, self)),
                donate_argnums=(1,))
            # Compiled verify widths (tokens per round = width): the
            # full γ+1 round plus a narrow 2-wide probe round, so a
            # γ=0-throttled lane re-checks its echo at near-plain cost.
            # XLA keys on the drafts shape, so each (rung, width) pair
            # is its own executable — all warmed in warmup().
            gamma = engine_cfg.num_speculative_tokens
            self._spec_widths = sorted({2, gamma + 1})

    # ------------------------------------------------------------------
    # Device graphs (pure functions of arrays; jitted once per bucket/batch)
    # ------------------------------------------------------------------

    def _paged_attn(self, cfg: ModelConfig, block_tables, positions, valid,
                    q_offset, kv_len, sp_mode: Optional[str] = None,
                    **kind_args):
        """make_paged_attn bound to this engine's page size, attention
        backend, mesh and kernel mode."""
        return make_paged_attn(
            cfg, self.engine_cfg.page_size, block_tables, positions, valid,
            q_offset=q_offset, kv_len=kv_len,
            attn_backend=self.attn_backend, mesh=self.mesh, sp_mode=sp_mode,
            interpret=self._pallas_interpret, **kind_args)

    def _prefill_fn(self, params, kv: KVPages, tokens, prompt_len, prefix_len,
                    block_table, key, temperature, top_p, top_k, seed,
                    rpen, rlast, window, sp_mode=None):
        """One sequence, tokens [1, S_bucket] right-padded.

        prefix_len > 0 means ``prefix_len`` tokens are already cached in this
        sequence's pages (multi-turn / chunked prefill); new tokens occupy
        positions [prefix_len, prefix_len + prompt_len).
        """
        cfg = self.model_cfg
        s = tokens.shape[1]
        ar = jnp.arange(s)[None, :]
        positions = prefix_len[:, None] + ar                     # [1, S]
        valid = ar < prompt_len[:, None]
        total_len = prefix_len + prompt_len
        positions = jnp.minimum(positions, self.engine_cfg.max_context - 1)
        if "cross" in cfg.layer_types[:cfg.n_layers]:
            # The layers behind the one full-attention layer write no
            # state: the program runs them for the sampled position only.
            at = prompt_len - 1
            attn = self._paged_attn(cfg, block_table, positions, valid,
                                    q_offset=prefix_len, kv_len=total_len,
                                    cross_at=at)
            hidden, kv = self.mod.forward_hidden(
                params, cfg, tokens, positions, kv, attn, cross_at=at)
            return self._sample_prefill(params, kv, hidden[:, 0], total_len,
                                        key, temperature, top_p, top_k, seed,
                                        rpen, rlast, window)
        attn = self._paged_attn(cfg, block_table, positions, valid,
                                q_offset=prefix_len, kv_len=total_len,
                                sp_mode=sp_mode)
        hidden, kv = self.mod.forward_hidden(params, cfg, tokens, positions,
                                             kv, attn)
        last = jnp.take_along_axis(
            hidden, (prompt_len - 1)[:, None, None].astype(jnp.int32), axis=1
        )[:, 0]                                                  # [1, D]
        return self._sample_prefill(params, kv, last, total_len, key,
                                    temperature, top_p, top_k, seed, rpen,
                                    rlast, window)

    def _sample_prefill(self, params, kv, last, total_len, key, temperature,
                        top_p, top_k, seed, rpen, rlast, window):
        """A prefill's tail: the sampled position's hidden state [P, D]
        -> (kv, first token [P], its float32 logits [P, V])."""
        cfg = self.model_cfg
        logits = self.mod.unembed(params, cfg, last)             # [1, V]
        sp = SamplingParams(temperature=temperature, top_p=top_p,
                            top_k=top_k, seed=seed)
        tok = sample(logits, key, sp, ctx=total_len, penalty_window=window,
                     repeat_penalty=rpen, repeat_last_n=rlast)
        return kv, tok, logits

    def _decode_multi_fn(self, params, kv: KVPages, tokens, ctx_lens,
                         block_tables, allowed, eos_ids, key, temperature,
                         top_p, top_k, seed, rpen, rlast, window,
                         k_steps: Optional[int] = None):
        """K fused decode steps under one dispatch (lax.scan on device).

        Sampled tokens feed back into the next step without leaving HBM;
        the host syncs once per K steps instead of per token, which is the
        difference between dispatch-latency-bound and compute-bound decode
        (SURVEY.md §7 hard part 3: host<->device overlap).

        allowed: [B] int32 — steps each slot may advance this call (folds
        budget, context cap, and page headroom). eos_ids: [B] int32, -1
        when the request has no EOS. window: [B, W] recent-token ring for
        the repetition penalty, updated on device each step so fused
        steps see their own samples. Returns (kv, out [K, B] int32, final
        carry tokens [B], final window [B, W]) with -1 out entries for
        slots that produced nothing at that step.
        """
        cfg = self.model_cfg
        ecfg = self.engine_cfg

        def step(carry, s):
            kv, tokens, ctx_lens, alive, window = carry
            act = alive & (s < allowed)
            positions = jnp.minimum(ctx_lens, ecfg.max_context - 1)[:, None]
            attn = self._paged_attn(cfg, block_tables, positions,
                                    act[:, None], q_offset=ctx_lens,
                                    kv_len=ctx_lens + 1)
            hidden, kv = self.mod.forward_hidden(params, cfg, tokens[:, None],
                                                 positions, kv, attn)
            logits = self.mod.unembed(params, cfg, hidden[:, 0])
            sp = SamplingParams(temperature=temperature, top_p=top_p,
                                top_k=top_k, seed=seed)
            # The token being sampled will sit at absolute index ctx+1
            # (the current input token occupies ctx) — the seeded-stream
            # position that makes per-request seeds scheduling-invariant.
            toks = sample(logits, jax.random.fold_in(key, s), sp,
                          ctx=ctx_lens + 1, penalty_window=window,
                          repeat_penalty=rpen, repeat_last_n=rlast)
            toks = jnp.where(act, toks, tokens)
            window = roll_window(window, toks, act)
            out = jnp.where(act, toks, -1)
            if ecfg.keep_logits:
                # The rows leave with the tokens: outs is then (tokens
                # [K, B(+n)], logits [K, B, V]).
                kept = logits.astype(jnp.float32)
            if kv.aux is not None:
                # The model's routing counts since the last emission (a
                # prefill's included) leave with the step's tokens: the
                # one readback there is (_fold_aux_stats).
                out = jnp.concatenate([out, kv.aux])
                kv = kv._replace(aux=jnp.zeros_like(kv.aux))
            alive = alive & jnp.where(act, toks != eos_ids, True)
            ctx_lens = ctx_lens + act.astype(jnp.int32)
            if ecfg.keep_logits:
                out = (out, kept)
            return (kv, toks, ctx_lens, alive, window), out

        if k_steps is None:
            k_steps = max(1, ecfg.decode_steps_per_call)
        alive0 = jnp.ones(tokens.shape, bool)
        (kv, final_tokens, _, _, final_window), outs = jax.lax.scan(
            step, (kv, tokens, ctx_lens, alive0, window),
            jnp.arange(k_steps, dtype=jnp.int32))
        # final_tokens [B] (and final_window) = each lane's carry after
        # the last step: the input for a chained next call, letting
        # callers dispatch call N+1 against call N's device-resident
        # output with no host sync (dispatch-ahead, SURVEY.md §7 hard
        # part 3 — the host round trip otherwise gates decode
        # throughput).
        return kv, outs, final_tokens, final_window

    def _hybrid_step_fn(self, params, kv: KVPages,
                        p_tokens, p_prompt_len, p_prefix_len, p_block_table,
                        p_key, p_temp, p_top_p, p_top_k, p_seed, p_rpen,
                        p_rlast, p_window,
                        d_tokens, d_ctx_lens, d_block_tables, d_allowed,
                        d_eos_ids, d_key, d_temp, d_top_p, d_top_k, d_seed,
                        d_rpen, d_rlast, d_window):
        """One hybrid step: a [1, S_bucket] prefill chunk AND the [B]
        K-step fused decode under a single dispatch.

        The fusion is safe because the two halves are page-disjoint: the
        chunk writes (then attends over) only the prefilling sequence's
        block table, and every decode lane reads/writes only its own
        pages — so the sequential composition below computes exactly
        what the two serial dispatches compute, while the device sees
        one launch instead of a decode batch stalling a full chunk wall.
        Returns (kv, chunk's sampled token [1], decode outs [K, B],
        final carry tokens [B], final penalty window [B, W]) — the
        decode tail matches _decode_multi_fn so hybrid calls chain into
        the same dispatch-ahead pipeline as plain decode calls.
        """
        kv, p_tok, p_logits = self._prefill_fn(
            params, kv, p_tokens, p_prompt_len, p_prefix_len, p_block_table,
            p_key, p_temp, p_top_p, p_top_k, p_seed, p_rpen, p_rlast,
            p_window)
        if self.engine_cfg.keep_logits:
            p_tok = (p_tok, p_logits)
        kv, outs, final, final_window = self._decode_multi_fn(
            params, kv, d_tokens, d_ctx_lens, d_block_tables, d_allowed,
            d_eos_ids, d_key, d_temp, d_top_p, d_top_k, d_seed, d_rpen,
            d_rlast, d_window)
        return kv, p_tok, outs, final, final_window

    # -- The programs the engine compiles: (params, kv, base_key, packed
    # -- operand[s][, carry]) -> unpack, fold the key -> the bodies above.

    def _prefill_layout(self, bucket: int) -> staging.PackedLayout:
        layout = self._prefill_layouts.get(bucket)
        if layout is None:
            layout = self._prefill_layouts[bucket] = staging.prefill_layout(
                bucket, self.bt_width)
        return layout

    def _prefill_operands(self, base_key, packed) -> tuple:
        """_prefill_fn's operands (tokens .. window) out of a packed
        prefill operand ``[p, width]``."""
        f = self._prefill_layout(staging.prefill_bucket(
            packed.shape[1], self.bt_width)).unpack(packed)
        return (f["tokens"], f["prompt_len"], f["prefix_len"],
                f["block_table"], jax.random.fold_in(base_key, f["step"][0]),
                f["temp"], f["top_p"], f["top_k"], f["seed"], f["rpen"],
                f["rlast"], f["window"])

    def _decode_operands(self, base_key, packed, carry) -> tuple:
        """_decode_multi_fn's operands (tokens .. window) out of a
        packed decode operand ``[rung, width]``. Each ``carried`` lane
        takes the token and penalty window of ``carry`` (the final
        tokens and window of the newest call in flight); lanes in no
        in-flight call keep their host-known state."""
        f = self._decode_layout.unpack(packed)
        tokens, window = f["tokens"], f["windows"]
        if carry is not None:
            carried = f["carried"] != 0
            tokens = jnp.where(carried, carry[0], tokens)
            window = jnp.where(carried[:, None], carry[1], window)
        return (tokens, f["ctx"], f["bts"], f["allowed"], f["eos_ids"],
                jax.random.fold_in(base_key, f["step"][0]), f["temps"],
                f["top_ps"], f["top_ks"], f["seeds"], f["rpens"],
                f["rlasts"], window)

    def _prefill_packed(self, params, kv: KVPages, base_key, packed,
                        sp_mode=None):
        return self._prefill_fn(
            params, kv, *self._prefill_operands(base_key, packed),
            sp_mode=sp_mode)

    def _decode_packed(self, params, kv: KVPages, base_key, packed,
                       carry=None, k_steps: Optional[int] = None):
        return self._decode_multi_fn(
            params, kv, *self._decode_operands(base_key, packed, carry),
            k_steps=k_steps)

    def _hybrid_packed(self, params, kv: KVPages, base_key, p_packed,
                       d_packed, carry=None):
        return self._hybrid_step_fn(
            params, kv, *self._prefill_operands(base_key, p_packed),
            *self._decode_operands(base_key, d_packed, carry))

    # ------------------------------------------------------------------
    # Host-side orchestration
    # ------------------------------------------------------------------

    def note_checkpoint_load(self, seconds: float) -> None:
        """The caller loaded this engine's weights from a checkpoint
        itself, before construction: that time belongs to the boot's
        weights phase."""
        self.boot_s["weights"] += seconds
        self.telemetry.boot_weights_s.set(self.boot_s["weights"])

    def device_info(self) -> dict:
        """Where this engine really runs, read off the KV pool's own
        placement (not off the process default): its devices, their
        platform and kind, the sizes in effect and the devices' peak
        memory — the facts /healthz, the worker hello and chip_smoke.py
        report. Safe from any thread."""
        devs = self._devices
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devs]
        state = ({} if self.state_slots is None else {
            "state_slots": self.state_slots.num_slots - 1,
            "state_bytes_per_slot": self.model_cfg.state_bytes_per_seq()})
        tail_step = kvc.kda_tail_step_path(self.model_cfg,
                                           self.attn_backend == "pallas")
        if tail_step:
            state["kda_tail_step"] = tail_step
        return {
            **state,
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "ids": [d.id for d in devs],
            "attn_backend": self.attn_backend,
            "max_batch_size": self.engine_cfg.max_batch_size,
            "num_pages": self.engine_cfg.num_pages,
            "page_size": self.engine_cfg.page_size,
            "kv_decode_write": kvc.decode_write_path(
                self.model_cfg, self.attn_backend == "pallas"),
            "ladder": list(self.ladder),
            "warmup_s": round(self.warmup_s, 3),
            "warmup_graphs": self.warmup_graphs,
            "peak_bytes_in_use": (max(peaks) if all(
                p is not None for p in peaks) else None),
        }

    def warmup(self) -> float:
        """Compile every prefill bucket + the decode graph before serving.

        Without this, the first requests pay XLA compile inside their TTFT
        (and the compile blocks the GIL, starving the HTTP event loop so
        streamed tokens burst out after headers). Shapes are what XLA keys
        on, so prompt_len=1 per bucket suffices; writes land on the trash
        page. Returns seconds spent.
        """
        t0 = time.perf_counter()
        ecfg = self.engine_cfg
        graphs = 0

        timeline: List[dict] = []
        mon = telemetry.compile_monitor()
        by_gather = family_fn(self.model_cfg, "combines_by_gather")
        self.gather_combine_programs = 0

        def run(label, jitted, *args, rows=()):
            """One warm-up dispatch = one compiled graph (every call
            below has a shape no earlier call had). Each is timed and
            named in the boot timeline, with where XLA got it from.
            ``rows``: the token rows of each forward pass of this model
            the program holds (what sizes an expert layer's layout)."""
            nonlocal graphs
            graphs += 1
            if by_gather is not None and rows:
                self.gather_combine_programs += all(
                    by_gather(self.model_cfg, r) for r in rows)
            before = mon.snapshot() if mon is not None else None
            t = time.perf_counter()
            out = jitted(*args)
            entry = {"graph": label, "program": jitted.__name__,
                     "seconds": round(time.perf_counter() - t, 4)}
            if before is not None:
                compiles, _, hits = mon.snapshot()
                entry["from"] = ("cache" if hits > before[2] else
                                 "compiled" if compiles > before[0]
                                 else "memory")
            timeline.append(entry)
            return out

        # Role-specialized warmup (README "P/D disaggregation"): a
        # prefill worker never dispatches the decode ladder and a decode
        # worker never dispatches a prompt prefill (adoption restores KV
        # without a forward), so each role compiles only its own phase's
        # graphs — per-role warmup drops to a fraction of the mixed
        # compile set. The OTHER phase still works (lazy compile) so a
        # degraded fleet's fallback routing never strands a request.
        warm_prefill = self.role != "decode"
        warm_decode = self.role != "prefill"
        prefill_batch_sizes = (self._prefill_batch_sizes if warm_prefill
                               else ())
        def operand(layout, lanes):
            """A warm-up operand: every lane at its layout's defaults
            (nothing allowed, or a 1-token prompt on the trash page),
            with the next step number."""
            host = layout.blank(lanes)
            layout.views(host)["step"][:] = self._next_step()
            return self._put_operands(host)[0]

        for p in prefill_batch_sizes:
            for bucket in ecfg.prefill_buckets:
                if bucket > ecfg.max_context:
                    continue
                layout = self._prefill_layout(bucket)
                shape = f"{p}x{bucket}"
                self.kv, _, _ = run(
                    f"prefill {shape}", self._prefill_jit, self.params,
                    self.kv, self._base_key, operand(layout, p),
                    rows=(p * bucket,))
                if self.sp > 1 and bucket % self.sp == 0:
                    self.kv, _, _ = run(
                        f"prefill_sp {shape}", self._prefill_sp_jit,
                        self.params, self.kv, self._base_key,
                        operand(layout, p), rows=(p * bucket,))

        def warm_carry(label, jitted, b, *operands, rows):
            """One decode-side graph at rung ``b``. Deeper than 1 the
            program takes a carry: warmed on the null one, then run
            once more on its own outputs, which is what serving hands
            it (no second graph unless their placement differs)."""
            carry = self._null_carry.get(b)
            if carry is None:
                return run(label, jitted, self.params, self.kv,
                           self._base_key, *operands, rows=rows)
            out = run(label, jitted, self.params, self.kv, self._base_key,
                      *operands, carry, rows=rows)
            return jitted(self.params, out[0], self._base_key, *operands,
                          out[-2:])

        if not warm_decode:
            return self._warmup_done(t0, graphs, timeline)
        # EVERY ladder rung compiles here: continuous batching moves
        # between rung graphs as occupancy changes, and a rung first
        # reached mid-serving must find its executable warm (the
        # mid-serving-compile failure mode ADVICE r3 flagged).
        for b in self.ladder:
            self.kv = warm_carry(
                f"decode b={b}", self._decode_multi_jit, b,
                operand(self._decode_layout, b), rows=(b,))[0]
            if self._decode_one_jit is not self._decode_multi_jit:
                # The 1-step graph is a second full decode compile,
                # but decode_step()/decode_steps(max_steps=1) route
                # to it regardless of latency mode — warm it whenever
                # it's a distinct graph or a first single-step call
                # pays a full XLA compile mid-serving (ADVICE r3).
                self.kv, _, _, _ = run(
                    f"decode b={b}", self._decode_one_jit, self.params,
                    self.kv, self._base_key,
                    operand(self._decode_layout, b), rows=(b,))
        if self.spec_enabled:
            # The verify-only graph compiles at EVERY ladder rung x
            # EVERY active verify width (the full γ+1 round AND the
            # narrow probe round; per-sequence adaptive γ below the
            # width lives in n_prop masking, never a new shape). The
            # γ=0 fallback rounds run the plain decode graphs warmed
            # above — between the three, no speculating
            # dispatch can meet a cold executable mid-serving (the
            # test_ladder.py zero-compile pin, extended).
            for b in self.ladder:
                for width in self._spec_widths:
                    out = run(
                        f"spec_verify b={b} w={width}",
                        self._verify_jit, self.params, self.kv,
                        jnp.zeros((b,), jnp.int32),
                        jnp.zeros((b,), jnp.int32),
                        jnp.zeros((b, self.bt_width), jnp.int32),
                        jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool),
                        jnp.zeros((b, width - 1), jnp.int32),
                        jnp.zeros((b,), jnp.int32), self._next_key(),
                        jnp.zeros((b,), jnp.float32),
                        jnp.ones((b,), jnp.float32),
                        jnp.zeros((b,), jnp.int32),
                        jnp.ones((b,), jnp.float32),
                        jnp.zeros((b,), jnp.int32),
                        jnp.full((b, PENALTY_WINDOW), -1, jnp.int32))
                    self.kv = out.kv
        if ecfg.hybrid_prefill and not self.spec_enabled and warm_prefill:
            # One hybrid graph per REACHABLE prefill bucket per ladder
            # rung (the decode half dispatches at the current rung), so
            # the first long prompt under mixed traffic doesn't pay an
            # XLA compile mid-serving. Hybrid chunks never exceed the
            # chunk cap (budget pressure only shrinks them), so buckets
            # above bucket_for(cap) are unreachable and compiling them
            # would only slow boot — the compile count stays bounded at
            # reachable_buckets x rungs.
            bucket_cap = ecfg.bucket_for(
                min(ecfg.chunk_tokens_cap, ecfg.max_context))
            for bucket in ecfg.prefill_buckets:
                if bucket > ecfg.max_context or bucket > bucket_cap:
                    continue
                for b in self.ladder:
                    self.kv = warm_carry(
                        f"hybrid 1x{bucket} b={b}", self._hybrid_jit, b,
                        operand(self._prefill_layout(bucket), 1),
                        operand(self._decode_layout, b),
                        rows=(bucket, b))[0]
        return self._warmup_done(t0, graphs, timeline)

    def _warmup_done(self, t0: float, graphs: int,
                     timeline: List[dict]) -> float:
        jax.block_until_ready(self.kv)
        self.warmup_s = time.perf_counter() - t0
        self.warmup_graphs = graphs
        self.telemetry.boot_warmup_s.set(self.warmup_s)
        # One structured event for the whole boot: where its seconds
        # went, graph by graph (a graph's seconds are its call's wall:
        # compile or cache fetch + enqueue; the runs themselves overlap
        # the next call and end inside ``warmup_s``).
        telemetry.log_event(
            "boot_timeline", level="warning",
            weights_s=round(self.boot_s["weights"], 3),
            pool_s=round(self.boot_s["pool"], 3),
            warmup_s=round(self.warmup_s, 3), graphs=timeline)
        return self.warmup_s

    def embed(self, token_ids: List[int]) -> np.ndarray:
        """Mean-pooled final hidden state for one token sequence (the
        Ollama /api/embeddings backing). See embed_many."""
        return self.embed_many([token_ids])[0]

    # Max rows per embedding dispatch; lane counts pad to powers of two,
    # so compiles are bounded at ~5 batch shapes x sequence buckets and
    # one huge /api/embed list can't build an unbounded [N, S] forward.
    EMBED_CHUNK = 16

    def embed_many(self, batch: List[List[int]]) -> np.ndarray:
        """Mean-pooled final hidden states for N token sequences, batched
        into dense (cache-free) [n, S] forwards of at most EMBED_CHUNK
        rows — an /api/embed list input costs ceil(N/chunk) dispatches,
        not N. Sequence buckets are chosen per chunk; per-row length
        masks make padding invariant (pad sits causally after each row's
        valid tokens). Returns [N, d_model] f32."""
        from tpu_inference.models.common import make_dense_attn

        self._refuse_dense_forward("embed_many")
        ecfg = self.engine_cfg
        if not batch:
            return np.zeros((0, self.model_cfg.d_model), np.float32)
        # Cap at the largest compiled bucket (bucket_for saturates there,
        # and the zero-padded buffer is bucket-sized).
        cap = min(ecfg.max_context - 1, ecfg.prefill_buckets[-1])
        rows = [list(ids)[-cap:] or [0] for ids in batch]
        with self._embed_lock:
            # Lazy singleton under a lock: concurrent first requests from
            # the server's worker threads must not each pay the compile.
            if self._embed_jit is None:
                cfg = self.model_cfg

                def fn(params, tokens, lengths):
                    s = tokens.shape[1]
                    pos = jnp.broadcast_to(
                        jnp.arange(s, dtype=jnp.int32)[None], tokens.shape)
                    hidden, _ = self.mod.forward_hidden(
                        params, cfg, tokens, pos, None,
                        make_dense_attn(cfg.sliding_window))
                    mask = (jnp.arange(s)[None, :] <
                            lengths[:, None])[..., None]
                    pooled = (jnp.sum(hidden * mask, axis=1)
                              / jnp.maximum(lengths[:, None], 1))
                    return pooled.astype(jnp.float32)

                self._embed_jit = jax.jit(fn)
        out = []
        for at in range(0, len(rows), self.EMBED_CHUNK):
            chunk = rows[at:at + self.EMBED_CHUNK]
            bucket = ecfg.bucket_for(max(len(r) for r in chunk))
            n = 1 << (len(chunk) - 1).bit_length()     # pad lanes to 2^k
            toks = np.zeros((n, bucket), np.int32)
            lengths = np.zeros((n,), np.int32)
            for i, r in enumerate(chunk):
                toks[i, :len(r)] = r
                lengths[i] = len(r)
            pooled = self._embed_jit(self.params, jnp.asarray(toks),
                                     jnp.asarray(lengths))
            out.append(np.asarray(pooled)[:len(chunk)])
        return np.concatenate(out, axis=0)

    def _refuse_dense_forward(self, what: str) -> None:
        """embed_many / check_numerics run the model cache-free through
        ``common.make_dense_attn``, ONE attention function for every
        layer: a stack of kinds calls ``attn.kinds[kind]`` and would
        fail inside the trace with a TypeError."""
        if self.model_cfg.layer_types:
            raise ValueError(
                f"{self.model_cfg.name} (layers of mixed kinds) does not "
                f"support {what}: its cache-free forward needs an "
                "attention function a kind (the family's "
                "make_dense_attn), which this path does not build")

    def check_numerics(self) -> None:
        """Numerics sanitizer (SURVEY.md §5 race/sanitizer tier).

        Fails fast if any param leaf is non-finite, then runs one
        checkify'd forward (NaN/inf float checks compiled into the graph)
        on tiny inputs. Use at startup after loading a checkpoint, or from
        debug tooling after a suspect update. For always-on checking, run
        with ``--debug-nans`` (jax_debug_nans) instead — it re-runs any
        NaN-producing op un-jitted and pinpoints it.
        """
        from jax.experimental import checkify

        from tpu_inference.models.common import make_dense_attn

        self._refuse_dense_forward("check_numerics")
        leaves = jax.tree_util.tree_flatten_with_path(self.params)[0]
        bad = [jax.tree_util.keystr(path) for path, x in leaves
               if not bool(jnp.isfinite(x).all())]
        if bad:
            raise FloatingPointError(
                f"non-finite values in params at {bad}")

        cfg = self.model_cfg

        def fwd(params, tokens, positions):
            hidden, _ = self.mod.forward_hidden(
                params, cfg, tokens, positions, None,
                make_dense_attn(cfg.sliding_window))
            return self.mod.unembed(params, cfg, hidden)

        toks = jnp.zeros((1, 8), jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32)[None], (1, 8))
        err, _ = jax.jit(checkify.checkify(
            fwd, errors=checkify.float_checks))(self.params, toks, pos)
        err.throw()

    # -- Decode dispatch/bubble accounting (telemetry.py phase model).

    def _run(self, kind: str, jitted, args: tuple, *, rung: int = 0,
             slots: int = 0, tokens: int = 0,
             chunk_tokens: int = 0) -> Tuple[Any, int, float, float]:
        """Enqueue one step program: the ``enqueue`` phase of the loop
        clock around the jitted call, a fresh dispatch number, and —
        only while a profile is being captured — a ``tpu_inf/dispatch``
        annotation carrying that number and the dispatch's shape, so
        ledger records and trace events join on ``seq``. Returns
        (outputs, seq, instant the call began, instant it returned).
        ``tokens`` is what the dispatch may generate (its grants)."""
        clock = self.telemetry.clock
        seq = self._dispatch_seq = self._dispatch_seq + 1
        t0 = clock.enter("enqueue")
        if telemetry.profile_capturing():
            with jax.profiler.TraceAnnotation(
                    "tpu_inf/dispatch", kind=kind, seq=seq, rung=rung,
                    slots=slots, tokens=tokens, chunk_tokens=chunk_tokens):
                out = jitted(*args)
        else:
            out = jitted(*args)
        return out, seq, t0, clock.dispatched(seq)

    def _observed(self, seq: int, now: float) -> None:
        """The host has read back a result of dispatch ``seq`` at
        ``now``: it and every program enqueued before it are done.
        Prefill chunks pushed to the ledger at enqueue (nobody reads a
        non-final chunk's token) get their ``t_done`` and true
        ``device_s`` here, from a readback the engine performs anyway —
        for a non-final chunk that is the next readback behind it, so
        its ``t_done`` is an upper bound. ``now`` is also the earliest
        instant the device is known free for what is still in flight
        (_sync_oldest's ``device_s``)."""
        tel = self.telemetry
        tel.clock.observed(seq)
        self._device_free_at = now
        while self._unsettled and self._unsettled[0][0] <= seq:
            chunk_seq, t_enq, stalled = self._unsettled.pop(0)
            tel.step_ledger.settle(chunk_seq, tel.recorder.to_unix(now))
            if stalled:
                tel.decode_stall_during_prefill_s.observe(now - t_enq)

    def _wait(self, seq: int, read: Callable[[], Any]
              ) -> Tuple[Any, float, float]:
        """A blocking readback of dispatch ``seq``'s result: ``read()``
        under the ``device_wait`` phase, then the dispatch counts as
        observed. Returns (what ``read`` returned, the instant the wait
        began, the instant it ended)."""
        clock = self.telemetry.clock
        t_wait = clock.enter("device_wait")
        out = read()
        t_done = clock.enter("other")
        self._observed(seq, t_done)
        return out, t_wait, t_done

    def _run_decode(self, kind: str, jitted, args: tuple, *, rung: int,
                    slots: int, tokens: int, chunk_tokens: int = 0
                    ) -> Tuple[Any, int, float, float]:
        """``_run`` for a dispatch with decode lanes, and the one place
        that notes the host-side bubble before it (the gap since the
        last decode dispatch or sync ended, while the decode streak is
        unbroken) and its enqueue wall after. Returns (outputs, seq,
        the instant the jitted call began, the enqueue wall)."""
        out, seq, t0, t1 = self._run(kind, jitted, args, rung=rung,
                                     slots=slots, tokens=tokens,
                                     chunk_tokens=chunk_tokens)
        tel = self.telemetry
        last = self._last_decode_end
        self._pending_bubble = 0.0
        if last is not None and tel.enabled:
            tel.dispatch_bubble_s.observe(t0 - last)
            self._pending_bubble = t0 - last   # step-ledger host-bound input
        tel.decode_dispatch_s.observe(t1 - t0)
        tel.decode_dispatches.inc()
        self._decode_streak(t1)
        return out, seq, t0, t1 - t0

    def _decode_streak(self, now: float) -> None:
        """Refresh the bubble's reference point: ``now`` ends a decode
        enqueue or a blocking sync (device time, not a host bubble). The
        streak survives only while some sequence is still live —
        cross-idle gaps are not bubbles."""
        self._last_decode_end = (
            now if any(s is not None and not s.done for s in self.slots)
            else None)

    def _fold_aux_stats(self, outs: np.ndarray) -> None:
        """The model's counts that rode a decode readback ``outs``
        [K, rung + n] behind the lanes' tokens (_decode_multi_fn)."""
        if self.aux_stats is not None:
            rows, at = outs[:, -len(self.aux_stats):], self._aux_max_slots
            seen = np.maximum(self.aux_stats[at], rows[:, at].max(axis=0))
            self.aux_stats += rows.sum(axis=0, dtype=np.int64)
            self.aux_stats[at] = seen

    def _next_step(self) -> int:
        """The next dispatch's step number: its sampling key is
        fold_in(base key, this), folded inside the step program."""
        self._step_count += 1
        return self._step_count

    def _next_key(self) -> jax.Array:
        """The same key folded eagerly, for the programs that take their
        operands one by one (the speculative rounds)."""
        return jax.random.fold_in(self._base_key, self._next_step())

    def _put_operands(self, *hosts: np.ndarray) -> tuple:
        """Hand one step program its packed host operands: the one
        place a dispatch's small operands cross to the device, one
        transfer an array (one a dispatch; a hybrid call's two). The
        arrays must not be written again: on the CPU the device array
        may alias the numpy memory."""
        tel = self.telemetry
        tel.stage_dispatches.inc()
        tel.stage_transfers.inc(len(hosts))
        return tuple(jax.device_put(h, self._operand_sharding)
                     for h in hosts)

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def _prefill_tokens(self, seq: Sequence) -> List[int]:
        """Token stream the next (re)prefill must put into KV: the
        original prompt, plus — on a recompute-resume — every token
        generated before the preemption."""
        if seq.resume_base:
            return seq.prompt_tokens + seq.generated[:seq.resume_base]
        return seq.prompt_tokens

    def _pages_reserved(self, seq: Sequence) -> int:
        """Worst-case page need for admission control (capped at the
        per-sequence maximum, since ctx is clamped to max_context).

        With behind-window eviction the worst case is NOT prompt +
        max_new: live pages peak at the full prompt during prefill (no
        eviction until the first decode token), then drop to the
        window's span (+1 for the head page being written, +1 for
        window/page misalignment) — long-generation requests must not
        be queued for capacity they will never hold."""
        ecfg = self.engine_cfg
        base = self._prefill_tokens(seq)
        total = len(base) + seq.max_new_tokens - seq.resume_base
        need = kvc.pages_needed(total, ecfg.page_size)
        if self.swa_evict and self.win_allocator is None:
            # (With a pool a kind this is the full kind's need, which no
            # window bounds: _window_pages_reserved is the other's.)
            # Dispatch-ahead can grant depth*K tokens of head pages
            # before eviction (at the fold) catches up — include them.
            win = self.model_cfg.sliding_window
            ahead = (ecfg.decode_steps_per_call
                     * max(1, ecfg.decode_pipeline_depth))
            window_span = -(-(win + ahead) // ecfg.page_size) + 2
            # The post-prefill transient: dispatch-ahead grants up to
            # ``ahead`` decode tokens (head pages allocated) BEFORE the
            # first fold-time eviction frees any behind-window page, so a
            # long-prompt sequence briefly holds its whole prompt PLUS
            # the dispatch-ahead burst (ADVICE r4: charging only the
            # prefill peak degrades to a decode stall under a
            # fully-committed pool).
            peak_tokens = min(len(base), ecfg.max_context)
            transient = kvc.pages_needed(
                min(peak_tokens + ahead, ecfg.max_context), ecfg.page_size)
            need = min(need, max(window_span, transient))
        return min(need, self.max_pages)

    def _pages_for_admission(self, seq: Sequence) -> int:
        """Pages a request is charged at admission. "reserve" mode —
        and the starvation guard's re-admission after
        preempt_max_per_request preemptions — charge the full worst
        case; "optimistic" charges the prompt footprint plus a small
        decode headroom, with watermark preemption as the safety net."""
        full = self._pages_reserved(seq)
        if (self.admission != "optimistic"
                or seq.preemptions >= self.engine_cfg.preempt_max_per_request):
            return full
        ecfg = self.engine_cfg
        prompt_pages = kvc.pages_needed(
            min(len(self._prefill_tokens(seq)), ecfg.max_context),
            ecfg.page_size)
        need = max(1, prompt_pages + ecfg.optimistic_headroom_pages)
        return min(full, need, self.max_pages)

    def _window_pages_reserved(self, seq: Sequence) -> int:
        """Window-kind pages a sequence is charged for its whole life
        (0: the model has one kind): its tokens' pages, or the window's
        span where behind-window release bounds them."""
        if self.win_allocator is None:
            return 0
        total = (len(self._prefill_tokens(seq)) + seq.max_new_tokens
                 - seq.resume_base)
        need = min(kvc.pages_needed(total, self.engine_cfg.page_size),
                   self.max_pages)
        return min(need, self.window_span) if self.swa_evict else need

    def admission_need(self, seq: Sequence) -> np.ndarray:
        """What a request is charged at admission: pages a kind, [full
        (the only kind of most models), window], and state slots (one
        where the model has state-space layers)."""
        return np.asarray([self._pages_for_admission(seq),
                           self._window_pages_reserved(seq),
                           int(self.state_slots is not None)], np.int64)

    def admission_fits(self, want: np.ndarray, headroom: int = 0) -> bool:
        """Whether ``want`` pages a kind (admission_need, summed over the
        requests one pass selects) can be granted. A model of one kind
        compares with what is free or evictable now. With a pool a kind
        every bound sequence's WHOLE charge is held back too, taken or
        not yet, so that no pool can run out under a sequence that was
        admitted: pressure in either kind is a wait at admission. A
        model with state-space layers also needs a free state slot a
        request."""
        room = self._free_plus_evictable() - headroom
        if self.state_slots is not None and \
                self.state_slots.num_free < want[2]:
            return False        # a state slot a sequence
        if self.win_allocator is None:
            return room >= want[0]
        usable = np.asarray([self.engine_cfg.num_pages - 1,
                             self.win_allocator.num_pages - 1])
        booked = self.pages_booked_seen = self.pages_booked()
        self.pages_booked_peak = np.maximum(self.pages_booked_peak, booked)
        left = usable - booked
        return bool(min(room, left[0] - headroom) >= want[0]
                    and min(self.win_allocator.num_free, left[1]) >= want[1])

    def pages_booked(self) -> np.ndarray:
        """Pages a kind, [full, window], that the bound sequences are
        charged for their whole lives (admission_need), taken or not
        yet. The engine loop's to call: it reads live sequences."""
        bound = [s for s in self.slots if s is not None and not s.done]
        return sum((self.admission_need(s) for s in bound),
                   np.zeros(3, np.int64))[:2]

    def _free_plus_evictable(self) -> int:
        n = self.allocator.num_free
        if self.prefix_cache is not None:
            n += self.prefix_cache.evictable
        return n

    def peek_prefix_pages(self, tokens: Sequence[int]) -> Tuple[int, int]:
        """(hit_pages, prompt_pages) the dp router scores this replica
        with: how many full KV pages of ``tokens`` this engine's prefix
        cache already holds, and how many pages the prompt needs in
        total. Mirrors _prefill_setup's truncation (keep the most recent
        max_context-1 tokens) and its max_tokens cap (the final prompt
        token is always recomputed for logits), so the peek counts
        exactly the pages a real prefill here could reuse.

        Side-effect-free and safe to call from any thread (PrefixCache.
        peek contract); the answer may be stale by the time the request
        prefills — the router tolerates that, the prefill re-checks.
        """
        ecfg = self.engine_cfg
        prompt_len = min(len(tokens), ecfg.max_context - 1)
        prompt_pages = kvc.pages_needed(prompt_len, ecfg.page_size)
        if self.prefix_cache is None or prompt_len <= 1:
            return 0, prompt_pages
        prompt = (tokens[-prompt_len:] if len(tokens) > prompt_len
                  else tokens)
        hit = self.prefix_cache.peek(prompt, max_tokens=prompt_len - 1)
        return hit, prompt_pages

    @property
    def pool_pressure(self) -> float:
        """1 - (free+evictable)/total: 0 = fully reclaimable, 1 = every
        page pinned by a running sequence (or chaos pressure)."""
        total = self.engine_cfg.num_pages - 1
        return 1.0 - self._free_plus_evictable() / max(total, 1)

    @property
    def under_pressure(self) -> bool:
        """Below the preemption low watermark — the router prefers
        replicas where this is False."""
        return (self._free_plus_evictable()
                < self.engine_cfg.preempt_watermark_pages)

    def set_page_pressure(self, n_pages: int) -> int:
        """Arm/disarm chaos_page_pressure: hold ``n_pages`` real pages
        out of the pool (clamped to what is currently free) so the
        exhaustion/preemption paths run deterministically on CPU.
        Returns the number of pages actually held.

        Mutates the allocator — call only from the engine thread (or
        while no scheduler is running); other threads use
        request_page_pressure and the engine loop applies it."""
        self.allocator.free(self._pressure_pages)
        self._pressure_pages = []
        n = max(0, min(int(n_pages), self.allocator.num_free))
        if n > 0:
            self._pressure_pages = self.allocator.allocate(n)
        self.chaos_page_pressure = len(self._pressure_pages)
        return self.chaos_page_pressure

    def request_page_pressure(self, n_pages: int) -> int:
        """Thread-safe arm/disarm request: stores the target (atomic
        int store); the scheduler loop applies it on the engine thread
        within one iteration. Returns the requested target."""
        n = max(0, int(n_pages))
        self._pressure_target = n
        return n

    def apply_pending_page_pressure(self) -> None:
        """Apply a cross-thread pressure request (engine thread only)."""
        target = self._pressure_target
        if target is not None:
            self._pressure_target = None
            self.set_page_pressure(target)

    def _allocate_reclaiming(self, n: int) -> List[int]:
        """Allocate n pages, evicting LRU prefix-cache pages on pressure —
        cached pages are reclaimable capacity, never reserved memory.
        With a host tier attached, the eviction DEMOTES pages to host
        RAM (engine/prefix_cache.py) instead of dropping their KV."""
        short = n - self.allocator.num_free
        if short > 0 and self.prefix_cache is not None:
            if self.host_pool is not None:
                # Demotes pay one device-stream sync per offload batch:
                # evict at least a swap chunk's worth so steady churn
                # amortizes the sync instead of paying it per page —
                # capped at the host tier's CAPACITY, so a tiny tier
                # never has its over-evicted extras destroyed (beyond
                # capacity they would land in the void, not the tier).
                short = max(short, min(kvc.SWAP_CHUNK,
                                       self.host_pool.capacity))
            self.prefix_cache.evict(short)
        return self.allocator.allocate(n)

    # ------------------------------------------------------------------
    # Tiered KV cache: device<->host page swaps (README "Tiered KV cache")
    # ------------------------------------------------------------------

    def _offload_pages(self, pages: List[int]) -> List["kvc.HostKVPage"]:
        """Demote-time device->host copy (the prefix cache's offload_fn):
        one bundled transfer for the whole victim batch, with swap
        telemetry. Engine thread only (reads the live pool)."""
        clock = self.telemetry.clock
        prev = clock.phase
        t0 = clock.enter("swap")
        out = kvc.offload_pages(self.kv, pages)
        t1 = clock.enter(prev or "other")
        if out:
            # The device_get read the live pool: every dispatch so far
            # has settled.
            self._observed(self._dispatch_seq, t1)
        if out and self.host_pool is not None:
            # Pool accounting is part of the tier's stats surface (like
            # offloaded/restored totals) — NOT gated on telemetry.
            self.host_pool.note_swap_wall("out", t1 - t0)
        tel = self.telemetry
        if tel.enabled and out:
            tel.kv_swap_s.observe(t1 - t0)
            tel.kv_offload_pages.inc(len(out))
            nbytes = sum(hp.nbytes for hp in out)
            tel.kv_offload_bytes.inc(nbytes)
            # Swap-out spans have no single owning request (eviction
            # batches mix victims): they land in the recorder's
            # maintenance lane of the Chrome timeline instead.
            tel.recorder.add_maintenance("kv_swap_out", t0, t1,
                                         pages=len(out), bytes=nbytes)
        return out

    def _restore_batch(self, fresh: List[int],
                       entries: List["kvc.HostKVPage"],
                       trace_id: str = "") -> None:
        """Scatter host page copies into freshly allocated device pages
        (async dispatch — a following prefill chains behind it on
        device) and record swap telemetry. ``trace_id`` attributes the
        swap-in span to the request that triggered it (empty = a
        maintenance-lane span)."""
        clock = self.telemetry.clock
        prev = clock.phase
        t0 = clock.enter("swap")
        self.kv = kvc.restore_pages(self.kv, fresh, entries)
        t1 = clock.enter(prev or "other")
        if self.host_pool is not None:
            # Pool accounting is part of the tier's stats surface —
            # NOT gated on telemetry (offloaded/restored totals aren't).
            self.host_pool.note_swap_wall("in", t1 - t0)
        tel = self.telemetry
        if tel.enabled:
            tel.kv_swap_s.observe(t1 - t0)
            tel.kv_restore_pages.inc(len(fresh))
            nbytes = sum(e.nbytes for e in entries)
            tel.kv_restore_bytes.inc(nbytes)
            if trace_id:
                tel.recorder.add("kv_swap_in", trace_id, t0, t1,
                                 pages=len(fresh), bytes=nbytes)
            else:
                tel.recorder.add_maintenance("kv_swap_in", t0, t1,
                                             pages=len(fresh),
                                             bytes=nbytes)

    def _restore_host_entries(self, pages: List[Optional[int]],
                              host_entries,
                              trace_id: str = "") -> List[int]:
        """Fill the host-tier slots of a tiered lookup result: allocate
        fresh device pages, swap the host copies in, and publish the
        restored digests back into the HBM tier (promote). On
        allocation failure every reference taken by the lookup is
        undone (HBM refs freed, host entries readmitted) and the
        MemoryError propagates — same contract as a cold allocation
        shortfall in _prefill_setup."""
        if not host_entries:
            return list(pages)
        try:
            fresh = self._allocate_reclaiming(len(host_entries))
        except MemoryError:
            self.allocator.free([p for p in pages if p is not None])
            self.prefix_cache.readmit_host(
                [(d, e) for _, d, e in host_entries])
            raise
        self._restore_batch(fresh, [e for _, _, e in host_entries],
                            trace_id=trace_id)
        out = list(pages)
        for (i, digest, _), page in zip(host_entries, fresh):
            out[i] = page
            self.prefix_cache.promote(digest, page)
        return out

    def _seq_digests(self, seq: Sequence,
                     prompt: List[int]) -> List[bytes]:
        """Chain digests of ``prompt`` (the truncated prefill stream),
        computed ONCE per fresh request and cached on the Sequence (the
        router's scoring pass may have filled them already — the
        triple-hash fix). Resume streams include generated tokens and
        may have shifted the truncation window, so they hash into their
        OWN cache slot, valid until the next preemption (preempt()
        clears it) — a queue-waiting resume being prefetched over
        several partial passes must not rehash a long stream per pass."""
        from tpu_inference.engine.prefix_cache import _chain_hashes
        if seq.resume_base:
            if seq.resume_digests is None:
                seq.resume_digests = _chain_hashes(
                    prompt, self.engine_cfg.page_size)
            return seq.resume_digests
        if seq.prefix_digests is None:
            seq.prefix_digests = _chain_hashes(prompt,
                                               self.engine_cfg.page_size)
        return seq.prefix_digests

    def prefetch_host_hits(self, seq: Sequence) -> int:
        """Queue-wait swap-in: restore a WAITING request's host-tier
        pages into cache-owned device pages, so its eventual admission
        sees plain HBM hits and prefill starts warm — the swap overlaps
        the queue wait instead of sitting in TTFT.

        Only genuinely free pages are used (prefetch never evicts
        someone else's warmth), the restore dispatch is async, and the
        promoted pages are ordinary evictable cache entries — pressure
        can re-demote them if the request never admits. Partial
        restores (free list shorter than the host hits) keep the
        request eligible for another pass next loop iteration.
        Returns pages promoted. Engine thread only."""
        if (self.prefix_cache is None or self.host_pool is None
                or seq.host_prefetched or seq.done):
            return 0
        free = self.allocator.num_free
        if free <= 0:
            # Retry when pages free up — checked BEFORE any prompt/hash
            # work: this runs every scheduler iteration while the head
            # request waits, and a full pool (the watermark-pressure
            # steady state) must cost O(1), not a rehash of a multi-
            # thousand-token resume stream.
            return 0
        ecfg = self.engine_cfg
        prompt = self._prefill_tokens(seq)[-(ecfg.max_context - 1):]
        if len(prompt) <= 1:
            seq.host_prefetched = True
            return 0
        digests = self._seq_digests(seq, prompt)
        limit = (len(prompt) - 1) // ecfg.page_size
        taken = self.prefix_cache.take_host_matches(digests, limit)
        if not taken:
            seq.host_prefetched = True
            return 0
        complete = len(taken) <= free
        if not complete:
            # Keep the FRONT of the run (later pages are unusable
            # without the earlier ones) and return the rest.
            self.prefix_cache.readmit_host(taken[free:])
            taken = taken[:free]
        fresh = self.allocator.allocate(len(taken))
        self._restore_batch(fresh, [e for _, e in taken],
                            trace_id=seq.trace_id or str(seq.request_id))
        for (digest, _), page in zip(taken, fresh):
            self.prefix_cache.adopt(digest, page)
        if complete:
            seq.host_prefetched = True
        return len(taken)

    # ------------------------------------------------------------------
    # KV page migration (README "Process fleet"): drain-time export of a
    # live sequence's KV pages in the host serialization layout, and
    # import of a sibling replica's export into this engine's host tier.
    # ------------------------------------------------------------------

    def _tokens_in_kv(self, seq: Sequence, drop_last: bool = False
                      ) -> List[int]:
        """The tokens actually resident in the sequence's KV pages, in
        page order: the prefill stream under the same max_context
        truncation the prefill used, plus the generated suffix
        (``drop_last`` excludes the just-sampled token the cache
        publish runs before writing back). The ONE stream
        reconstruction shared by _publish_to_cache, export_sequence_kv,
        and export_sequence_kv_live — their chain digests must never
        diverge."""
        base = self._prefill_tokens(seq)[-(self.engine_cfg.max_context
                                           - 1):]
        gen = seq.generated[seq.resume_base:]
        return base + (gen[:-1] if drop_last else gen)

    def _refuse_state_export(self) -> None:
        """A sequence's pages are not all of it where a token advances a
        state: nothing exports or adopts the state slot."""
        if self.state_slots is not None:
            raise ValueError(
                f"{self.model_cfg.name} "
                f"({_WHAT_IT_IS[model_columns(self.model_cfg)[-1]]}) does "
                "not support KV export / adoption: "
                + _WHY_NOT["role"][model_columns(self.model_cfg)[-1]])

    def export_sequence_kv(self, seq: Sequence
                           ) -> Tuple[List[bytes], List["kvc.HostKVPage"]]:
        """Drain-time migration export: (chain digests, host page
        copies) for the sequence's full, settled KV pages — prompt plus
        generated-so-far, exactly the stream a destination's
        recompute-resume prefill will hash, so the import lands as
        host-tier hits there and admission becomes a swap-in-resume.

        Only the contiguous run of full, non-SWA-evicted pages from
        page 0 exports (a chain hit must be contiguous from the start;
        the partial last page recomputes at the destination). Call with
        the scheduler stopped and the pipeline drained — it reads the
        live pool."""
        self._refuse_state_export()
        from tpu_inference.engine.prefix_cache import _chain_hashes
        if not seq.pages or seq.ctx_len <= 0:
            return [], []
        ecfg = self.engine_cfg
        in_kv = self._tokens_in_kv(seq)[:seq.ctx_len]
        digests = _chain_hashes(in_kv, ecfg.page_size)
        n = min(len(digests), len(seq.pages))
        run = 0
        while run < n and seq.pages[run] != 0:
            run += 1
        if run == 0:
            return [], []
        host = self._offload_pages(seq.pages[:run])
        self.migrate_out_pages += len(host)
        self.migrate_out_bytes += sum(hp.nbytes for hp in host)
        return digests[:run], host

    def export_sequence_kv_live(self, seq: Sequence
                                ) -> Tuple[List[bytes],
                                           List["kvc.HostKVPage"], int]:
        """P/D handoff export (README "P/D disaggregation"): the settled
        KV of a LIVE sequence — (full-page chain digests, host page
        copies, ctx_len). Unlike the drain export, the page list covers
        EVERY page holding the first ctx_len tokens, INCLUDING the
        partial final page: the destination restores it verbatim (its
        trailing rows are dead weight no reader past ctx_len touches)
        and resumes decode with zero recomputed tokens, where the
        drain/migrate path stops at the last full page and recomputes
        the remainder. Digests still cover only the full pages (a chain
        digest is defined on full pages) for host-tier import fallback.

        Returns ([], [], 0) when the sequence has no exportable KV
        (empty, or SWA-evicted pages punch holes in the run) — the
        caller then keeps the sequence local instead of handing off.
        Engine thread only; the offload's device_get orders after any
        in-flight dispatch by data dependency."""
        self._refuse_state_export()
        from tpu_inference.engine.prefix_cache import _chain_hashes
        if not seq.pages or seq.ctx_len <= 0:
            return [], [], 0
        ecfg = self.engine_cfg
        n_pages = -(-seq.ctx_len // ecfg.page_size)
        pages = seq.pages[:n_pages]
        if len(pages) < n_pages or any(p == 0 for p in pages):
            return [], [], 0
        in_kv = self._tokens_in_kv(seq)[:seq.ctx_len]
        digests = _chain_hashes(in_kv, ecfg.page_size)
        host = self._offload_pages(pages)
        self.handoffs_out += 1
        self.handoff_out_pages += len(host)
        return digests[:seq.ctx_len // ecfg.page_size], host, seq.ctx_len

    def adopt_sequence(self, seq: Sequence) -> int:
        """P/D handoff adoption (engine thread, at admission): restore
        the handoff's KV pages (seq.adopt_kv, incl. the partial final
        page) straight into freshly allocated device pages, bind a slot,
        and resume DECODE — no prefill dispatch runs, so nothing is
        recomputed and greedy continuation is byte-identical to the
        mixed topology by construction (same pool bytes, same last
        token). Raises on a malformed blob or pool shortfall; the
        scheduler's fallback then clears adopt_kv and recompute-resumes
        through the ordinary prefill path instead."""
        self._refuse_state_export()
        host_pages, ctx_len = seq.adopt_kv
        ecfg = self.engine_cfg
        expected = -(-ctx_len // ecfg.page_size)
        if ctx_len <= 0 or len(host_pages) != expected:
            raise ValueError(
                f"handoff blob has {len(host_pages)} pages for "
                f"ctx_len={ctx_len} (need {expected})")
        slot = self.free_slots()[0]
        seq.admit_idx = self._admit_counter
        self._admit_counter += 1
        fresh = self._allocate_reclaiming(len(host_pages))
        try:
            self._restore_batch(fresh, host_pages,
                                trace_id=seq.trace_id
                                or str(seq.request_id))
        except BaseException:
            self.allocator.free(fresh)
            raise
        seq.pages = fresh
        seq.pages_version += 1
        seq.ctx_len = ctx_len
        seq.slot = slot
        seq.adopt_kv = None
        # The whole resume stream (prompt + the tokens the handoff
        # replays) arrives as settled KV or recorded tokens — nothing
        # recomputes. cached_tokens reports exactly that to the
        # router's reused-vs-recomputed accounting.
        seq.cached_tokens = min(ctx_len + seq.resume_base,
                                ecfg.max_context - 1)
        seq.host_restored_pages += len(host_pages)
        now = time.perf_counter()
        seq.prefill_start = seq.prefill_start or now
        seq.first_token_time = now
        seq.adopted = True
        self.adoptions_in += 1
        self.swap_in_resumes += 1
        self.slots[slot] = seq
        return slot

    def request_import_host(self, entries) -> threading.Event:
        """Queue migrated (digest, HostKVPage) entries for adoption into
        the host tier. Any thread; returns an Event set once the engine
        loop has applied the import — the worker's import-kv RPC replies
        only then, so a subsequently submitted request is guaranteed to
        see the pages at prefill time."""
        done = threading.Event()
        with self._pending_imports_lock:
            self._pending_imports.append((list(entries), done))
        return done

    def apply_pending_imports(self) -> None:
        """Adopt queued migration imports (engine thread — called by the
        scheduler loop right before admission, next to
        apply_pending_page_pressure). No-ops without a host tier, but
        always signals completion so RPC callers never hang."""
        with self._pending_imports_lock:
            pending, self._pending_imports = self._pending_imports, []
        for entries, done in pending:
            try:
                if self.prefix_cache is not None and self.host_pool is not None:
                    # Pool-delta accounting: import_host may SKIP
                    # already-resident digests anywhere in the list, so
                    # summing a prefix of ``entries`` would charge the
                    # wrong pages' bytes.
                    bytes_before = self.host_pool.import_bytes_total
                    self.migrate_in_pages += self.prefix_cache.import_host(
                        entries)
                    self.migrate_in_bytes += (
                        self.host_pool.import_bytes_total - bytes_before)
            finally:
                done.set()

    def _grant_decode_steps(self, seq: Sequence, k_steps: int,
                            pred_ctx: Optional[int] = None,
                            pred_done: Optional[int] = None) -> int:
        """Steps this lane may advance in one fused call — folds the
        generation budget, the context cap, and KV-page headroom — and
        allocates the pages it needs. ``pred_*`` override ctx/generated
        with predicted values while dispatch-ahead calls are in flight."""
        ecfg = self.engine_cfg
        ctx = seq.ctx_len if pred_ctx is None else pred_ctx
        done = len(seq.generated) if pred_done is None else pred_done
        budget = seq.max_new_tokens - done
        # From ctx c the host keeps at most max_context - 1 - c tokens
        # (_maybe_finish caps at ctx + 1 >= max_context); granting more
        # would waste a forward pass + KV write per capped sequence.
        room = ecfg.max_context - 1 - ctx
        steps = max(0, min(k_steps, budget, room))
        if steps > 0:
            need = kvc.pages_needed(steps, ecfg.page_size, already=ctx)
            grantable = self._free_plus_evictable()
            if self.win_allocator is not None:
                # Both tables grow by the same pages: the scarcer kind
                # bounds the grant.
                grantable = min(grantable, self.win_allocator.num_free)
            if need > grantable:
                # Pool pressure: advance only as far as the slack in the
                # current last page plus the pages we can still grant.
                slack = len(seq.pages) * ecfg.page_size - ctx
                steps = min(steps, slack + grantable * ecfg.page_size)
                need = (kvc.pages_needed(steps, ecfg.page_size,
                                         already=ctx)
                        if steps > 0 else 0)
            if need > 0:
                seq.pages.extend(self._allocate_reclaiming(need))
                if self.win_allocator is not None:
                    seq.pages.window.extend(
                        self.win_allocator.allocate(need))
        return steps

    def _keep(self, seq: Sequence, pos: int, row) -> None:
        """File the float32 row a program sampled the token after
        position ``pos`` from (EngineConfig.keep_logits); the last 4 x K
        positions stay."""
        held = seq.kept_logits
        if held is None:
            held = seq.kept_logits = {}
        held[pos] = np.asarray(row, np.float32)
        cap = 4 * max(1, self.engine_cfg.decode_steps_per_call)
        for p in sorted(held)[:-cap]:
            del held[p]

    def _fold_lane(self, seq: Sequence, toks, rows=None) -> List[int]:
        """Fold device-produced tokens (iterable of ints, -1 = no token)
        into one sequence's host state; stops at done/-1. ``rows``: the
        steps' logits [K, V], filed as the tokens are folded."""
        got: List[int] = []
        for i, tok in enumerate(toks):
            if seq.done or tok < 0:
                break
            if rows is not None:
                self._keep(seq, seq.ctx_len, rows[i])
            seq.ctx_len += 1
            seq.generated.append(tok)
            if seq.first_token_time == 0.0:
                seq.first_token_time = time.perf_counter()
            self._maybe_finish(seq, tok)
            got.append(tok)
        return got

    def can_admit(self, seq: Sequence) -> bool:
        return bool(self.free_slots()) and self.admission_fits(
            self.admission_need(seq))

    def can_ever_admit(self, seq: Sequence) -> bool:
        """False if the request exceeds a pool even when fully idle."""
        return (self._pages_reserved(seq) <= self.engine_cfg.num_pages - 1
                and (self.win_allocator is None
                     or self._window_pages_reserved(seq)
                     <= self.win_allocator.num_pages - 1))

    def _block_table_array(self, pages: List[int]) -> np.ndarray:
        """One block-table row: ``pages`` by position, and behind it the
        window kind's table where ``pages`` carries one
        (kvc.KindPages)."""
        bt = np.zeros((self.bt_width,), np.int32)
        bt[:len(pages)] = pages
        window = getattr(pages, "window", ())
        bt[self.max_pages:self.max_pages + len(window)] = window
        if self.state_slots is not None:
            bt[-1] = getattr(pages, "state", 0)
        return bt

    def _window_pages_for(self, seq: Sequence, start: int, end: int) -> None:
        """A pool a kind: before tokens [start, end) are written, release
        the window-kind pages no query from ``start`` on can see and take
        those up to ``end`` (admission held them back: admission_fits).
        The full kind's pages of a prompt are taken whole at
        _prefill_setup; the window kind's a chunk at a time, so a long
        prompt never holds more of them than window + chunk."""
        if self.win_allocator is None:
            return
        if self.swa_evict:
            self._evict_behind_window(seq, start)
        window = seq.pages.window
        need = kvc.pages_needed(end, self.engine_cfg.page_size) - len(window)
        if need > 0:
            window.extend(self.win_allocator.allocate(need))

    def _free_pages(self, seq: Sequence) -> None:
        """Give back every page a sequence holds, of either kind."""
        self.allocator.free(seq.pages)
        if self.win_allocator is not None:
            self.win_allocator.free(getattr(seq.pages, "window", ()))
        if self.state_slots is not None:
            self.state_slots.free(getattr(seq.pages, "state", 0))
        seq.pages = []

    def _prefill_setup(self, seq: Sequence, slot: int) -> List[int]:
        """Allocate pages (with prefix-cache reuse), bind the slot, and
        return the (possibly truncated) prompt to prefill."""
        ecfg = self.engine_cfg
        # Keep the most recent tokens of over-long prompts (leave room
        # for at least one generated token). On a recompute-resume the
        # "prompt" is the original prompt plus everything generated
        # before the preemption.
        prompt = self._prefill_tokens(seq)[-(ecfg.max_context - 1):]
        seq.admit_idx = self._admit_counter
        self._admit_counter += 1
        if seq.resume_base:
            self.resumes_total += 1
        # Prefix-cache hit: reuse full pages of an identical prior prefix
        # and skip their prefill compute — HBM hits are shared in place;
        # host-tier hits swap back into freshly allocated device pages
        # before the prefill resumes past them. Always recompute at
        # least the final prompt token — its logits seed the first
        # sampled token.
        shared: List[int] = []
        n_restored = 0
        if self.prefix_cache is not None:
            clock = self.telemetry.clock
            clock.enter("prefix_lookup")
            pages, host_entries, seq.cached_tokens = self.prefix_cache.lookup(
                prompt, max_tokens=len(prompt) - 1,
                digests=self._seq_digests(seq, prompt))
            clock.enter("admit")
            shared = self._restore_host_entries(
                pages, host_entries,
                trace_id=seq.trace_id or str(seq.request_id))
            n_restored = len(host_entries)
        n_new = kvc.pages_needed(len(prompt), ecfg.page_size) - len(shared)
        try:
            seq.pages = shared + self._allocate_reclaiming(n_new)
            if (self.win_allocator is not None
                    or self.state_slots is not None):
                seq.pages = kvc.KindPages(seq.pages)
        except MemoryError:
            self.allocator.free(shared)
            raise
        if self.state_slots is not None:
            # As many slots as lanes, so a free lane has one. Nothing is
            # zeroed here: a chunk at position 0 reads zeros in the
            # slot's place (PagedState.fresh).
            seq.pages.state = self.state_slots.allocate()
        seq.pages_version += 1        # staging block-table rows re-key
        seq.evicted_pages = 0         # the window's cursor is the list's
        # Swap accounting AFTER the allocation can no longer fail: a
        # MemoryError-and-requeue retry must not double-count one
        # logical resume/restore in the span and counters.
        seq.host_restored_pages += n_restored
        if seq.resume_base and seq.cached_tokens:
            # The preemption's published pages survived (in HBM or via
            # the host tier): this resume swaps them in instead of
            # recomputing the whole prompt+generated stream.
            self.swap_in_resumes += 1
        seq.slot = slot
        seq.prefill_start = time.perf_counter()
        return prompt

    def _prefill_finish(self, seq: Sequence, prompt: List[int],
                        first: int, logits=None) -> None:
        """Common post-prefill bookkeeping for one sequence. ``logits``
        [V]: the row ``first`` was sampled from (keep_logits)."""
        if self.keep_logits and logits is not None:
            self._keep(seq, len(prompt) - 1, logits)
        seq.ctx_len = len(prompt)
        seq.generated.append(first)
        if seq.first_token_time == 0.0:
            # Resume prefills keep the ORIGINAL first-token time: the
            # client already received earlier tokens.
            seq.first_token_time = time.perf_counter()
        self.slots[seq.slot] = seq
        self._maybe_finish(seq, first)

    def _use_sp(self, offset: int, chunk_len: int, prompt_len: int,
                bucket: int) -> bool:
        """Ring-attention prefill is eligible for fresh single-chunk
        prompts on an sp>1 mesh (self-attention only, no cached prefix)."""
        return (self.sp > 1 and offset == 0 and chunk_len == prompt_len
                and bucket % self.sp == 0)

    def _fill_prefill_lane(self, f: dict, i: int, seq: Sequence,
                           chunk: List[int], offset: int) -> None:
        """Lane ``i`` of a packed prefill operand's fields ``f``:
        ``chunk`` of ``seq``'s prompt behind ``offset`` cached tokens.
        First sampled token's penalty window = the prompt tail."""
        f["tokens"][i, :len(chunk)] = chunk
        f["prompt_len"][i] = len(chunk)
        f["prefix_len"][i] = offset
        if offset == 0 and self.state_slots is not None:
            self.state_slots.resets_total += 1
        f["block_table"][i] = self._block_table_array(seq.pages)
        f["temp"][i] = seq.temperature
        f["top_p"][i] = seq.top_p
        f["top_k"][i], f["seed"][i] = self._sampling_arrays(seq)
        f["rpen"][i], f["rlast"][i] = self._penalty_arrays(seq)
        if f["rpen"][i] != 1.0:
            f["window"][i] = self._penalty_window_row(seq)

    def _stage_chunk_arrays(self, seq: Sequence, prompt: List[int],
                            offset: int, chunk_cap: int) -> dict:
        """Host arrays for one prefill chunk at ``offset`` — the SINGLE
        staging point shared by the serial dispatch (_prefill_one_chunk)
        and hybrid staging (_stage_hybrid_chunk / _stage_chunk_only_call),
        so the two scheduling modes cannot drift apart and byte-equality
        holds by construction.

        First sampled token's penalty window = the prompt tail (only the
        final chunk's sample is kept, so mid-chunk windows don't matter).
        """
        chunk = prompt[offset:offset + chunk_cap]
        clock = self.telemetry.clock
        clock.part("pages")
        self._window_pages_for(seq, offset, offset + len(chunk))
        clock.part("fill")
        bucket = self.engine_cfg.bucket_for(len(chunk))
        layout = self._prefill_layout(bucket)
        packed = layout.blank(1)
        st = layout.views(packed)
        self._fill_prefill_lane(st, 0, seq, chunk, offset)
        # The fields stay readable by name (the ledger); the program
        # gets ``packed``, which they are views of.
        st.update(seq=seq, prompt=prompt, chunk_tokens=len(chunk),
                  bucket=bucket, packed=packed)
        return st

    def _chunk_host_operand(self, st: dict) -> np.ndarray:
        """A staged chunk's packed operand with a fresh step number —
        shared by every dispatch site that consumes
        _stage_chunk_arrays."""
        self.telemetry.clock.part("put")
        st["step"][:] = self._next_step()
        return st["packed"]

    def _prefill_one_chunk(self, seq: Sequence, prompt: List[int],
                           offset: int) -> Tuple[int, Any]:
        """Run one prefill chunk at ``offset``; returns (next_offset,
        sampled-token device array for the chunk)."""
        ecfg = self.engine_cfg
        chunk_cap = ecfg.chunk_tokens_cap
        self.telemetry.clock.enter("stage")
        st = self._stage_chunk_arrays(seq, prompt, offset, chunk_cap)
        use_sp = self._use_sp(offset, st["chunk_tokens"], len(prompt),
                              st["bucket"])
        prefill = self._prefill_sp_jit if use_sp else self._prefill_jit
        # Decode lanes active right now sit stalled behind this serial
        # chunk — exactly the stall hybrid steps remove, so the
        # histogram is scoped to CHUNKED-prefill dispatches (single-
        # chunk admission stalls are untouched by hybrid stepping and
        # already visible in prefill_dispatch_s). Mid-prefill sequences
        # are excluded by active_sequences, so this counts only victims.
        stalled = bool(self.active_sequences())
        self._last_decode_end = None     # prefill breaks the decode streak
        c = st["chunk_tokens"]
        args = (self.params, self.kv, self._base_key,
                *self._put_operands(self._chunk_host_operand(st)))
        (self.kv, tok, lg), dseq, t0, t1 = self._run(
            "prefill_chunk", prefill, args, slots=1, chunk_tokens=c)
        if self.keep_logits:
            tok = (tok, lg)      # read together at the final chunk
        if self.telemetry.enabled:
            dt = t1 - t0
            self.telemetry.prefill_dispatch_s.observe(dt)
            self.telemetry.prefill_dispatches.inc()
            # Per-chunk trace span (README "Observability" span schema):
            # children of the request's prefill span, so a long prompt's
            # chunk cadence is visible on the trace timeline.
            self.telemetry.recorder.add(
                "prefill_chunk", seq.trace_id or str(seq.request_id),
                t0, t1, parent="prefill",
                offset=int(offset), tokens=int(c))
            final = offset + c >= len(prompt)
            # Pushed at enqueue with the enqueue wall; the true device_s
            # and t_done land when a readback covers this chunk
            # (_observed: the final chunk's own token, else the next
            # sync behind it) — no sync exists only to time it. The
            # stall histogram (lanes stalled behind this serial chunk)
            # is fed from the same instant.
            self._ledger_push(
                "prefill_chunk", rung=0, slots=1,
                tokens=1 if final else 0, chunk_tokens=c,
                device_s=dt, kv_read=c * offset + c * (c + 1) // 2,
                compile_event=st["bucket"]
                not in self._prefill_buckets_seen,
                seq=dseq, t_enqueue=t0)
            self._unsettled.append((dseq, t0, stalled))
            self._prefill_buckets_seen.add(st["bucket"])
        return offset + c, tok, dseq

    def _prefill_chunked(self, seq: Sequence, prompt: List[int]) -> None:
        """Serial (one-lane) prefill; chunks prompts that exceed the
        largest bucket. Each chunk attends to itself + all cached tokens
        (prefix_len); only the final chunk's sampled token is kept."""
        offset = seq.cached_tokens
        tok = dseq = None
        while offset < len(prompt):
            offset, tok, dseq = self._prefill_one_chunk(seq, prompt, offset)
        self._prefill_finish(seq, prompt, *self._read_first(dseq, tok))

    def _read_first(self, dseq: int, tok) -> tuple:
        """A one-lane prefill's sampled token off the device, and with
        keep_logits (``tok`` is then (token, logits)) its row."""
        if isinstance(tok, tuple):
            (first, row), _, _ = self._wait(
                dseq, lambda: (int(tok[0][0]), np.asarray(tok[1][0])))
            return first, row
        first, _, _ = self._wait(dseq, lambda: int(tok[0]))
        return (first,)

    # -- Incremental (interleavable) prefill: one chunk per call, so the
    # -- scheduler can run decode steps between a long prompt's chunks
    # -- instead of stalling the whole batch for the full prefill.

    def prefill_begin(self, seq: Sequence,
                      slot: Optional[int] = None) -> int:
        """Set up an incremental prefill (pages, slot, cache lookup);
        drive it with prefill_step(). Returns the slot.

        The slot binds into ``self.slots`` HERE, not at finish: batch
        admission re-reads free_slots() between this sequence's chunks
        (that interleaving is the point of incremental prefill), and an
        unreserved slot would be handed to a second sequence, which the
        finishing prefill then silently overwrites — orphaning it.
        ``active_sequences`` excludes mid-prefill slots, so decode never
        touches the half-filled sequence."""
        if slot is None:
            slot = self.free_slots()[0]
        seq.prefill_prompt = self._prefill_setup(seq, slot)
        seq.prefill_offset = seq.cached_tokens
        self.slots[slot] = seq
        return slot

    def prefill_step(self, seq: Sequence) -> bool:
        """Run ONE chunk of an incremental prefill; True when complete
        (first token sampled and bookkeeping done)."""
        prompt = seq.prefill_prompt
        assert prompt is not None, "prefill_step without prefill_begin"
        self._chaos_step_gate()
        seq.prefill_offset, tok, dseq = self._prefill_one_chunk(
            seq, prompt, seq.prefill_offset)
        if seq.prefill_offset < len(prompt):
            return False
        self._prefill_finish(seq, prompt, *self._read_first(dseq, tok))
        seq.prefill_prompt = None
        return True

    def prefill(self, seq: Sequence, slot: Optional[int] = None) -> int:
        """Admit a sequence: allocate pages, run the prefill graph (chunked
        when the prompt exceeds the largest bucket), sample the first token.
        Returns the slot index."""
        if slot is None:
            slot = self.free_slots()[0]
        prompt = self._prefill_setup(seq, slot)
        self._prefill_chunked(seq, prompt)
        return slot

    def _prefill_run_batched(self, group: List[Tuple[Sequence, List[int]]],
                             bucket: int, use_sp: bool) -> None:
        """One multi-lane prefill dispatch: P sequences, same bucket.

        Lanes are padded up to a compiled batch size; dummy lanes carry
        prompt_len=1 with an all-zero block table, so their single write
        lands on the trash page and their sampled token is discarded.
        """
        clock = self.telemetry.clock
        clock.enter("stage")
        clock.part("pages")
        for seq, prompt in group:
            self._window_pages_for(seq, seq.cached_tokens, len(prompt))
        clock.part("fill")
        p = next(s for s in self._prefill_batch_sizes if s >= len(group))
        layout = self._prefill_layout(bucket)
        packed = layout.blank(p)
        f = layout.views(packed)
        plen, pref = f["prompt_len"], f["prefix_len"]
        for i, (seq, prompt) in enumerate(group):
            self._fill_prefill_lane(f, i, seq, prompt[seq.cached_tokens:],
                                    seq.cached_tokens)
        prefill = self._prefill_sp_jit if use_sp else self._prefill_jit
        self._last_decode_end = None     # prefill breaks the decode streak
        n = len(group)
        chunk_tokens = int(plen[:n].sum())
        clock.part("put")
        f["step"][:] = self._next_step()
        args = (self.params, self.kv, self._base_key,
                *self._put_operands(packed))
        (self.kv, tok, lg), dseq, t0, _ = self._run(
            "prefill_chunk", prefill, args, slots=n, tokens=n,
            chunk_tokens=chunk_tokens)
        toks_out, _, t_done = self._wait(dseq, lambda: np.asarray(tok))
        if self.telemetry.enabled:
            dt = t_done - t0                 # includes the token readback
            self.telemetry.prefill_dispatch_s.observe(dt)
            self.telemetry.prefill_dispatches.inc()
            graph_key = (bucket, p, use_sp)
            self._ledger_push(
                "prefill_chunk", rung=0, slots=n, tokens=n,
                chunk_tokens=chunk_tokens, device_s=dt,
                kv_read=int((plen[:n] * pref[:n]
                             + plen[:n] * (plen[:n] + 1) // 2).sum()),
                compile_event=graph_key not in self._prefill_buckets_seen,
                seq=dseq, t_enqueue=t0, t_done=t_done)
            self._prefill_buckets_seen.add(graph_key)
        rows = np.asarray(lg) if self.keep_logits else [None] * n
        for i, (seq, prompt) in enumerate(group):
            self._prefill_finish(seq, prompt, int(toks_out[i]), rows[i])

    def prefill_many(self, seqs: List[Sequence]) -> None:
        """Admit several sequences, batching same-bucket single-chunk
        prefills into one device dispatch (a burst of arrivals no longer
        pays one serial [1, S] forward each — the MXU sees [P, S]).

        Prompts needing multiple chunks fall back to the serial path.
        """
        self._chaos_step_gate()
        ecfg = self.engine_cfg
        chunk_cap = ecfg.chunk_tokens_cap
        slots = self.free_slots()
        if len(slots) < len(seqs):
            # zip truncation would silently drop (and strand) requests.
            raise RuntimeError(
                f"prefill_many: {len(seqs)} sequences but only "
                f"{len(slots)} free slots")
        staged: List[Tuple[Sequence, List[int]]] = []
        for seq, slot in zip(seqs, slots):
            staged.append((seq, self._prefill_setup(seq, slot)))
        groups: Dict[Tuple[int, bool], List[Tuple[Sequence, List[int]]]] = {}
        for seq, prompt in staged:
            rest = len(prompt) - seq.cached_tokens
            if rest <= chunk_cap:
                bucket = ecfg.bucket_for(rest)
                use_sp = self._use_sp(seq.cached_tokens, rest, len(prompt),
                                      bucket)
                groups.setdefault((bucket, use_sp), []).append((seq, prompt))
            else:
                self._prefill_chunked(seq, prompt)
        cap = self._prefill_batch_sizes[-1]
        for (bucket, use_sp), group in groups.items():
            for i in range(0, len(group), cap):
                self._prefill_run_batched(group[i:i + cap], bucket, use_sp)

    def _chaos_step_gate(self) -> None:
        """Engine-level fault injection, mirroring the HTTP _chaos_gate:
        runs at the top of every prefill/decode dispatch. The wedge
        sleeps BEFORE the failure roll so a wedged-and-failing replica
        exercises the watchdog first, like a real hung-then-killed call."""
        if self.chaos_step_wedge_s > 0:
            time.sleep(self.chaos_step_wedge_s)
        if (self.chaos_step_failure_rate > 0
                and _chaos_random.random() < self.chaos_step_failure_rate):
            raise ChaosStepError("chaos: injected engine step failure")

    def _maybe_finish(self, seq: Sequence, tok: int) -> None:
        if seq.eos_token_id is not None and tok == seq.eos_token_id:
            seq.done, seq.finish_reason = True, "stop"
        elif len(seq.generated) >= seq.max_new_tokens:
            seq.done, seq.finish_reason = True, "length"
        elif seq.ctx_len + 1 >= self.engine_cfg.max_context:
            seq.done, seq.finish_reason = True, "length"
        if seq.done:
            seq.finish_time = time.perf_counter()
        elif self.swa_evict:
            self._evict_behind_window(seq)

    def _evict_behind_window(self, seq: Sequence,
                             ctx: Optional[int] = None) -> None:
        """Free KV pages entirely behind the sliding window of a query at
        ``ctx`` (default: the next token's); the block-table slot becomes
        the trash page (0). With a pool a kind these are the WINDOW
        kind's pages (a full layer's are never released while the
        sequence runs). No windowed reader ever
        touches them: the Pallas kernels' page grids start at the
        window's first page, and the dense path gathers-then-masks.
        In-flight dispatch-ahead calls staged with higher predicted ctx
        have even later window starts, so reuse-after-free can't race a
        reader. The per-sequence cursor makes total work O(pages freed)
        over a sequence's life, not O(pages) per accepted token."""
        win = self.model_cfg.sliding_window
        ctx = seq.ctx_len if ctx is None else ctx
        first_needed = max(0, ctx - win) // self.engine_cfg.page_size
        pages, allocator = seq.pages, self.allocator
        if self.win_allocator is not None:
            pages, allocator = seq.pages.window, self.win_allocator
        j = seq.evicted_pages
        while j < min(first_needed, len(pages)):
            if pages[j]:
                allocator.free([pages[j]])
                pages[j] = 0
                self.window_pages_released += 1
            j += 1
        seq.evicted_pages = j

    def _publish_to_cache(self, seq: Sequence) -> None:
        """Publish a sequence's full pages (prompt + generated history)
        to the prefix cache, so a follow-up turn resending the
        conversation — or a preempted sequence's recompute-resume —
        reuses them instead of re-prefilling."""
        if self.prefix_cache is None or not seq.pages:
            return
        # drop_last: the just-sampled token isn't written back yet.
        in_kv = self._tokens_in_kv(seq, drop_last=True)
        # Reuse the request's one hash pass (router or admission): only
        # the generated-suffix pages are hashed here. Resume streams may
        # have shifted the truncation window — they rehash.
        digests = None if seq.resume_base else seq.prefix_digests
        self.prefix_cache.insert(in_kv[:seq.ctx_len], seq.pages,
                                 digests=digests)
        self._publish_to_fabric(seq, digests)

    def _publish_to_fabric(self, seq: Sequence, digests) -> None:
        """Ship the settled prefix run to the fleet fabric pool (README
        "KV fabric"): the contiguous full-page prompt prefix, keyed by
        its chain digests, offloaded to host layout and handed to the
        armed publish callable. Bounded below by
        fabric_publish_min_pages (tiny prefixes aren't worth fleet
        space) and deduped against _fabric_published so steady traffic
        over one system prompt serializes it once, not per release."""
        if self.fabric_publish is None or not digests:
            return
        full = len(self._tokens_in_kv(seq, drop_last=True)[:seq.ctx_len]) \
            // self.engine_cfg.page_size
        k = min(len(digests), full, len(seq.pages))
        while k > 0 and not all(seq.pages[i] for i in range(k)):
            k -= 1
        if k < max(1, self.fabric_publish_min_pages):
            return
        fresh = [i for i in range(k)
                 if digests[i] not in self._fabric_published]
        if not fresh:
            return
        try:
            host_pages = kvc.offload_pages(
                self.kv, [seq.pages[i] for i in fresh])
            self.fabric_publish(
                [(digests[i], p) for i, p in zip(fresh, host_pages)])
        except Exception as e:  # noqa: BLE001 — release() must complete
            # Publishing is best-effort and this runs inside release(),
            # which has to finish freeing the sequence; but a failed
            # device copy or transport is said out loud, not skipped.
            telemetry.log_event("fabric_publish_failed", level="warning",
                                request_id=seq.trace_id
                                or str(seq.request_id), error=repr(e))
            return
        for i in fresh:
            self._fabric_published[digests[i]] = None
        while len(self._fabric_published) > 4096:
            self._fabric_published.popitem(last=False)
        self.fabric_published_pages += len(fresh)

    def release(self, seq: Sequence) -> None:
        """Free a finished sequence's pages and slot, publishing its full
        pages to the prefix cache first."""
        self._publish_to_cache(seq)
        self._free_pages(seq)
        seq.prefill_prompt = None          # cancel/error mid-prefill
        if seq.slot >= 0 and self.slots[seq.slot] is seq:
            self.slots[seq.slot] = None
        self._stage_forget(seq)

    # ------------------------------------------------------------------
    # Preemption + recompute-resume (admission="optimistic")
    # ------------------------------------------------------------------

    def preempt(self, seq: Sequence) -> None:
        """Evict a running sequence under pool pressure: release its
        slot and pages but KEEP host-side prompt + generated tokens, so
        a later re-admission recompute-resumes it (re-prefill over
        prompt + generated; token-identical under greedy decoding).

        Pages are published to the prefix cache first — the resume
        re-prefill reuses whatever pressure hasn't evicted by then,
        while the cached copies stay reclaimable capacity."""
        assert all(seq.slot not in call["allowed"]
                   for call in self._inflight), \
            "preempt of a sequence with dispatch-ahead calls in flight"
        self._publish_to_cache(seq)
        self._free_pages(seq)
        if seq.slot >= 0 and self.slots[seq.slot] is seq:
            self.slots[seq.slot] = None
        self._stage_forget(seq)
        seq.slot = -1
        seq.ctx_len = 0
        seq.evicted_pages = 0
        seq.cached_tokens = 0
        seq.prefill_prompt = None
        # The published pages may demote to host under the very pressure
        # that preempted this sequence — re-arm the queue-wait prefetch
        # so the resume swaps them back in while it waits.
        seq.host_prefetched = False
        seq.resume_digests = None      # stream/truncation change here
        seq.resume_base = len(seq.generated)
        seq.preemptions += 1
        self.preemptions_total += 1
        self._preempted_out.append(seq)
        telemetry.log_event(
            "request_preempted", level="info",
            request_id=seq.trace_id or str(seq.request_id),
            preemptions=seq.preemptions,
            generated_tokens=len(seq.generated),
            free_plus_evictable=self._free_plus_evictable())

    def take_preempted(self) -> List[Sequence]:
        """Sequences preempted since the last call, in preemption order.
        The caller requeues them at the HEAD of its wait queue for
        recompute-resume (FCFS fairness: they were admitted first)."""
        out, self._preempted_out = self._preempted_out, []
        return out

    def _preempt_victim(self, cands: List[Sequence]) -> Optional[Sequence]:
        """Most-recently-admitted candidate still holding preemption
        budget. Sequences past the starvation guard (re-admitted under
        full reservation) are exempt, so they provably finish."""
        limit = self.engine_cfg.preempt_max_per_request
        eligible = [s for s in cands if s.preemptions < limit]
        return max(eligible, key=lambda s: s.admit_idx) if eligible else None

    def _preempt_for_pressure(self, active_seqs: List[Sequence],
                              k_steps: int) -> List[Sequence]:
        """Optimistic admission's safety net, evaluated before decode
        grants: when the coming round's page needs cannot all be met AND
        free+evictable has fallen below the low watermark, preempt the
        most-recently-admitted sequences until the remainder fits (or no
        eligible victim is left). Returns the surviving active list."""
        if self.admission != "optimistic":
            return active_seqs
        ecfg = self.engine_cfg
        active = list(active_seqs)
        while len(active) > 1:
            need = sum(
                kvc.pages_needed(
                    min(k_steps,
                        max(0, s.max_new_tokens - len(s.generated)),
                        max(0, ecfg.max_context - 1 - s.ctx_len)),
                    ecfg.page_size, already=s.ctx_len)
                for s in active)
            avail = self._free_plus_evictable()
            if need <= avail or avail >= ecfg.preempt_watermark_pages:
                break
            victim = self._preempt_victim(active)
            if victim is None:
                break
            self.preempt(victim)
            active.remove(victim)
        return active

    def _starved(self, seq: Sequence) -> None:
        """A lane with zero page slack and zero grantable pages: under
        optimistic admission (budget allowing) it is preempted and
        requeued for recompute-resume; otherwise it fails with "oom"
        (reserve-mode admission makes that path exceptional)."""
        if (self.admission == "optimistic"
                and seq.preemptions < self.engine_cfg.preempt_max_per_request):
            self.preempt(seq)
            return
        seq.done, seq.finish_reason = True, "oom"
        seq.finish_time = time.perf_counter()

    def active_sequences(self) -> List[Sequence]:
        """Sequences decode may advance: bound, not finished, and not
        still mid-incremental-prefill (those hold their slot but have no
        complete KV yet)."""
        return [s for s in self.slots
                if s is not None and not s.done and s.prefill_prompt is None]

    def _sampling_arrays(self, seq: Sequence):
        """(top_k, seed) for one sequence, with engine defaults applied.

        Negative seeds mean "no seed" (the llama.cpp/Ollama -1 convention),
        mapping to the engine-global key stream; values are clamped into
        int32 range for the device arrays."""
        top_k = self.engine_cfg.top_k if seq.top_k is None else seq.top_k
        top_k = max(0, min(int(top_k), 2**31 - 1))
        if seq.seed is None or seq.seed < 0:
            seed = -1
        else:
            seed = int(seq.seed) & 0x7FFFFFFF
        return top_k, seed

    def _penalty_arrays(self, seq: Sequence):
        """(repeat_penalty, repeat_last_n) with Ollama conventions:
        last_n < 0 means 'whole context' (clamped to the static window),
        0 disables. Speculation composes: proposals are one-hot, and
        verify_round penalizes each position's target distribution
        against the window rolled with its accepted prefix — exactly the
        sequential plain-decode behavior."""
        rlast = int(seq.repeat_last_n)
        if rlast < 0:
            rlast = PENALTY_WINDOW
        return float(seq.repeat_penalty), min(rlast, PENALTY_WINDOW)

    @staticmethod
    def _penalty_window_row(seq: Sequence) -> np.ndarray:
        """Last W known tokens (prompt + generated), newest at the high
        end, -1 padded — the device-side ring picks up from here."""
        row = np.full((PENALTY_WINDOW,), -1, np.int32)
        hist = (seq.prompt_tokens + seq.generated)[-PENALTY_WINDOW:]
        if hist:
            row[-len(hist):] = hist
        return row

    # -- Batch ladder: rung selection + slot compaction (README
    # -- "Batch ladder"). The slot array is top-rung sized; dispatch
    # -- width is the smallest compiled rung covering the occupied
    # -- slots, so a near-empty batch never pays big-graph latency.

    def _rung_for_slots(self, seqs: List[Sequence]) -> int:
        """Smallest ladder rung whose graph covers every slot in
        ``seqs`` (the slots staged into the dispatch arrays)."""
        hi = max((s.slot for s in seqs), default=-1) + 1
        for r in self.ladder:
            if r >= hi:
                return r
        return self.ladder[-1]

    def _note_rung(self, rung: int) -> None:
        """Record the dispatch rung (gauge + graph-switch counter) and
        flag first-ever-rung dispatches for the step ledger (the compile
        event a warm-up-free boot pays on that dispatch)."""
        self._last_compile_event = rung not in self._rungs_seen
        self._rungs_seen.add(rung)
        if rung != self.decode_rung:
            self.rung_switches_total += 1
            self.decode_rung = rung
            self.rung_peak = max(self.rung_peak, rung)

    def _ledger_push(self, kind: str, *, rung: int, slots: int,
                     tokens: int, chunk_tokens: int = 0, steps: int = 1,
                     device_s: float = 0.0, kv_read: int = 0,
                     spec_accepted: int = 0,
                     staging_s: Optional[float] = None,
                     bubble_s: Optional[float] = None,
                     compile_event: Optional[bool] = None,
                     seq: int = 0, t_enqueue: float = 0.0,
                     t_done: float = 0.0) -> None:
        """Push one per-dispatch record into the step ledger, folding in
        the staged bubble/staging micros (unless the caller captured
        them at stage time — decode rounds push at SYNC, by which
        point the scratch belongs to a newer dispatch) and the KV-swap
        byte delta since the previous record. ``t_enqueue`` / ``t_done``
        are the loop clock's instants (perf counter; recorded as unix on
        the span recorder's anchor; 0 = not known yet). Callers gate on
        telemetry.enabled (the swap counters are NULL_METRIC otherwise).
        """
        tel = self.telemetry
        swap_total = (tel.kv_offload_bytes.value
                      + tel.kv_restore_bytes.value)
        swap = max(0.0, swap_total - self._last_swap_bytes_total)
        self._last_swap_bytes_total = swap_total
        if staging_s is None:
            staging_s = self._last_staging_s
            self._last_staging_s = 0.0
        if bubble_s is None:
            bubble_s = self._pending_bubble
            self._pending_bubble = 0.0
        if compile_event is None:
            compile_event = self._last_compile_event
            self._last_compile_event = False
        to_unix = tel.recorder.to_unix
        tel.step_ledger.push(
            kind, rung, slots, tokens, chunk_tokens, steps, device_s,
            staging_s, bubble_s, kv_read, swap, spec_accepted,
            compile_event, seq=seq,
            t_enqueue=to_unix(t_enqueue) if t_enqueue else 0.0,
            t_done=to_unix(t_done) if t_done else 0.0,
            layer_passes=max(1, steps) * self.model_cfg.n_kv_slots)

    def _compact_slots(self) -> None:
        """Step-down helper: relocate bound sequences out of high slots
        into lower free ones so the next dispatch can run a smaller
        compiled rung once occupancy drops. A slot move is pure host
        bookkeeping — block tables ship per dispatch, KV pages never
        move — but it is only legal while NO dispatch-ahead call is in
        flight (in-flight calls address lanes by the slot they were
        staged at). Mid-incremental-prefill sequences relocate too:
        their chunk dispatches address pages, not slots."""
        if len(self.ladder) == 1 or self._inflight:
            return
        bound = [i for i, s in enumerate(self.slots) if s is not None]
        if not bound:
            return
        target = next(r for r in self.ladder if r >= len(bound))
        if bound[-1] < target:
            return                        # already fits the target rung
        free = [i for i in range(target) if self.slots[i] is None]
        for i in reversed(bound):
            if i < target or not free:
                break
            j = free.pop(0)
            seq = self.slots[i]
            self.slots[j], self.slots[i] = seq, None
            seq.slot = j

    def _stage_buffers(self, rung: int) -> dict:
        """The persistent packed operand of a rung (stage_host_reuse)
        and the views its rows are written through. Rows refresh
        incrementally: per-dispatch fields (token, ctx) always; sampling
        params only when the slot's occupant changes; the block-table
        row only when its (len, evicted) key moves."""
        buf = self._stage_bufs.get(rung)
        if buf is None:
            packed = self._decode_layout.blank(rung)
            buf = self._decode_layout.views(packed)
            buf.update(packed=packed, owner=[None] * rung,
                       bt_key=[None] * rung)
            self._stage_bufs[rung] = buf
        return buf

    def _stage_forget(self, seq: Sequence) -> None:
        """Drop a departing sequence's staging-buffer rows (every rung;
        identity scan because compaction may have left it cached under
        an older slot). Without this the owner lists would pin finished
        Sequences — and their full token histories — until the same
        slot happens to restage at the same rung."""
        for buf in self._stage_bufs.values():
            owner = buf["owner"]
            for i, s in enumerate(owner):
                if s is seq:
                    owner[i] = None
                    buf["bt_key"][i] = None

    def _stage_batch(self, active_seqs: List[Sequence], rung: int
                     ) -> Tuple[np.ndarray, dict]:
        """Fill the per-slot fields of one decode dispatch (tokens, ctx,
        bts, temps, top_ps, top_ks, seeds, rpens, rlasts, windows) in a
        packed ``[rung, width]`` operand; returns that host array and
        the views of its fields (engine/staging.py), every other field
        at its default.

        With ``stage_host_reuse`` (default) the operand persists across
        dispatches and only changed rows are rewritten; the dispatch
        gets ONE COPY, because device_put aliases numpy memory on CPU
        and the rows mutate next step. Rows of freed slots go stale,
        which is benign: their ``allowed`` is 0, so the graph masks
        every read and write (writes land on the trash page) and their
        token is discarded (-1). Without it the operand is rebuilt from
        its defaults each dispatch (the bubble comparison arm).

        Runs inside the caller's ``stage`` visit, as its ``fill`` part;
        the step ledger's ``staging_s`` is the wall between the mark
        that opens the part here and the one before the copy."""
        clock = self.telemetry.clock
        t_stage = clock.part("fill")
        if self._stage_reuse:
            buf = self._stage_buffers(rung)
            owner, bt_key = buf["owner"], buf["bt_key"]
        else:
            packed = self._decode_layout.blank(rung)
            buf = self._decode_layout.views(packed)
            owner, bt_key = [None] * rung, [None] * rung
        for seq in active_seqs:
            i = seq.slot
            buf["tokens"][i] = seq.last_token
            buf["ctx"][i] = seq.ctx_len
            if owner[i] is not seq:
                owner[i] = seq
                bt_key[i] = None
                buf["temps"][i] = seq.temperature
                buf["top_ps"][i] = seq.top_p
                buf["top_ks"][i], buf["seeds"][i] = \
                    self._sampling_arrays(seq)
                buf["rpens"][i], buf["rlasts"][i] = \
                    self._penalty_arrays(seq)
            # Pages mutate by growing (decode grants / prefill setup),
            # by behind-window eviction (entries zeroed, cursor moves),
            # or by wholesale replacement at a (re)prefill — keyed by
            # (version, len, evicted) so every one of those invalidates.
            key = (seq.pages_version, len(seq.pages), seq.evicted_pages)
            if bt_key[i] != key:
                bt_key[i] = key
                buf["bts"][i] = self._block_table_array(seq.pages)
            if buf["rpens"][i] != 1.0:
                buf["windows"][i] = self._penalty_window_row(seq)
        self._last_staging_s = clock.part("fill") - t_stage
        if not self._stage_reuse:
            return packed, buf
        packed = buf["packed"].copy()
        return packed, self._decode_layout.views(packed)

    # ------------------------------------------------------------------
    # The decode round: stage -> enqueue -> (later) sync -> fold, written
    # once. How many calls may be in flight (decode_pipeline_depth), a
    # step cap and a prefill chunk riding along are data of that round:
    # the synchronous round is its depth-1 case.
    # ------------------------------------------------------------------

    def decode_step(self) -> Dict[int, int]:
        """One batched decode step (single-step view of the fused graph:
        ``allowed`` is capped at 1, so lanes advance exactly one token).
        Returns {request_id: new_token}. Prefer decode_steps() in serving
        loops — this exists for tests and fine-grained stepping."""
        return {rid: toks[-1]
                for rid, toks in self.decode_steps(max_steps=1).items()}

    def decode_steps(self, max_steps: Optional[int] = None
                     ) -> Dict[int, List[int]]:
        """One synchronous round: up to ``decode_steps_per_call`` fused
        decode steps in ONE device dispatch, read back before returning.
        Returns {request_id: [tokens generated, in order]}.

        Per-sequence ``allowed`` folds the generation budget, the context
        cap, and KV-page headroom, so the device never writes a slot the
        host hasn't provisioned. EOS stops a lane on device; the host's
        ``_maybe_finish`` stays the source of truth for finish state.
        ``max_steps`` additionally caps every lane (decode_step uses 1).
        Calls a pipelined entry point left in flight are folded first
        (their tokens lead the returned lists), so entry points mix.
        """
        self._chaos_step_gate()
        result = self.drain_pipeline()
        return _extend(result, self._round(1, max_steps=max_steps))

    def decode_steps_pipelined(self, prefill_seq: Optional[Sequence] = None
                               ) -> Dict[int, List[int]]:
        """Serving step: stage one round, then sync only the oldest call
        once ``decode_pipeline_depth`` are in flight. At depth 1 that is
        the call just staged (a synchronous round); deeper, token
        delivery lags dispatch by depth-1 calls and device compute
        overlaps all host work in between.

        With ``prefill_seq`` (a sequence mid-incremental-prefill;
        EngineConfig.hybrid_prefill) its next chunk rides the round's
        dispatch, so running lanes keep producing tokens instead of
        stalling a chunk wall per chunk. Once the prompt is fully staged
        further calls stage plain decode rounds and the final chunk's
        sampled token folds at its sync — the caller observes completion
        as ``prefill_seq.prefill_prompt is None``.
        Returns the tokens folded by this call (possibly {}).
        """
        assert prefill_seq is None or not self.spec_enabled, \
            "hybrid steps don't compose with speculative decoding"
        if (self.admission == "optimistic" and self.under_pressure
                and (prefill_seq is None or self.active_sequences())):
            # Watermark pressure settles first: in-flight calls hold
            # predicted-ctx page grants, so a preemption decision waits
            # for a synchronous round (which drains, then preempts as
            # it stages; the chaos gate runs inside it). The chunk then
            # advances SERIALLY: its pages were all allocated at
            # prefill_begin, so it cannot deepen the shortage, and
            # skipping it would starve the prefill for as long as
            # pressure holds. (With no lanes there is nothing to settle
            # and the round below carries the chunk.)
            result = self.decode_steps()
            if (prefill_seq is not None and not prefill_seq.done
                    and prefill_seq.prefill_prompt is not None):
                self.prefill_step(prefill_seq)
            return result
        self._chaos_step_gate()
        return self._round(max(1, self.engine_cfg.decode_pipeline_depth),
                           prefill_seq)

    def _round(self, depth: int, prefill_seq: Optional[Sequence] = None,
               max_steps: Optional[int] = None) -> Dict[int, List[int]]:
        """Stage one round and enqueue it, then sync the oldest call in
        flight once there are ``depth`` of them, or when nothing could
        be staged."""
        result: Dict[int, List[int]] = {}
        if self.spec_enabled or self._pipeline_rung_blocked():
            # Proposals need the previous round's accepted tokens (spec
            # rounds cannot chain blind like plain decode carries), and
            # a batch that outgrew the in-flight rung settles, then
            # grows.
            result = self.drain_pipeline()
        if self.spec_enabled:
            call = self._stage_ngram_call(max_steps)
        else:
            call = self._stage_decode_call(prefill_seq, max_steps)
        if call is not None:
            self._inflight.append(call)
        if self._inflight and (len(self._inflight) >= depth or call is None):
            _extend(result, self._sync_oldest())
        return result

    @property
    def pipeline_pending(self) -> bool:
        return bool(self._inflight)

    def abort_pipeline(self) -> None:
        """Discard in-flight calls WITHOUT folding (decode-error
        recovery): after an error their outputs are suspect, and leaving
        stale entries would poison ctx prediction / carry tokens for
        whatever request reuses those slots next."""
        self._inflight.clear()

    def drain_pipeline(self) -> Dict[int, List[int]]:
        """Sync every in-flight call (idle/finish/shutdown path)."""
        result: Dict[int, List[int]] = {}
        while self._inflight:
            _extend(result, self._sync_oldest())
        return result

    def _hybrid_chunk_cap(self, decode_tokens: int) -> int:
        """Chunk-token cap for one hybrid step: the serial chunk cap,
        further bounded by ``step_token_budget`` minus the decode tokens
        actually GRANTED for this dispatch (not lanes * K — lanes near
        their generation budget are granted fewer steps, and deducting
        their full K share would over-shrink the chunk), floored at
        page_size so the prefill always advances. Real (unpadded)
        tokens are what the budget counts; bucket padding is a
        compile-shape artifact."""
        ecfg = self.engine_cfg
        cap = ecfg.chunk_tokens_cap
        budget = ecfg.step_token_budget
        if budget > 0:
            cap = min(cap, max(ecfg.page_size, budget - decode_tokens))
        return cap

    def _stage_hybrid_chunk(self, seq: Sequence,
                            decode_tokens: int) -> Optional[dict]:
        """Host arrays for ``seq``'s next prefill chunk (no dispatch).

        Advances ``seq.prefill_offset`` at STAGE time, so chained hybrid
        dispatches can stage chunk N+1 while chunk N is still in flight
        — the device serializes them on the donated pool, and chunk N+1's
        prefix attention reads pages chunk N has written by then. Only
        the FINAL chunk's sampled token is read back (at sync). Returns
        None once the whole prompt is staged."""
        prompt = seq.prefill_prompt
        if prompt is None or seq.done or seq.prefill_offset >= len(prompt):
            return None
        offset = seq.prefill_offset
        st = self._stage_chunk_arrays(seq, prompt, offset,
                                      self._hybrid_chunk_cap(decode_tokens))
        seq.prefill_offset = offset + st["chunk_tokens"]
        st["final"] = seq.prefill_offset >= len(prompt)
        return st

    def _stage_chunk_only_call(self, chunk: dict) -> dict:
        """Dispatch one staged prefill chunk WITHOUT a decode half (no
        lane could advance this call) and wrap it as a pipeline call, so
        chained chunks keep flowing through _sync_oldest/drain exactly
        like hybrid calls. Counts as a prefill dispatch, not a hybrid
        step, and observes no decode stall — the lanes it would have
        stalled are covered by in-flight work."""
        self._last_decode_end = None   # prefill breaks the decode streak
        c = chunk["chunk_tokens"]
        args = (self.params, self.kv, self._base_key,
                *self._put_operands(self._chunk_host_operand(chunk)))
        (self.kv, p_tok, lg), dseq, t0, t1 = self._run(
            "prefill_chunk", self._prefill_jit, args, slots=1,
            chunk_tokens=c)
        if self.keep_logits:
            p_tok = (p_tok, lg)
        call = {"outs": None, "final": None, "final_window": None,
                "allowed": {}, "seqs": {}, "rung": 0,
                "seq": dseq, "t_enqueue": t0,
                "prefill": {"seq": chunk["seq"], "prompt": chunk["prompt"],
                            "final": chunk["final"], "tok": p_tok}}
        if self.telemetry.enabled:
            self.telemetry.prefill_dispatch_s.observe(t1 - t0)
            self.telemetry.prefill_dispatches.inc()
            off = int(chunk["prefix_len"][0])
            call["ledger"] = {
                "kind": "prefill_chunk", "rung": 0, "slots": 1,
                "tokens": 1 if chunk["final"] else 0,
                "chunk_tokens": c, "steps": 1,
                "staging_s": 0.0, "bubble_s": 0.0,
                "kv_read": c * off + c * (c + 1) // 2,
                "compile_event": chunk["bucket"]
                not in self._prefill_buckets_seen}
            self._prefill_buckets_seen.add(chunk["bucket"])
        return call

    def _stage_decode_call(self, prefill_seq: Optional[Sequence] = None,
                           max_steps: Optional[int] = None
                           ) -> Optional[dict]:
        """Stage and enqueue one fused-decode dispatch (non-blocking)
        from current host state plus the ctx deltas of still-in-flight
        calls (predicted ctx). With nothing in flight those deltas are
        empty and the device is handed exactly the host-known state: the
        synchronous round. ``max_steps`` caps every lane's grant; a cap
        of 1 runs the one-step graph (the scheduler's latency mode).

        With ``prefill_seq`` (a sequence mid-incremental-prefill), its
        next chunk rides the same dispatch: the hybrid graph advances
        the chunk and the decode lanes together (page-disjoint, so the
        fusion is value-identical to the serial order), and the call
        chains into the pipeline exactly like a plain decode call.

        Returns None when nothing can advance. Grants are evaluated at
        the predicted positions; lanes that stop mid-flight (EOS) waste
        at most their staged steps, whose tokens the sync step discards
        (KV garbage at dead positions is always rewritten by a later
        owner before being attended).
        """
        ecfg = self.engine_cfg
        clock = self.telemetry.clock
        clock.enter("stage")
        clock.part("pages")          # compaction, preemption, grants
        k_steps = max(1, ecfg.decode_steps_per_call)
        if max_steps is not None:
            k_steps = min(k_steps, max_steps)
        if not self._inflight:
            self._compact_slots()     # rung can step down between bursts
        # Predicted per-slot ctx advance from unsynced calls.
        ahead: Dict[int, int] = {}
        for call in self._inflight:
            for slot, steps in call["allowed"].items():
                ahead[slot] = ahead.get(slot, 0) + steps
        active_seqs = self.active_sequences()
        if not active_seqs and prefill_seq is None:
            return None
        if not self._inflight:
            # Watermark check first: under optimistic admission,
            # pressure preempts the most-recently-admitted lanes BEFORE
            # any grants, so the surviving lanes advance at full
            # k_steps. Only with nothing in flight (in-flight calls hold
            # predicted-ctx page grants): decode_steps drains to here.
            active_seqs = self._preempt_for_pressure(active_seqs, k_steps)
        allowed_by_slot: Dict[int, int] = {}
        staged: List[Sequence] = []
        for seq in active_seqs:
            lag = ahead.get(seq.slot, 0)
            steps = self._grant_decode_steps(
                seq, k_steps, pred_ctx=seq.ctx_len + lag,
                pred_done=len(seq.generated) + lag)
            if steps <= 0:
                if lag == 0:
                    # Nothing in flight can finish it and the pool has
                    # zero slack: preempt (optimistic; lag == 0 means no
                    # in-flight call touches it, so eviction is safe) or
                    # fail the sequence with "oom". Budget/room
                    # exhaustion can't land here — _maybe_finish already
                    # marked those done.
                    self._starved(seq)
                continue                      # ahead calls may still emit
            allowed_by_slot[seq.slot] = steps
            staged.append(seq)
        # Stage the chunk AFTER grant filtering: the step token budget
        # deducts only the lanes actually advancing in THIS dispatch, so
        # a call whose lanes are all covered by in-flight work doesn't
        # shrink the chunk for decode tokens it isn't producing.
        chunk = None
        if prefill_seq is not None:
            chunk = self._stage_hybrid_chunk(
                prefill_seq, sum(allowed_by_slot.values()))
        if not staged and chunk is None:
            return None
        if not staged:
            # No decode lane can advance this call (all grants covered by
            # in-flight work, or no lanes at all): dispatch the chunk on
            # the plain prefill graph instead of burning a dead B x K
            # decode scan inside the hybrid graph.
            return self._stage_chunk_only_call(chunk)

        # A lane _starved() preempted above has no slot anymore — drop
        # it before staging host arrays (seq.slot == -1 would index the
        # last batch row).
        active_seqs = [s for s in active_seqs
                       if not s.done and s.slot >= 0]
        # Ladder rung for this call: smallest compiled graph covering
        # the staged slots, never below any in-flight call's rung —
        # carry folds are element-wise over [rung] arrays, so every
        # in-flight call must share one width. Growth past the in-flight
        # rung is handled by _round (it drains first); shrink lags the
        # pipeline depth, then steps down here.
        b = self._rung_for_slots(active_seqs)
        for call in self._inflight:
            b = max(b, call["rung"])
        self._note_rung(b)
        packed, f = self._stage_batch(active_seqs, b)
        allowed, ctx_lens = f["allowed"], f["ctx"]
        for seq in staged:
            allowed[seq.slot] = allowed_by_slot[seq.slot]
            ctx_lens[seq.slot] = seq.ctx_len + ahead.get(seq.slot, 0)
            if seq.eos_token_id is not None:
                f["eos_ids"][seq.slot] = seq.eos_token_id
        # Each continuing lane consumes the carry token (and penalty
        # window) of the NEWEST in-flight call that advanced it; lanes
        # in no in-flight call (fresh prefills) keep their host-known
        # state. The newest call with a decode half carries them all: a
        # lane it did not advance left it as it came in, from the call
        # before (every call in flight was staged on the ones before it,
        # at one rung). The fold runs inside the program.
        # k_steps == 1 runs the 1-iteration graph (one forward per
        # visible token) instead of masking K-1 steps of the fused graph.
        program = self._hybrid_jit if chunk is not None else (
            self._decode_one_jit if k_steps == 1 else self._decode_multi_jit)
        one_step = (chunk is None and k_steps == 1
                    and ecfg.decode_steps_per_call > 1)
        carry = ()
        if self._null_carry and not one_step:
            carry = (self._null_carry[b],)
            for call in self._inflight:
                if call["final"] is None:
                    continue    # chunk-only call: no decode half, no carry
                carry = ((call["final"], call["final_window"]),)
                for slot in call["allowed"]:
                    f["carried"][slot] = 1
        else:
            assert all(c["final"] is None for c in self._inflight), \
                "a carry in flight and a program that takes none"
        clock.part("put")
        f["step"][:] = self._next_step()
        granted = int(allowed.sum())
        # Non-blocking dispatch: the wall _run_decode records is the
        # enqueue; the device wait surfaces in decode_sync_s at
        # _sync_oldest.
        if chunk is None:
            (self.kv, outs, final, final_window), dseq, t0, _ = \
                self._run_decode(
                    "decode", program,
                    (self.params, self.kv, self._base_key,
                     *self._put_operands(packed), *carry),
                    rung=b, slots=len(staged), tokens=granted)
            p_tok = None
        else:
            # The decode half's step number was drawn first.
            ((self.kv, p_tok, outs, final, final_window), dseq, t0,
             dispatch_dt) = self._run_decode(
                "hybrid", program,
                (self.params, self.kv, self._base_key,
                 *self._put_operands(self._chunk_host_operand(chunk),
                                     packed), *carry),
                rung=b, slots=len(staged), tokens=granted,
                chunk_tokens=chunk["chunk_tokens"])
            self.hybrid_steps_total += 1
            self.telemetry.hybrid_steps.inc()
            self.telemetry.hybrid_dispatch_s.observe(dispatch_dt)
        call = {"outs": outs, "final": final,
                "final_window": final_window,
                "allowed": allowed_by_slot, "rung": b,
                "seq": dseq, "t_enqueue": t0,
                "seqs": {s.slot: s for s in staged}}
        if chunk is not None:
            call["prefill"] = {"seq": chunk["seq"], "prompt": chunk["prompt"],
                               "final": chunk["final"], "tok": p_tok}
        if self.telemetry.enabled:
            # Step-ledger metadata captured at STAGE time (the scratch
            # micros belong to this dispatch); _sync_oldest pushes the
            # record with the folded token count and the device wall.
            kv_read = sum(int(ctx_lens[s.slot]) * allowed_by_slot[s.slot]
                          for s in staged)
            scratch = self._take_stage_scratch()
            if chunk is not None:
                c = chunk["chunk_tokens"]
                off = int(chunk["prefix_len"][0])
                kv_read += c * off + c * (c + 1) // 2
                hkey = ("hybrid", chunk["bucket"])
                if hkey not in self._prefill_buckets_seen:
                    scratch["compile_event"] = True
                self._prefill_buckets_seen.add(hkey)
            call["ledger"] = {
                "kind": "decode" if chunk is None else "hybrid",
                "rung": b, "slots": len(staged),
                # the final chunk's sampled first token folds at sync
                "tokens": 1 if chunk is not None and chunk["final"]
                else 0,
                "chunk_tokens": 0 if chunk is None
                else chunk["chunk_tokens"],
                "steps": k_steps, "kv_read": kv_read, **scratch}
        return call

    def _take_stage_scratch(self) -> dict:
        """The staging wall, bubble and compile flag of the dispatch
        just enqueued, for its ledger record: taken now because the
        record lands at sync, by which point the scratch belongs to a
        newer dispatch."""
        scratch = {"staging_s": self._last_staging_s,
                   "bubble_s": self._pending_bubble,
                   "compile_event": self._last_compile_event}
        self._last_staging_s = self._pending_bubble = 0.0
        self._last_compile_event = False
        return scratch

    def _sync_oldest(self) -> Dict[int, List[int]]:
        """Block on the oldest in-flight call, fold its tokens into host
        state and push its step-ledger record — the one writer of
        decode / hybrid / n-gram verify records. Tokens for lanes that
        finished in an earlier call are discarded (their compute was
        speculative)."""
        call = self._inflight.pop(0)
        tel = self.telemetry
        pf = call.get("prefill")
        spec = call.get("spec", False)

        def read():
            if spec:                       # n-gram verify round
                return (np.asarray(call["emitted"]),      # [B, γ+1]
                        np.asarray(call["n_accepted"]))
            if call["outs"] is not None:
                # [K, B]; with keep_logits (tokens, logits [K, B, V])
                return jax.tree.map(np.asarray, call["outs"])
            # Chunk-only call (no decode half): the blocking sync is on
            # the chunk's sampled token instead (the pipeline's ordering
            # needs it: a later call may release pages it writes).
            if pf is not None:
                jax.block_until_ready(pf["tok"])
            return None

        # The device could not start this call before the readback that
        # preceded it (programs run in order): with nothing else in
        # flight that is before its enqueue, and device_s runs from the
        # enqueue's start to this readback's end.
        t_start = max(call["t_enqueue"], self._device_free_at)
        outs, t_wait, t_done = self._wait(call["seq"], read)
        rows = None
        if self.keep_logits and not spec and outs is not None:
            outs, rows = outs            # (tokens, logits [K, B, V])
        if outs is not None:
            # Chunk-only waits stay out of decode_sync_s (pure prefill
            # device time, not a decode sync).
            tel.decode_sync_s.observe(t_done - t_wait)
        # The blocking sync is DEVICE time: refresh the bubble reference
        # point so the next decode entry measures only host work after
        # it — without this, dispatch-ahead mode would re-count every
        # device step as "host-side bubble".
        self._decode_streak(t_done)
        acc0 = self.spec_accepted
        if spec:
            # Its fold is emission-shaped (accept-prefix + caps), not
            # K-step-shaped.
            result = self._fold_spec_emissions(
                call["seqs"], call["allowed"], call["n_prop"], *outs)
        else:
            if outs is not None:
                self._fold_aux_stats(outs)
            result = {}
            for slot, seq in call["seqs"].items():
                if seq.done or self.slots[seq.slot] is not seq:
                    continue
                got = self._fold_lane(
                    seq, (int(outs[s, slot]) for s in range(outs.shape[0])),
                    None if rows is None else rows[:, slot])
                if got:
                    result[seq.request_id] = got
        if pf is not None:
            # Hybrid call: the chunk's offset advanced at stage time; only
            # the FINAL chunk has host work left — fold its sampled token
            # and complete the incremental prefill. A cancel that landed
            # mid-flight skips the fold (the scheduler reaps the sequence;
            # its pages are released only after the pipeline settles).
            seq = pf["seq"]
            if (pf["final"] and not seq.done
                    and seq.prefill_prompt is not None
                    and seq.slot >= 0 and self.slots[seq.slot] is seq):
                tok = pf["tok"]
                if isinstance(tok, tuple):
                    self._prefill_finish(seq, pf["prompt"],
                                         int(np.asarray(tok[0])[0]),
                                         np.asarray(tok[1])[0])
                else:
                    self._prefill_finish(seq, pf["prompt"],
                                         int(np.asarray(tok)[0]))
                seq.prefill_prompt = None
        led = call.get("ledger")
        if tel.enabled:
            n_tokens = sum(len(t) for t in result.values())
            if outs is not None:
                tel.tokens_per_dispatch.observe(n_tokens)
            if led is not None:
                led["tokens"] += n_tokens
                self._ledger_push(
                    **led, device_s=t_done - t_start,
                    spec_accepted=self.spec_accepted - acc0,
                    seq=call["seq"], t_enqueue=call["t_enqueue"],
                    t_done=t_done)
        return result

    def _pipeline_rung_blocked(self) -> bool:
        """True when staging now would need a bigger ladder rung than
        the in-flight calls were staged at — carry folds are element-
        wise over [rung] arrays, so the pipeline must settle before the
        batch grows past its compiled width. Growth is an occupancy-
        increasing moment (a fresh prefill just took a high slot), so
        the one-call hiccup is rare and bounded."""
        if not self._inflight or len(self.ladder) == 1:
            return False
        # Chunk-only prefill calls (rung 0) have no decode half — no
        # carry to fold, so they impose no width constraint and must
        # not masquerade as a cap (that would drain the pipeline every
        # chunk and re-serialize exactly the stall hybrid chaining
        # removes).
        rungs = [call["rung"] for call in self._inflight
                 if call["final"] is not None]
        if not rungs:
            return False
        cap = max(rungs)
        if cap >= self.ladder[-1]:
            return False
        active = self.active_sequences()
        if not active:
            return False
        return self._rung_for_slots(active) > cap

    def _spec_grant(self, active_seqs: List[Sequence], s_len: int,
                    max_steps: Optional[int]) -> Tuple[List[Sequence],
                                                       Dict[int, int]]:
        """Per-slot emission caps + page grants for one spec round:
        the device writes KV for up to ``s_len``
        positions, so provision pages for what fits and clamp emissions
        to written capacity. Prefix-cache-held pages are reclaimable
        capacity here just as in _grant_decode_steps — counting only the
        raw free list would starve spec rounds once the cache warms up.
        Starved lanes preempt (optimistic) or fail, mirroring the plain
        path. Returns (surviving sequences, {slot: emit_cap})."""
        ecfg = self.engine_cfg
        emit_by_slot: Dict[int, int] = {}
        for seq in active_seqs:
            budget = seq.max_new_tokens - len(seq.generated)
            room = ecfg.max_context - 1 - seq.ctx_len
            emit_cap = max(0, min(s_len, budget, room))
            if max_steps is not None:
                emit_cap = min(emit_cap, max_steps)
            want = min(s_len, room)
            # Provision against pages HELD, not ctx: a partially-accepted
            # round leaves the sequence holding pages past ceil(ctx/ps)
            # (the rejected tail's rows), and recharging from ctx every
            # round would leak one page per partial round until the
            # block table overflows max_pages_per_seq.
            total_pages = kvc.pages_needed(seq.ctx_len + want,
                                           ecfg.page_size)
            need = max(0, min(total_pages, self.max_pages)
                       - len(seq.pages))
            grantable = self._free_plus_evictable()
            if need > grantable:
                slack = len(seq.pages) * ecfg.page_size - seq.ctx_len
                emit_cap = min(emit_cap,
                               slack + grantable * ecfg.page_size)
                need = min(need, grantable)
            if emit_cap <= 0:
                self._starved(seq)
                continue
            if need > 0:
                seq.pages.extend(self._allocate_reclaiming(need))
            emit_by_slot[seq.slot] = emit_cap
        return ([s for s in active_seqs if not s.done and s.slot >= 0],
                emit_by_slot)

    # ------------------------------------------------------------------
    # N-gram speculation (README "Speculative decoding"). The host
    # proposes continuations by suffix-matching each sequence's own
    # prompt+generated history (cheap numpy in the host bubble), and a
    # verify-only round scores γ+1 positions in ONE target forward — every
    # accepted token is a decode step the chip never ran sequentially.
    # Per-sequence EWMA acceptance throttles cold streams to γ=0; rounds
    # where nothing proposes run the plain fused-K graph, so speculation
    # can never lose.
    # ------------------------------------------------------------------

    def _seq_spec_gamma(self, seq: Sequence) -> int:
        """Current adaptive γ for one sequence, ticking the throttle
        probe countdown: a γ=0-throttled sequence re-earns one round of
        real proposals every ``spec_probe_every`` rounds, so a stream
        that turns echoic mid-generation recovers its speedup."""
        gamma = self.engine_cfg.num_speculative_tokens
        if seq.spec_gamma < 0:
            # Fresh streams EARN the full width: the first proposal
            # rides the narrow γ=1 verify (cost ≈ one plain step), and
            # one clean accept promotes to the full γ — so cold traffic
            # that never echoes pays narrow rounds, not γ+1-wide ones.
            seq.spec_gamma = 1 if gamma > 1 else gamma
        if seq.spec_gamma == 0:
            seq.spec_probe_countdown -= 1
            if seq.spec_probe_countdown <= 0:
                # Probe at γ=1: the narrow compiled verify width, so
                # re-checking an echo-free stream costs ~one plain
                # decode step. A clean accept lifts the EWMA and
                # restores the full γ next round.
                seq.spec_gamma = 1
        return seq.spec_gamma

    def _spec_update_adaptive(self, seq: Sequence, drafted: int,
                              accepted: int) -> None:
        """Fold one round's acceptance into the sequence's EWMA and
        throttle/restore its γ. Observes the per-round acceptance-rate
        histogram (the /metrics signal the replay artifact commits)."""
        if drafted <= 0:
            return
        ecfg = self.engine_cfg
        rate = accepted / drafted
        alpha = ecfg.spec_ewma_alpha
        seq.spec_accept_ewma += alpha * (rate - seq.spec_accept_ewma)
        self.telemetry.spec_accept_rate.observe(rate)
        # Per-request spec exposure for the decode trace span.
        seq.spec_rounds += 1
        seq.spec_accepted_toks += accepted
        thr = ecfg.spec_throttle_below
        if thr > 0 and seq.spec_accept_ewma < thr:
            if seq.spec_gamma != 0:
                self.spec_throttles_total += 1
            base = max(1, ecfg.spec_probe_every)
            # Consecutive failed probes double the re-check interval
            # (capped at 8x), so a stream that never echoes spends a
            # vanishing fraction of its rounds on probe verifies.
            seq.spec_probe_interval = min(
                8 * base, max(base, seq.spec_probe_interval * 2))
            seq.spec_gamma = 0
            seq.spec_probe_countdown = seq.spec_probe_interval
        else:
            seq.spec_gamma = ecfg.num_speculative_tokens
            seq.spec_probe_interval = 0

    def _ngram_proposals(self, active_seqs: List[Sequence]
                         ) -> Dict[int, np.ndarray]:
        """Host-side prompt-lookup proposals for every non-throttled
        lane: {slot: proposed token array (1..γ)}. Runs in the host
        bubble between dispatches; sequences with no history match (or
        throttled to γ=0) simply propose nothing."""
        ecfg = self.engine_cfg
        gammas = [self._seq_spec_gamma(seq) for seq in active_seqs]
        # Probe alignment: ANY lane proposing makes the round a verify
        # dispatch for the whole batch, so a lane whose probe is due
        # drags every still-throttled lane into the same probe round —
        # the batch pays one shared verify instead of one per lane's
        # independent countdown (failed probes re-throttle with their
        # own backed-off intervals as usual).
        if any(g > 0 and s.spec_probe_interval > 0
               for s, g in zip(active_seqs, gammas)):
            gammas = [1 if g == 0 else g for g in gammas]
        props: Dict[int, np.ndarray] = {}
        for seq, gamma in zip(active_seqs, gammas):
            if gamma <= 0:
                continue
            # Slice BEFORE concatenating: the proposer only reads the
            # trailing NGRAM_SCAN_CAP tokens, and a full prompt+generated
            # list concat would put O(context) Python copying per lane
            # per round on the decode critical path at long contexts.
            hist = seq.generated[-NGRAM_SCAN_CAP:]
            if len(hist) < NGRAM_SCAN_CAP:
                hist = (seq.prompt_tokens[len(hist) - NGRAM_SCAN_CAP:]
                        + hist)
            prop = ngram_propose(hist, gamma, ecfg.ngram_window)
            if prop.size:
                props[seq.slot] = prop
            elif seq.spec_probe_interval > 0:
                # A probing lane that found nothing to propose goes back
                # to sleep instead of staying armed (scanning every
                # round and firing a verify on the next garbage match);
                # no new evidence, so the interval doesn't double.
                seq.spec_gamma = 0
                seq.spec_probe_countdown = seq.spec_probe_interval
        return props

    def _gate_mixed_batch(self, active_seqs: List[Sequence],
                          proposals: Dict[int, np.ndarray]
                          ) -> Dict[int, np.ndarray]:
        """Mixed-batch guard for fused-K dispatch (K > 1): a verify
        round advances a NON-proposing lane by exactly one token, while
        a fallback round advances every lane by up to K — so a lone
        echoic lane must not drag a wide batch of echo-free bystanders
        into 1-token rounds. Dispatch the verify only when the
        proposers' expected accepted tokens (EWMA-weighted) at least
        cover one token per bystander; otherwise degrade the round to
        the plain fused-K graph. K == 1 has no bystander deficit (a
        verify round strictly dominates a 1-step call), so the gate is
        off there. Returns proposals, or {} to force the fallback."""
        k_steps = max(1, self.engine_cfg.decode_steps_per_call)
        if k_steps <= 1 or not proposals:
            return proposals
        by_slot = {s.slot: s for s in active_seqs}
        expected = sum(by_slot[slot].spec_accept_ewma * len(p)
                       for slot, p in proposals.items()
                       if slot in by_slot)
        bystanders = len(active_seqs) - len(proposals)
        return proposals if expected >= bystanders else {}

    def _spec_width_for(self, proposals: Dict[int, np.ndarray]) -> int:
        """Smallest compiled verify width (γ+1) covering this round's
        longest proposal — probe-only rounds (every proposal length 1)
        run the narrow graph at near-plain cost."""
        longest = max(len(p) for p in proposals.values())
        for w in self._spec_widths:
            if w >= longest + 1:
                return w
        return self._spec_widths[-1]

    def _dispatch_verify(self, active_seqs: List[Sequence],
                         proposals: Dict[int, np.ndarray], s_len: int):
        """Stage + dispatch one verify-only round at the smallest ladder
        rung covering the batch and the compiled width ``s_len``
        (non-blocking). Returns (VerifyRoundOut, {slot: n_proposed},
        rung, the dispatch's number, the instant its call began)."""
        ecfg = self.engine_cfg
        gamma = s_len - 1
        b = self._rung_for_slots(active_seqs)
        self._note_rung(b)
        clock = self.telemetry.clock
        clock.enter("stage")
        _, f = self._stage_batch(active_seqs, b)
        cap = np.zeros((b,), np.int32)
        act = np.zeros((b,), bool)
        drafts = np.zeros((b, gamma), np.int32)
        n_prop = np.zeros((b,), np.int32)
        for seq in active_seqs:
            cap[seq.slot] = len(seq.pages) * ecfg.page_size
            act[seq.slot] = True
            prop = proposals.get(seq.slot)
            if prop is not None and prop.size:
                n = min(len(prop), gamma)
                drafts[seq.slot, :n] = prop[:n]
                n_prop[seq.slot] = n
        # Per-request seeds are not plumbed into spec rounds (acceptance
        # consumes randomness at a data-dependent rate, so a position-
        # keyed stream would not reproduce anyway); greedy — where the
        # byte-identity guarantee lives — is unaffected.
        clock.part("put")
        out, dseq, t0, _ = self._run_decode(
            "spec_verify", self._verify_jit,
            (self.params, self.kv, jnp.asarray(f["tokens"]),
             jnp.asarray(f["ctx"]), jnp.asarray(f["bts"]), jnp.asarray(cap),
             jnp.asarray(act), jnp.asarray(drafts), jnp.asarray(n_prop),
             self._next_key(), jnp.asarray(f["temps"]),
             jnp.asarray(f["top_ps"]), jnp.asarray(f["top_ks"]),
             jnp.asarray(f["rpens"]), jnp.asarray(f["rlasts"]),
             jnp.asarray(f["windows"])),
            rung=b, slots=len(active_seqs),
            tokens=s_len * len(active_seqs))
        self.kv = out.kv
        self.spec_rounds_total += 1
        if self.telemetry.enabled:
            full = ecfg.num_speculative_tokens
            gammas = [s.spec_gamma if s.spec_gamma >= 0 else full
                      for s in active_seqs]
            self.telemetry.spec_gamma_g.set(sum(gammas) / len(gammas))
        return (out, {s.slot: int(n_prop[s.slot]) for s in active_seqs}, b,
                dseq, t0)

    def _fold_spec_emissions(self, seqs: Dict[int, Sequence],
                             emit_by_slot: Dict[int, int],
                             prop_by_slot: Dict[int, int],
                             emitted: np.ndarray, n_acc: np.ndarray
                             ) -> Dict[int, List[int]]:
        """Fold one n-gram verify round's emissions into host state
        (_sync_oldest's ``spec`` arm): emit caps truncate at
        budget/pool limits, EOS stops a lane mid-round via
        _maybe_finish, and each lane's acceptance updates its adaptive
        γ. Lanes cancelled/preempted while the call was in flight are
        skipped — their tokens were speculative compute."""
        result: Dict[int, List[int]] = {}
        s_len = emitted.shape[1]      # this round's compiled width
        for slot, seq in seqs.items():
            if seq.done or seq.slot != slot or self.slots[slot] is not seq:
                continue
            got: List[int] = []
            for j in range(s_len):
                if seq.done or len(got) >= emit_by_slot.get(slot, 0):
                    break
                tok = int(emitted[slot, j])
                if tok < 0:
                    break
                seq.ctx_len += 1
                seq.generated.append(tok)
                if seq.first_token_time == 0.0:
                    seq.first_token_time = time.perf_counter()
                self._maybe_finish(seq, tok)
                got.append(tok)
            # Only positions the host could emit count as drafted (the
            # emit cap can cut a round short when the budget or the
            # context runs out), and accepted clamps to that window, so
            # capped rounds can't drift the rate.
            drafted = min(prop_by_slot.get(slot, 0),
                          emit_by_slot.get(slot, 0))
            accepted = min(int(n_acc[slot]), drafted)
            self.spec_drafted += drafted
            self.spec_accepted += accepted
            self._spec_update_adaptive(seq, drafted, accepted)
            if got:
                result[seq.request_id] = got
        return result

    def _stage_ngram_call(self, max_steps: Optional[int] = None
                          ) -> Optional[dict]:
        """Stage one spec round (non-blocking): propose (host
        numpy), then enqueue the verify-accept forward at the current
        ladder rung and width. The call enters ``_inflight`` like a
        plain decode call, so at depth > 1 the host overlaps its device
        time with scheduler work and the NEXT round's n-gram matching.
        Rounds where NO slot proposes — cold streams, throttled streams,
        no history echo — stage the plain fused-K round instead, so
        speculation is never slower than plain decode. The pipeline is
        empty here (_round drains first)."""
        ecfg = self.engine_cfg
        s_len = ecfg.num_speculative_tokens + 1
        self._compact_slots()         # rung steps down when occupancy drops
        active_seqs = self.active_sequences()
        if not active_seqs:
            return None
        active_seqs = self._preempt_for_pressure(active_seqs, s_len)
        active_seqs = [s for s in active_seqs
                       if not s.done and s.slot >= 0]
        if not active_seqs:
            return None
        proposals = self._gate_mixed_batch(
            active_seqs, self._ngram_proposals(active_seqs))
        if not proposals:
            self.spec_fallback_rounds += 1
            return self._stage_decode_call(max_steps=max_steps)
        s_len = self._spec_width_for(proposals)
        active_seqs, emit_by_slot = self._spec_grant(active_seqs, s_len,
                                                     max_steps)
        if not active_seqs:
            return None
        out, prop_by_slot, rung, dseq, t0 = self._dispatch_verify(
            active_seqs, proposals, s_len)
        call = {"spec": True, "emitted": out.emitted,
                "seq": dseq, "t_enqueue": t0,
                "n_accepted": out.n_accepted,
                "allowed": dict(emit_by_slot), "n_prop": prop_by_slot,
                "seqs": {s.slot: s for s in active_seqs},
                "rung": rung, "outs": None, "final": None,
                "final_window": None}
        if self.telemetry.enabled:
            # Stage-time micros ride on the call; _sync_oldest pushes
            # the record. The verify forward reads the cache at the ctx
            # the lanes ENTER the round with.
            call["ledger"] = {
                "kind": "spec_verify", "rung": rung,
                "slots": len(active_seqs), "tokens": 0,
                "kv_read": sum(s.ctx_len for s in active_seqs) * s_len,
                **self._take_stage_scratch()}
        return call

    # ------------------------------------------------------------------
    # Convenience batch generation (tests, bench, config-1 path)
    # ------------------------------------------------------------------

    def generate(self, prompts: Sequence[Sequence[int]], max_new_tokens: int,
                 temperature: float = 0.0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None) -> List[List[int]]:
        """Generate for a batch of token-id prompts; returns generated ids."""
        seqs = [Sequence(request_id=i, prompt_tokens=list(p),
                         max_new_tokens=max_new_tokens, temperature=temperature,
                         top_p=top_p, eos_token_id=eos_token_id)
                for i, p in enumerate(prompts)]
        for s in seqs:
            if not self.can_ever_admit(s):
                raise ValueError(
                    f"request {s.request_id} needs {self._pages_reserved(s)} "
                    f"pages; pool holds {self.engine_cfg.num_pages - 1}")
        results: Dict[int, List[int]] = {}
        pending = list(seqs)
        while pending or self.active_sequences():
            while pending and self.free_slots() and self.can_admit(pending[0]):
                self.prefill(pending.pop(0))
            self.decode_steps()
            # Optimistic admission may have preempted sequences; requeue
            # them at the head for recompute-resume.
            pending[0:0] = self.take_preempted()
            for s in [s for s in self.slots if s is not None and s.done]:
                results[s.request_id] = s.generated
                self.release(s)
        return [results[i] for i in range(len(seqs))]
