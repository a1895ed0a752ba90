"""Typed configuration for models, engine, parallelism, and server.

The reference framework configures itself with a module-level dict literal
(reference: traffic_generator/main.py:302-313) and three module constants
(main.py:298-300). Here configuration is typed dataclasses; the harness-facing
dict keys (`url`, `model`, `temperature`, `max_tokens`, `trace_path`,
`data_path`, `max_trace`, `log_path`) are preserved by the client harness in
`traffic_generator/` so existing configs keep working.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """Llama-3.1 "llama3" rope frequency rescale (static, per-channel).

    Long-wavelength channels (wavelen > original_max_len /
    low_freq_factor) divide their frequency by ``factor``; short ones
    keep it; the band between interpolates smoothly. Position-independent,
    so it folds into the inverse-frequency table
    (models/common.py rope_frequencies).
    """

    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_len: int = 8192


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rope rescale (DeepSeek-V3 / Kimi-K2 ``rope_scaling`` of type
    "yarn"): channels that turn more than ``beta_fast`` times over
    ``original_max_len`` keep their frequency, those that turn fewer than
    ``beta_slow`` times run ``factor`` x slower, a linear ramp between.
    ``mscale`` / ``mscale_all_dim`` scale cos/sin by the ratio of their
    ``0.1 * m * ln(factor) + 1`` terms (1 when equal) and the attention
    softmax by the square of ``mscale_all_dim``'s
    (models/common.py rope_frequencies, yarn_mscale)."""

    factor: float = 32.0
    original_max_len: int = 4096
    beta_fast: float = 1.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    # A stated cos/sin multiplier (HF ``attention_factor``); 0 = the
    # ratio of the two mscale terms above.
    attention_factor: float = 0.0


# Kinds of a stack whose layers differ (ModelConfig.layer_types). Four
# hold sequence state (engine/kv_cache.py): "full" attends causally over
# the whole context (over K / V pages, or over latent entries where the
# model has ``kv_lora_rank``: models/bailing_hybrid.py) and "window" over
# the last ``sliding_window`` keys, each with a page pool of its own;
# "ssm" (a selective scan, models/sambay.py) and "kda" (a gated delta
# rule with a decay a channel, models/bailing_hybrid.py) hold a
# fixed-size state a SEQUENCE, which a token advances: STATE_KINDS. A
# model has at most one of those two. Two kinds hold nothing: "gmu" gates
# the last ssm layer's scan output of the same token, "cross" attends
# with a query of its own over the "full" layer's keys and values.
LAYER_KINDS = ("full", "window", "ssm", "gmu", "cross", "kda")
STATE_KINDS = ("ssm", "kda")


def sambay_layer_kinds(n_layers: int) -> tuple:
    """SambaY's kinds, derived from the depth as the source's modeling
    file derives them: up to the middle, even layers scan and odd ones
    attend over the window; layer n/2 + 1 is the one full-attention
    layer; behind it even layers are gated memory units and odd ones
    cross-attend."""
    mid = n_layers // 2
    return tuple(("ssm" if l % 2 == 0 else "window") if l <= mid
                 else "full" if l == mid + 1
                 else ("gmu" if l % 2 == 0 else "cross")
                 for l in range(n_layers))


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters for a decoder-only transformer.

    Covers Llama-style (RMSNorm/RoPE/GQA/SwiGLU), Mixtral (adds MoE fields)
    GPT-2 (LayerNorm/learned-positional/GELU), DeepSeek-V3 / Kimi-K2
    (latent attention, sigmoid-routed experts beside a shared one, a
    chip's share of an expert-parallel deployment), Ouro (a looped
    stack: the layers run ``loop_steps`` times a token) and Laguna
    (full and window attention layers with different head counts in one
    stack, a per-head output gate, routed experts beside a shared one)
    and SmallThinker (rope-less full layers among window ones, every
    layer routed from its pre-attention norm to ReLU-gated experts)
    families.
    """

    name: str = "llama"
    # "llama" | "mixtral" | "gpt2" | "deepseek_v3" | "ouro" | "laguna" |
    # "sambay" | "smallthinker" | "bailing_hybrid"
    family: str = "llama"
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    # Llama-3.1+ checkpoints rescale rope frequencies (rope_type
    # "llama3" in HF config.json); None = vanilla rope.
    # or YarnScaling (rope_type "yarn": DeepSeek-V3 / Kimi-K2).
    rope_scaling: Optional[Any] = None
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # MoE (Mixtral family); n_experts == 0 means dense FFN.
    n_experts: int = 0
    n_experts_per_tok: int = 2
    # Static per-expert token capacity = ceil(k*T/E * factor); overflow drops.
    expert_capacity_factor: float = 2.0
    # Sliding-window attention (Mistral): each token attends to itself
    # and the window-1 tokens before it. 0 = full causal attention.
    # Decode runs on the window-aware Pallas kernel (O(window) page
    # reads); prefill uses the window-masked dense path. sp>1 prefill
    # doesn't window yet (engine.__init__ guards).
    sliding_window: int = 0
    # GPT-2 family uses learned positional embeddings + LayerNorm with bias.
    use_learned_pos: bool = False
    use_bias: bool = False
    # Llama-family dialect knobs (all default to vanilla Llama):
    # Qwen2 puts bias terms on the q/k/v projections only.
    qkv_bias: bool = False
    # Gemma stores RMSNorm weights as offsets from 1: y = normed * (o + w).
    # Applied in float32 inside the norm so 1+w never rounds through bf16.
    norm_offset: float = 0.0
    # FFN gate activation: "silu" (Llama/Qwen) | "gelu_tanh" (Gemma).
    hidden_act: str = "silu"
    # Gemma scales token embeddings by sqrt(d_model) (cast to cfg.dtype,
    # matching HF's rounded normalizer) before the first block.
    embed_scale: bool = False
    # Gemma-7B decouples head_dim from d_model/n_heads (3072/16 heads but
    # head_dim 256). 0 = derive from d_model // n_heads.
    head_dim_override: int = 0
    # --- family "deepseek_v3" (DeepSeek-V3 / Kimi-K2 block) ---
    # Latent attention: the cache holds ONE (kv_lora_rank +
    # qk_rope_head_dim)-wide entry per token per layer, shared by all
    # query heads. kv_lora_rank == 0 means K and V per kv head.
    # q_lora_rank == 0 beside a kv_lora_rank: ONE query projection
    # ``wq`` and no query compression (Ling-3.0: ``q_lora_rank`` null).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # The first ``first_k_dense`` layers carry a dense SwiGLU of width
    # d_ff; the rest route to experts of width moe_d_ff beside
    # n_shared_experts always-on ones of the same width. n_experts is
    # the router's width (every routed expert of a layer, on any chip).
    first_k_dense: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    # Router: "softmax" (Mixtral) | "sigmoid" scores with a selection
    # bias added for the top-k only (DeepSeek-V3 noaux_tc); gates are
    # the chosen scores, normalised over the chosen when norm_topk_prob,
    # times routed_scaling_factor. The deepseek_v3 family runs the
    # published pair only (sigmoid, normalised): validate().
    moe_scoring: str = "softmax"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # Group-limited routing (DeepSeek-V3 ``n_group`` / ``topk_group``):
    # the router's experts lie in n_group groups of consecutive experts;
    # a group's score is the sum of its two largest biased scores, the
    # best topk_group groups stay and the top-k is taken within them.
    # n_group == 1: every expert is ranked at once.
    n_group: int = 1
    topk_group: int = 1
    # This chip's share of an expert-parallel deployment: ep_size chips
    # share each layer's routed experts; rank ep_rank holds experts
    # [ep_rank * n_experts / ep_size, ...). What the absent experts
    # would add is left out (no exchange on one chip); no routed pair
    # that lands on a held expert is dropped.
    ep_size: int = 1
    ep_rank: int = 0
    # --- family "ouro" (a looped stack, models/ouro.py) ---
    # The n_layers layers run loop_steps times a token with the SAME
    # weights; the final norm closes every pass and its output is the
    # next pass's input. Each (pass, layer) keeps K / V of its own:
    # n_kv_slots below, derived and never stored.
    loop_steps: int = 1
    # A second RMSNorm on each branch's OUTPUT (x + norm(attn(norm(x))),
    # the same around the FFN): four norms a layer.
    sandwich_norm: bool = False
    # A token leaves at the first pass whose cumulative exit probability
    # reaches this (models/ouro.exit_probabilities). 1.0, as published,
    # is the last pass for every token; per-token depth is not served
    # (the engine refuses < 1).
    early_exit_threshold: float = 1.0
    # --- layer kinds within one model (models/laguna.py) ---
    # layer_types[l] is layer l's attention kind, one of LAYER_KINDS;
    # () = one kind for the whole model (every other family: its
    # sliding_window, if any, holds for all layers). A depth cut keeps a
    # prefix, so the tuple may be longer than n_layers. A "window" layer
    # attends over ``sliding_window`` keys with ``window_n_heads`` query
    # heads and plain rope of ``window_rope_theta`` on every dim; a
    # "full" layer has ``n_heads`` and ``rope_theta`` / ``rope_scaling``
    # on the first ``partial_rotary_factor`` of each head's dims.
    layer_types: tuple = ()
    window_n_heads: int = 0
    window_rope_theta: float = 0.0
    # Share of each head's dims that rope turns (the leading ones; the
    # rest pass through). 1.0 = all.
    partial_rotary_factor: float = 1.0
    # Output gate on the attention heads: "per_head" multiplies head h's
    # output by sigmoid(x_normed . w_h) before the output projection
    # (gated attention, head-wise); "none" = no gate.
    attn_gate: str = "none"
    # Kinds whose layers apply NO rope: a query sees no position signal
    # there (NoPE; models/smallthinker.py's full layers). () = every
    # kind takes its rope.
    nope_kinds: tuple = ()
    # Where an expert layer's router reads: "ffn_norm" (the experts' own
    # input, the post-attention norm) | "attn_norm" (the normed LAYER
    # input: the routing is known before attention runs).
    router_input: str = "ffn_norm"
    # The routed experts' gate activation: act(x Wg) * (x Wu). "silu"
    # (SwiGLU) | "relu" (ReGLU: an expert's hidden row is sparse).
    moe_act: str = "silu"
    # The expert layers also count the rows the grouped kernels ran,
    # whole tiles, beside the real pairs (models/deepseek_v3.py
    # ROW_STATS: one more aux stat, so a preset that had none keeps its
    # step programs as they were).
    moe_row_stats: bool = False
    # --- family "sambay" (models/sambay.py): state-space layers, one
    # full-attention layer whose K / V the cross layers read, gated
    # memory units, differential attention. ``layer_types`` is DERIVED
    # from ``n_layers`` (sambay_layer_kinds), so a depth cut gives a
    # whole small model. The scan's sizes (Mamba-1): d_inner =
    # ssm_expand * d_model, ssm_d_state, ssm_d_conv, ssm_dt_rank (0 =
    # ceil(d_model / 16)).
    ssm_expand: int = 2
    ssm_d_state: int = 16
    ssm_d_conv: int = 4
    ssm_dt_rank: int = 0
    # Differential attention: the KV pool holds PAIR heads, head j of the
    # first half beside head j of the second (models/sambay.py), so a
    # pool entry has n_kv_heads / 2 heads of 2 x head_dim.
    diff_attn: bool = False
    # A KV pool is ALLOCATED with a page's (position, head) rows as one
    # dim, ``[slots, P, page * heads, width]``: the attention kernels'
    # own view of it. With 8 (or 4, 16) KV heads the chip lays the
    # five-dim pool out that way by itself; with the 10 pair heads of
    # Phi-4-mini-flash it pads the heads to 16 or transposes them behind
    # the page dim, and copies the whole pool in front of every kernel
    # call. Only a stack of mixed kinds reads it (engine.make_kind_attn).
    pool_rows_merged: bool = False
    # --- hyper-connections (models/hyper_connections.py) ---
    # Residual STREAMS a token carries through the stack: 1 = the plain
    # residual ``x + f(norm(x))`` of every other preset. With n > 1 each
    # sublayer reads a per-token mix of the n streams and writes back
    # through a doubly stochastic n x n matrix (``hc_sinkhorn_iters``
    # column-then-row normalisations of exp(clip(., +-hc_res_clamp)),
    # ``hc_eps`` in every divisor and in the stream norm) plus a
    # per-stream share of its output. The streams live inside one
    # forward call: no cache entry knows of them.
    # --- family "bailing_hybrid" (models/bailing_hybrid.py): "kda"
    # layers (Kimi delta attention, arXiv:2510.26692) beside latent
    # attention ("full" layers over the latent pool). A kda layer has
    # kda_n_heads heads of kda_head_dim for q, k and v alike, a causal
    # depthwise convolution of kda_d_conv taps in front of each, a decay
    # a CHANNEL ``g = kda_gate_lower_bound * sigmoid(.)`` (so exp(g) is
    # never under exp(lower bound) a token) and a float32 state
    # ``[kda_head_dim, kda_head_dim]`` a head a sequence.
    kda_n_heads: int = 0
    kda_head_dim: int = 128
    kda_d_conv: int = 4
    kda_gate_lower_bound: float = -5.0
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0
    dtype: jnp.dtype = jnp.bfloat16

    def __post_init__(self):
        if self.family == "sambay":
            object.__setattr__(self, "layer_types",
                               sambay_layer_kinds(self.n_layers))

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        """Width of a state-space layer's scan."""
        return self.ssm_expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return self.ssm_dt_rank or -(-self.d_model // 16)

    @property
    def pool_kv_heads(self) -> int:
        """KV heads of a pool entry as stored."""
        return self.n_kv_heads // 2 if self.diff_attn else self.n_kv_heads

    @property
    def pool_head_dim(self) -> int:
        """Width of a pool entry's head as stored."""
        return self.head_dim * 2 if self.diff_attn else self.head_dim

    @property
    def kda_width(self) -> int:
        """Columns of a kda layer's q (and of its k, and of its v)."""
        return self.kda_n_heads * self.kda_head_dim

    @property
    def state_kind(self) -> str:
        """The kind of this model's layers that hold a state a sequence
        ("" = none): one of STATE_KINDS."""
        kinds = self.layer_types[:self.n_layers]
        return next((k for k in STATE_KINDS if k in kinds), "")

    def state_shapes(self) -> tuple:
        """What ONE state slot of ONE layer holds: (the convolution's
        tail, the recurrent state), each as a shape behind the pool's
        ``[layers, slots]``. "ssm": the last d_conv - 1 inputs
        [d_conv - 1, d_inner] and h [d_state, d_inner]; "kda": the last
        kda_d_conv - 1 inputs of q, k and v side by side, each input's
        3 x heads x d channels as [3 x heads, d] (a head a row: whole
        tiles a lane, so the chip holds a lane's tail in one piece and
        kernels/delta_rule.kda_tail_step advances it where it lies;
        as [taps, channels] the chip lays the SLOTS along the tiles'
        rows and a lane's tail is 288 half-rows of other lanes' tiles)
        and a matrix [d_k, d_v] a head. The tail is in the model dtype,
        the state float32."""
        if self.state_kind == "kda":
            return ((self.kda_d_conv - 1, 3 * self.kda_n_heads,
                     self.kda_head_dim),
                    (self.kda_n_heads, self.kda_head_dim, self.kda_head_dim))
        return ((self.ssm_d_conv - 1, self.d_inner),
                (self.ssm_d_state, self.d_inner))

    def state_bytes_per_seq(self) -> int:
        """Bytes of per-sequence state the layers of ``state_kind`` hold
        (0: none): ``state_shapes`` a layer, the state float32 and the
        convolution's tail in the model dtype."""
        kind = self.state_kind
        if not kind:
            return 0
        tail, state = self.state_shapes()
        return len(self.kind_layers(kind)) * (
            math.prod(state) * 4
            + math.prod(tail) * jnp.dtype(self.dtype).itemsize)

    @property
    def latent_dim(self) -> int:
        """Width of one latent cache entry (0 = K/V per kv head)."""
        return (self.kv_lora_rank + self.qk_rope_head_dim
                if self.kv_lora_rank else 0)

    @property
    def n_kv_slots(self) -> int:
        """Leading dim of the KV pool: one slot per (pass, layer), pass
        major (slot = pass * n_layers + layer); n_layers unlooped. A
        latent pool beside layers of another kind has a slot a "full"
        layer."""
        if self.layer_types and self.kv_lora_rank:
            return len(self.kind_layers("full"))
        return self.n_layers * self.loop_steps

    def kind_layers(self, kind: str) -> tuple:
        """Indices of the layers of attention kind ``kind`` (a kind's KV
        slot is a layer's place in this tuple)."""
        return tuple(l for l, k in enumerate(self.layer_types[:self.n_layers])
                     if k == kind)

    def kind_heads(self, kind: str) -> int:
        """Query heads of a layer of ``kind``."""
        return self.window_n_heads if kind == "window" else self.n_heads

    @property
    def n_local_experts(self) -> int:
        """Routed experts of one layer held on this chip."""
        return self.n_experts // self.ep_size

    @property
    def n_rep(self) -> int:
        """Query heads per KV head (GQA group size)."""
        return self.n_heads // self.n_kv_heads

    def validate(self) -> None:
        if not self.head_dim_override:
            assert self.d_model % self.n_heads == 0
        assert self.n_heads % self.n_kv_heads == 0
        if self.n_experts:
            assert self.n_experts_per_tok <= self.n_experts
            # This chip's share: the held experts divide the router's
            # width, and the rank names one of the shares.
            assert self.n_experts % self.ep_size == 0
            assert 0 <= self.ep_rank < self.ep_size
            assert 0 <= self.first_k_dense <= self.n_layers
            assert self.moe_scoring in ("softmax", "sigmoid")
            # Whole groups of experts; a chip's share is whole groups or
            # a whole part of one.
            assert self.n_group >= 1 and self.n_experts % self.n_group == 0
            assert 1 <= self.topk_group <= self.n_group
            assert (self.n_experts_per_tok
                    <= self.topk_group * (self.n_experts // self.n_group))
        rot = self.head_dim * self.partial_rotary_factor
        assert 0 < rot <= self.head_dim and rot == int(rot) and rot % 2 == 0
        assert self.attn_gate in ("none", "per_head")
        assert self.router_input in ("ffn_norm", "attn_norm")
        assert self.moe_act in ("silu", "relu")
        assert set(self.nope_kinds) <= set(self.layer_types)
        if self.layer_types:
            kinds = self.layer_types[:self.n_layers]
            assert len(kinds) == self.n_layers, \
                "layer_types names fewer layers than n_layers"
            assert set(kinds) <= set(LAYER_KINDS)
            if self.family == "sambay":
                # Whole (ssm, window) pairs up to the middle, the middle
                # layer a scan (its output feeds the memory units), whole
                # (gmu, cross) pairs behind the full layer.
                assert self.n_layers >= 8 and self.n_layers % 4 == 0
                assert self.sliding_window > 0 and self.n_kv_heads % 2 == 0
                # models/sambay.py writes differential attention only.
                assert self.diff_attn
                assert self.n_rep * 2 * self.pool_kv_heads == self.n_heads
            elif "window" in kinds:
                assert set(kinds) <= {"full", "window"}
                assert self.sliding_window > 0 and self.window_rope_theta > 0
                assert self.window_n_heads > 0
                assert self.window_n_heads % self.n_kv_heads == 0
            elif self.family == "bailing_hybrid":
                # Delta-rule layers beside latent attention: the full
                # kind's pool is the latent pool.
                assert set(kinds) <= {"full", "kda"} and self.kv_lora_rank
                assert self.kda_n_heads > 0 and self.kda_d_conv >= 2
                assert self.kda_gate_lower_bound < 0
            # One KV slot a layer; a latent pool only beside kda layers.
            assert self.loop_steps == 1
            assert self.family == "bailing_hybrid" or not self.kv_lora_rank
        # Pair heads and merged rows are a stack of mixed kinds' pools'.
        assert self.layer_types or not (self.diff_attn
                                        or self.pool_rows_merged)
        assert self.loop_steps >= 1 and 0.0 <= self.early_exit_threshold <= 1.0
        # Only the looped family's forward runs passes / output norms.
        assert self.family == "ouro" or (self.loop_steps == 1
                                         and not self.sandwich_norm)
        assert self.hc_mult >= 1 and self.hc_sinkhorn_iters >= 1
        assert self.hc_eps > 0 and self.hc_res_clamp > 0
        # Only models/deepseek_v3.py's block carries several streams.
        assert self.hc_mult == 1 or self.family == "deepseek_v3"
        if self.family in ("deepseek_v3", "bailing_hybrid"):
            assert self.kv_lora_rank and self.qk_rope_head_dim % 2 == 0
            # The one routing a preset has: models/deepseek_v3.py route()
            # implements no other until a configuration needs it.
            assert self.moe_scoring == "sigmoid" and self.norm_topk_prob


# ---------------------------------------------------------------------------
# Presets. Tiny variants are for tests (random init, CPU-mesh friendly).
# ---------------------------------------------------------------------------

def llama3_8b() -> ModelConfig:
    return ModelConfig(
        name="llama-3-8b", family="llama", vocab_size=128256, d_model=4096,
        n_layers=32, n_heads=32, n_kv_heads=8, d_ff=14336,
        max_seq_len=8192, rope_theta=500000.0,
    )


def llama3_70b() -> ModelConfig:
    return ModelConfig(
        name="llama-3-70b", family="llama", vocab_size=128256, d_model=8192,
        n_layers=80, n_heads=64, n_kv_heads=8, d_ff=28672,
        max_seq_len=8192, rope_theta=500000.0,
    )


def llama31_8b() -> ModelConfig:
    """Llama-3.1-8B: 3.0 dims + the "llama3" rope rescale that extends
    context to 128k (rope_scaling in HF config.json, parsed by
    weights.config_from_hf)."""
    return ModelConfig(
        name="llama-3.1-8b", family="llama", vocab_size=128256, d_model=4096,
        n_layers=32, n_heads=32, n_kv_heads=8, d_ff=14336,
        max_seq_len=131072, rope_theta=500000.0, rope_scaling=RopeScaling(),
    )


def mixtral_8x7b() -> ModelConfig:
    return ModelConfig(
        name="mixtral-8x7b", family="mixtral", vocab_size=32000, d_model=4096,
        n_layers=32, n_heads=32, n_kv_heads=8, d_ff=14336,
        max_seq_len=8192, rope_theta=1000000.0, n_experts=8,
        n_experts_per_tok=2,
    )


def mistral_7b() -> ModelConfig:
    """Mistral-7B-v0.1 — the model the reference's Ollama endpoint
    actually served (reference: traffic_generator/main.py:308 config
    'model': 'mistral'). Its signature sliding window flows through the
    window-aware serving path (dense mask + windowed Pallas kernels +
    behind-window page eviction)."""
    return ModelConfig(
        name="mistral-7b", family="llama", vocab_size=32000, d_model=4096,
        n_layers=32, n_heads=32, n_kv_heads=8, d_ff=14336,
        max_seq_len=8192, rope_theta=10000.0, sliding_window=4096,
    )


def qwen2_7b() -> ModelConfig:
    """Qwen2-7B: Llama-shaped with bias on the q/k/v projections and a
    1M rope base. Loads from HF ``model_type: qwen2`` checkpoints
    (weights.config_from_hf)."""
    return ModelConfig(
        name="qwen2-7b", family="llama", vocab_size=152064, d_model=3584,
        n_layers=28, n_heads=28, n_kv_heads=4, d_ff=18944,
        max_seq_len=8192, rope_theta=1000000.0, norm_eps=1e-6,
        qkv_bias=True,
    )


def phi3_mini() -> ModelConfig:
    """Phi-3-mini-4k: Llama-shaped MHA (32 heads, no GQA) with a
    2047-token sliding window. HF checkpoints store fused qkv_proj /
    gate_up_proj tensors; the loader splits them at read time
    (models/weights.py fused-plan branch) so TP sharding and quantization
    see the standard llama layout."""
    return ModelConfig(
        name="phi-3-mini", family="llama", vocab_size=32064, d_model=3072,
        n_layers=32, n_heads=32, n_kv_heads=32, d_ff=8192,
        max_seq_len=4096, rope_theta=10000.0, sliding_window=2047,
    )


def gemma_7b() -> ModelConfig:
    """Gemma-7B: RMSNorm offset (+1), GeGLU FFN, sqrt(d)-scaled embeddings,
    tied unembedding, and head_dim 256 decoupled from d_model/n_heads."""
    return ModelConfig(
        name="gemma-7b", family="llama", vocab_size=256000, d_model=3072,
        n_layers=28, n_heads=16, n_kv_heads=16, d_ff=24576,
        max_seq_len=8192, rope_theta=10000.0, norm_eps=1e-6,
        tie_embeddings=True, norm_offset=1.0, hidden_act="gelu_tanh",
        embed_scale=True, head_dim_override=256,
    )


def kimi_k2_ep32() -> ModelConfig:
    """Kimi-K2-Instruct (the DeepSeek-V3 block) as ONE chip's share of a
    32-way expert-parallel deployment, at every published width: 64
    heads of latent attention, a router over all 384 experts of which
    this chip (rank 0) holds 12, one shared expert, YaRN rope. Depth is
    1 dense + 6 expert layers (further layers lie on later pipeline
    stages) and the vocabulary is this chip's 1/8 (rows 0..20479):
    bench/configs/kimi-k2-ep32-bf16.json states the cut."""
    return ModelConfig(
        name="kimi-k2-ep32", family="deepseek_v3", vocab_size=20480,
        d_model=7168, n_layers=7, n_heads=64, n_kv_heads=64, d_ff=18432,
        max_seq_len=131072, rope_theta=50000.0,
        rope_scaling=YarnScaling(factor=32.0, original_max_len=4096,
                                 beta_fast=1.0, beta_slow=1.0,
                                 mscale=1.0, mscale_all_dim=1.0),
        norm_eps=1e-6, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        first_k_dense=1, moe_d_ff=2048, n_shared_experts=1,
        n_experts=384, n_experts_per_tok=8, moe_scoring="sigmoid",
        norm_topk_prob=True, routed_scaling_factor=2.827,
        ep_size=32, ep_rank=0,
    )


def xing4_29b_pp6() -> ModelConfig:
    """Xing4.0-29B-A4B (XingChen-AGI) as ONE stage of a six-stage
    pipeline, at every published width: 1 dense + 6 expert layers of the
    40, FOUR residual streams a token mixed by manifold-constrained
    hyper-connections twice a layer (arXiv:2512.24880; 20 Sinkhorn
    iterations), latent attention at 32 heads, a router over 64 experts
    of width 1024 ALL held here (top-4, sigmoid scores, a selection
    bias, scaling 2) beside one shared expert, YaRN 64 x 4096, and the
    whole untied vocabulary of 131,072 so that the stage serves alone.
    bench/configs/xing4-29b-pp6-bf16.json states the cut and what is
    assumed."""
    return ModelConfig(
        name="xing4-29b-pp6", family="deepseek_v3", vocab_size=131072,
        d_model=3584, n_layers=7, n_heads=32, n_kv_heads=32, d_ff=9216,
        max_seq_len=262144, rope_theta=10000.0,
        rope_scaling=YarnScaling(factor=64.0, original_max_len=4096,
                                 beta_fast=32.0, beta_slow=1.0,
                                 mscale=1.0, mscale_all_dim=1.0),
        norm_eps=1e-6, q_lora_rank=768, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        first_k_dense=1, moe_d_ff=1024, n_shared_experts=1,
        n_experts=64, n_experts_per_tok=4, moe_scoring="sigmoid",
        norm_topk_prob=True, routed_scaling_factor=2.0,
        ep_size=1, ep_rank=0, moe_row_stats=True, hc_mult=4,
        hc_sinkhorn_iters=20, hc_eps=1e-6, hc_res_clamp=30.0,
    )


def ling3_flash_ep8() -> ModelConfig:
    """Ling-3.0-flash (inclusionAI; ``bailing_hybrid``) as ONE chip of an
    EP8 x PP3 deployment, at every published width: rank 0 of stage 0
    holds published layers 0 and 2..13 (the two leading dense layers
    count once): 1 dense + 12 expert layers, of which 11 are KDA layers
    (32 heads of a 128 x 128 float32 state, a decay a channel behind a
    4-tap convolution) and 2 latent-attention layers (published layers 5
    and 11: ``(l + 1) % 6 == 0``; 32 heads, ONE query projection, a
    sigmoid gate a head). The router scores all 512 experts in 8 groups
    of 64, keeps the best 4 groups and takes the top 8 within them; this
    chip holds group 0 and 1/8 of the vocabulary (rows 0..19647), with
    the embedding and the head slice so that it serves alone.
    bench/configs/ling3-flash-ep8-bf16.json states the cut and what is
    assumed."""
    return ModelConfig(
        name="ling3-flash-ep8", family="bailing_hybrid", vocab_size=19648,
        d_model=2560, n_layers=13, n_heads=32, n_kv_heads=32, d_ff=6144,
        max_seq_len=262144, rope_theta=6000000.0, norm_eps=1e-6,
        q_lora_rank=0, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, attn_gate="per_head",
        first_k_dense=1, moe_d_ff=768, n_shared_experts=1, n_experts=512,
        n_experts_per_tok=8, moe_scoring="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=2.5, n_group=8, topk_group=4,
        ep_size=8, ep_rank=0,
        layer_types=(("kda",) * 4 + ("full",) + ("kda",) * 5 + ("full",)
                     + ("kda",) * 2),
        kda_n_heads=32, kda_head_dim=128, kda_d_conv=4,
        kda_gate_lower_bound=-5.0,
    )


def ouro_2_6b() -> ModelConfig:
    """Ouro-2.6B (ByteDance), a looped LM, at every published size: 48
    layers run 4 times a token with shared weights, MHA 16 x 128, four
    RMSNorms a layer, the final norm after every pass; 192 KV slots
    (1.5 MiB of bf16 K / V a token).
    bench/configs/ouro-2.6b-bf16.json lists what is assumed."""
    return ModelConfig(
        name="ouro-2.6b", family="ouro", vocab_size=49152, d_model=2048,
        n_layers=48, n_heads=16, n_kv_heads=16, d_ff=5632,
        max_seq_len=65536, rope_theta=1000000.0, norm_eps=1e-6,
        head_dim_override=128, loop_steps=4, sandwich_norm=True,
        early_exit_threshold=1.0,
    )


def laguna_s_ep8() -> ModelConfig:
    """Laguna-S-2.1 (poolside) as ONE chip's share of an EP8 x PP4
    deployment, at every published width: layers 0-11 of 48 (a full
    attention layer with 48 query heads, then three window-512 layers
    with 72, three times over; 8 KV heads x 128 throughout), layer 0 a
    dense SwiGLU of 12288, layers 1-11 a router over all 256 experts of
    which this chip (rank 0 of 8) holds 32, top-10, one shared expert;
    a per-head sigmoid output gate; YaRN rope on half of each head on a
    full layer, plain rope on a window layer; vocabulary rows 0..12543.
    bench/configs/laguna-s-ep8-bf16.json states the cut and what is
    assumed."""
    period = ("full", "window", "window", "window")
    return ModelConfig(
        name="laguna-s-ep8", family="laguna", vocab_size=12544,
        d_model=3072, n_layers=12, n_heads=48, n_kv_heads=8, d_ff=12288,
        max_seq_len=1048576, rope_theta=500000.0,
        rope_scaling=YarnScaling(factor=128.0, original_max_len=8192,
                                 beta_fast=32.0, beta_slow=1.0,
                                 attention_factor=1.4852030263919618),
        norm_eps=1e-6, head_dim_override=128, sliding_window=512,
        layer_types=period * 12, window_n_heads=72,
        window_rope_theta=10000.0, partial_rotary_factor=0.5,
        attn_gate="per_head", first_k_dense=1, moe_d_ff=1024,
        n_shared_experts=1, n_experts=256, n_experts_per_tok=10,
        moe_scoring="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=2.5, ep_size=8, ep_rank=0,
    )


def smallthinker_21b_pp4() -> ModelConfig:
    """SmallThinker-21BA3B-Instruct (PowerInfer) as ONE stage of a
    four-stage pipeline, at every published width: 12 of 52 layers
    (three whole periods of one full-attention layer WITHOUT rope and
    three window-4096 layers with rope of theta 1.5e6; 28 query / 4 KV
    heads of 128), every layer a router over 64 experts of width 768,
    ALL of them held here, top-6, the gates a softmax over the six
    chosen logits, the router fed the PRE-attention norm, ReLU-gated
    experts, no shared expert, no dense layer; the embedding and the
    untied head over 151,936 ids both here, so that the stage serves
    alone. bench/configs/smallthinker-21b-pp4-bf16.json states the cut
    and what is assumed."""
    return ModelConfig(
        name="smallthinker-21b-pp4", family="smallthinker",
        vocab_size=151936, d_model=2560, n_layers=12, n_heads=28,
        n_kv_heads=4, d_ff=0, max_seq_len=16384, rope_theta=1500000.0,
        norm_eps=1e-6, head_dim_override=128, sliding_window=4096,
        layer_types=("full", "window", "window", "window") * 13,
        window_n_heads=28, window_rope_theta=1500000.0,
        nope_kinds=("full",), router_input="attn_norm", moe_act="relu",
        moe_row_stats=True, moe_d_ff=768, n_experts=64, n_experts_per_tok=6,
        moe_scoring="softmax", norm_topk_prob=True,
        routed_scaling_factor=1.0, ep_size=1, ep_rank=0,
    )


def phi4_mini_flash() -> ModelConfig:
    """Phi-4-mini-flash-reasoning (microsoft; SambaY, arXiv:2507.06607)
    whole, at every published size: 32 layers of which 9 are Mamba-1
    scans (d_inner 5120, state 16), 8 attend over a 512-token window, one
    over the whole context, 7 are gated memory units and 7 cross-attend
    over that one layer's K / V; differential attention, 40 query / 20 KV
    heads of 64; LayerNorm with bias; no positions of any kind.
    bench/configs/phi4-mini-flash-bf16.json lists what is assumed."""
    return ModelConfig(
        name="phi4-mini-flash", family="sambay", vocab_size=200064,
        d_model=2560, n_layers=32, n_heads=40, n_kv_heads=20, d_ff=10240,
        max_seq_len=262144, rope_theta=0.0, norm_eps=1e-5,
        tie_embeddings=True, sliding_window=512, diff_attn=True,
        pool_rows_merged=True,
    )


def gpt2_small() -> ModelConfig:
    return ModelConfig(
        name="gpt2", family="gpt2", vocab_size=50257, d_model=768,
        n_layers=12, n_heads=12, n_kv_heads=12, d_ff=3072,
        max_seq_len=1024, norm_eps=1e-5, use_learned_pos=True, use_bias=True,
        tie_embeddings=True,
    )


def tiny_llama(vocab_size: int = 512) -> ModelConfig:
    """Small Llama for unit tests; dims chosen TPU-tile friendly."""
    return ModelConfig(
        name="tiny-llama", family="llama", vocab_size=vocab_size, d_model=128,
        n_layers=2, n_heads=4, n_kv_heads=2, d_ff=256, max_seq_len=1024,
        rope_theta=10000.0, dtype=jnp.float32,
    )


def tiny_llama_fatkv(vocab_size: int = 512) -> ModelConfig:
    """tiny_llama with a production-shaped KV:compute ratio. The stock
    tiny models carry ~1 KiB of KV per token — two orders of magnitude
    leaner than a real 8B (32 layers x 8 KV heads x 128 dims), which
    makes any KV *data-plane* measurement on them fixed-cost bound.
    Four MHA layers at head_dim 64 put 16 KiB of f32 KV behind every
    token, so handoff/migration payloads reach realistic MiB scale at
    prompt lengths a CPU lane can still prefill in well under a
    second. Unit-scale weights otherwise (d_model 128, d_ff 256)."""
    return ModelConfig(
        name="tiny-llama-fatkv", family="llama", vocab_size=vocab_size,
        d_model=128, n_layers=4, n_heads=8, n_kv_heads=8, d_ff=256,
        max_seq_len=1024, rope_theta=10000.0, head_dim_override=64,
        dtype=jnp.float32,
    )


def tiny_mixtral(vocab_size: int = 512) -> ModelConfig:
    return ModelConfig(
        name="tiny-mixtral", family="mixtral", vocab_size=vocab_size,
        d_model=128, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=256,
        max_seq_len=1024, rope_theta=10000.0, n_experts=4,
        n_experts_per_tok=2, dtype=jnp.float32,
    )


def tiny_mistral(vocab_size: int = 512) -> ModelConfig:
    """Small Mistral-style model (tiny_llama + sliding window): exercises
    the full SWA serving path — windowed masks/kernels, behind-window
    eviction, SWA x sp composition — without a checkpoint."""
    return dataclasses.replace(tiny_llama(vocab_size), name="tiny-mistral",
                               sliding_window=64)


def tiny_qwen2(vocab_size: int = 512) -> ModelConfig:
    """tiny_llama + qkv bias (the Qwen2 dialect) for unit tests."""
    return dataclasses.replace(tiny_llama(vocab_size), name="tiny-qwen2",
                               qkv_bias=True)


def tiny_gemma(vocab_size: int = 512) -> ModelConfig:
    """Small Gemma exercising every dialect knob, including a head_dim
    (48) decoupled from d_model/n_heads (128/4 = 32)."""
    return ModelConfig(
        name="tiny-gemma", family="llama", vocab_size=vocab_size, d_model=128,
        n_layers=2, n_heads=4, n_kv_heads=2, d_ff=256, max_seq_len=1024,
        rope_theta=10000.0, norm_eps=1e-6, tie_embeddings=True,
        norm_offset=1.0, hidden_act="gelu_tanh", embed_scale=True,
        head_dim_override=48, dtype=jnp.float32,
    )


def tiny_phi3(vocab_size: int = 512) -> ModelConfig:
    """tiny_llama + a binding sliding window; loads from fused-projection
    (phi3-style) checkpoints via the fused-plan branch in weights.py."""
    return dataclasses.replace(tiny_llama(vocab_size), name="tiny-phi3",
                               sliding_window=8)


def tiny_kimi(vocab_size: int = 512) -> ModelConfig:
    """The Kimi-K2 / DeepSeek-V3 structure at test widths: 1 dense + 2
    expert layers, 16 routed experts top-4 of which this chip (rank 0 of
    2) holds 8, a shared expert, rope / nope split, YaRN on."""
    return ModelConfig(
        name="tiny-kimi", family="deepseek_v3", vocab_size=vocab_size,
        d_model=128, n_layers=3, n_heads=4, n_kv_heads=4, d_ff=256,
        max_seq_len=4096, rope_theta=10000.0,
        rope_scaling=YarnScaling(factor=8.0, original_max_len=128,
                                 beta_fast=1.0, beta_slow=1.0),
        norm_eps=1e-6, q_lora_rank=64, kv_lora_rank=128,
        qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
        first_k_dense=1, moe_d_ff=128, n_shared_experts=1, n_experts=16,
        n_experts_per_tok=4, moe_scoring="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=2.5, ep_size=2, ep_rank=0,
        dtype=jnp.float32,
    )


def tiny_xing(vocab_size: int = 512) -> ModelConfig:
    """The Xing4.0 structure at test widths: four residual streams mixed
    by hyper-connections around 1 dense + 3 expert layers of latent
    attention, 16 routed experts top-4 ALL held, one shared."""
    return dataclasses.replace(
        tiny_kimi(vocab_size), name="tiny-xing", n_layers=4, ep_size=1,
        routed_scaling_factor=2.0, moe_row_stats=True, hc_mult=4)


def tiny_ling(vocab_size: int = 512) -> ModelConfig:
    """The Ling-3.0-flash structure at test widths: 1 dense + 3 expert
    layers, (kda, kda, full, kda): delta-rule layers of 2 heads with a
    64 x 64 state beside one latent-attention layer with one query
    projection and a gate a head; 16 routed experts in 4 groups of which
    the best 2 stay, top-4, this chip (rank 0 of 4) holding group 0."""
    return ModelConfig(
        name="tiny-ling", family="bailing_hybrid", vocab_size=vocab_size,
        d_model=128, n_layers=4, n_heads=4, n_kv_heads=4, d_ff=256,
        max_seq_len=4096, rope_theta=10000.0, norm_eps=1e-6,
        q_lora_rank=0, kv_lora_rank=128, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, attn_gate="per_head",
        first_k_dense=1, moe_d_ff=128, n_shared_experts=1, n_experts=16,
        n_experts_per_tok=4, moe_scoring="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=2.5, n_group=4, topk_group=2,
        ep_size=4, ep_rank=0, layer_types=("kda", "kda", "full", "kda"),
        kda_n_heads=2, kda_head_dim=64, dtype=jnp.float32,
    )


def tiny_gpt2(vocab_size: int = 512) -> ModelConfig:
    return ModelConfig(
        name="tiny-gpt2", family="gpt2", vocab_size=vocab_size, d_model=128,
        n_layers=2, n_heads=4, n_kv_heads=4, d_ff=256, max_seq_len=512,
        use_learned_pos=True, use_bias=True, tie_embeddings=True,
        dtype=jnp.float32,
    )


def tiny_ouro(vocab_size: int = 512) -> ModelConfig:
    """The Ouro structure at test widths: 2 layers x 3 passes (6 KV
    slots), MHA, sandwich norms, an exit gate."""
    return ModelConfig(
        name="tiny-ouro", family="ouro", vocab_size=vocab_size, d_model=128,
        n_layers=2, n_heads=4, n_kv_heads=4, d_ff=256, max_seq_len=1024,
        rope_theta=10000.0, norm_eps=1e-6, loop_steps=3,
        sandwich_norm=True, dtype=jnp.float32,
    )


def tiny_laguna(vocab_size: int = 512) -> ModelConfig:
    """The Laguna structure at test widths: two periods of (full, window,
    window, window) with 6 / 9 query heads on 3 KV heads, window 8,
    layer 0 dense, then 16 routed experts top-3 of which this chip
    (rank 0 of 4) holds 4, a shared expert, the per-head gate, YaRN on
    half of a full layer's head dims."""
    return ModelConfig(
        name="tiny-laguna", family="laguna", vocab_size=vocab_size,
        d_model=96, n_layers=8, n_heads=6, n_kv_heads=3, d_ff=256,
        max_seq_len=4096, rope_theta=10000.0,
        rope_scaling=YarnScaling(factor=8.0, original_max_len=64,
                                 beta_fast=8.0, beta_slow=1.0,
                                 attention_factor=1.2),
        norm_eps=1e-6, head_dim_override=32, sliding_window=8,
        layer_types=("full", "window", "window", "window") * 2,
        window_n_heads=9, window_rope_theta=1000.0,
        partial_rotary_factor=0.5, attn_gate="per_head", first_k_dense=1,
        moe_d_ff=64, n_shared_experts=1, n_experts=16,
        n_experts_per_tok=3, moe_scoring="sigmoid", norm_topk_prob=True,
        routed_scaling_factor=2.5, ep_size=4, ep_rank=0,
        dtype=jnp.float32,
    )


def tiny_smallthinker(vocab_size: int = 512) -> ModelConfig:
    """The SmallThinker structure at test widths: two periods of (full
    without rope, window x 3 with rope), 8 query / 2 KV heads of 16,
    window 8, every layer routed from its pre-attention norm to top-3
    of 8 ReLU-gated experts, all held, no shared expert, no dense
    layer."""
    return ModelConfig(
        name="tiny-smallthinker", family="smallthinker",
        vocab_size=vocab_size, d_model=64, n_layers=8, n_heads=8,
        n_kv_heads=2, d_ff=0, max_seq_len=4096, rope_theta=10000.0,
        norm_eps=1e-6, head_dim_override=16, sliding_window=8,
        layer_types=("full", "window", "window", "window") * 2,
        window_n_heads=8, window_rope_theta=10000.0,
        nope_kinds=("full",), router_input="attn_norm", moe_act="relu",
        moe_row_stats=True, moe_d_ff=32, n_experts=8, n_experts_per_tok=3,
        moe_scoring="softmax", norm_topk_prob=True,
        routed_scaling_factor=1.0, dtype=jnp.float32,
    )


def tiny_sambay(vocab_size: int = 512) -> ModelConfig:
    """The SambaY structure at test widths: 8 layers holding every kind
    (3 ssm, 2 window, 1 full, 1 gmu, 1 cross), window 8, 8 query / 4 KV
    heads of 16, scan width 128 with state 8."""
    return ModelConfig(
        name="tiny-sambay", family="sambay", vocab_size=vocab_size,
        d_model=64, n_layers=8, n_heads=8, n_kv_heads=4, d_ff=128,
        max_seq_len=4096, rope_theta=0.0, norm_eps=1e-5,
        tie_embeddings=True, sliding_window=8, head_dim_override=16,
        ssm_d_state=8, diff_attn=True, pool_rows_merged=True,
        dtype=jnp.float32,
    )


PRESETS = {
    "llama-3-8b": llama3_8b,
    "llama-3.1-8b": llama31_8b,
    "llama-3-70b": llama3_70b,
    "mixtral-8x7b": mixtral_8x7b,
    "mistral-7b": mistral_7b,
    "qwen2-7b": qwen2_7b,
    "gemma-7b": gemma_7b,
    "phi-3-mini": phi3_mini,
    "gpt2": gpt2_small,
    "kimi-k2-ep32": kimi_k2_ep32,
    "ouro-2.6b": ouro_2_6b,
    "laguna-s-ep8": laguna_s_ep8,
    "phi4-mini-flash": phi4_mini_flash,
    "smallthinker-21b-pp4": smallthinker_21b_pp4,
    "xing4-29b-pp6": xing4_29b_pp6,
    "ling3-flash-ep8": ling3_flash_ep8,
    "tiny-llama": tiny_llama,
    "tiny-llama-fatkv": tiny_llama_fatkv,
    "tiny-qwen2": tiny_qwen2,
    "tiny-gemma": tiny_gemma,
    "tiny-mixtral": tiny_mixtral,
    "tiny-mistral": tiny_mistral,
    "tiny-phi3": tiny_phi3,
    "tiny-gpt2": tiny_gpt2,
    "tiny-kimi": tiny_kimi,
    "tiny-ouro": tiny_ouro,
    "tiny-laguna": tiny_laguna,
    "tiny-sambay": tiny_sambay,
    "tiny-smallthinker": tiny_smallthinker,
    "tiny-xing": tiny_xing,
    "tiny-ling": tiny_ling,
}


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """Device-mesh axes. Axis size 1 disables that axis.

    The mesh is (dp, tp, sp). TP shards attention heads and FFN hidden dim
    with XLA all-reduce over ICI; EP (Mixtral) reuses the tp axis for experts
    (parallel/shardings.py). SP shards the sequence dim for ring-attention
    prefill. The server builds a mesh from this config when n_devices > 1
    (server/http.py InferenceServer.__init__). dp > 1 is replica-per-group
    serving: each replica owns a tp*sp submesh, KV pool and scheduler,
    behind either fleet backend (ServerConfig.fleet) — "in-process"
    threads in one process (server/replicas.py EngineGroup) or
    "subprocess" engine-worker OS processes supervised by a router
    (server/fleet.py ProcessEngineGroup).
    """

    dp: int = 1
    tp: int = 1
    sp: int = 1

    @property
    def n_devices(self) -> int:
        return self.dp * self.tp * self.sp


# What a page count means while ``EngineConfig.page_size`` is None: the
# 16-token page every configuration file and flag was written in.
KV_PAGE_UNIT = 16


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Serving-engine knobs: paging, batching, bucketing."""

    # Paged KV cache. Tokens per KV page: an integer is taken as given;
    # None ("--page-size auto") is chosen ONCE from the bytes of a page
    # and the backend that reads the pool (engine/autosize.py
    # resolve_page_size), before anything else reads this config. While
    # it is None the three page counts below are in KV_PAGE_UNIT-token
    # units, and the resolver restates them in pages of the chosen size.
    page_size: Optional[int] = None
    num_pages: int = 512              # pool size (per chip, per model)
    # A model whose layers differ in kind (ModelConfig.layer_types) has a
    # second pool for its window layers; ``num_pages`` is then the full
    # kind's. 0 = every lane's window span (engine.window_span_pages),
    # which is also what 'auto' sizing gives it.
    num_window_pages: int = 0
    # => max context = page_size * this (16 tokens * this while
    # page_size is None, rounded up to a whole page of the chosen size)
    max_pages_per_seq: int = 64
    # Continuous batching.
    max_batch_size: int = 8           # decode slots in the batched graph
    # Compiled decode-graph ladder (README "Batch ladder"): batch sizes
    # the decode graphs are compiled at, strictly increasing and ending
    # at max_batch_size. The engine dispatches at the smallest rung that
    # covers the occupied slots and moves between rungs as occupancy
    # changes, so a near-empty batch never pays the top rung's per-step
    # latency while a full one uses every HBM-budgeted lane. () = the
    # single legacy rung (max_batch_size,). The CLI's --max-batch-size
    # auto derives both the top rung (from the chip's HBM budget,
    # engine/autosize.py) and the ladder below it.
    decode_ladder: tuple[int, ...] = ()
    max_queue_len: int = 512
    # Prefill bucketing: prompt is right-padded up to the nearest bucket so
    # XLA compiles a bounded number of prefill graphs.
    prefill_buckets: tuple[int, ...] = (64, 128, 256, 512, 1024)
    chunked_prefill_size: int = 0     # 0 = whole-prompt prefill
    # Same-bucket single-chunk prefills batched into one [P, S] dispatch
    # (burst arrivals stop paying one serial forward each). Graphs are
    # compiled for P in {1, this}.
    max_prefill_batch: int = 4
    # Decode attention backend: "auto" picks the Pallas paged-attention
    # kernel (kernels/paged_attention.py) on real TPU and the dense
    # gather path elsewhere; "pallas"/"dense" force one.
    attn_backend: str = "auto"
    # Weight quantization: "int8" stores matmul weights as int8 with
    # per-output-channel scales (models/quant.py), halving the per-step
    # HBM weight traffic that bounds decode throughput. "none" = serve
    # in the model dtype.
    quant: str = "none"
    # KV-cache quantization: "int8" stores pool pages as int8 codes with
    # per-(token, kv-head) f32 scales (engine/kv_cache.py quantize_kv) —
    # halves KV HBM traffic AND doubles the context that fits in a pool
    # of the same byte size. "int4" nibble-packs codes (uint8 pool,
    # trailing dim D/2) for quarter traffic / 4x context at lower
    # fidelity (7 levels per half-range; int8 is the accuracy-safe
    # tier). Dequant is in-kernel (Pallas) or at gather (dense path).
    kv_quant: str = "none"
    # Sequence-parallel prefill algorithm on an sp>1 mesh: "ring"
    # (ppermute K/V rotation, O((S/n)^2) memory — the long-context
    # default) or "ulysses" (two all-to-alls, full-sequence attention
    # per head group — fewer collective hops, balanced causal load;
    # needs head counts divisible by sp after tp sharding).
    sp_attn: str = "ring"
    # Device-side decode steps fused per host call (lax.scan): each host
    # round trip costs ~dispatch latency, so K steps per call multiply
    # steady-state decode throughput by up to K. Streamed tokens are
    # flushed every K steps (latency cost: K * per-step time).
    decode_steps_per_call: int = 8
    # Latency mode: when at most this many sequences are decoding (and
    # nothing is queued or in flight), the scheduler switches to the
    # single-step decode graph so every token streams out as it is
    # sampled — a lone interactive chat gets per-token streaming while
    # loaded batches keep the fused-K throughput path. 0 disables.
    latency_decode_threshold: int = 1
    # Decode dispatch pipeline depth: >1 keeps that many fused-decode
    # calls in flight (later calls consume earlier calls' device-resident
    # carry tokens), hiding host round-trip/dispatch latency behind
    # device compute. Costs up to (depth-1)*K extra speculative steps
    # for lanes that stop mid-flight (their tokens are discarded) and
    # adds (depth-1)*K steps of streaming latency. 1 = fully synchronous.
    decode_pipeline_depth: int = 1
    # Hybrid prefill-decode steps (Sarathi-Serve-style chunked-prefill
    # piggybacking): while a multi-chunk prompt prefills, each chunk is
    # FUSED into the same device dispatch as the batch's K decode steps,
    # so running lanes keep producing tokens instead of stalling a full
    # chunk wall per chunk. Safe because the chunk and the decode lanes
    # touch disjoint KV pages (each sequence reads/writes only its own
    # block table). Off by default; no effect on single-chunk prompts
    # (they still batch-admit through prefill_many) or under speculative
    # decoding (the spec round has its own fused graph).
    hybrid_prefill: bool = False
    # Per-hybrid-step token budget: chunk tokens are capped at
    # step_token_budget minus the decode tokens granted for that
    # dispatch (floored at page_size so the prefill always advances),
    # bounding how much prefill compute any one fused step adds on top
    # of the decode work —
    # the knob that trades TTFT of the long prompt against inter-token
    # latency of everyone else. 0 = uncapped (chunked_prefill_size /
    # largest bucket governs, as in serial chunking).
    step_token_budget: int = 0
    # Sampling defaults (overridable per request).
    temperature: float = 0.0          # 0 => greedy
    top_k: int = 0                    # 0 => disabled
    top_p: float = 1.0
    max_new_tokens: int = 1024
    # Speculative decoding (0 = off; README "Speculative decoding"). γ =
    # proposed tokens per round; each verified round emits 1..γ+1 tokens
    # from ONE target forward. Proposals are n-gram self-drafts (prompt
    # lookup, Saxena 2023): the host matches the sequence's last tokens
    # against its own prompt+generated history and proposes the
    # continuation of the most recent match as one-hot drafts; the
    # verify-only round keeps exact greedy argmax-match acceptance and
    # distribution-exact sampled acceptance. No second model, no second
    # KV pool, no extra HBM — so the decode ladder, host KV tier, SWA
    # eviction and the repetition penalty all stay active.
    num_speculative_tokens: int = 0
    # Longest suffix n-gram matched against the history
    # (matching tries window..1 and takes the most recent match).
    ngram_window: int = 3
    # Per-sequence acceptance-rate EWMA update weight (a
    # fresh echo-free stream throttles after ~2 rejected rounds; an
    # echoic one un-throttles after ~1-2 accepted probe rounds).
    spec_ewma_alpha: float = 0.4
    # A sequence whose acceptance EWMA falls below this is
    # throttled to γ=0 (no proposals; rounds where NO slot proposes run
    # the plain fused-K decode graph instead) so speculation can never
    # lose on echo-free streams. At the defaults a fresh stream
    # throttles after ONE fully-rejected round (0.5 -> 0.3) while an
    # established echoic stream (EWMA near 1) tolerates transient
    # misses; un-throttling needs one clean probe. 0 disables.
    spec_throttle_below: float = 0.35
    # A throttled sequence re-probes (one narrow γ=1 verify
    # round) after this many rounds, so a stream that turns echoic
    # mid-generation can re-earn its γ. Consecutive failed probes back
    # off (doubling, capped at 8x) and the engine aligns every
    # throttled lane onto the same probe round, so echo-free streams
    # spend a vanishing fraction of rounds probing.
    spec_probe_every: int = 48
    # Prefix caching: finished sequences publish their full KV pages for
    # reuse by later requests sharing the prefix (multi-turn chats).
    enable_prefix_cache: bool = True
    # Host-RAM KV tier (README "Tiered KV cache"): evicted prefix-cache
    # pages demote to host memory (up to this many pages) instead of
    # being dropped, and promote back into freshly allocated device
    # pages when a returning prompt — or a preempted sequence's
    # swap-in-resume — needs them. 0 disables the tier (classic
    # free-on-evict). The CLI accepts ``--host-cache-pages auto`` to
    # size from the machine's available RAM (engine/autosize.py).
    host_cache_pages: int = 0
    # --- Admission control (README "Admission & preemption") ---
    # "reserve": a request is admitted only when the pool can hold its
    # prompt plus its FULL max_new_tokens budget — OOM-free by
    # construction, but BurstGPT-style traffic (generations finishing
    # far short of their budget) strands a large fraction of the pool
    # and sheds load while pages are actually free.
    # "optimistic": admit against the prompt footprint plus a small
    # decode headroom; KV exhaustion is handled by preempting the most
    # recently admitted sequence(s) and recompute-resuming them
    # (re-prefill over prompt+generated, token-identical under greedy
    # decoding) instead of rejecting or failing.
    admission: str = "reserve"
    # Optimistic mode: decode-headroom pages charged per request at
    # admission on top of its prompt pages.
    optimistic_headroom_pages: int = 2
    # Low watermark on free+evictable pages: when a decode grant comes
    # up short AND the pool is below this, the engine preempts victims
    # (most recently admitted first) instead of degrading to a stall.
    preempt_watermark_pages: int = 4
    # Starvation guard: after this many preemptions a request is
    # re-admitted under the full worst-case reservation (and is never
    # chosen as a victim again), so it provably finishes.
    preempt_max_per_request: int = 3
    # Fault injection: hold this many real pages out of the pool at
    # engine boot (runtime-adjustable via engine.set_page_pressure /
    # POST /debug/chaos {"page_pressure": n}) so pool-exhaustion paths
    # run deterministically on CPU. Off in production.
    chaos_page_pressure: int = 0
    # Engine-level fault injection (the engine-side counterpart of
    # ServerConfig.chaos_*): every prefill/decode dispatch raises with
    # this probability, exercising the scheduler error paths and the
    # replica health machine deterministically on CPU. Works under both
    # fleet backends (per-worker via the chaos RPC in "subprocess" mode;
    # kill -9 / SIGTERM-drain chaos for real process faults lives in the
    # fleet layer — POST /debug/chaos {"kill": ...}). Off in production.
    chaos_step_failure_rate: float = 0.0
    # Each dispatch sleeps this long first, simulating the documented TPU
    # wedge failure mode (the step watchdog detects it in either fleet
    # backend; with --fleet subprocess the wedge is confined to one
    # worker process instead of sharing the router's GIL).
    chaos_step_wedge_s: float = 0.0
    # Reuse the decode-step host staging arrays (block tables, sampling
    # params) across dispatches, refreshing only the rows whose occupant
    # or pages changed, instead of rebuilding every array per dispatch —
    # shrinks the host-side bubble between decode calls. False = legacy
    # rebuild-per-dispatch (the bubble comparison arm of the ladder
    # artifact). Output-invariant either way.
    stage_host_reuse: bool = True
    # Batch-ladder admission headroom: once the bound lanes would exceed
    # the ladder's BASE rung, a further admission must leave this many
    # reclaimable (free + evictable) pages spare — growing the batch
    # toward the top rung must not drain the pool to the preemption
    # watermark or force decode grants to churn the whole hot set
    # (with a host tier the churn demotes instead of destroying; the
    # headroom keeps it off the steady-state path either way). 0 = off
    # (legacy admission gate only).
    ladder_admit_headroom_pages: int = 0
    # Rolling SLO targets (README "Observability": SLO gauges; CLI
    # --slo-ttft-ms / --slo-tpot-ms). Each finished request's TTFT and
    # TPOT feed exact windowed quantile gauges
    # (tpu_inf_slo_ttft_seconds{q=...} / tpu_inf_slo_tpot_seconds{q=...})
    # regardless; with a non-zero target, requests past it additionally
    # count into tpu_inf_slo_breaches_total{slo=...} — the signal an
    # SLO-driven autoscaler scales on. 0 = no target.
    slo_ttft_ms: float = 0.0
    slo_tpot_ms: float = 0.0
    # Step ledger depth (README "Performance attribution"): how many
    # per-dispatch records the roofline-attribution ring retains. Each
    # record is one small tuple, so deeper rings cost only memory; 60 s
    # of bs=8 decode at ~10 ms/dispatch is ~6000 records.
    step_ledger_depth: int = 256
    # Worker phase role (README "P/D disaggregation"): "mixed" runs both
    # phases (the compatibility default — every pre-P/D topology);
    # "prefill" serves prompt prefills only and HANDS each settled
    # prefill off (KV pages incl. the partial final page + stream state)
    # to a decode worker, so warmup compiles only the prefill buckets;
    # "decode" resumes handed-off sequences and decodes at high
    # occupancy with zero prefill interference, so warmup compiles only
    # the decode ladder (and spec-verify) graphs. The role specializes
    # WARMUP and scheduling intent, not capability — a degraded fleet
    # can still run the other phase (lazy compile) so failover never
    # strands a request. Per-worker roles come from
    # ServerConfig.worker_roles; this field is what one engine sees.
    role: str = "mixed"
    # The float32 rows a step program handed to ``sample`` are also an
    # OUTPUT of it, and the engine files them on the sequence
    # (``Sequence.kept_logits``: position -> [V], the last 4 x
    # decode_steps_per_call positions): what bench/probes/kept.py reads.
    # A static branch: off, every program lowers as it did without it.
    keep_logits: bool = False

    @property
    def max_context(self) -> int:
        return (self.page_size or KV_PAGE_UNIT) * self.max_pages_per_seq

    @property
    def ladder_rungs(self) -> tuple:
        """The decode-graph ladder actually in effect: ``decode_ladder``
        or the single legacy rung. Validated by the engine at boot."""
        return tuple(self.decode_ladder) or (self.max_batch_size,)

    @property
    def chunk_tokens_cap(self) -> int:
        """Effective chunk length for multi-chunk prefills:
        ``chunked_prefill_size`` clamped to the largest compiled bucket —
        a larger value would slice chunks no prefill graph can hold
        (the [1, bucket] token buffer raises on assignment). 0 means the
        largest bucket governs."""
        cap = self.chunked_prefill_size or self.prefill_buckets[-1]
        return min(cap, self.prefill_buckets[-1])

    def bucket_for(self, length: int) -> int:
        for b in self.prefill_buckets:
            if length <= b:
                return b
        return self.prefill_buckets[-1]


def validate_spec_config(num_speculative_tokens: int,
                         ngram_window: int) -> None:
    """Bounds on the knobs of speculative decoding when it is on, shared
    by the engine and the CLIs (server + replay), so a bad value fails
    as a usage error before any weights load.

    Raises ValueError; messages mention the flag spelling so argparse
    surfaces actionable errors."""
    if not (1 <= num_speculative_tokens <= 16):
        raise ValueError(
            f"--num-speculative-tokens {num_speculative_tokens}: "
            "must be in [1, 16] when speculative decoding is on "
            "(γ drafts verify in one γ+1-position forward; huge γ "
            "only compiles wider graphs to reject more)")
    if not (1 <= ngram_window <= 8):
        raise ValueError(
            f"--ngram-window {ngram_window}: must be in [1, 8] "
            "(longest suffix n-gram matched against the history)")


# Worker phase roles (README "P/D disaggregation").
WORKER_ROLES = ("prefill", "decode", "mixed")

# Request priority classes (README "Elastic fleet"), best-first. Admission
# and scheduling order by rank; preemption steals from the worst rank up.
PRIORITY_CLASSES = ("interactive", "batch", "background")


def class_rank(priority_class: str) -> int:
    """Scheduling rank of a class (0 = most latency-sensitive). Unknown
    names rank as interactive so a typo'd header can never starve a
    request — validation with a 400 belongs at the HTTP edge."""
    try:
        return PRIORITY_CLASSES.index(priority_class)
    except ValueError:
        return 0


def resolve_worker_roles(dp: int, worker_roles, default_role: str = "mixed"
                         ) -> tuple:
    """THE role-resolution rule, shared by the fleet router and the CLIs
    so they cannot drift: expand ``worker_roles`` (one entry per dp
    replica, or () = ``default_role`` everywhere) into a validated
    dp-length tuple. Raises ValueError with a flag-spelling message on a
    bad role name or a length mismatch; warns (returns anyway) are the
    caller's business — a fleet of only-decode workers still serves,
    it just prefills lazily."""
    roles = tuple(worker_roles or ())
    if not roles:
        roles = (default_role,) * max(1, dp)
    if len(roles) != max(1, dp):
        raise ValueError(
            f"--roles needs exactly one role per dp replica: got "
            f"{len(roles)} for dp={dp}")
    for r in roles:
        if r not in WORKER_ROLES:
            raise ValueError(f"unknown worker role {r!r}: one of "
                             f"{WORKER_ROLES}")
    return roles


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    """HTTP server config (Ollama-protocol endpoint, SURVEY.md §2c)."""

    host: str = "127.0.0.1"
    port: int = 11434
    model_name: str = "tiny-llama"    # name echoed in NDJSON records
    tokenizer: str = "byte"           # "byte" | path to HF tokenizer
    request_timeout_s: float = 600.0
    # Compile all engine graphs before accepting traffic (keeps XLA compile
    # out of the first requests' TTFT).
    warmup: bool = True
    # Hold HTTP headers until the first token is ready so client-side TTFT
    # (first streamed chunk) matches header-arrival time (SURVEY.md §2c).
    defer_headers_until_first_token: bool = True
    # Debug/observability endpoints (/debug/requests, /debug/profile) are
    # unauthenticated introspection; off unless explicitly enabled
    # (CLI --debug). The profiler writes only under profile_dir — the
    # client never chooses the path.
    enable_debug: bool = False
    profile_dir: str = "/tmp/jax-trace"
    # Crash flight recorder (README "Performance attribution"): bounded
    # per-replica capture dir for step records + spans + config + stats
    # on watchdog trip / step_error / SIGTERM / atexit. Same security
    # stance as profile_dir: the OPERATOR configures the path (CLI
    # --blackbox-dir), never a client. "" disables the recorder — the
    # library default, so embedded/test engine groups do no disk I/O
    # unless a path is set; the CLI serves with /tmp/tpu-inf-blackbox.
    blackbox_dir: str = ""
    # Captures retained per replica before the oldest is pruned.
    blackbox_retain: int = 8
    # Fault injection (SURVEY.md §5 failure detection: "HTTP-stub chaos
    # mode"): randomly reject this fraction of generate/chat/embed
    # requests with 503 and/or delay them, to test client resilience.
    # Off in production.
    chaos_failure_rate: float = 0.0
    chaos_delay_s: float = 0.0
    # --- Replica supervision (server/replicas.py health state machine) ---
    # A replica whose decode/prefill dispatch stays in flight longer than
    # this is wedged (the round-5 TPU failure mode): it is quarantined and
    # its in-flight requests fail over. 0 disables the watchdog — the
    # first dispatch after a cold boot without warmup includes XLA
    # compile, which can legitimately take minutes at 70B scale, so the
    # deadline is opt-in (the CLI enables it with --step-watchdog-s).
    step_watchdog_s: float = 0.0
    # Consecutive step failures before healthy -> degraded -> quarantined
    # (the first failure degrades; this many quarantine).
    quarantine_after_failures: int = 3
    # A quarantined replica waits this long, then re-enters as
    # "recovered" (probation): one clean step re-promotes it to healthy,
    # one failure re-quarantines it immediately.
    quarantine_cooldown_s: float = 30.0
    # Failover budget: a request failed/stranded by a sick replica with
    # NO tokens delivered yet is resubmitted from its prompt to a healthy
    # replica at most this many times. Requests that already streamed
    # tokens fail cleanly instead of being silently re-generated.
    failover_max_retries: int = 1
    # Admission control: reject (HTTP 429 + Retry-After) when the least
    # loaded routable replica already has this many requests queued or
    # running. 0 = unlimited (legacy behavior: queue until
    # request_timeout_s).
    admission_queue_depth: int = 0
    # Retry-After hint (seconds) sent with 429/503 shed responses.
    retry_after_s: float = 1.0
    # --- Replica routing (server/replicas.py EngineGroup) ---
    # "prefix_affinity" (default): score every routable replica by the
    # KV prefill work routing there would cost —
    #   prompt_pages - route_hit_weight * peeked_hit_pages
    #     + route_load_pages * load  (+ a pressure penalty)
    # — and route to the cheapest, so a returning conversation lands on
    # the replica that already holds its history's pages instead of
    # re-prefilling it cold (dp-1)/dp of the time. Cold prompts (no
    # replica holds anything) degrade to least-loaded. "least_loaded":
    # the legacy load-only policy (the benchmark comparison arm).
    routing: str = "prefix_affinity"
    # Pages of prefill compute one peeked cache-hit page is worth in the
    # routing score. 1.0 = at cost (a hit page saves exactly one page of
    # prefill). Raising it makes warmth beat load/pressure harder: past
    # ~1 + (prompt_pages+1)/hit_pages a fully-warm replica under
    # preemption pressure outbids a cold idle one; at the default a
    # pressured warm replica loses to a cold idle sibling.
    route_hit_weight: float = 1.0
    # Pages of prefill compute one HOST-tier hit page is worth in the
    # routing score (three temperatures: HBM-warm > host-warm > cold).
    # A host hit saves the prefill compute but still pays a host->device
    # swap-in, so it scores below an HBM hit; 0 makes the router ignore
    # host warmth entirely.
    route_host_hit_weight: float = 0.5
    # Page-equivalents of routing cost charged per queued-or-running
    # request on a replica — blends queue depth into the affinity score
    # so warmth cannot herd every conversation onto one overloaded
    # replica. Not a CLI flag; tune in config when page_size is unusual.
    route_load_pages: float = 1.0
    # --- Fleet KV fabric (README "KV fabric") ---
    # Router-side digest-keyed LRU pool of serialized KV prefix pages
    # shared across EVERY replica: a prefix prefilled on any replica
    # warms all of them (pages pull into a replica's host tier before
    # its prefill). Capacity in pages; 0 = fabric off. CLI:
    # --fabric-cache-pages.
    fabric_cache_pages: int = 0
    # Minimum contiguous settled prefix pages a sequence must hold
    # before its engine publishes them to the fabric — keeps one-page
    # scraps from churning the pool. CLI: --fabric-publish-min-pages.
    fabric_publish_min_pages: int = 1
    # Pages of the fabric's hot (MRU) set pushed into an autoscale/
    # rollout worker via import-kv before it enters the routable pool,
    # so scaled-up capacity serves its first request warm. 0 = boot
    # cold. CLI: --fabric-warmboot-pages.
    fabric_warmboot_pages: int = 64
    # Pages of prefill compute one FABRIC-covered page is worth in the
    # routing score — the fourth cache temperature, between host-warm
    # (route_host_hit_weight) and cold (0): a fabric page saves the
    # prefill compute but pays a pool pull + host->device swap-in.
    # Only pages beyond a candidate's own warm depth earn it. CLI:
    # --route-fabric-hit-weight.
    route_fabric_hit_weight: float = 0.25
    # --- Process fleet (README "Process fleet") ---
    # Fleet backend: "in-process" = dp EngineSchedulers as threads of the
    # server process (server/replicas.py EngineGroup — one process, one
    # GIL, one failure domain); "subprocess" = a router plus one
    # engine-worker OS process per replica, speaking a length-prefixed
    # JSON RPC over a local unix socket (server/worker.py +
    # server/fleet.py ProcessEngineGroup). Same facade either way.
    fleet: str = "in-process"
    # --- Zero-copy KV data plane (README "KV data plane") ---
    # "relay" = KV blobs (handoff/migrate/fabric/warmboot) traverse the
    # RPC sockets through the router — the universal path. "shm" =
    # subprocess-fleet workers write each blob ONCE into a shared-
    # memory page arena and frames carry {seg, off, len, crc32c}
    # descriptors instead; adopting workers read straight from the
    # arena. Silently degrades to relay for --fleet in-process, on
    # non-Linux hosts, or when the arena cannot be created; every
    # arena read re-verifies crc32c and falls back to relay/recompute
    # on any stale or corrupt slab. CLI: --kv-plane.
    kv_plane: str = "relay"
    # Total bytes of the shared-memory arena (split into equal
    # per-worker regions). A blob that does not fit a region's free
    # space relays through the router instead. CLI: --shm-arena-bytes.
    shm_arena_bytes: int = 256 * 1024 * 1024
    # Subprocess fleet: restarts allowed per worker (with doubling
    # backoff from worker_restart_backoff_s) before it is left down and
    # the fleet serves degraded on the survivors.
    worker_restart_max: int = 3
    worker_restart_backoff_s: float = 0.5
    # Subprocess fleet: a SIGTERM'd (or drain-RPC'd) worker gets this
    # long to settle in-flight dispatches and export its sequences' KV
    # pages before exiting.
    drain_timeout_s: float = 10.0
    # Drain-time KV page migration: a draining worker exports in-flight
    # sequences' KV pages (the PR-6 host serialization layout) over the
    # RPC channel and the router imports them into the destination
    # worker's host tier, so resubmission becomes a swap-in-resume.
    # False = the resubmission-only comparison arm (full re-prefill).
    fleet_migrate: bool = True
    # --- P/D disaggregation (README "P/D disaggregation") ---
    # Per-worker phase roles for the subprocess fleet, one entry per dp
    # replica ("prefill" | "decode" | "mixed"). () = every worker runs
    # EngineConfig.role (default "mixed" — the dp fallback with
    # unchanged behavior). With phase-specialized roles the router
    # admits new prompts to prefill-capable workers only and moves each
    # settled prefill to a decode worker as a live KV handoff (no
    # re-prefill, byte-identical under greedy). CLI: --role / --roles /
    # --pd-ratio.
    worker_roles: tuple[str, ...] = ()
    # Fan-out deadline for the router's per-candidate peek RPCs: peeks
    # are issued concurrently and any candidate that hasn't answered by
    # this deadline scores with a cold fallback instead of adding its
    # round-trip to the admission path.
    route_peek_timeout_s: float = 2.0
    # Decode-phase routing (handoffs + mid-stream resumes): page-
    # equivalents of routing cost a FULLY-occupied decode ladder adds to
    # a candidate's score — decode picks by ladder occupancy + load,
    # minus host-warm pages (the least-loaded decode worker wins when
    # occupancies tie).
    route_occupancy_pages: float = 8.0
    # os.nice() increment applied to prefill-ROLE worker processes at
    # boot (0 = off). On a real TPU fleet the P/D isolation is physical
    # (phases sit on different chips); on a shared-CPU host the worker
    # processes still contend for cores, and deprioritizing the prefill
    # tier keeps decode cadence flat under prefill bursts — the mixed/
    # hybrid topologies CANNOT buy this with any priority, because
    # their interference is in-engine dispatch serialization, not CPU
    # share. Used by the --compare-pd replay lane; irrelevant (but
    # harmless) when each worker owns its accelerator.
    pd_prefill_nice: int = 0
    # --- Elastic fleet (README "Elastic fleet") ---
    # SLO-driven autoscaler on the subprocess fleet: the router watches
    # the fleet-pooled TTFT/TPOT quantile windows (the PR-12 SLO sensor)
    # and spawns an extra worker when p95 breaches the configured
    # slo_ttft_ms/slo_tpot_ms target for autoscale_breach_window_s
    # straight, or drain-and-migrates the coldest replica away (lossless
    # scale-down: KV pages migrate, streams keep going) when pooled
    # ladder occupancy stays under autoscale_low_watermark for
    # autoscale_idle_window_s. Hysteresis comes from the two distinct
    # windows plus autoscale_cooldown_s between ANY two scale decisions,
    # and the autoscaler never acts while a worker is booting or
    # restarting — so a chaos-killed worker's restart can never race a
    # scale-up into a double spawn. False = fixed fleet (legacy).
    autoscale: bool = False
    # Replica-count bounds for the autoscaler. max 0 = dp + 2.
    autoscale_min_replicas: int = 1
    autoscale_max_replicas: int = 0
    # Sustained-breach window before a scale-up (seconds of continuous
    # p95-over-target on the pooled windows).
    autoscale_breach_window_s: float = 3.0
    # Minimum seconds between any two scale decisions.
    autoscale_cooldown_s: float = 10.0
    # Scale-down trigger: pooled decode-ladder occupancy (0..1) must stay
    # under this for autoscale_idle_window_s straight.
    autoscale_low_watermark: float = 0.25
    autoscale_idle_window_s: float = 5.0
    # Role spawned by a scale-up: "decode" on a P/D-split fleet (decode
    # capacity is what TPOT breaches starve for); "" = "decode" when P/D
    # roles are in play, else "mixed" (a mixed fleet needs prefill
    # capacity too for TTFT relief).
    autoscale_role: str = ""
    # --- Priority classes (README "Elastic fleet": class semantics) ---
    # Class assumed for requests without an X-Priority header:
    # "interactive" | "batch" | "background".
    default_class: str = "interactive"
    # Generate to ``max_tokens`` whatever is sampled: no request stops on
    # the tokenizer's EOS (CLI --ignore-eos). For load tests on random
    # weights, whose greedy argmax hits the EOS id by chance.
    ignore_eos: bool = False
    # Per-class router-side deferral queues: when the fleet is at the
    # admission cap, batch/background requests park in a bounded
    # deferral queue (drained as load drops) instead of shedding 429,
    # and an interactive arrival preempts a running batch-lane request
    # (recompute-resume, byte-identical under greedy) to make room.
    # 0 = classes ride the legacy single global cap.
    class_queue_depth: int = 0
    # --- Byzantine transport (README "Failure model") ---
    # Per-verb RPC deadline classes replacing the historical blanket
    # 60 s waits: "fast" covers control-plane verbs that answer from
    # memory (peek/cancel/healthz/stats/metrics/...), "slow" covers
    # verbs that touch the engine loop or move KV bytes
    # (submit/import-kv/drain). Boot handshake, shutdown, embed and
    # profiler captures keep their own explicit budgets.
    rpc_deadline_fast_s: float = 10.0
    rpc_deadline_slow_s: float = 60.0
    # Poison-request quarantine: a request whose attempts have crashed
    # or wedged this many DISTINCT workers is failed terminally with a
    # structured 500 (and a router-side blackbox capture) instead of
    # marching through the fleet via failover. 0 disables the gate.
    poison_max_workers: int = 3
    # Transport fault injection (--chaos-rpc-*): seeded chaos shim
    # around the frame codec on both sides of every worker connection.
    # Rates are per-frame probabilities; faults are drawn from a
    # private RNG keyed only by (seed, frame index) so a pinned seed
    # reproduces the exact fault schedule. All off by default.
    chaos_rpc_seed: int = 0
    chaos_rpc_corrupt_rate: float = 0.0   # flip a byte (CRC catches it)
    chaos_rpc_drop_rate: float = 0.0      # drop = connection reset
    chaos_rpc_delay_rate: float = 0.0     # hold the frame delay_s
    chaos_rpc_delay_s: float = 0.02
    chaos_rpc_truncate_rate: float = 0.0  # torn write: prefix + reset
    # Wedge one router->worker connection (socket open, writes stop
    # landing) after this many frames; one-shot — the replacement
    # connection after the deadline-driven recycle serves clean.
    # 0 = no wedge. chaos_rpc_wedge_replica picks the victim.
    chaos_rpc_wedge_after: int = 0
    chaos_rpc_wedge_replica: int = 0
    # Frame eligibility filters: verbs (empty = all; matched against
    # the RPC verb / reply verb / event name) and direction
    # ("send" = router->worker, "recv" = worker->router, "both").
    chaos_rpc_verbs: tuple[str, ...] = ()
    chaos_rpc_direction: str = "both"


@dataclasses.dataclass
class FrameworkConfig:
    """Top-level bundle used by the CLI and server entry point."""

    model: ModelConfig = dataclasses.field(default_factory=tiny_llama)
    engine: EngineConfig = dataclasses.field(default_factory=EngineConfig)
    parallel: ParallelConfig = dataclasses.field(default_factory=ParallelConfig)
    server: ServerConfig = dataclasses.field(default_factory=ServerConfig)
    checkpoint_path: Optional[str] = None  # HF safetensors dir; None = random init
    seed: int = 0


# ---------------------------------------------------------------------------
# JSON config transport (subprocess fleet): the router serializes one
# FrameworkConfig and ships it to each engine-worker process over stdin,
# so router and workers can never drift on engine geometry (page_size /
# ladder / prefix digests all depend on it). Only the non-JSON-native
# leaves need special casing: the model dtype (by numpy name) and the
# tuple-valued EngineConfig fields.
# ---------------------------------------------------------------------------

_TUPLE_FIELDS = ("decode_ladder", "prefill_buckets")


def model_config_to_dict(m: ModelConfig) -> dict:
    import numpy as np

    d = dataclasses.asdict(m)
    d["dtype"] = np.dtype(m.dtype).name
    return d


def model_config_from_dict(d: dict) -> ModelConfig:
    d = dict(d)
    dtype = d.get("dtype")
    if isinstance(dtype, str):
        # jnp exposes bfloat16/float16/float32 as attributes; np.dtype
        # round-trips them by name once jax (ml_dtypes) is imported.
        d["dtype"] = getattr(jnp, dtype)
    rs = d.get("rope_scaling")
    if isinstance(rs, dict):
        d["rope_scaling"] = (YarnScaling if "beta_fast" in rs
                             else RopeScaling)(**rs)
    for k in ("layer_types", "nope_kinds"):
        if k in d:
            d[k] = tuple(d[k])
    return ModelConfig(**d)


def framework_config_to_dict(cfg: FrameworkConfig) -> dict:
    return {
        "model": model_config_to_dict(cfg.model),
        "engine": dataclasses.asdict(cfg.engine),
        "parallel": dataclasses.asdict(cfg.parallel),
        "server": dataclasses.asdict(cfg.server),
        "checkpoint_path": cfg.checkpoint_path,
        "seed": cfg.seed,
    }


def framework_config_from_dict(d: dict) -> FrameworkConfig:
    eng = dict(d.get("engine") or {})
    for k in _TUPLE_FIELDS:
        if k in eng and eng[k] is not None:
            eng[k] = tuple(eng[k])
    srv = dict(d.get("server") or {})
    for k in ("worker_roles", "chaos_rpc_verbs"):
        if srv.get(k) is not None:
            srv[k] = tuple(srv[k])
    return FrameworkConfig(
        model=model_config_from_dict(d["model"]),
        engine=EngineConfig(**eng),
        parallel=ParallelConfig(**(d.get("parallel") or {})),
        server=ServerConfig(**srv),
        checkpoint_path=d.get("checkpoint_path"),
        seed=d.get("seed", 0),
    )
