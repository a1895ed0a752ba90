"""HBM-aware engine sizing: derive batch and KV pool from the chip.

VERDICT r3 found the replay saturating at ``max_batch_size=8`` — "a batch
size chosen for tests, not for the chip": at 1B params + int8 KV a 16 GB
v5e supports batch 16-32 easily, and a server capped below the trace's
arrival rate measures queue depth, not the model. The reference had no
equivalent knob to size (its server half was an external Ollama binary);
this module is the TPU-native answer: compute what the chip's HBM
actually supports and serve with ``--max-batch-size auto --num-pages
auto``.

Sizing model (per chip, serving-engine residents only):

    usable  = (1 - reserve_frac) * hbm        # XLA runtime reservations
    budget  = usable - weights/tp - activation_headroom
    tokens  = budget // (kv_bytes_per_token / tp)
    pages   = tokens // page_size
    batch   = min(batch_cap, tokens // target_ctx)

``target_ctx`` is the context the operator expects a typical sequence to
hold (default: half the per-sequence maximum) — the pool is sized by
bytes, the batch by how many such sequences can decode concurrently
without page-pressure evictions. The cap keeps small models (1B on 16 GB
could hold hundreds of sequences) at a batch the MXU still benefits
from rather than one that only stretches tail latency.

Weight-byte estimates count embeddings + matmul params from the config
(exact enough for sizing; int8 adds per-channel scales and keeps
embeddings in model dtype — see models/quant.py). KV bytes follow the
pool layouts in engine/kv_cache.py.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from tpu_inference.config import KV_PAGE_UNIT
from tpu_inference.engine.kv_cache import (decode_write_path,
                                           kda_tail_step_path,
                                           window_span_pages,
                                           written_ahead_tokens)


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Published per-chip figures: HBM bytes, dense peak rates, HBM
    bandwidth (bytes/s)."""
    hbm_bytes: float
    peak_bf16_flops: float
    peak_int8_ops: float
    hbm_bw: float


# THE device table, keyed by jax's ``device_kind``. Source: Google Cloud
# TPU documentation, "System architecture" page of each generation
# ("TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM, 819 GB/s;
# "TPU v4": 275 TFLOP/s, 32 GiB, 1228 GB/s; "TPU v5p": 459 TFLOP/s bf16,
# 918 TOP/s int8, 95 GB, 2765 GB/s; "TPU v6e": 918 TFLOP/s bf16, 1836
# TOP/s int8, 32 GB, 1640 GB/s). A TPU that is not in it is an error
# (chip_spec), never a default; the CPU has no entry on purpose.
CHIP_SPECS = {
    "TPU v5 lite": ChipSpec(16e9, 197e12, 393e12, 819e9),
    "TPU v4": ChipSpec(32e9, 275e12, 275e12, 1228e9),
    "TPU v5p": ChipSpec(95e9, 459e12, 918e12, 2765e9),
    "TPU v6 lite": ChipSpec(32e9, 918e12, 1836e12, 1640e9),
}


def chip_spec(device=None) -> Optional[ChipSpec]:
    """Published figures of ``device`` (default: the first visible one).
    None on the CPU — there is no chip to rate against, so MFU and
    roofline shares are "not measured" there, not computed against
    somebody else's peak. An unknown TPU kind raises."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    try:
        return CHIP_SPECS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"unknown TPU device_kind {device.device_kind!r}: add its "
            "published HBM size, peak rates and bandwidth to "
            "engine/autosize.py CHIP_SPECS (known: "
            f"{', '.join(sorted(CHIP_SPECS))})") from None


def active_param_count(model_cfg) -> int:
    """Parameters one token position multiplies through: for a routed
    model, its expected share of the experts HELD HERE."""
    from tpu_inference.models.registry import family_fn

    own = family_fn(model_cfg, "param_count")
    return own(model_cfg, True) if own else estimate_param_count(model_cfg)


def estimate_param_count(model_cfg) -> int:
    """Parameter count from the architecture config (norms elided); a
    family whose module counts its own (``param_count``) is asked."""
    from tpu_inference.models.registry import family_fn

    own = family_fn(model_cfg, "param_count")
    if own:
        return own(model_cfg, False)
    d, f, L, V = (model_cfg.d_model, model_cfg.d_ff, model_cfg.n_layers,
                  model_cfg.vocab_size)
    kv_w = model_cfg.n_kv_heads * model_cfg.head_dim
    embed = V * d * (1 if model_cfg.tie_embeddings else 2)
    attn = 2 * d * d + 2 * d * kv_w
    if model_cfg.n_experts:
        ffn = model_cfg.n_experts * 3 * d * f + d * model_cfg.n_experts
    else:
        ffn = 3 * d * f
    return embed + L * (attn + ffn)


def weight_bytes(model_cfg, quant: str = "none") -> int:
    """Resident weight bytes. int8 stores matmul weights as one byte +
    per-output-channel f32 scales; int4 as half a byte + per-group
    scales (models/quant.py GROUP_SIZE=128: 4 scale bytes per 128
    codes ≈ 6% overhead); embeddings stay in model dtype
    (models/quant.py quantizes matmuls only)."""
    n = estimate_param_count(model_cfg)
    itemsize = 2  # bf16 serving dtype
    if quant in ("int8", "int4"):
        d, V = model_cfg.d_model, model_cfg.vocab_size
        embed = V * d * (1 if model_cfg.tie_embeddings else 2)
        matmul = n - embed
        if quant == "int4":
            from tpu_inference.models.quant import GROUP_SIZE

            # 0.5 B codes + one f32 scale per GROUP_SIZE weights.
            return embed * itemsize + int(matmul * (0.5 + 4 / GROUP_SIZE))
        # Scales: one f32 per output channel; ~d_model-ish rows per
        # matmul — well under 1% of codes. Budget 1% rather than walk
        # every shape.
        return embed * itemsize + int(matmul * 1.01)
    return n * itemsize


def weight_read_bytes(model_cfg, quant: str = "none") -> int:
    """Weight bytes ONE decode step reads: the resident bytes, plus the
    looped layers once more for every pass after the first (the
    embedding and head are read once)."""
    wb = weight_bytes(model_cfg, quant)
    if model_cfg.loop_steps == 1:
        return wb
    d, V = model_cfg.d_model, model_cfg.vocab_size
    embed = V * d * (1 if model_cfg.tie_embeddings else 2) * 2
    return wb + (model_cfg.loop_steps - 1) * (wb - embed)


def kv_bytes_per_token(model_cfg, kv_quant: str = "none",
                       kind: Optional[str] = None) -> int:
    """Pool bytes one token occupies across all KV slots (K and V; L =
    layers, times the passes of a looped stack). ``kind``: across one
    kind's layers only (a model whose layers differ in kind has a pool
    a kind, ModelConfig.layer_types).

    bf16: 2 * L * Hkv * D * 2; int8: codes (1 byte) + a per-(token,
    kv-head) f32 scale; int4: nibble-packed codes (D/2 bytes) + the
    same f32 scale — engine/kv_cache.py layouts."""
    L = model_cfg.n_kv_slots      # one per (pass, layer) of a looped stack
    if kind is not None:
        L = len(model_cfg.kind_layers(kind))
    if model_cfg.latent_dim:
        # One latent entry per token per layer for all heads, at the
        # pool's stored (lane-padded) width; never quantized.
        from tpu_inference.engine.kv_cache import latent_width
        return L * latent_width(model_cfg) * 2
    # (As stored: a differential-attention pool holds pair heads, half
    # as many and twice as wide. The same bytes.)
    hkv = model_cfg.pool_kv_heads
    d = model_cfg.pool_head_dim
    if kv_quant == "int8":
        return 2 * L * hkv * (d + 4)
    if kv_quant == "int4":
        return 2 * L * hkv * (d // 2 + 4)
    return 2 * L * hkv * d * 2


# The tokens of a KV page, where nobody gave a number. The Pallas decode
# kernels bring the pool in one DMA descriptor a page a pool, issued and
# waited for by the scalar core in the fold's own instruction stream:
# ~45 ns a descriptor whatever it carries, where a 16 KB page needs 20 ns
# of HBM. On the v5e one kernel (kernels/paged_attention.py) read 43% of
# its roofline at 4 KV heads (16 KB a 16-token page: SmallThinker) and
# 77-81% at 8 and 16 heads (32 / 64 KB: Mistral doc, Ouro); the latent
# kernel 55-59% at 20 KB (Kimi-K2, Xing) (ledger, PR 47). So a page under
# SMALL_PAGE_BYTES at 16 tokens is made WIDE_PAGE_TOKENS long, which puts
# it at 64-80 KB: where the large pages already are (by hand at 32 / 64 /
# 128 tokens in cell 8: PERF.md section 6, PR 48).
SMALL_PAGE_BYTES = 32 << 10
WIDE_PAGE_TOKENS = 64


def page_bytes(model_cfg, tokens: int, kv_quant: str = "none",
               tp: int = 1) -> int:
    """Bytes ONE pool's page of ``tokens`` tokens holds on one chip, in
    one layer: K's (V's are the same), or the latent entries'. What one
    DMA descriptor of a decode kernel carries."""
    if model_cfg.latent_dim:
        from tpu_inference.engine.kv_cache import latent_width
        return tokens * latent_width(model_cfg) * 2
    heads = max(1, model_cfg.pool_kv_heads // max(1, tp))
    d = model_cfg.pool_head_dim
    row = {"int8": d, "int4": d // 2}.get(kv_quant, 2 * d)
    return tokens * heads * row


def pallas_reads_pool(attn_backend: str,
                      platform: Optional[str] = None) -> bool:
    """Whether ``attn_backend`` comes to the Pallas kernels. 'auto' is
    Pallas on a TPU: by ``platform`` where the caller has the CLI's
    --platform (anything but 'cpu' is a TPU or no server at all,
    runtime.require_backend) and may not touch JAX, else by jax's
    default backend."""
    if attn_backend != "auto":
        return attn_backend == "pallas"
    if platform is not None:
        return platform != "cpu"
    import jax

    return jax.default_backend() == "tpu"


def auto_page_tokens(model_cfg, *, pallas: bool, kv_quant: str = "none",
                     tp: int = 1) -> int:
    """THE rule, from two things the program can observe:
    WIDE_PAGE_TOKENS where the Pallas kernels read the pool (only there
    is a page a DMA descriptor) and a KV_PAGE_UNIT-token page is under
    SMALL_PAGE_BYTES, else KV_PAGE_UNIT."""
    small = page_bytes(model_cfg, KV_PAGE_UNIT, kv_quant,
                       tp) < SMALL_PAGE_BYTES
    return WIDE_PAGE_TOKENS if pallas and small else KV_PAGE_UNIT


def resolve_page_size(model_cfg, engine_cfg, *, tp: int = 1,
                      pallas: Optional[bool] = None):
    """EngineConfig with ``page_size`` an integer. One that is given
    stays, and nothing else changes. None becomes ``auto_page_tokens``,
    and the page counts, written in KV_PAGE_UNIT-token units until now,
    become pages of the chosen size: the context cap rounded UP to a
    whole page, the pools DOWN.

    ``pallas``: whether the Pallas backend reads the pool, for a caller
    that knows (the engine's constructor; a router that must stay off
    JAX, from its --platform); None asks ``pallas_reads_pool``."""
    if engine_cfg.page_size is not None:
        return engine_cfg
    if pallas is None:
        pallas = pallas_reads_pool(engine_cfg.attn_backend)
    page = auto_page_tokens(model_cfg, pallas=pallas,
                            kv_quant=engine_cfg.kv_quant, tp=tp)
    return dataclasses.replace(
        engine_cfg, page_size=page,
        max_pages_per_seq=-(-engine_cfg.max_pages_per_seq * KV_PAGE_UNIT
                            // page),
        num_pages=engine_cfg.num_pages * KV_PAGE_UNIT // page,
        num_window_pages=engine_cfg.num_window_pages * KV_PAGE_UNIT // page)


@dataclasses.dataclass(frozen=True)
class AutoSizing:
    max_batch_size: int
    num_pages: int
    # Evidence for logs/metrics: where the budget went (per chip).
    hbm_bytes: int
    weight_bytes_per_chip: int
    kv_pool_bytes_per_chip: int
    kv_bytes_per_token: int
    target_ctx: int
    # A model with a pool a kind: the window kind's pool (every lane's
    # span, or fewer where the lanes' contexts lie under the window:
    # _auto_size_kinds); num_pages / kv_bytes_per_token are then the
    # full kind's.
    num_window_pages: int = 0


def stream_activation_bytes(model_cfg, chunk_tokens: int) -> int:
    """What a prefill chunk's resident activations take BEYOND the one
    residual the fixed headroom was set for, where a token carries
    ``hc_mult`` streams (models/hyper_connections.py): each further
    stream's carry, the mixed copy being written and the float32 sum it
    is rounded from, 2 + 2 + 4 bytes a value. A decode step's rows are
    not worth counting."""
    return (model_cfg.hc_mult - 1) * chunk_tokens * model_cfg.d_model * 8


def auto_size(model_cfg, *, hbm_bytes: float, quant: str = "none",
              kv_quant: str = "none", tp: int = 1,
              page_size: int = 16, max_pages_per_seq: int = 64,
              target_ctx: Optional[int] = None, batch_cap: int = 32,
              reserve_frac: float = 0.15,
              activation_headroom: int = 512 << 20,
              window_span: int = 0, written_ahead: int = 0,
              chunk_tokens: int = 1024) -> AutoSizing:
    """Size ``max_batch_size`` and ``num_pages`` for the chip.

    Raises ValueError when the weights alone exceed the per-chip budget
    (the caller should quantize, raise tp, or pick a bigger chip) or
    when the KV budget can't hold even one full-length sequence.

    A model whose layers differ in kind (``layer_types``) has a pool a
    kind: the window kind's holds ``window_span`` pages
    (kv_cache.window_span_pages) for each lane of the batch where
    ``target_ctx`` reaches the window, the full kind's gets the rest of
    the budget, and the batch is the largest whose lanes fit both at
    ``target_ctx``. Where ``target_ctx`` + ``written_ahead`` tokens
    (kv_cache.written_ahead_tokens) lie under the window a lane holds
    the same tokens in both kinds, and both pools are sized on them
    (_auto_size_kinds).
    """
    hbm = float(hbm_bytes)
    wb = weight_bytes(model_cfg, quant)
    per_chip_w = wb // tp
    activation_headroom += stream_activation_bytes(model_cfg, chunk_tokens)
    usable = (1.0 - reserve_frac) * hbm
    budget = usable - per_chip_w - activation_headroom
    if budget <= 0:
        raise ValueError(
            f"{model_cfg.name}: weights (~{per_chip_w / 1e9:.1f} GB/chip, "
            f"quant={quant}, tp={tp}) + {activation_headroom >> 20} MB "
            f"activation headroom exceed {usable / 1e9:.1f} GB usable HBM "
            f"({hbm / 1e9:.0f} GB chip); use --quant int8, more tp, or a "
            "bigger chip")
    if model_cfg.layer_types and window_span:
        return _auto_size_kinds(
            model_cfg, budget=budget, hbm=hbm, per_chip_w=per_chip_w,
            kv_quant=kv_quant, page_size=page_size,
            max_pages_per_seq=max_pages_per_seq, target_ctx=target_ctx,
            batch_cap=batch_cap, window_span=window_span,
            written_ahead=written_ahead)
    kv_tok = kv_bytes_per_token(model_cfg, kv_quant)
    ctx = int(target_ctx) if target_ctx else (page_size * max_pages_per_seq
                                              // 2)
    ctx = max(1, min(ctx, page_size * max_pages_per_seq))
    # A state a sequence beside ONE page pool (delta-rule layers beside
    # a latent pool): a lane costs its state slot and ``ctx`` tokens of
    # pages, the trash slot one state more; the pool gets what the
    # slots leave.
    state = model_cfg.state_bytes_per_seq()
    if state:
        batch_cap = max(1, min(batch_cap, int(
            (budget - state) // (state + ctx * kv_tok / tp))))
        budget -= (batch_cap + 1) * state
    tokens = int(budget // (kv_tok / tp))
    num_pages = tokens // page_size
    # Don't hoard HBM a small model can never address: cap the pool at
    # every slot holding a full-length sequence, with 4x slack for the
    # prefix cache and freed-page fragmentation.
    num_pages = min(num_pages, 4 * batch_cap * max_pages_per_seq)
    # Page 0 is the allocator's reserved trash page (kv_cache.py):
    # admission only ever grants num_pages - 1, so the token/batch math
    # must budget on the usable count (ADVICE r4).
    tokens = min(tokens, (num_pages - 1) * page_size)
    if num_pages < max_pages_per_seq + 1:  # +1: trash page (kv_cache.py)
        raise ValueError(
            f"{model_cfg.name}: KV budget ({budget / 1e9:.2f} GB/chip) "
            f"holds only {num_pages} pages < one full sequence "
            f"({max_pages_per_seq}); lower --max-pages-per-seq or "
            "shrink the pool bytes with --kv-quant int8 (or int4)")
    win = getattr(model_cfg, "sliding_window", 0)
    if win:
        # Behind-window eviction (engine._evict_behind_window) caps a
        # running SWA sequence's live KV at ~window tokens — batch
        # sizes against that, not the full context. (The prefill peak
        # briefly holds the whole prompt; the page-span margin covers
        # typical prompts, and admission charges the true peak.)
        ctx = min(ctx, win + 2 * page_size)
    batch = max(1, min(batch_cap, tokens // ctx))
    return AutoSizing(
        max_batch_size=batch, num_pages=num_pages, hbm_bytes=int(hbm),
        weight_bytes_per_chip=int(per_chip_w),
        kv_pool_bytes_per_chip=int(num_pages * page_size * kv_tok // tp
                                   + (batch + 1) * state),
        kv_bytes_per_token=kv_tok, target_ctx=ctx)


def _auto_size_kinds(model_cfg, *, budget: float, hbm: float,
                     per_chip_w: int, kv_quant: str, page_size: int,
                     max_pages_per_seq: int, target_ctx: Optional[int],
                     batch_cap: int, window_span: int,
                     written_ahead: int = 0) -> AutoSizing:
    """auto_size for a pool a kind (one chip: tp is refused). Three
    things to fit where the model has state-space layers: every lane's
    window span, every lane's state slot (a fixed size a sequence), and
    the full kind's pages with what is left.

    Where a lane at ``target_ctx`` (plus what it writes ahead of a
    release) stays UNDER the window, the span is pages nobody fills: a
    lane holds the same tokens in both kinds, so the window kind's pool
    is sized as the full kind's is, on live tokens. The budget then buys
    both kinds the same count of pages (the window kind's never more
    than every lane's span, never less than one), and the batch is the
    largest whose lanes find ``target_ctx`` tokens in each: the full
    kind's condition as it always was, held for both. (The chunk
    written ahead decides only WHETHER a lane stays under the window;
    it is one prompt's transient, which admission has charged, and the
    full kind's condition never counted it either.) A smaller window
    pool is a wait at admission, never a failed allocation:
    engine.admission_fits holds back every bound sequence's whole
    charge a kind."""
    full_tok = kv_bytes_per_token(model_cfg, kv_quant, kind="full")
    win_tok = kv_bytes_per_token(model_cfg, kv_quant, kind="window")
    state = model_cfg.state_bytes_per_seq()
    ctx = int(target_ctx) if target_ctx else (page_size * max_pages_per_seq
                                              // 2)
    ctx = max(1, min(ctx, page_size * max_pages_per_seq))
    lane = min(window_span, -(-(ctx + written_ahead) // page_size) + 2)
    for batch in range(batch_cap, 0, -1):
        win_pages = batch * window_span + 1
        if lane < window_span:
            even = int((budget - (batch + 1) * state)
                       // ((full_tok + win_tok) * page_size))
            win_pages = min(win_pages, even)
        rest = (budget - win_pages * page_size * win_tok
                - (batch + 1) * state)
        num_pages = min(int(rest // (full_tok * page_size)),
                        4 * batch_cap * max_pages_per_seq)
        # (Under the window a lane's tokens lie in both pools.)
        holds = num_pages if lane == window_span else min(num_pages,
                                                          win_pages)
        if (num_pages >= max_pages_per_seq + 1
                and win_pages - 1 >= window_span
                and (holds - 1) * page_size // ctx >= batch):
            return AutoSizing(
                max_batch_size=batch, num_pages=num_pages,
                hbm_bytes=int(hbm), weight_bytes_per_chip=int(per_chip_w),
                kv_pool_bytes_per_chip=int(
                    page_size * (num_pages * full_tok + win_pages * win_tok)
                    + (batch + 1) * state),
                kv_bytes_per_token=full_tok, target_ctx=ctx,
                num_window_pages=win_pages)
    raise ValueError(
        f"{model_cfg.name}: KV budget ({budget / 1e9:.2f} GB/chip) holds "
        f"no lane's window span ({window_span} pages) beside one "
        f"full-length sequence ({max_pages_per_seq} pages of the full "
        "kind); lower --max-pages-per-seq")


def detect_host_ram_bytes() -> int:
    """Available host RAM in bytes: /proc/meminfo MemAvailable (the
    kernel's own estimate of allocatable-without-swapping memory),
    falling back to half of the sysconf total on platforms without it.
    The host KV tier's auto-sizing input."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    import os

    try:
        return (os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")) // 2
    except (ValueError, OSError, AttributeError):
        return 8 << 30


def auto_host_cache_pages(model_cfg, *, kv_quant: str = "none",
                          page_size: int = 16,
                          host_ram_bytes: Optional[int] = None,
                          fraction: float = 0.5,
                          reserve_bytes: int = 2 << 30) -> int:
    """Size ``--host-cache-pages auto`` from the machine's available
    RAM: ``fraction`` of (available - reserve) divided by the page's
    byte cost in the serving kv_quant layout. The reserve keeps the OS,
    the Python heap, and tokenizer/weight staging out of the tier's
    budget; 0 when the machine has no headroom (the tier then simply
    stays off rather than inviting the OOM killer). 0 too for a latent
    (MLA) pool (the tier's page copies assume K and V pools) and for a
    looped stack (a page of pass x layer slots is tens of MiB to copy
    out at every eviction; untested) and for a pool a layer kind (a page
    of one kind has no twin in the other): 'auto' leaves it off there and
    an explicit size is refused by the engine."""
    if (model_cfg.latent_dim or model_cfg.loop_steps > 1
            or model_cfg.layer_types):
        return 0
    avail = (detect_host_ram_bytes() if host_ram_bytes is None
             else int(host_ram_bytes))
    budget = max(0, int((avail - reserve_bytes) * fraction))
    per_page = page_size * kv_bytes_per_token(model_cfg, kv_quant)
    return budget // max(per_page, 1)


def detect_hbm_bytes(device=None) -> float:
    """Per-chip HBM that ``auto`` sizing budgets against: what the
    device itself reports (``memory_stats()["bytes_limit"]``) where the
    backend does, else the table. Without a known chip (the CPU) there
    is nothing to size against and this raises — pass explicit sizes."""
    if device is None:
        import jax

        device = jax.devices()[0]
    spec = chip_spec(device)
    if spec is None:
        raise ValueError(
            f"--max-batch-size/--num-pages auto size from the chip's HBM "
            f"and this process runs on {device.platform!r}: pass "
            "explicit sizes")
    limit = (device.memory_stats() or {}).get("bytes_limit")
    return float(limit or spec.hbm_bytes)


def decode_ladder_rungs(top: int, base: int = 8) -> tuple:
    """The compiled-decode-graph ladder for a top batch size: doubling
    rungs from ``base`` (8/16/32/64...) strictly below ``top``, plus
    ``top`` itself. The engine compiles every rung at warmup and moves
    between them as occupancy changes, so a near-empty batch never pays
    the top rung's per-step latency (README "Batch ladder").

        top=32 -> (8, 16, 32);  top=24 -> (8, 16, 24);  top=8 -> (8,)

    ``top <= base`` collapses to the single legacy rung — small serving
    configs (tests, CPU smoke) keep exactly one compiled decode graph.
    """
    top = int(top)
    if top <= 0:
        raise ValueError(f"decode ladder needs a positive top, got {top}")
    rungs = []
    r = base
    while r < top:
        rungs.append(r)
        r *= 2
    rungs.append(top)
    return tuple(rungs)


def validate_ladder(rungs, top: int) -> tuple:
    """THE ladder invariant — strictly increasing positive rungs ending
    at ``top`` (the engine's slot-array size) — shared by
    parse_decode_ladder (CLI, before any model loads) and
    InferenceEngine.__init__ (boot), so the two sites cannot drift.
    Returns the rungs as a tuple."""
    rungs = tuple(rungs)
    if (not rungs or list(rungs) != sorted(set(rungs)) or rungs[0] < 1
            or rungs[-1] != top):
        raise ValueError(
            f"decode_ladder {list(rungs)} must be strictly increasing, "
            f"positive, and end at max_batch_size ({top})")
    return rungs


def parse_decode_ladder(spec: str, top: int) -> tuple:
    """THE --decode-ladder parser, shared by the server CLI and the
    benchmarks so their accepted grammar cannot drift: 'auto' (doubling
    rungs up to ``top``), 'off' (one graph at ``top``), or comma rungs
    like '8,16,32' — which must end at ``top``, the engine's slot-array
    size. Raises ValueError with a usage-quality message; CLI callers
    turn that into an argparse error before any model loads."""
    if spec == "auto":
        return decode_ladder_rungs(top)
    if spec == "off":
        return (top,)
    try:
        rungs = tuple(int(r) for r in spec.split(","))
    except ValueError:
        raise ValueError(
            f"--decode-ladder {spec!r}: expected 'auto', 'off', or "
            "comma-separated rungs like '8,16,32'")
    # The engine's boot-time invariant, applied HERE so a bad spec is a
    # usage error before any checkpoint loads, per the contract above.
    return validate_ladder(rungs, top)


# Chip-seconds one decode token costs relative to one prefill token in
# the pd-split heuristic: decode is memory-bound single-token dispatch
# work (the whole weight stream per token) while prefill amortizes the
# stream over the prompt, so a decode token "weighs" several prefill
# tokens when dividing workers between the phases.
PD_DECODE_COST_FACTOR = 4.0


def pd_worker_roles(dp: int, spec: str,
                    prompt_token_rate: Optional[float] = None,
                    decode_token_rate: Optional[float] = None) -> tuple:
    """Size the prefill:decode worker split for ``--pd-ratio`` (README
    "P/D disaggregation"): returns a dp-length role tuple
    ``("prefill",)*P + ("decode",)*D``.

    ``spec`` is either an explicit ``"P:D"`` ratio (scaled to dp, each
    side floored at one worker) or ``"auto"``: split by each phase's
    share of chip-seconds, computed from the observed prompt/decode
    token mix when the caller has one (``*_token_rate``, tokens per
    second offered to each phase) and from the BurstGPT-shaped default
    (512-token prompts, 128-token replies) otherwise, with decode
    tokens weighted PD_DECODE_COST_FACTOR heavier per token.

    Raises ValueError with flag-spelling messages (CLI callers turn
    them into usage errors before any model loads)."""
    if dp < 2:
        raise ValueError(
            f"--pd-ratio needs dp >= 2 (got dp={dp}): the split puts "
            "prefill and decode on different workers")
    if spec == "auto":
        p_rate = float(prompt_token_rate) if prompt_token_rate else 512.0
        d_rate = float(decode_token_rate) if decode_token_rate else 128.0
        share = p_rate / (p_rate + PD_DECODE_COST_FACTOR * d_rate)
    else:
        try:
            p_part, d_part = (int(x) for x in spec.split(":"))
        except ValueError:
            raise ValueError(
                f"--pd-ratio {spec!r}: expected 'auto' or 'P:D' "
                "(e.g. '1:1', '1:3')")
        if p_part < 1 or d_part < 1:
            raise ValueError(
                f"--pd-ratio {spec!r}: both sides must be >= 1")
        share = p_part / (p_part + d_part)
    n_prefill = max(1, min(dp - 1, round(dp * share)))
    return ("prefill",) * n_prefill + ("decode",) * (dp - n_prefill)


def resolve_model_and_checkpoint(model: str,
                                 checkpoint: Optional[str] = None):
    """(model_cfg, checkpoint_path) from a preset name, an HF checkpoint
    dir, or "auto" with ``checkpoint`` set. THE model-resolution rule:
    build_server and the pre-boot sizing path both call this, so the
    model that gets sized is always the model that boots."""
    import os

    from tpu_inference.config import PRESETS

    if model in PRESETS:
        return PRESETS[model](), checkpoint
    from tpu_inference.models import weights

    src = checkpoint if (model == "auto" and checkpoint) else model
    if not (isinstance(src, str)
            and os.path.exists(os.path.join(src, "config.json"))):
        raise ValueError(
            f"unknown model {model!r}: not a preset "
            f"({', '.join(sorted(PRESETS))}) and not a HF checkpoint "
            f"directory with a config.json")
    return weights.config_from_hf(src), (checkpoint or src)


def resolve_model_config(model: str, checkpoint: Optional[str] = None):
    """Model config only (see resolve_model_and_checkpoint)."""
    return resolve_model_and_checkpoint(model, checkpoint)[0]


def int_or_auto(v: str):
    """argparse type for --max-batch-size/--num-pages: an int or the
    literal 'auto' (clean usage error on anything else)."""
    import argparse

    if v == "auto":
        return v
    try:
        return int(v)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected an integer or 'auto', got {v!r}")


def sizing_request(args) -> dict:
    """The CLI's sizing ask (batch, pool, ladder and the two knobs of
    'auto'), before any device is known. Explicit sizes get their ladder
    validated on the spot — a usage error before any model loads; with
    'auto' in either size that waits for ``resolve_sizing`` in the
    process that owns the device. JSON-able: the subprocess fleet ships
    it to its workers."""
    page = getattr(args, "page_size", "auto")
    req = {"max_batch_size": args.max_batch_size,
           "num_pages": args.num_pages,
           # the tokens a page of a numeric --num-pages holds
           "pages_of": KV_PAGE_UNIT if page == "auto" else page,
           "decode_ladder": getattr(args, "decode_ladder", "off"),
           "target_ctx": getattr(args, "target_ctx", 0),
           "batch_cap": getattr(args, "batch_cap", 32)}
    if "auto" not in (req["max_batch_size"], req["num_pages"]):
        parse_decode_ladder(req["decode_ladder"], req["max_batch_size"])
    return req


def resolve_sizing(model_cfg, engine_cfg, req: Optional[dict], *,
                   tp: int = 1, hbm_bytes: Optional[float] = None):
    """EngineConfig with ``req``'s batch, pool and ladder filled in.
    'auto' sizes come from ``hbm_bytes`` (default: the visible device's
    own figure — so this runs in the process that owns the chip)."""
    unit = engine_cfg.page_size or KV_PAGE_UNIT
    engine_cfg = resolve_page_size(model_cfg, engine_cfg, tp=tp)
    if req is None:
        return engine_cfg
    mbs, pages = req["max_batch_size"], req["num_pages"]
    if pages != "auto":
        # (A number counts pages of ``pages_of`` tokens: 16 where the
        # page size was left to the program, which may have chosen
        # another; a router that settled the page for its workers says
        # so in the request.)
        pages = pages * req.get("pages_of", unit) // engine_cfg.page_size
    if "auto" in (mbs, pages):
        sz = auto_size(
            model_cfg,
            hbm_bytes=detect_hbm_bytes() if hbm_bytes is None else hbm_bytes,
            quant=engine_cfg.quant, kv_quant=engine_cfg.kv_quant, tp=tp,
            page_size=engine_cfg.page_size,
            max_pages_per_seq=engine_cfg.max_pages_per_seq,
            target_ctx=req["target_ctx"] or None,
            batch_cap=req["batch_cap"],
            window_span=window_span_pages(model_cfg, engine_cfg)
            if "window" in model_cfg.layer_types[:model_cfg.n_layers] else 0,
            written_ahead=written_ahead_tokens(engine_cfg),
            chunk_tokens=engine_cfg.chunk_tokens_cap)
        mbs = sz.max_batch_size if mbs == "auto" else mbs
        pages = sz.num_pages if pages == "auto" else pages
        import sys

        # (The window kind's pool is set only where it was sized on live
        # tokens, under every lane's span: left at 0 it is every lane's
        # span at the batch that is served, kv_cache.num_window_pages.)
        if pages == sz.num_pages and 0 < sz.num_window_pages < (
                mbs * window_span_pages(model_cfg, engine_cfg) + 1):
            engine_cfg = dataclasses.replace(
                engine_cfg, num_window_pages=sz.num_window_pages)
        pallas = pallas_reads_pool(engine_cfg.attn_backend)
        write = decode_write_path(model_cfg, pallas)
        tail_step = kda_tail_step_path(model_cfg, pallas)
        print(f"[autosize] {model_cfg.name}: batch={mbs} num_pages={pages} "
              f"page_tokens={engine_cfg.page_size} "
              f"kv_decode_write={write} "
              + (f"num_window_pages={sz.num_window_pages} "
                 if sz.num_window_pages else "")
              + (f"state_slots={mbs} state_bytes_per_slot="
                 f"{model_cfg.state_bytes_per_seq()} "
                 if model_cfg.state_kind else "")
              + (f"kda_tail_step={tail_step} " if tail_step else "") +
              f"(hbm {sz.hbm_bytes / 1e9:.2f} GB, weights/chip "
              f"{sz.weight_bytes_per_chip / 1e9:.2f} GB, kv pool/chip "
              f"{sz.kv_pool_bytes_per_chip / 1e9:.2f} GB, target ctx "
              f"{sz.target_ctx})", file=sys.stderr)
    return dataclasses.replace(
        engine_cfg, max_batch_size=mbs, num_pages=pages,
        decode_ladder=parse_decode_ladder(req["decode_ladder"], mbs))


def resolve_sizing_args(args) -> tuple:
    """In-process CLI hook (benchmarks): (max_batch_size, num_pages)
    with 'auto' resolved against this process's device; a no-op on
    ints. Reads model/checkpoint/quant/kv_quant/tp/page_size/
    max_pages_per_seq and the optional target_ctx/batch_cap attrs."""
    mbs, pages = args.max_batch_size, args.num_pages
    if "auto" not in (mbs, pages):
        return mbs, pages
    from tpu_inference.config import EngineConfig

    ecfg = resolve_sizing(
        resolve_model_config(args.model, args.checkpoint),
        EngineConfig(quant=args.quant, kv_quant=args.kv_quant,
                     page_size=args.page_size,
                     max_pages_per_seq=args.max_pages_per_seq),
        dict(sizing_request(args), decode_ladder="off"), tp=args.tp)
    return ecfg.max_batch_size, ecfg.num_pages
