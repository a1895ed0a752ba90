"""TPU kernels (Pallas/Mosaic) — the framework's native-performance tier.

The reference repo contains no native code at all (SURVEY.md §2b: zero
C++/Rust/CUDA components; the GPU kernels it relies on live inside its
external Ollama server). Pallas kernels are the TPU-idiomatic equivalent
of that missing tier: hand-scheduled HBM->VMEM pipelines for the ops XLA
can't fuse well on its own (paged-KV attention), validated against the
dense jnp reference paths in models/common.py.
"""

import jax
import jax.numpy as jnp


def mxu_precision(dtype):
    """bf16 operands go to the MXU as they are whatever
    ``jax_default_matmul_precision`` says (Mosaic refuses a bf16 dot asked
    for at float32 precision); float32 operands (interpret-mode tests)
    keep the ambient setting. Defined ahead of the imports below: every
    kernel module takes it from here."""
    return jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None


from tpu_inference.kernels.paged_attention import paged_attention  # noqa: E402,F401
from tpu_inference.kernels.prefill_attention import (  # noqa: E402,F401
    paged_prefill_attention)
from tpu_inference.kernels.ring_attention import (  # noqa: E402,F401
    ring_attention, ring_attention_local)
