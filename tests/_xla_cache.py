"""Persistent XLA compilation cache for the test suite.

The suite is ~70% XLA:CPU compile time on a single-core box, and the
graphs are identical run to run, so the compiled executables are cached
on disk (keyed by HLO + compile options + jaxlib version). Shared by
tests/conftest.py and the bare-subprocess tests/_multihost_worker.py so
the knobs cannot drift. WHERE the cache lives is the program's own rule
(tpu_inference/runtime.py enable_compile_cache: JAX_COMPILATION_CACHE_DIR
when set, else <checkout>/.jax_cache); this file only adds the CPU-only
extras the tests need on top.

``jax_persistent_cache_enable_xla_caches="all"`` is required for XLA:CPU
executable reuse (the default scope caches nothing useful on CPU).
Reusing an executable on the same machine triggers a cosmetic
cpu_aot_loader machine-feature warning per load (XLA's pseudo-features
like +prefer-no-scatter are absent from the host-feature string), so
TF_CPP_MIN_LOG_LEVEL silences C++ logging below FATAL; tests assert via
Python exceptions, not glog. Numeric parity tests would catch a
genuinely bad cached executable; delete the dir to force recompiles.

Debugging knobs (ADVICE r5 — a blanket log gag must never survive into
a debugging run):
- ``TPU_INF_NO_XLA_CACHE=1`` opts out of the cache entirely AND skips
  the log suppression, so a debugging run gets full XLA logs.
- ``TPU_INF_XLA_LOGS=1`` keeps the (fast) cache but skips the
  suppression — full logs without paying cold recompiles.
"""

import os


def enable(jax) -> None:
    if os.environ.get("TPU_INF_NO_XLA_CACHE"):
        # No cache -> no cosmetic reuse warning to hide, so the blanket
        # TF_CPP_MIN_LOG_LEVEL suppression is skipped too: debugging
        # runs see every XLA warning/error.
        return
    if not os.environ.get("TPU_INF_XLA_LOGS"):
        os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    from tpu_inference.runtime import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
