"""Test environment: the CPU backend with a virtual 8-device mesh.

Multi-chip sharding tests (TP/EP/ring attention) run on 8 virtual CPU
devices. The tests never touch a chip: what runs on the TPU is
``chip_smoke.py`` (README "Running"), and tests/test_tpu_compile.py
compiles the kernels for a described v5e without one.

``select_platform`` also exports ``JAX_PLATFORMS=cpu`` so every process a
test spawns (fleet workers, the CLI under test) lands on the CPU too.
"""

import os
import sys

import pytest

os.environ.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "highest")

import jax  # noqa: E402

from tpu_inference.runtime import select_platform  # noqa: E402

select_platform("cpu", 8)
# XLA:CPU's oneDNN matmuls run in reduced precision by default (~1e-1 abs
# error on standard-normal f32 inputs), which swamps parity tolerances.
jax.config.update("jax_default_matmul_precision", "highest")

# Persistent XLA compilation cache: a warm run of an engine test file
# drops 41s -> 11s (rationale + knobs in tests/_xla_cache.py).
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _xla_cache  # noqa: E402

_xla_cache.enable(jax)


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs():
    """A process keeps every program it has compiled (the jit caches),
    each a few mappings of executable memory. A worker that has run a
    thousand-odd tests of a dozen files reaches the kernel's limit of
    mappings a process (``vm.max_map_count``, 65530), and XLA:CPU's next
    compile kills it: "LLVM ERROR: Unable to allocate section memory!",
    or a segmentation fault, in whatever test comes next (PR 45: three
    whole runs of three each lost a worker, and the dead worker's tests
    replayed in one process die at the same test at the parent commit
    too). No test file runs another's programs again: drop them when a
    file is done; the persistent cache above makes the few that recur
    cheap."""
    yield
    jax.clear_caches()


def randomize_qkv_biases(params, seed: int = 7, scale: float = 0.1) -> None:
    """init_params zero-inits Qwen2's q/k/v biases; tests randomize them
    in place so the bias term actually participates in parity checks.
    Shared across test modules (engine + TP suites)."""
    key = jax.random.PRNGKey(seed)
    for i, name in enumerate(("bq", "bk", "bv")):
        b = params["blocks"][name]
        params["blocks"][name] = scale * jax.random.normal(
            jax.random.fold_in(key, i), b.shape, b.dtype)
