"""Process start-up: which JAX platform this process runs on and where
it keeps its compiled programs.

A TPU chip belongs to one process at a time: the first process to
initialise a JAX backend takes it, and a child that needs it afterwards
fails or hangs. So everything here is safe to call in a process that
must stay OFF the chip (the subprocess fleet's router): nothing below
initialises a backend except ``require_backend``, which only the
process that owns the device calls.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache — fixed and derived from the package's own path
# (the directory is part of every cache key's lookup, so a path that
# moves between runs never hits). Git-ignored.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compile_cache() -> str:
    """Persistent XLA compilation cache, placed from outside: when
    ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it itself and no
    directory is set here; otherwise the cache lives at
    ``<checkout>/.jax_cache``. Call before the first compile. Server,
    fleet workers, chip_smoke.py's children and the tests all call this,
    so one fleet (and one smoke run) compiles each graph once. Returns
    the directory in use.

    For "once" to hold across processes, a program's cache key must not
    depend on WHO compiled it. jax strips debug info from the HLO it
    hashes, but a Pallas TPU kernel rides inside it as an opaque
    serialized module that keeps its own — by default the Python
    traceback of the trace, ten frames deep, which reaches from the
    kernels up past ``engine.warmup()`` into whoever called it. So a
    fleet worker (``worker.boot``) missed every graph the in-process
    server (``http._on_startup``) had just cached: 234 s of warm-up on
    the v5e instead of 14. Locations without tracebacks make the key
    what it should be, a function of the program."""
    import jax

    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR


# TPU_CHIPS_PER_PROCESS_BOUNDS for a process of n chips on one host (the
# x,y,z extent of its sub-mesh; v5e hosts are 2x2 or 2x4).
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def chip_env(first: int, n_chips: int) -> dict:
    """Environment entries that make a process see only chips ``first ..
    first + n_chips - 1`` of its host: a chip belongs to one process at
    a time and libtpu otherwise claims every chip on the host. These are
    the variables libtpu itself reads (a TPU_CHIPS_PER_PROCESS_BOUNDS
    that is a subset of the host is also what lets several processes
    load it side by side); off a TPU nothing reads them. {} for a chip
    count that is no sub-mesh of a host."""
    bounds = _CHIP_BOUNDS.get(n_chips)
    if bounds is None:
        return {}
    return {
        "TPU_VISIBLE_CHIPS": ",".join(
            str(c) for c in range(first, first + n_chips)),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def select_platform(platform: str, cpu_devices: int = 1) -> None:
    """Apply ``--platform`` before any backend exists. 'cpu'/'tpu' pin
    the platform for this process (jax.config) AND for every worker it
    spawns (``JAX_PLATFORMS`` in the inherited environment); 'auto'
    leaves the environment's own choice alone. ``cpu_devices`` is the
    virtual device count of the CPU backend, which is what a mesh is
    built from wherever the platform turns out to be the CPU (on a TPU
    it only sizes the idle host backend)."""
    import jax

    if platform != "auto":
        os.environ["JAX_PLATFORMS"] = platform
        jax.config.update("jax_platforms", platform)
    jax.config.update("jax_num_cpu_devices", max(1, int(cpu_devices)))


def require_backend(platform: str) -> None:
    """Initialise the backend and refuse a silent fallback: unless the
    CPU was asked for by name, anything but a TPU is an error (jax on a
    machine without a chip otherwise quietly serves from the CPU, and
    every number such a server prints looks like a device number)."""
    import jax

    backend = jax.default_backend()
    if platform != "cpu" and backend != "tpu":
        raise SystemExit(
            f"--platform {platform}: no TPU found (jax backend is "
            f"{backend!r}); pass --platform cpu to serve from the CPU "
            "on purpose")
