"""The comparison that decides ``correct``, shown to fail: at a tiny size
on the CPU the program's engine passes against the reference and the
control (the engine with its own lower-precision path, int4 weights, on)
does not. The same is run on the chip at the cells' own sizes (PERF.md
section 2 has both readings and the limit)."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, REPO

REHEARSAL = os.path.join(BENCH, "tests", "rehearsal", "BENCHMARK.json")


def parity(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "parity.py"), "--manifest",
         REHEARSAL, "--workload", "tiny-mistral_tiny-chat", "--seeds",
         "1,2,4000000000", *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(x) for x in p.stdout.splitlines() if x[:1] == "{"]
    return lines[:-1], lines[-1]


@pytest.fixture(scope="module")
def sound():
    return parity()


@pytest.fixture(scope="module")
def control():
    return parity("--control")


def test_the_program_passes(sound):
    seeds, summary = sound
    assert summary["ok"] is True and len(seeds) == 3
    assert all(s["ok"] and not s["sizes_wrong"] for s in seeds)


def test_the_control_fails_on_every_seed(control):
    seeds, summary = control
    assert summary["ok"] is False
    assert all(not s["ok"] for s in seeds)


def test_the_two_are_far_apart(sound, control):
    worst_sound = max(s["rms"] for s in sound[0])
    least_control = min(s["rms"] for s in control[0])
    limit = sound[0][0]["limit"]["rms"]
    assert worst_sound < limit < least_control
    assert least_control > 3 * worst_sound


def test_reference_int8_is_the_stated_quantisation():
    """scale = max|w|/127 per output channel; codes in [-127, 127]."""
    import importlib.util

    import numpy as np

    spec = importlib.util.spec_from_file_location(
        "ref", os.path.join(BENCH, "references", "llama_family.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    w = np.random.default_rng(0).normal(size=(3, 64, 32)).astype(np.float32)
    got = np.asarray(ref.int8_per_channel(w))
    scale = np.abs(w).max(axis=-2, keepdims=True) / 127.0
    codes = got / scale
    assert np.allclose(codes, np.round(codes), atol=1e-3)
    assert np.abs(codes).max() <= 127.0 + 1e-3
    assert np.abs(got - w).max() <= scale.max() / 2 + 1e-6
