"""The one traffic generator: a traffic file's parameters -> a schedule.

A traffic mix is a JSON file of parameters (``bench/traffic/<name>.json``);
this module is the only code that reads one. What makes two runs of a
cell comparable is fixed here, not drawn:

* the COUNT: an open-loop window of ``seconds`` at ``rate`` holds exactly
  ``N = round(rate * seconds)`` requests; request i is due at
  ``(i + u_i) * seconds / N`` with ``u_i`` in [0, 1) drawn from the seed
  (the pace is seconds / N, so that all N fall inside the window);
* the LENGTHS: the k-th (prompt, answer) pair of N is the
  ``(k + 0.5) / N`` quantile of the stated distributions (answers taken
  through a fixed permutation of k, so long prompts do not all get long
  answers). The multiset of pairs is the same for every seed;
* the ORDER: a fixed low-discrepancy base order (every run of ``BLOCK``
  consecutive requests spans the distribution), the same for EVERY seed,
  so which requests overlap does not depend on the seed (measured, PERF.md:
  with the order shuffled per seed inside blocks of 8, ``tpot_p50_s`` read
  3-4% apart between seeds and repeated to 0.4% within one).

A closed loop's queue repeats that base order lap after lap
(``closed_loop``), so that no window drains it.

The seed draws the ``u_i`` and makes every prompt's bytes, so no two
seeds send the same schedule. The program under test sees only the
generated requests. No JAX, no program import.
"""

from __future__ import annotations

import dataclasses
import json
import math
import statistics
from typing import List, Optional

import numpy as np

BLOCK = 8
# Laps of a closed mix's ``requests`` made up front: the fastest cell
# draws 1.2 laps in warm lap + window (doc-reask: 711 of a period of 600,
# PERF.md section 2), so a program 3x as fast still finds a queue.
LAPS = 4
_NORMAL = statistics.NormalDist()
# Printable bytes the server's byte tokenizer maps to one token each.
_ALPHABET = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz ,.", dtype=np.uint8)


@dataclasses.dataclass
class Request:
    index: int            # position in the schedule; < 0 = warm lap
    due_s: Optional[float]  # seconds from window open; None = closed loop
    prompt_tokens: int    # tokens the server will count (BOS included)
    answer_tokens: int    # num_predict
    shared: int           # index of the shared prompt it starts with, or -1
    prompt: str = ""


def quantile(dist: dict, q: float) -> int:
    """The q-quantile of a length distribution from a traffic file."""
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(q))
    elif kind == "uniform":
        x = dist["min"] + q * (dist["max"] - dist["min"])
    elif kind == "fixed":
        return int(dist["value"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(max(round(x), dist["min"]), dist["max"]))


def _stride(n: int, frac: float) -> int:
    """An integer near frac*n that is coprime to n: i -> i*stride mod n
    is then a permutation that scatters neighbours across the range."""
    g = max(1, round(n * frac))
    while math.gcd(g, n) != 1:
        g += 1
    return g


def length_pairs(traffic: dict, n: int) -> List[tuple]:
    """The fixed multiset of n (prompt, answer) pairs, in the fixed base
    order. Nothing here depends on a seed."""
    if n <= 0:
        return []
    ga = _stride(n, 0.381966)      # answers decorrelated from prompts
    go = _stride(n, 0.618034)      # base order: golden-ratio scatter
    pairs = []
    for i in range(n):
        k = (i * go) % n
        p = quantile(traffic["prompt"], (k + 0.5) / n)
        a = quantile(traffic["answer"], ((k * ga) % n + 0.5) / n)
        pairs.append((p, a))
    return pairs


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def _text(rng: np.random.Generator, n_bytes: int) -> str:
    return _ALPHABET[rng.integers(0, len(_ALPHABET), n_bytes)
                     ].tobytes().decode("ascii")


def shared_prompts(traffic: dict, seed: int) -> List[str]:
    """The mix's shared (system) prompts. The first starts with the BOS
    the tokenizer adds, so ``tokens`` - 1 bytes make ``tokens`` tokens."""
    sh = traffic.get("shared")
    if not sh:
        return []
    rng = _rng(seed, 3)
    return [_text(rng, sh["tokens"] - 1) for _ in range(sh["count"])]


def _finish(reqs: List[Request], traffic: dict, seed: int) -> List[Request]:
    """Give every request its bytes. A request of p own tokens sends p-1
    bytes alone (BOS is the p-th), or p bytes after a shared prompt."""
    shared = shared_prompts(traffic, seed)
    rng = _rng(seed, 4)
    for r in reqs:
        if shared:
            # Round-robin over the shared prompts: the same for all seeds.
            r.shared = r.index % len(shared)
            own = r.prompt_tokens
            r.prompt = shared[r.shared] + _text(rng, own)
            r.prompt_tokens = traffic["shared"]["tokens"] + own
        else:
            r.prompt = _text(rng, r.prompt_tokens - 1)
    return reqs


def open_loop(traffic: dict, seed: int, seconds: float,
              rate: Optional[float] = None) -> List[Request]:
    """Warm lap (index < 0, due < 0) then the window's requests, by due
    instant. ``rate`` overrides the file's (``sweep.py`` only)."""
    rate = float(rate if rate is not None else traffic["rate"])
    n = round(rate * seconds)
    pace = seconds / n
    n_warm = round(traffic["warm_lap_s"] / pace)
    rng_u = _rng(seed, 1)
    pairs = ([(j - n_warm, pa) for j, pa
              in enumerate(length_pairs(traffic, n_warm))]
             + list(enumerate(length_pairs(traffic, n))))
    reqs = [Request(i, (i + float(rng_u.random())) * pace, p, a, -1)
            for i, (p, a) in pairs]
    return _finish(reqs, traffic, seed)


def closed_loop(traffic: dict, seed: int, laps: int = LAPS) -> List[Request]:
    """The queue the clients draw from, in order: ``requests`` pairs in
    the fixed base order, repeated lap after lap, so that no window can
    drain it (a client that found it empty would leave, and a FASTER
    program would read a lower ``out_tok_s``; ``run.py`` fails a run in
    which that happens). ``requests`` is the period. A window cuts a
    slice out of the stream, so which lengths fall inside it must not
    depend on the seed (measured: with the order shuffled per seed,
    ``tpot_p50_s`` of 16 documents spread 4-5% across seeds and repeated
    to 0.1% within one). The seed makes the bytes.
    The first ``clients`` answers are cut to (j+1)/clients of their length
    so that clients which start together do not stay in phase: lap 0
    only. Request ``i`` of a later lap takes the uncut pair of
    ``i mod requests``, keeps ``index = i`` (the shared prompts go on
    round-robin) and draws its bytes further along the seed's stream, so
    a repeated pair never repeats a prompt. Everything is made here,
    before the window: nothing is drawn or encoded inside it."""
    n, c = int(traffic["requests"]), int(traffic["clients"])
    pairs = length_pairs(traffic, n)
    reqs = [Request(i, None, *pairs[i % n], -1) for i in range(laps * n)]
    for j, r in enumerate(reqs[:c]):
        r.answer_tokens = max(traffic["answer"]["min"] // 2,
                              r.answer_tokens * (j + 1) // c)
    return _finish(reqs, traffic, seed)


def load(path: str) -> dict:
    with open(path) as f:
        t = json.load(f)
    if t["loop"] not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed'")
    need = (("rate", "drain_s") if t["loop"] == "open"
            else ("clients", "requests"))
    for key in ("prompt", "answer", "warm_lap_s") + need:
        if key not in t:
            raise ValueError(f"{path}: traffic file lacks {key!r}")
    return t
