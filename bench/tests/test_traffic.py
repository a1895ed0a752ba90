"""The paced schedule: what must be the same in every run of a cell, and
what the seed may change."""

import collections
import json
import os

import pytest

import traffic as T

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")


def load(name):
    return T.load(os.path.join(TRAFFIC, name + ".json"))


def window(reqs):
    return [r for r in reqs if r.index >= 0]


@pytest.mark.parametrize("mix", ["chat", "sysprompt-chat"])
@pytest.mark.parametrize("seconds", [10, 48])
def test_count_is_rate_times_seconds(mix, seconds):
    t = load(mix)
    for seed in (1, 2**31 + 7):
        reqs = T.open_loop(t, seed, seconds)
        n = round(t["rate"] * seconds)
        assert len(window(reqs)) == n
        assert all(0 <= r.due_s < seconds for r in window(reqs)), \
            "every request of the window is due inside it"
        assert len(reqs) - n == round(t["warm_lap_s"] * n / seconds)


@pytest.mark.parametrize("mix", ["chat", "sysprompt-chat"])
def test_due_instants_are_paced(mix):
    t = load(mix)
    reqs = T.open_loop(t, 5, 48)
    pace = 48 / len(window(reqs))
    for r in reqs:
        assert r.index * pace <= r.due_s < (r.index + 1) * pace
    assert min(r.due_s for r in reqs) >= -t["warm_lap_s"] - 1e-9


@pytest.mark.parametrize("mix", ["chat", "sysprompt-chat", "doc"])
def test_same_lengths_in_the_same_order_for_any_seed(mix):
    t = load(mix)
    make = ((lambda s: window(T.open_loop(t, s, 48))) if t["loop"] == "open"
            else (lambda s: T.closed_loop(t, s)[t["clients"]:]))
    bags = [collections.Counter((r.prompt_tokens, r.answer_tokens)
                                for r in make(seed))
            for seed in (0, 1, 987654321, 2**31 + 11)]
    assert all(b == bags[0] for b in bags)
    orders = [[(r.prompt_tokens, r.answer_tokens) for r in make(seed)]
              for seed in (0, 1)]
    # Which requests overlap must not follow the seed: same order, other
    # bytes and (open loop) other instants inside each request's own slot.
    assert orders[0] == orders[1]
    assert make(0)[0].prompt != make(1)[0].prompt
    if t["loop"] == "open":
        assert ([r.due_s for r in make(0)] != [r.due_s for r in make(1)]), \
            "two seeds sent the same schedule"


def test_lengths_are_quantiles_of_the_stated_distribution():
    t = load("chat")
    n = 120
    pairs = T.length_pairs(t, n)
    prompts = sorted(p for p, _ in pairs)
    want = sorted(T.quantile(t["prompt"], (k + 0.5) / n) for k in range(n))
    assert prompts == want
    assert min(prompts) >= t["prompt"]["min"]
    assert max(prompts) <= t["prompt"]["max"]
    # median of the quantiles is the stated median (within rounding)
    assert abs(prompts[n // 2] - t["prompt"]["median"]) <= 4
    answers = sorted(a for _, a in pairs)
    assert answers == sorted(T.quantile(t["answer"], (k + 0.5) / n)
                             for k in range(n))


def test_the_window_is_sent_in_the_base_order():
    t = load("chat")
    base = T.length_pairs(t, round(t["rate"] * 48))
    got = [(r.prompt_tokens, r.answer_tokens)
           for r in window(T.open_loop(t, 77, 48))]
    assert got == base


def test_every_block_spans_the_distribution():
    """The base order is low-discrepancy: no block of 8 is all short or
    all long prompts, so local load does not drift."""
    t = load("chat")
    pairs = T.length_pairs(t, 128)
    med = sorted(p for p, _ in pairs)[64]
    for lo in range(0, 128, T.BLOCK):
        short = sum(1 for p, _ in pairs[lo:lo + T.BLOCK] if p < med)
        assert 2 <= short <= 6


def test_prompt_bytes_make_the_stated_tokens():
    t = load("chat")
    for r in T.open_loop(t, 3, 10):
        assert len(r.prompt.encode()) == r.prompt_tokens - 1   # + BOS
    s = load("sysprompt-chat")
    reqs = T.open_loop(s, 3, 10)
    shared = T.shared_prompts(s, 3)
    assert len(shared) == 3 and len({*shared}) == 3
    for r in reqs:
        assert r.prompt.startswith(shared[r.shared])
        assert len(r.prompt.encode()) == r.prompt_tokens - 1
        assert r.prompt_tokens > s["shared"]["tokens"]
    assert {r.shared for r in reqs if r.index < 0} == {0, 1, 2}, \
        "the warm lap must prime every shared prompt"
    assert T.open_loop(s, 3, 10)[5].prompt == reqs[5].prompt
    assert T.open_loop(s, 4, 10)[5].prompt != reqs[5].prompt


def test_closed_loop_first_answers_are_staggered():
    t = load("doc")
    reqs = T.closed_loop(t, 9)
    first = [r.answer_tokens for r in reqs[:t["clients"]]]
    assert first == sorted(first) or len(set(first)) > t["clients"] // 2
    assert all(r.due_s is None for r in reqs)
    assert len(reqs) == T.LAPS * t["requests"], "laps of the period"


def test_a_traffic_file_missing_a_key_is_refused(tmp_path):
    bad = dict(load("chat"))
    del bad["rate"]
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(bad))
    with pytest.raises(ValueError):
        T.load(str(p))


def test_a_mix_that_ignores_eos_starts_the_server_with_the_flag():
    """A chance EOS under random weights ends an answer early on some
    seeds and not on others: a mix may say every answer runs to its
    length, and the configuration's own flags are left as they are."""
    import run
    cfg = {"serving": {"flags": ["--model", "m", "--quant", "int8"]}}
    assert run.server_flags(cfg, {"ignore_eos": True}) == \
        cfg["serving"]["flags"] + ["--ignore-eos"]
    assert run.server_flags(cfg, {}) == cfg["serving"]["flags"]
    assert run.server_flags(cfg, {"ignore_eos": False}) == \
        cfg["serving"]["flags"]
    already = {"serving": {"flags": ["--model", "m", "--ignore-eos"]}}
    assert run.server_flags(already, {"ignore_eos": True}).count(
        "--ignore-eos") == 1
    assert cfg["serving"]["flags"] == ["--model", "m", "--quant", "int8"]


def test_every_closed_cell_runs_its_answers_to_their_length():
    """The seed must not change a closed cell's work: each runs under
    --ignore-eos, by its mix or by its configuration."""
    import run
    from manifest import Manifest
    man = Manifest(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "BENCHMARK.json"))
    closed = 0
    for cell in man.data["workloads"]:
        mix = T.load(man.traffic_path(cell))
        if mix["loop"] == "closed":
            closed += 1
            assert "--ignore-eos" in run.server_flags(man.config(cell), mix), \
                cell["name"]
    assert closed >= 4               # cells 3-9; a later cell joins
