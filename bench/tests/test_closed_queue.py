"""The closed loop's queue (PR 35): lap 0 is the accepted benchmark's
queue to the byte, later laps repeat its pairs with fresh bytes, a queue
that a window drains fails the run, and the client's account of a
request's life (waiting / decoding) over the window."""

import asyncio
import collections
import hashlib
import json
import os
import subprocess
import sys
import threading

import pytest
from aiohttp import web

import loadgen
import metrics as M
import traffic as T
from conftest import BENCH, REPO
from server import free_port

MIXES = ("doc", "doc-reask", "reason", "code-mixed")
SEEDS = (0, 1, 3500000101)
# sha256 over (index, prompt tokens, answer tokens, shared, prompt bytes)
# of ``closed_loop(traffic, seed)`` at the parent commit (2808d4c), whose
# whole queue was ``requests`` long.
PARENT = {
    "doc:0": "f1ab6353e44041d4", "doc:1": "8074b6e0745077be",
    "doc:3500000101": "17faaf6e9a96519f",
    "doc-reask:0": "4ab9e1c677ed11d4", "doc-reask:1": "a688e4ac55b05a3e",
    "doc-reask:3500000101": "1f0c1697f27971db",
    "reason:0": "1fb68dabd6d50075", "reason:1": "df04dcc8afec2f55",
    "reason:3500000101": "2a5e1bd893510b5d",
    "code-mixed:0": "b0ac2fd4be95556a", "code-mixed:1": "b7a1da325f87efb4",
    "code-mixed:3500000101": "cd8b28fa86035f9a",
}


def load(mix):
    return T.load(os.path.join(BENCH, "traffic", mix + ".json"))


def digest(reqs):
    h = hashlib.sha256()
    for r in reqs:
        h.update(f"{r.index}|{r.prompt_tokens}|{r.answer_tokens}|"
                 f"{r.shared}|".encode())
        h.update(r.prompt.encode())
        h.update(b"\n")
    return h.hexdigest()[:16]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("mix", MIXES)
def test_lap_0_is_the_parents_queue_to_the_byte(mix, seed):
    t = load(mix)
    reqs = T.closed_loop(t, seed)
    assert len(reqs) >= 4 * t["requests"]
    assert digest(reqs[:t["requests"]]) == PARENT[f"{mix}:{seed}"]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("mix", MIXES)
def test_later_laps_repeat_the_pairs_and_nothing_else(mix, seed):
    t = load(mix)
    n, c = t["requests"], t["clients"]
    reqs = T.closed_loop(t, seed)
    own = t["shared"]["tokens"] if t.get("shared") else 0
    uncut = T.length_pairs(t, n)
    lap0 = [(r.prompt_tokens - own, r.answer_tokens) for r in reqs[:n]]
    assert lap0[c:] == uncut[c:], "lap 0 past the stagger is the base order"
    assert lap0[:c] != uncut[:c], "the first answers are cut"
    for k in range(1, len(reqs) // n):
        lap = reqs[k * n:(k + 1) * n]
        assert [(r.prompt_tokens - own, r.answer_tokens)
                for r in lap] == uncut, "no stagger cut after lap 0"
        assert [r.index for r in lap] == list(range(k * n, (k + 1) * n))
    if own:
        count = t["shared"]["count"]
        assert all(r.shared == r.index % count for r in reqs), \
            "the shared prompts go on round-robin over the laps"
    # A repeated pair never repeats a prompt: the prefix cache must not
    # hit a question. (Own bytes: what follows the shared prompt.)
    tails = collections.Counter(r.prompt[max(own - 1, 0):] for r in reqs
                                if r.prompt_tokens - own >= 24)
    assert max(tails.values()) == 1
    assert all(len(r.prompt.encode()) == r.prompt_tokens - 1 for r in reqs)


# ------------------------------------------------------------- dry queue
TOY = {"loop": "closed", "clients": 2, "requests": 3, "warm_lap_s": 0.2,
       "prompt": {"dist": "fixed", "value": 8},
       "answer": {"dist": "uniform", "min": 2, "max": 2}}


class _Stub:
    """A server that answers every request at once: one token, done."""

    def __enter__(self):
        self.ready = threading.Event()
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()
        assert self.ready.wait(10)
        return self

    def _serve(self):
        async def generate(request):
            body = await request.json()
            resp = web.StreamResponse()
            await resp.prepare(request)
            await resp.write(b'{"response": "a"}\n')
            await resp.write(json.dumps(
                {"done": True, "eval_count": 1, "done_reason": "length",
                 "prompt_eval_count": len(body["prompt"]) + 1}
            ).encode() + b"\n")
            return resp

        async def main():
            app = web.Application()
            app.router.add_post("/api/generate", generate)
            runner = web.AppRunner(app)
            await runner.setup()
            port = free_port()
            await web.TCPSite(runner, "127.0.0.1", port).start()
            self.base = f"http://127.0.0.1:{port}"
            self.stop = asyncio.Event()
            self.loop = asyncio.get_running_loop()
            self.ready.set()
            await self.stop.wait()
            await runner.cleanup()

        asyncio.run(main())

    def __exit__(self, *exc):
        self.loop.call_soon_threadsafe(self.stop.set)
        self.thread.join(10)


def test_a_queue_the_window_drains_fails_the_run():
    import run

    reqs = T.closed_loop(TOY, 7, laps=2)
    assert len(reqs) == 6
    with _Stub() as stub:
        out = loadgen.run(stub.base, "closed", reqs, TOY["warm_lap_s"],
                          1.0, clients=TOY["clients"])
    q = out["queue"]
    assert q["drawn"] == q["queued"] == 6 and q["dry_s"] is not None
    assert q["dry_s"] < 1.0, "before the close"
    assert all(M.finished(r) for r in out["records"])
    with pytest.raises(run.BenchFailure,
                       match=r"closed queue ran dry at -?\d+\.\d s: "
                             r"6 of 6 drawn"):
        run.check_queue(q)
    assert run.COMPARED["queue_drawn"] == [6, 6]


def test_a_queue_that_lasts_says_what_was_drawn():
    import run

    reqs = T.closed_loop(dict(TOY, requests=4000), 7, laps=1)
    with _Stub() as stub:
        out = loadgen.run(stub.base, "closed", reqs, TOY["warm_lap_s"],
                          0.5, clients=TOY["clients"])
    q = out["queue"]
    assert q["dry_s"] is None and 0 < q["drawn"] < q["queued"] == 4000
    assert q["drawn"] == sum(r["sent_s"] is not None for r in out["records"])
    run.check_queue(q)
    assert run.COMPARED["queue_drawn"] == [q["drawn"], 4000]


def test_an_open_loop_has_no_queue():
    with _Stub() as stub:
        out = loadgen.run(stub.base, "open",
                          [T.Request(0, 0.0, 8, 1, -1, "abcdefg")], 0.0,
                          0.1, drain_s=1.0)
    assert out["queue"] is None and M.finished(out["records"][0])


def test_run_py_prints_no_result_when_the_queue_runs_dry(tmp_path):
    """The whole command on the CPU: the rehearsal's closed cell over a
    mix of its own whose four laps are 8 requests."""
    rehearsal = os.path.join(BENCH, "tests", "rehearsal")
    with open(os.path.join(rehearsal, "BENCHMARK.json")) as f:
        data = json.load(f)
    with open(os.path.join(rehearsal, "traffic", "tiny-closed.json")) as f:
        mix = json.load(f)
    mix["requests"] = 2
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "tiny-dry.json").write_text(json.dumps(mix))
    # What a root lacks is taken from the next: manifest.Manifest._find
    # looks under every root in turn, and _root may be absolute.
    data["_root"] = str(tmp_path)
    data["workloads"] = [dict(w, traffic="tiny-dry") for w in
                         data["workloads"] if w["traffic"] == "tiny-closed"]
    cell = data["workloads"][0]["name"]
    for section in ("end_to_end", "per_layer"):
        data[section] = [dict(m, workloads=[cell]) if "workloads" in m
                         and cell in m["workloads"] else m
                         for m in data[section]
                         if "workloads" not in m or cell in m["workloads"]]
    manifest = tmp_path / "BENCHMARK.json"
    manifest.write_text(json.dumps(data))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "11", "--seconds", "5", "--trace", "0",
         "--manifest", str(manifest)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode != 0
    assert "closed queue ran dry at" in p.stderr and "8 of 8 drawn" in p.stderr
    assert not any(line.startswith('{"correct"')
                   for line in p.stdout.splitlines())
    assert '"queue_drawn": [8, 8]' in p.stdout


# ------------------------------------------------- a request's life
def rec(sent, tokens, done=None, failed=None):
    return {"sent_s": sent, "token_s": list(tokens), "done_s": done,
            "failed_s": failed, "error": "x" if failed is not None else None}


def test_in_flight_means_on_hand_made_records():
    seconds = 10.0
    records = [
        # straddles the open: sent and first token in the warm lap
        rec(-3.0, [-1.0, 0.5, 2.0], done=2.1),       # decoding 2.0
        # straddles the close: still streaming when the client cut it
        rec(4.0, [6.0, 8.0, 9.5]),                   # waiting 2, decoding 4
        # one token: it waited, it never decoded
        rec(1.0, [3.5], done=3.6),                   # waiting 2.5
        # sent, no token by the close
        rec(9.0, []),                                # waiting 1
        # failed before a token / in mid stream
        rec(2.0, [], failed=2.5),                    # waiting 0.5
        rec(5.0, [5.5, 6.5], failed=7.0),            # waiting .5, decoding 1
        # never drawn from the queue
        rec(None, []),
        # wholly outside
        rec(-9.0, [-8.0, -7.0], done=-6.9), rec(11.0, [12.0, 13.0]),
    ]
    assert M.in_flight_mean(records, seconds, "decoding") \
        == pytest.approx((2.0 + 4.0 + 1.0) / seconds)
    assert M.in_flight_mean(records, seconds, "waiting") \
        == pytest.approx((2.0 + 2.5 + 1.0 + 0.5 + 0.5) / seconds)
    assert M.in_flight_mean([], seconds, "waiting") == 0.0
    with pytest.raises(ValueError):
        M.in_flight_mean(records, seconds, "thinking")


def test_the_account_of_a_closed_loop_closes():
    """c clients, each always in exactly one of waiting / decoding /
    turning round: the two means and the turn-round add up to c."""
    seconds, c = 20.0, 3
    records, turn = [], 0.0
    for j in range(c):
        t = -2.0 + 0.3 * j
        while t < seconds + 3:
            first, last = t + 0.7, t + 0.7 + 1.9
            records.append(rec(t, [first, first + 1.0, last],
                               done=last + 0.01))
            nxt = last + 0.05                      # done + client's turn
            turn += max(0.0, min(nxt, seconds) - max(last, 0.0))
            t = nxt
    total = (M.in_flight_mean(records, seconds, "waiting")
             + M.in_flight_mean(records, seconds, "decoding")
             + turn / seconds)
    assert total == pytest.approx(c)


def test_client_stat_reads_them_and_the_manifest_names_the_files():
    from manifest import Manifest

    man = Manifest(os.path.join(REPO, "BENCHMARK.json"))
    closed = {w["name"] for w in man.data["workloads"]
              if load(w["traffic"])["loop"] == "closed"}
    assert len(closed) >= 4          # cells 3-9; a later cell joins
    records = [rec(0.0, [1.0, 3.0], done=3.1)]
    ctx = {"records": records, "ok": records, "failed": [], "traffic": {},
           "seconds": 4.0}
    want = {"streams_decoding_mean": 0.5, "clients_waiting_mean": 0.25,
            "ttft_mean_s.batch": 1.0, "ttft_p90_s.batch": 1.0}
    for name, value in want.items():
        entry = man._entry("per_layer", name)
        assert set(entry["workloads"]) == closed
        assert entry["moves"] == "out_tok_s"
        assert entry["layer"] == "client / HTTP (server/http.py)"
        spec = man.layer_metric(name)
        assert man.reader(spec["reader"])(ctx, **spec["args"]) \
            == pytest.approx(value)
    for name in ("queue_wait_mean_ms.batch", "queue_boundary_wait_ms.batch",
                 "queue_capacity_wait_ms.batch"):
        entry = man._entry("per_layer", name)
        assert set(entry["workloads"]) == closed
        assert entry["moves"] == "out_tok_s"
        assert man.layer_metric(name)["reader"] == "metrics_delta"


def test_the_window_is_closed_before_a_slow_profiler_returns():
    """``on_close`` (the scrape that ends every /metrics delta) runs at
    the close, not when ``during`` (the profiler, which may take longer
    to write its trace than the window has left) has returned."""
    import time
    at = {}

    async def during(t_open):
        at["open"] = t_open
        await asyncio.sleep(t_open + 1.0 - time.monotonic())
        at["during"] = time.monotonic() - t_open
        return "trace"

    async def on_close():
        at["close"] = time.monotonic() - at["open"]
        return "end"

    reqs = T.closed_loop(dict(TOY, requests=4000), 7, laps=1)
    with _Stub() as stub:
        out = loadgen.run(stub.base, "closed", reqs, TOY["warm_lap_s"], 0.4,
                          clients=TOY["clients"], during=during,
                          on_close=on_close)
    assert out["side"] == ["trace", "end"]
    assert 0.4 <= at["close"] < 0.7 < at["during"]
