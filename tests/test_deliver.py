"""The hand-off from the engine thread to the HTTP event loop
(``server/http.py DeliveryOutbox`` + ``EngineScheduler.on_delivered``):
one wake-up for all that a turn of the loop delivers, nothing left
waiting behind the device, nothing stranded, the wire unchanged.

No test here sleeps for timing: waits are events with a timeout that
only a hang reaches."""

import asyncio
import json
import sys
import threading

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

import _prom
from tpu_inference import config as cfgs
from tpu_inference.config import FrameworkConfig, ServerConfig
from tpu_inference.engine.engine import InferenceEngine, Sequence
from tpu_inference.engine.scheduler import EngineScheduler
from tpu_inference.models import build_model
from tpu_inference.server.http import DeliveryOutbox, InferenceServer
from tpu_inference.server.tokenizer import IncrementalDecoder, StopMatcher

WAIT_S = 120.0
K = 8           # EngineConfig.decode_steps_per_call: tokens a lane a turn


def _engine_cfg(**kw):
    return cfgs.EngineConfig(page_size=8, num_pages=128, max_pages_per_seq=8,
                             max_batch_size=4, prefill_buckets=(16, 32), **kw)


@pytest.fixture(scope="module")
def engines():
    model_cfg = cfgs.tiny_llama(vocab_size=256)
    params, _ = build_model(model_cfg, seed=0)
    return [InferenceEngine(model_cfg, _engine_cfg(), params=params)
            for _ in range(2)]


@pytest.fixture
def engine(engines):
    return engines[0]


class InlineLoop:
    """Stands in for the event loop: a posted callback runs at once on
    the posting thread, so after every wake-up the outbox is empty, and
    ``wakeups`` numbers them."""

    def __init__(self):
        self.wakeups = 0

    def call_soon_threadsafe(self, callback, *args):
        self.wakeups += 1
        callback(*args)


class Stream:
    """Stands in for a request's asyncio.Queue: each item with the
    number of the wake-up that carried it."""

    def __init__(self, loop: InlineLoop):
        self.loop = loop
        self.items = []
        self.first = threading.Event()
        self.finished = threading.Event()

    def put_nowait(self, item):
        self.items.append((self.loop.wakeups, item))
        self.first.set()
        if item[0] == "finish":
            self.finished.set()

    def tokens(self):
        return [it[1] for _, it in self.items if it[0] == "token"]


def _closures(outbox, stream):
    """What server/http.py hands the group for one request."""
    return (lambda s, tok: outbox.put(stream, ("token", tok)),
            lambda s: outbox.put(stream, ("finish", s)))


def _bound(engine):
    """A scheduler whose deliveries go through an outbox on an inline
    loop, as InferenceServer._on_startup binds them."""
    loop = InlineLoop()
    outbox = DeliveryOutbox(loop)
    sched = EngineScheduler(engine)
    sched.on_delivered = outbox.post
    return sched, outbox, loop


def _seq(rid, n_prompt, max_new, seed=0):
    rng = np.random.default_rng(1000 * seed + rid)
    return Sequence(request_id=rid, max_new_tokens=max_new,
                    prompt_tokens=rng.integers(0, 256, size=n_prompt).tolist())


def _submit(sched, outbox, loop, seq):
    stream = Stream(loop)
    sched.submit(seq, *_closures(outbox, stream))
    return stream


def _run_turns(engine, lanes, max_new):
    """``lanes`` requests queued BEFORE the loop starts (one batched
    prefill, then fused-K turns of all of them) -> (sched, loop, seqs,
    streams) after every finish arrived."""
    sched, outbox, loop = _bound(engine)
    seqs = [_seq(i, 5, max_new) for i in range(lanes)]
    streams = [_submit(sched, outbox, loop, s) for s in seqs]
    sched.start()
    try:
        for st in streams:
            assert st.finished.wait(WAIT_S), "a stream never finished"
    finally:
        sched.stop()
    assert not outbox._items
    return sched, loop, seqs, streams


def test_a_turn_of_lanes_posts_exactly_one_wakeup(engine):
    lanes = 4
    sched, loop, seqs, streams = _run_turns(engine, lanes, 1 + 2 * K)
    # One wake-up for the batched prefill's first tokens together, one
    # a fused-K turn; the last turn's carries its finishes too.
    assert loop.wakeups == 3
    assert sched.stats.deliver_wakeups == 3
    assert sched.stats.deliver_tokens == lanes * (1 + 2 * K)
    by_wakeup = {}
    for st in streams:
        for n, (kind, _) in st.items:
            by_wakeup.setdefault(n, []).append(kind)
    assert by_wakeup[1] == ["token"] * lanes
    assert by_wakeup[2] == ["token"] * (lanes * K)      # tokens / wakeup
    assert sorted(by_wakeup[3]) == (["finish"] * lanes
                                    + ["token"] * (lanes * K))


def test_per_stream_tokens_in_order_then_the_finish_of_the_same_turn(engine):
    _, _, seqs, streams = _run_turns(engine, 3, 1 + K + 3)
    for seq, st in zip(seqs, streams):
        kinds = [it[0] for _, it in st.items]
        assert kinds == ["token"] * len(seq.generated) + ["finish"]
        assert st.tokens() == seq.generated
        assert st.items[-1][1][1] is seq
        # The finish rode the wake-up of the turn's last tokens: it
        # neither overtook them nor cost a wake-up of its own.
        assert st.items[-1][0] == st.items[-2][0]


def test_one_token_a_turn_is_one_wakeup_a_token(engine):
    """Latency mode (one active sequence): as before the outbox, a
    wake-up a token, posted when the token is."""
    sched, loop, (seq,), (st,) = _run_turns(engine, 1, 6)
    assert len(seq.generated) == 6
    assert [n for n, _ in st.items] == [1, 2, 3, 4, 5, 6, 6]
    assert sched.stats.deliver_tokens == sched.stats.deliver_wakeups == 6


def test_nothing_handed_out_waits_behind_the_device_or_a_sleep(
        engine, monkeypatch):
    """A prefill's first tokens are posted before the decode dispatch
    that follows is staged, and the same holds at every other site: on
    entry to each engine call that stages or waits on the device, and
    to the idle wait, the outbox holds nothing of the engine thread's."""
    sched, outbox, loop = _bound(engine)
    breaches, entered = [], []

    def guarded(name):
        inner = getattr(engine, name)

        def call(*args, **kwargs):
            entered.append((name, loop.wakeups))
            if outbox._items:
                breaches.append((name, len(outbox._items)))
            return inner(*args, **kwargs)
        monkeypatch.setattr(engine, name, call)

    for name in ("decode_steps_pipelined", "decode_steps", "prefill_many",
                 "prefill_step", "drain_pipeline"):
        guarded(name)
    clock = engine.telemetry.clock
    enter = clock.enter

    def watched_enter(phase):
        if phase in ("idle", "stage", "device_wait") and outbox._items:
            breaches.append((phase, len(outbox._items)))
        return enter(phase)
    monkeypatch.setattr(clock, "enter", watched_enter)

    first = [_submit(sched, outbox, loop, _seq(i, 5, 20)) for i in range(2)]
    sched.start()
    try:
        assert first[0].first.wait(WAIT_S)
        # Arrivals while the batch decodes: a short prompt, a prompt of
        # several chunks (40 tokens over 32-token chunks), and one that
        # is cancelled after its first token.
        late = [_submit(sched, outbox, loop, _seq(10, 7, 12)),
                _submit(sched, outbox, loop, _seq(11, 40, 10))]
        gone = _submit(sched, outbox, loop, _seq(12, 6, 40))
        assert gone.first.wait(WAIT_S)
        sched.cancel(12)
        for st in first + late + [gone]:
            assert st.finished.wait(WAIT_S)
    finally:
        sched.stop()
    assert breaches == []
    decodes = [n for name, n in entered if name == "decode_steps_pipelined"]
    assert decodes and decodes[0] >= 1, \
        "the first tokens' wake-up precedes the first decode dispatch"
    assert gone.items[-1][1][1].finish_reason == "cancelled"
    assert not outbox._items


@pytest.mark.parametrize("reason", ["queue_full", "too_large"])
def test_a_rejection_on_the_callers_thread_is_posted_at_once(
        engine, reason, monkeypatch):
    sched, outbox, loop = _bound(engine)         # never started
    if reason == "queue_full":
        for i in range(engine.engine_cfg.max_queue_len):
            _submit(sched, outbox, loop, _seq(i, 3, 1))
    else:
        monkeypatch.setattr(engine, "can_ever_admit", lambda seq: False)
    seq = _seq(9999, 3, 1)
    got = []
    t = threading.Thread(
        target=lambda: got.append(_submit(sched, outbox, loop, seq)))
    t.start()
    t.join(WAIT_S)
    assert not t.is_alive()
    (st,) = got
    assert loop.wakeups == 1 and not outbox._items
    assert [it[0] for _, it in st.items] == ["finish"]
    assert seq.finish_reason == reason


def test_a_foreign_put_carries_what_sits_in_the_outbox_with_it():
    """A thread that posts for itself leaves items in the outbox until
    its post; a put from a thread that never posts (a cancel's finish
    from shutdown, a failover's replay) wakes the loop there and then,
    for everything appended so far, in order."""
    loop = InlineLoop()
    outbox = DeliveryOutbox(loop)
    st = Stream(loop)
    assert outbox.post() is False            # this thread posts for itself
    for tok in (1, 2, 3):
        outbox.put(st, ("token", tok))
    assert loop.wakeups == 0 and len(outbox._items) == 3
    t = threading.Thread(target=outbox.put, args=(st, ("finish", None)))
    t.start()
    t.join(WAIT_S)
    assert loop.wakeups == 1 and not outbox._items
    assert [it for _, it in st.items] == [
        ("token", 1), ("token", 2), ("token", 3), ("finish", None)]
    assert outbox.post() is False and loop.wakeups == 1   # nothing left


def test_two_schedulers_feed_one_loop(engines):
    """Two replicas' engine threads share the server's outbox and its
    real event loop: every stream gets its own tokens in order and its
    finish last, and each thread posts its own turns."""
    async def main():
        outbox = DeliveryOutbox(asyncio.get_running_loop())
        scheds = [EngineScheduler(e) for e in engines]
        seqs, queues = [], []
        for i, sched in enumerate(scheds):
            sched.on_delivered = outbox.post
            for j in range(3):
                seq = _seq(10 * i + j, 4 + j, 1 + K + j, seed=i)
                queue = asyncio.Queue()
                sched.submit(seq, *_closures(outbox, queue))
                seqs.append(seq)
                queues.append(queue)
        for sched in scheds:
            sched.start()
        try:
            got = []
            for queue in queues:
                items = []
                while not items or items[-1][0] != "finish":
                    items.append(await asyncio.wait_for(queue.get(), WAIT_S))
                got.append(items)
        finally:
            for sched in scheds:
                await asyncio.to_thread(sched.stop)
        return scheds, seqs, got

    scheds, seqs, got = asyncio.run(main())
    for seq, items in zip(seqs, got):
        assert [it[1] for it in items[:-1]] == seq.generated
        assert items[-1] == ("finish", seq)
    for sched in scheds:
        assert sched.stats.deliver_tokens == sum(
            len(s.generated) for s in seqs if s.request_id // 10
            == scheds.index(sched))
        assert 0 < sched.stats.deliver_wakeups < sched.stats.deliver_tokens


def test_outbox_under_many_threads_loses_and_reorders_nothing():
    """Threads that post for themselves beside threads that never post,
    at a switch interval that interleaves them: every stream receives
    all of its items, in order, and the outbox ends empty."""
    turns, per_turn, posters, others = 40, 25, 4, 4

    async def main():
        outbox = DeliveryOutbox(asyncio.get_running_loop())
        queues = [asyncio.Queue() for _ in range(posters + others)]

        def produce(i):
            for turn in range(turns):
                for k in range(per_turn):
                    outbox.put(queues[i], turn * per_turn + k)
                if i < posters:
                    outbox.post()

        threads = [threading.Thread(target=produce, args=(i,))
                   for i in range(posters + others)]
        for t in threads:
            t.start()
        got = [[await asyncio.wait_for(q.get(), WAIT_S)
                for _ in range(turns * per_turn)] for q in queues]
        for t in threads:
            await asyncio.to_thread(t.join, WAIT_S)
            assert not t.is_alive()
        return got, len(outbox._items), [q.qsize() for q in queues]

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        got, left, sizes = asyncio.run(main())
    finally:
        sys.setswitchinterval(old)
    assert all(g == list(range(turns * per_turn)) for g in got)
    assert left == 0 and not any(sizes)


# ------------------------------------------------------------ over HTTP


@pytest.fixture(scope="module")
def server():
    # latency_decode_threshold 0: a lone request decodes in fused-K
    # turns too, so one stream shows a turn of K tokens.
    cfg = FrameworkConfig(
        model=cfgs.tiny_llama(vocab_size=512),
        engine=_engine_cfg(latency_decode_threshold=0),
        server=ServerConfig(model_name="tiny-llama", tokenizer="byte"))
    return InferenceServer(cfg)


def _serve(server, scenario):
    async def wrapper():
        async with TestClient(TestServer(server.make_app())) as client:
            return await scenario(client)

    return asyncio.run(wrapper())


async def _counters(client):
    _, samples = _prom.parse(await (await client.get("/metrics")).text())
    return {n: v for n, _, v in samples
            if n in ("tpu_inf_deliver_tokens_total",
                     "tpu_inf_deliver_wakeups_total")}


async def _generate(client, prompt, stream, **extra):
    resp = await client.post("/api/generate", json={
        "prompt": prompt, "stream": stream,
        "options": {"temperature": 0.0}, **extra})
    assert resp.status == 200
    if not stream:
        return await resp.json()
    return [json.loads(line) for line in (await resp.read()).splitlines()]


def test_a_stop_string_inside_a_turn_cuts_the_stream_there(server):
    async def scenario(client):
        before = await _counters(client)
        base = await _generate(client, "stop probe", False, max_tokens=12)
        after = await _counters(client)
        # 12 tokens = the prefill's, a turn of 8, a turn of 3 + finish.
        assert (after["tpu_inf_deliver_wakeups_total"]
                - before["tpu_inf_deliver_wakeups_total"]) == 3
        n_prompt = base["prompt_eval_count"]
        ids = base["context"][n_prompt:]
        decoder = IncrementalDecoder(server.tokenizer,
                                     prompt_tail=base["context"][:n_prompt][-8:])
        pieces = [decoder.push(t) for t in ids]
        assert all(pieces[:4]), pieces
        stop_s = "".join(pieces[1:4])
        # Where the handler must stop, by the matcher itself: the index
        # of the token whose text completes the stop string (token 0 is
        # the prefill's, 1..8 the first turn's).
        matcher, cut = StopMatcher([stop_s]), ""
        for j, piece in enumerate(pieces):
            emit, stopped = matcher.push(piece)
            cut += emit
            if stopped:
                break
        assert 1 <= j < K - 1, "the stop completes inside the first turn"
        for stream in (True, False):
            out = await _generate(client, "stop probe", stream, max_tokens=12,
                                  options={"temperature": 0.0,
                                           "stop": [stop_s]})
            final = out[-1] if stream else out
            text = ("".join(l["response"] for l in out[:-1]) if stream
                    else out["response"])
            assert text == cut
            assert final["done_reason"] == "stop"
            # The rest of the turn was delivered and never consumed.
            assert final["eval_count"] == j + 1
            assert final["context"] == base["context"][:n_prompt + j + 1]

    _serve(server, scenario)


def test_streamed_lines_are_a_token_each_and_equal_the_unary_answer(server):
    prompts = [f"same answer either way {i}" for i in range(3)]

    async def scenario(client):
        unary = await asyncio.gather(*[
            _generate(client, p, False, max_tokens=20) for p in prompts])
        streamed = await asyncio.gather(*[
            _generate(client, p, True, max_tokens=20) for p in prompts])
        return unary, streamed

    unary, streamed = _serve(server, scenario)
    for u, lines in zip(unary, streamed):
        final = lines[-1]
        assert all(set(l) == {"model", "created_at", "response", "done"}
                   and l["done"] is False for l in lines[:-1])
        # One line a token (and at most one more for the decoder's tail).
        assert len(lines) - 1 in (u["eval_count"], u["eval_count"] + 1)
        assert "".join(l["response"] for l in lines[:-1]) == u["response"]
        for key in ("model", "done", "done_reason", "context",
                    "prompt_eval_count", "eval_count"):
            assert final[key] == u[key], key


def test_the_counters_move_by_what_was_delivered(server):
    async def scenario(client):
        before = await _counters(client)
        outs = await asyncio.gather(*[
            _generate(client, f"count me {i}", i % 2 == 0, max_tokens=17)
            for i in range(4)])
        after = await _counters(client)
        return before, after, outs

    before, after, outs = _serve(server, scenario)
    tokens = sum((o[-1] if isinstance(o, list) else o)["eval_count"]
                 for o in outs)
    assert tokens == 4 * 17
    assert (after["tpu_inf_deliver_tokens_total"]
            - before["tpu_inf_deliver_tokens_total"]) == tokens
    wakeups = (after["tpu_inf_deliver_wakeups_total"]
               - before["tpu_inf_deliver_wakeups_total"])
    # At most a prefill dispatch a request and a turn per K tokens of
    # the longest-running one, far fewer than a wake-up a token.
    assert 3 <= wakeups <= 4 + 4 * 3
