"""Hermetic HTTP server tests: the exact wire contract the benchmark
harness depends on (SURVEY.md §2c), served by a tiny random-init model."""

import asyncio
import json

import pytest
from aiohttp.test_utils import TestClient, TestServer

import _prom
from tpu_inference.config import (EngineConfig, FrameworkConfig, ServerConfig,
                                  tiny_llama)
from tpu_inference.server.http import InferenceServer

FINAL_FIELDS = {"model", "created_at", "response", "done", "done_reason",
                "context", "total_duration", "load_duration",
                "prompt_eval_count", "prompt_eval_duration", "eval_count",
                "eval_duration"}


@pytest.fixture(scope="module")
def profile_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax-trace"))


@pytest.fixture(scope="module")
def server(profile_dir):
    cfg = FrameworkConfig(
        model=tiny_llama(vocab_size=512),
        engine=EngineConfig(page_size=8, num_pages=128, max_pages_per_seq=8,
                            max_batch_size=4, prefill_buckets=(16, 32, 64)),
        server=ServerConfig(model_name="tiny-llama", tokenizer="byte",
                            enable_debug=True, profile_dir=profile_dir))
    return InferenceServer(cfg)


def _run(server, coro_fn):
    async def wrapper():
        app = server.make_app()
        async with TestClient(TestServer(app)) as client:
            return await coro_fn(client)

    return asyncio.run(wrapper())


def test_streaming_ndjson_contract(server):
    async def go(client):
        resp = await client.post("/api/generate", json={
            "model": "tiny-llama", "prompt": "Hello TPU",
            "temperature": 0.0, "max_tokens": 8, "stream": True})
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("application/x-ndjson")
        raw = await resp.read()
        lines = [json.loads(l) for l in raw.splitlines()]
        assert len(lines) >= 2
        for line in lines[:-1]:
            assert line["done"] is False
            assert set(line) == {"model", "created_at", "response", "done"}
            assert line["model"] == "tiny-llama"
        final = lines[-1]
        assert final["done"] is True
        assert FINAL_FIELDS <= set(final)
        assert final["eval_count"] == 8 or final["done_reason"] == "stop"
        assert final["prompt_eval_count"] == len("Hello TPU") + 1  # +BOS
        assert final["prompt_eval_duration"] > 0
        assert final["total_duration"] > 0
        assert len(final["context"]) == final["prompt_eval_count"] + final["eval_count"]
        return lines

    _run(server, go)


def test_non_streaming_single_object(server):
    async def go(client):
        resp = await client.post("/api/generate", json={
            "prompt": "abc", "stream": False, "max_tokens": 5})
        assert resp.status == 200
        body = await resp.json()
        assert body["done"] is True
        assert isinstance(body["response"], str)
        assert FINAL_FIELDS <= set(body)
        return body

    _run(server, go)


def test_options_num_predict_honored(server):
    """Ollama-placement options.num_predict must control generation length."""
    async def go(client):
        resp = await client.post("/api/generate", json={
            "prompt": "xyz", "stream": False, "max_tokens": 99,
            "options": {"num_predict": 3, "temperature": 0.0}})
        body = await resp.json()
        assert body["eval_count"] == 3 or body["done_reason"] == "stop"
        return body

    _run(server, go)


def test_greedy_is_deterministic(server):
    async def go(client):
        outs = []
        for _ in range(2):
            resp = await client.post("/api/generate", json={
                "prompt": "determinism", "stream": False, "max_tokens": 6,
                "temperature": 0.0})
            outs.append((await resp.json())["context"])
        assert outs[0] == outs[1]

    _run(server, go)


def test_options_seed_reproducible(server):
    """options.seed makes temperature sampling reproducible across
    requests (and across different engine key states)."""
    async def go(client):
        outs = []
        for _ in range(2):
            resp = await client.post("/api/generate", json={
                "prompt": "seeded run", "stream": False, "max_tokens": 8,
                "options": {"temperature": 1.0, "seed": 1234}})
            outs.append((await resp.json())["context"])
        assert outs[0] == outs[1]
        # Different seed should (overwhelmingly) differ.
        resp = await client.post("/api/generate", json={
            "prompt": "seeded run", "stream": False, "max_tokens": 8,
            "options": {"temperature": 1.0, "seed": 99}})
        other = (await resp.json())["context"]
        assert other != outs[0]

    _run(server, go)


def test_options_top_k_one_is_greedy(server):
    """top_k=1 at high temperature degenerates to the greedy tokens."""
    async def go(client):
        greedy = await (await client.post("/api/generate", json={
            "prompt": "topk probe", "stream": False, "max_tokens": 6,
            "temperature": 0.0})).json()
        topk1 = await (await client.post("/api/generate", json={
            "prompt": "topk probe", "stream": False, "max_tokens": 6,
            "options": {"temperature": 5.0, "top_k": 1}})).json()
        assert topk1["context"] == greedy["context"]

    _run(server, go)


def test_stop_sequences(server):
    """options.stop truncates the response before the stop string, ends
    the request with done_reason=stop, in both unary and streaming."""
    async def go(client):
        # Discover the greedy continuation, then stop on a substring of it.
        base = await (await client.post("/api/generate", json={
            "prompt": "stop probe", "stream": False, "max_tokens": 12,
            "temperature": 0.0})).json()
        text = base["response"]
        assert len(text) >= 3
        stop_s = text[2:4]

        unary = await (await client.post("/api/generate", json={
            "prompt": "stop probe", "stream": False, "max_tokens": 12,
            "temperature": 0.0, "options": {"stop": [stop_s]}})).json()
        assert unary["done_reason"] == "stop"
        assert unary["response"] == text[:text.find(stop_s)]
        assert stop_s not in unary["response"]

        resp = await client.post("/api/generate", json={
            "prompt": "stop probe", "stream": True, "max_tokens": 12,
            "temperature": 0.0, "options": {"stop": stop_s}})
        lines = [json.loads(l) for l in (await resp.read()).splitlines()]
        assert lines[-1]["done"] and lines[-1]["done_reason"] == "stop"
        streamed = "".join(l.get("response", "") for l in lines[:-1])
        assert streamed == text[:text.find(stop_s)]

    _run(server, go)


def test_stop_matcher_unit():
    from tpu_inference.server.tokenizer import StopMatcher

    m = StopMatcher(["END"])
    assert m.push("hello ") == ("hello ", False)
    assert m.push("E") == ("", False)           # possible prefix: hold
    assert m.push("X") == ("EX", False)         # disambiguated: release
    out, stopped = m.push("abcENDxyz")
    assert (out, stopped) == ("abc", True)

    m = StopMatcher(["END"])                     # split across pushes
    assert m.push("aE") == ("a", False)
    assert m.push("N") == ("", False)
    assert m.push("D tail") == ("", True)

    m = StopMatcher([])
    assert m.push("anything") == ("anything", False)


def test_bad_requests(server):
    async def go(client):
        r1 = await client.post("/api/generate", data=b"{not json")
        assert r1.status == 400
        r2 = await client.post("/api/generate", json={"model": "x"})
        assert r2.status == 400
        # Malformed sampling options -> structured 400, not a 500.
        r3 = await client.post("/api/generate", json={
            "prompt": "x", "options": {"stop": 5}})
        assert r3.status == 400
        r4 = await client.post("/api/generate", json={
            "prompt": "x", "options": {"top_k": "lots"}})
        assert r4.status == 400
        r5 = await client.post("/api/generate", json={
            "prompt": "x", "options": "fast"})
        assert r5.status == 400
        return r1, r2

    _run(server, go)


def test_seed_edge_values(server):
    """64-bit seeds are accepted (clamped into int32 on device) and
    seed=-1 means unseeded (requests differ across retries)."""
    async def go(client):
        big = {"prompt": "edge", "stream": False, "max_tokens": 6,
               "options": {"temperature": 1.0, "seed": 2**40 + 123}}
        a = await (await client.post("/api/generate", json=big)).json()
        b = await (await client.post("/api/generate", json=big)).json()
        assert a["done"] and a["context"] == b["context"]
        outs = set()
        for _ in range(4):
            r = await (await client.post("/api/generate", json={
                "prompt": "edge", "stream": False, "max_tokens": 6,
                "options": {"temperature": 5.0, "seed": -1}})).json()
            outs.add(tuple(r["context"]))
        assert len(outs) > 1

    _run(server, go)


def test_aux_routes(server):
    async def go(client):
        assert (await client.get("/healthz")).status == 200
        tags = await (await client.get("/api/tags")).json()
        assert tags["models"][0]["name"] == "tiny-llama"
        metrics = await (await client.get("/metrics?format=json")).json()
        assert "kv_pages_in_use" in metrics
        version = await (await client.get("/api/version")).json()
        assert "version" in version
        show = await (await client.post("/api/show",
                                        json={"model": "m"})).json()
        assert show["details"]["family"] == "llama"
        info = show["model_info"]
        assert info["llama.context_length"] > 0
        # The pass count beside the block count (1 = not a looped stack).
        assert info["llama.block_count"] == 2 and info["llama.loop_steps"] == 1
        assert info["general.parameter_count"] > 0
        # SWA composition rules surface here (full-attention model:
        # window 0, no eviction, prefix cache on).
        assert info["llama.attention.sliding_window"] == 0
        assert info["serving.swa_eviction"] is False
        assert info["serving.prefix_cache"] is True
        # Ollama GET /api/ps: the one loaded model, never unloading.
        # size/size_vram are ONE model copy (not x dp — ADVICE r5); the
        # replica count is a separate additive field, and details carry
        # Ollama-shaped values ("3.2M"/"8.0B" parameter_size, "F32"/
        # "Q8_0"-style quantization_level).
        ps = await (await client.get("/api/ps")).json()
        (entry,) = ps["models"]
        assert entry["name"] == "tiny-llama"
        assert entry["size"] > 0 and entry["size_vram"] == entry["size"]
        assert entry["replicas"] == 1
        det = entry["details"]
        assert det["parameter_size"].endswith(("B", "M", "K"))
        assert det["quantization_level"] in ("F32", "F16", "BF16",
                                             "Q8_0", "Q4_0")
        assert entry["expires_at"].startswith("0001-01-01")

    _run(server, go)


def test_concurrent_requests_interleave(server):
    """Multiple in-flight requests (continuous batching through HTTP)."""
    async def go(client):
        async def one(i):
            resp = await client.post("/api/generate", json={
                "prompt": f"request {i}", "stream": False, "max_tokens": 6})
            return await resp.json()

        bodies = await asyncio.gather(*[one(i) for i in range(6)])
        for b in bodies:
            assert b["done"] is True
            assert b["eval_count"] >= 1
        return bodies

    _run(server, go)


def test_debug_requests_and_profile(server, profile_dir):
    """Observability endpoints: request timelines + profiler control."""

    async def scenario(client):
        resp = await client.post("/api/generate", json={
            "model": "m", "prompt": "observe me", "temperature": 0,
            "max_tokens": 6, "stream": False})
        assert resp.status == 200

        resp = await client.get("/debug/requests")
        timelines = await resp.json()
        assert len(timelines) >= 1
        t = timelines[-1]
        assert t["output_tokens"] == 6
        assert t["finish_reason"] == "length"
        assert t["queue_wait_s"] >= 0 and t["decode_s"] >= 0
        assert t["tpot_s"] > 0

        resp = await client.get("/metrics?format=json")
        stats = await resp.json()
        assert stats["model_params"] > 0
        assert stats["approx_flops_per_token"] == 2 * stats["model_params"]

        import os
        # Client-supplied "dir" is ignored: traces land only in the
        # server-configured profile_dir (unauthenticated endpoint must
        # not take filesystem paths from the wire).
        resp = await client.post("/debug/profile",
                                 json={"action": "start", "dir": "/etc"})
        assert resp.status == 200
        assert (await resp.json())["dir"] == profile_dir
        resp = await client.post("/debug/profile", json={"action": "stop"})
        assert resp.status == 200
        assert any(os.scandir(profile_dir))     # trace artifacts written
        resp = await client.post("/debug/profile", json={"action": "bogus"})
        assert resp.status == 400
        # The timed form answers with the loop clock over its seconds.
        resp = await client.post("/debug/profile", json={"seconds": 0.2})
        assert resp.status == 200
        rec = await resp.json()
        assert {"status", "dir", "seconds", "replica", "loop"} <= set(rec)
        assert 0.2 <= rec["loop"]["loop_wall_s"] < 1.2
        assert "tpu_inf_loop_stage_put_seconds_total" in rec["loop"]

    _run(server, scenario)


def test_debug_disabled_by_default():
    """Without enable_debug the /debug routes are not registered."""
    cfg = FrameworkConfig(
        model=tiny_llama(vocab_size=512),
        engine=EngineConfig(page_size=8, num_pages=32, max_pages_per_seq=4,
                            max_batch_size=2, prefill_buckets=(16,)),
        server=ServerConfig(model_name="t", tokenizer="byte",
                            warmup=False))   # routes-only test: no compile
    srv = InferenceServer(cfg)

    async def scenario(client):
        assert (await client.get("/debug/requests")).status == 404
        assert (await client.post("/debug/profile",
                                  json={"action": "start"})).status == 404
        assert (await client.get("/healthz")).status == 200

    _run(srv, scenario)


def test_chat_endpoint(server):
    """Ollama /api/chat: message records, counters, streaming + unary."""

    async def scenario(client):
        msgs = [{"role": "system", "content": "be brief"},
                {"role": "user", "content": "hi"}]
        resp = await client.post("/api/chat", json={
            "model": "m", "messages": msgs, "stream": False,
            "options": {"num_predict": 6, "temperature": 0}})
        assert resp.status == 200
        rec = await resp.json()
        assert rec["done"] and rec["message"]["role"] == "assistant"
        assert "context" not in rec and "response" not in rec
        assert rec["eval_count"] == 6

        resp = await client.post("/api/chat", json={
            "model": "m", "messages": msgs, "stream": True,
            "options": {"num_predict": 6, "temperature": 0}})
        lines = [json.loads(l) for l in (await resp.read()).splitlines() if l]
        assert all("message" in l for l in lines)
        assert lines[-1]["done"] and lines[-1]["eval_count"] == 6

        # Empty messages = the Ollama chat-model preload probe: an
        # immediate load ack, not a 400 (clients use this to warm up).
        resp = await client.post("/api/chat", json={"model": "m",
                                                    "messages": []})
        assert resp.status == 200
        ping = await resp.json()
        assert ping["done"] and ping["done_reason"] == "load"
        # Malformed (non-list / bad entries) still 400s.
        resp = await client.post("/api/chat", json={"model": "m",
                                                    "messages": "nope"})
        assert resp.status == 400

    _run(server, scenario)


def test_chaos_injection():
    """chaos_failure_rate=1.0 rejects every request with 503."""
    from tpu_inference.config import (EngineConfig, FrameworkConfig,
                                      ServerConfig, tiny_llama)
    from tpu_inference.server.http import InferenceServer

    cfg = FrameworkConfig(
        model=tiny_llama(vocab_size=512),
        engine=EngineConfig(page_size=8, num_pages=32, max_pages_per_seq=4,
                            max_batch_size=2, prefill_buckets=(16,)),
        server=ServerConfig(model_name="t", tokenizer="byte",
                            chaos_failure_rate=1.0,
                            warmup=False))   # 503s pre-engine: no compile
    srv = InferenceServer(cfg)

    async def scenario(client):
        resp = await client.post("/api/generate", json={
            "model": "m", "prompt": "x", "max_tokens": 2})
        assert resp.status == 503

    _run(srv, scenario)


@pytest.mark.parametrize("ignore_eos", [False, True])
def test_ignore_eos_generates_to_max_tokens(ignore_eos):
    """--ignore-eos: a request whose sampled token is the tokenizer's EOS
    goes on to max_tokens (load tests on random weights); without it the
    request stops there."""
    cfg = FrameworkConfig(
        model=tiny_llama(vocab_size=512),
        engine=EngineConfig(page_size=8, num_pages=32, max_pages_per_seq=4,
                            max_batch_size=2, prefill_buckets=(16,)),
        server=ServerConfig(model_name="t", tokenizer="byte", warmup=False,
                            ignore_eos=ignore_eos))
    srv = InferenceServer(cfg)
    ask = {"model": "m", "prompt": "abc", "max_tokens": 6, "stream": False,
           "temperature": 0}

    async def scenario(client):
        first = await (await client.post("/api/generate", json=ask)).json()
        assert first["eval_count"] == 6
        # Greedy is deterministic: make the third token the EOS.
        srv.tokenizer.eos_token_id = first["context"][-4]
        again = await (await client.post("/api/generate", json=ask)).json()
        assert (again["eval_count"] == 6) if ignore_eos else (
            again["eval_count"] < 6)
        assert again["done_reason"] == ("length" if ignore_eos else "stop")

    _run(srv, scenario)


@pytest.mark.parametrize("quant,kv_quant", [
    ("none", "none"),
    # The quantized-replica combination re-proves what test_quant and
    # test_kv_quant cover per-component; slow-marked as a sweep.
    pytest.param("int8", "int8", marks=pytest.mark.slow)])
def test_dp_replica_serving(quant, kv_quant):
    """dp=2 builds two replica engines on disjoint submeshes; concurrent
    requests spread across them and all succeed (least-loaded routing).
    Parametrized over the quantization tiers: each replica carries its
    own (possibly int8) weights + KV pool, and /metrics reports the
    modes."""
    from tpu_inference.config import ParallelConfig
    from tpu_inference.server.http import build_engine_group

    cfg = FrameworkConfig(
        model=tiny_llama(vocab_size=512),
        engine=EngineConfig(page_size=8, num_pages=64, max_pages_per_seq=4,
                            max_batch_size=2, prefill_buckets=(16,),
                            quant=quant, kv_quant=kv_quant),
        parallel=ParallelConfig(dp=2, tp=2),
        server=ServerConfig(model_name="t", tokenizer="byte"))
    group = build_engine_group(cfg)
    assert len(group.engines) == 2
    d0 = {d for d in group.engines[0].mesh.devices.flat}
    d1 = {d for d in group.engines[1].mesh.devices.flat}
    assert d0.isdisjoint(d1)
    if quant == "int8":
        from tpu_inference.models.quant import QuantizedArray
        for eng in group.engines:
            assert isinstance(eng.params["blocks"]["wq"], QuantizedArray)
            assert eng.kv.quantized
    srv = InferenceServer(cfg, group=group)

    async def scenario(client):
        async def one(i):
            resp = await client.post("/api/generate", json={
                "prompt": f"replica probe {i}", "stream": False,
                "max_tokens": 5})
            return await resp.json()

        bodies = await asyncio.gather(*[one(i) for i in range(6)])
        assert all(b["done"] and b["eval_count"] >= 1 for b in bodies)
        stats = await (await client.get("/metrics?format=json")).json()
        assert stats["dp"] == 2
        assert stats["quant"] == quant
        assert stats["kv_quant"] == kv_quant
        # Both replicas did work under concurrent load.
        assert all(r["requests_finished"] >= 1 for r in stats["replicas"])
        # Fleet phase histograms merge across replicas (not replica 0's
        # copy masquerading): every request shows up in the e2e count.
        assert stats["phases"]["e2e_s"]["count"] == sum(
            r["phases"]["e2e_s"]["count"] for r in stats["replicas"])
        # Prometheus exposition separates replicas by label: the same
        # family carries one series per replica, plus fleet-level
        # supervision series without a replica label.
        meta, samples = _prom.parse(
            await (await client.get("/metrics")).text())
        steps = {l.get("replica"): v for n, l, v in samples
                 if n == "tpu_inf_steps_total"}
        assert set(steps) == {"0", "1"}
        assert any(n == "tpu_inf_replicas" and "replica" not in l
                   for n, l, _ in samples)

    _run(srv, scenario)



def test_metrics_prometheus_exposition(server):
    """GET /metrics (default format) is standards-compliant Prometheus
    text: correct content type, HELP/TYPE for every family, histogram
    buckets cumulative-monotone with le="+Inf" == _count, and the step-
    phase metric names the round-6 dashboards will scrape."""
    async def go(client):
        resp = await client.post("/api/generate", json={
            "prompt": "scrape me", "stream": False, "max_tokens": 6,
            "temperature": 0.0})
        assert resp.status == 200

        resp = await client.get("/metrics")
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith(
            "text/plain; version=0.0.4")
        meta, samples = _prom.parse(await resp.text())

        # Every sample belongs to a declared family with HELP and TYPE.
        for name, labels, value in samples:
            fam = _prom.family(name, meta)
            assert "type" in meta[fam], f"no TYPE for {name}"
            assert "help" in meta[fam], f"no HELP for {name}"
        names = {_prom.family(n, meta) for n, _, _ in samples}
        for expected in ("tpu_inf_decode_dispatch_seconds",
                         "tpu_inf_prefill_dispatch_seconds",
                         "tpu_inf_dispatch_bubble_seconds",
                         "tpu_inf_tokens_per_dispatch",
                         "tpu_inf_queue_wait_seconds",
                         "tpu_inf_e2e_seconds",
                         "tpu_inf_kv_pages_in_use",
                         "tpu_inf_kv_page_allocs_total",
                         "tpu_inf_tokens_generated_total",
                         "tpu_inf_requests_finished_total"):
            assert expected in names, f"{expected} missing from /metrics"

        # Histogram contract per labelset: buckets monotone in le, last
        # le=+Inf, +Inf bucket == _count, and _sum present.
        counts = {(n[:-len("_count")], tuple(sorted(l.items()))): v
                  for n, l, v in samples if n.endswith("_count")}
        sums = {(n[:-len("_sum")], tuple(sorted(l.items()))): v
                for n, l, v in samples if n.endswith("_sum")}
        checked = 0
        for fam, info in meta.items():
            if info.get("type") != "histogram":
                continue
            for key, buckets in _prom.histogram_series(samples,
                                                       fam).items():
                vals = [v for _, v in buckets]
                assert vals == sorted(vals), f"{fam} not cumulative"
                assert buckets[-1][0] == float("inf")
                assert counts[(fam, key)] == vals[-1]
                assert sums[(fam, key)] >= 0
                checked += 1
        assert checked >= 5

        # The decode phase actually ran: non-zero observations.
        series = _prom.histogram_series(
            samples, "tpu_inf_decode_dispatch_seconds")
        assert any(b[-1][1] > 0 for b in series.values())
        # Per-reason finish counter carries a label.
        assert any(n == "tpu_inf_requests_finished_total"
                   and l.get("reason") == "length"
                   for n, l, _ in samples)
        # JSON mode is preserved and still carries the legacy keys.
        js = await (await client.get("/metrics?format=json")).json()
        assert "kv_pages_in_use" in js and "phases" in js

    _run(server, go)


def test_request_id_propagation_and_span_accounting(server):
    """X-Request-Id flows ingress -> engine -> response header, terminal
    record, and the /debug/requests span; the span's queue + prefill +
    decode phases sum to E2E (same clock stamps), and the new dispatch-
    wall/bubble phases are populated."""
    async def go(client):
        resp = await client.post("/api/generate", json={
            "prompt": "trace this request", "stream": False,
            "max_tokens": 6, "temperature": 0.0},
            headers={"X-Request-Id": "trace-me-42"})
        assert resp.status == 200
        assert resp.headers["X-Request-Id"] == "trace-me-42"
        rec = await resp.json()
        assert rec["request_id"] == "trace-me-42"

        timelines = await (await client.get("/debug/requests")).json()
        spans = [t for t in timelines if t.get("trace_id") == "trace-me-42"]
        assert spans, "span for the traced request must be recorded"
        t = spans[-1]
        assert t["attempt"] == 0
        # Phase sum-check: identical timestamps on both sides, so the
        # identity holds to rounding noise.
        phase_sum = t["queue_wait_s"] + t["prefill_s"] + t["decode_s"]
        assert abs(phase_sum - t["e2e_s"]) < 1e-3
        assert t["ttft_s"] >= t["queue_wait_s"]

        # Streaming + no client id: the server mints one and echoes it.
        resp = await client.post("/api/generate", json={
            "prompt": "minted id", "stream": True, "max_tokens": 4,
            "temperature": 0.0})
        assert resp.status == 200
        minted = resp.headers.get("X-Request-Id")
        assert minted
        lines = [json.loads(l) for l in (await resp.read()).splitlines()]
        assert lines[-1]["request_id"] == minted

    _run(server, go)


def test_context_continuation_hits_prefix_cache(server):
    """A continuation request (prior response's context + new prompt) is
    a strict prefix extension, so its prefill must reuse the cached KV
    pages of the first request (tokens_prefix_cached grows)."""
    async def go(client):
        resp = await client.post("/api/generate", json={
            "prompt": "cache me please", "stream": False, "max_tokens": 10,
            "temperature": 0.0})
        assert resp.status == 200
        first = await resp.json()
        before = (await (await client.get("/metrics?format=json")).json()
                  )["tokens_prefix_cached"]
        cont = await (await client.post("/api/generate", json={
            "prompt": " keep going", "stream": False, "max_tokens": 4,
            "temperature": 0.0, "context": first["context"]})).json()
        assert cont["done"]
        after = (await (await client.get("/metrics?format=json")).json()
                 )["tokens_prefix_cached"]
        assert after > before

    _run(server, go)


def test_sampling_warnings_surface(server):
    """Options accepted but not honored exactly are reported in a
    terminal-record ``warnings`` list (ADVICE r3): repeat_last_n beyond
    the static penalty window is clamped — the client learns instead of
    silently getting different sampling. Honored options add no field."""
    async def go(client):
        rec = await (await client.post("/api/generate", json={
            "prompt": "hi", "stream": False, "max_tokens": 4,
            "temperature": 0.0,
            "options": {"repeat_penalty": 1.1, "repeat_last_n": 512}})).json()
        assert rec["done"]
        assert any("repeat_last_n" in w and "clamped" in w
                   for w in rec["warnings"])

        clean = await (await client.post("/api/generate", json={
            "prompt": "hi", "stream": False, "max_tokens": 4,
            "temperature": 0.0,
            "options": {"repeat_penalty": 1.1, "repeat_last_n": 32}})).json()
        assert "warnings" not in clean

    _run(server, go)


def test_context_ids_validate_against_model_vocab(server):
    """An id the model cannot embed must 400 — the XLA gather would
    clamp it silently into a wrong embedding (ADVICE r3). tiny-llama
    model vocab is 512; the byte tokenizer's is smaller."""
    async def go(client):
        resp = await client.post("/api/generate", json={
            "prompt": "hi", "stream": False, "max_tokens": 2,
            "temperature": 0.0, "context": [0, 511]})
        assert resp.status == 200
        resp = await client.post("/api/generate", json={
            "prompt": "hi", "stream": False, "max_tokens": 2,
            "temperature": 0.0, "context": [512]})
        assert resp.status == 400
        assert "out of range" in (await resp.json())["error"]

    _run(server, go)


def test_boot_rejects_tokenizer_model_vocab_mismatch():
    """A tokenizer that can emit ids the model cannot embed must fail at
    boot (one loud error), not clamp embeddings one request at a time:
    the byte tokenizer needs 258 ids, so a 200-entry model vocab is a
    broken deployment."""
    cfg = FrameworkConfig(
        model=tiny_llama(vocab_size=200),
        engine=EngineConfig(page_size=8, num_pages=32, max_pages_per_seq=4,
                            max_batch_size=2, prefill_buckets=(16,)),
        server=ServerConfig(tokenizer="byte"))
    with pytest.raises(ValueError, match="tokenizer vocab"):
        InferenceServer(cfg)


def test_repeat_penalty_under_speculation_is_applied_without_a_warning(
        monkeypatch):
    """A request asking for repeat_penalty on a speculating server gets
    the penalised stream a plain server gives (the verify round applies
    the penalty) and no warning. The proposer always proposes, so verify
    rounds run whatever the tiny model says."""
    import numpy as np

    from tpu_inference.engine import engine as engine_mod
    from tpu_inference.engine.engine import InferenceEngine
    from tpu_inference.models import build_model

    model = tiny_llama(vocab_size=512)
    params, _ = build_model(model, seed=0)
    monkeypatch.setattr(
        engine_mod, "ngram_propose",
        lambda hist, gamma, max_n, min_n=1: np.asarray(hist[-gamma:],
                                                       np.int32))

    def ask(gamma, penalty):
        ecfg = EngineConfig(page_size=8, num_pages=64, max_pages_per_seq=8,
                            max_batch_size=2, prefill_buckets=(16, 32),
                            num_speculative_tokens=gamma)
        eng = InferenceEngine(model, ecfg, params=params)
        srv = InferenceServer(FrameworkConfig(
            model=model, engine=ecfg,
            server=ServerConfig(tokenizer="byte")), engine=eng)

        async def go(client):
            return await (await client.post("/api/generate", json={
                "prompt": "abcabcabcabc", "stream": False, "max_tokens": 24,
                "temperature": 0.0,
                "options": {"repeat_penalty": penalty}})).json()

        return _run(srv, go), eng

    spec, eng = ask(3, 1.3)
    plain, _ = ask(0, 1.3)
    unpenalised, _ = ask(0, 1.0)
    assert spec["done"] and "warnings" not in spec
    assert spec["response"] == plain["response"] != unpenalised["response"]
    assert eng.spec_rounds_total > 0
