#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof the system still starts on the chip.

Run from the repo root on a machine with a TPU:

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # the cross-chip paths only (4-chip host)
    python chip_smoke.py --combine-costs   # a microbenchmark, no phase:
                                    # kernels/moe_experts.py's constant

It drives the main path once through the entry points a user calls — the
server CLI over HTTP — serving Mistral-7B at its published widths (random
weights from --seed, int8, the Pallas attention kernels, HBM-derived batch
and pool, default ladder and warm-up), then checks the result by the repo's
own means: logits through prefill-then-paged-decode against a plain float32
dense forward. One chip, in order:

  serve    the CLI; requests over HTTP (one prompt per prefill bucket, a
           greedy repeat, a burst past the ladder's base rung, /api/chat,
           one prompt past the 4096 window); /healthz and /metrics must
           name the tpu backend, the chip's device_kind and pallas;
           SIGTERM must exit 0
  parity   same widths, depth cut to PARITY_LAYERS so the float32
           reference fits beside the engine: engine logits vs reference
  fleet    the CLI again with --fleet subprocess --dp 1; passes only if
           the router process stays off the chip

With --chips 4, only what exists across chips, and what it is compared
with: tp4 (the CLI with --tp 4 in bf16 at full depth, which one chip
cannot hold; then tp=4 vs tp=1 logits and shard placement) and dp4 (the
subprocess fleet with one worker process per chip).

This process never imports jax: a chip belongs to one process at a time,
so every phase is a child that owns the chip for its lifetime and has
exited before the next starts. Any phase that fails, times out or reports
a backend other than the TPU ends the run at once with a non-zero exit and
no result line. Earlier lines are per-phase JSON (wall seconds, warm-up
seconds and graphs, the sizes 'auto' chose, peak device bytes); the last
line of stdout is the device JSON the driver reads.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

# What the smoke serves and how hard it looks. A dict the phases take as
# an argument (and ship to their children), so tests/test_chip_smoke.py
# can drive the same phases at a tiny preset on the CPU without this
# script growing options.
SETTINGS = {
    "model": "mistral-7b",
    "platform": "tpu",
    "quant": "int8",
    "attn_backend": "pallas",
    "sizing": ["--max-batch-size", "auto", "--num-pages", "auto"],
    # 320 pages x 16 = 5120 tokens of context: past Mistral's 4096
    # window, so the windowed kernels' offset path and behind-window
    # page eviction both run (the default 64 = 1024 tokens never binds).
    "max_pages_per_seq": 320,
    "bucket_prompts": [40, 100, 200, 400, 900],   # one per prefill bucket
    "long_prompt": 4300,
    "max_tokens": 16,
    # More requests at once than the ladder's base rung of 8, each long
    # enough that they are all decoding together.
    "burst": 12,
    "burst_tokens": 64,
    "boot_timeout_s": 900,
    "drain_timeout_s": 30,
    # parity: depth cut so the f32 reference fits beside the engine on
    # one 16 GB chip (4 layers: ~1.4 GB int8 engine + f32 temporaries).
    "parity_layers": 4,
    "parity_prompts": [300, 1500],   # 1500 > the largest (1024) bucket
    "parity_decode_steps": 8,
    # Engine logits vs the float32 reference, as shares of a position's
    # reference logit spread (std over the vocabulary). Both sides
    # multiply the SAME int8 codes; they differ in activation dtype
    # (bf16 with f32 accumulation vs f32 at "highest" matmul precision)
    # and in attention (paged Pallas kernels vs dense). bf16 rounds the
    # residual stream and every projection to 8 mantissa bits; over 4
    # layers that is 1.7-1.9% rms of the spread — measured with NO
    # Pallas kernel in the path (dense backend on the CPU, same widths:
    # rms 0.0175 / 0.0187, max 0.070 / 0.087 over 32000 logits) and the
    # same on the v5e with the kernels (max 0.075 at the same position).
    # So these bounds sit ~1.6x above bf16's own noise; what they catch
    # is a wrong page, mask, scale or weight (errors of order 1). The
    # kernels' arithmetic is held much tighter, alone: kernel_tol.
    "parity_tol": {"rms": 0.03, "max": 0.15},
    # The two Pallas kernels alone, at the model's head shapes, window
    # and page size, on random bf16 K/V: output vs dense float32
    # attention over the same values, as a share of each query's output
    # spread. Operands are identical on both sides and the result is
    # taken in f32, so ALL that differs is the kernel's own arithmetic:
    # 1e-5 in interpret mode on the CPU and 2e-5 on the v5e (Mosaic
    # runs the kernels' f32 dots at full precision). 50x above that; a
    # kernel that kept its scores or softmax sums in bf16 (2^-9 = 2e-3
    # per term) lands above this.
    "kernel_tol": 0.001,
    # The latent-attention kernels (kernels/mla_attention.py) at Kimi-K2's
    # shapes, same measure. They feed the MXU in the pool's dtype: the
    # softmax weights are rounded to bf16 (2^-9 a term) before the
    # weighted sum, as flash kernels do: 0.0045 (decode) / 0.0080
    # (prefill) on the v5e (PR 26). 2.5x above that; a wrong layer, page
    # or mask is off by the output's whole spread (~1).
    "latent_kernel_tol": 0.02,
    # The delta-rule kernels (kernels/delta_rule.py) at Ling-3.0-flash's
    # head sizes against the recurrence a token at a time, as shares of
    # the reference's spread. Everything is float32 with matrix products
    # at the highest precision: ~1e-5 in interpret mode on the CPU over a
    # 1024-token chunk with half the channels at the gate's bound. A
    # chunked form that loses a block, a sub-block's decay or the state
    # at a boundary is off by the output's whole spread.
    "delta_rule_tol": 0.001,
    # The one-token convolution (kernels/delta_rule.kda_tail_step)
    # against the XLA form on the same bf16 tails and taps: four
    # products summed in float32 in one order on both sides and a SiLU;
    # x as a share of its spread. 0 in interpret mode on the CPU; the
    # chip's two compilers may round exp and the division differently,
    # a few ulps of float32 (1e-7 a value). A tap lost or taken from the
    # wrong row is off by the spread itself.
    "kda_tail_tol": 1e-5,
    # The routed-expert layer (models/deepseek_v3.py moe_ffn: router,
    # grouping, kernels/moe_experts.py) at Kimi-K2's widths against a
    # plain float32 loop over the held experts on the SAME routing: the
    # largest error of a token's output row as a share of the rows' rms.
    # The kernels round silu(g) * u to bf16 between the two matmuls
    # (2^-9 a term): 0.015 at 32 rows, 0.018-0.021 at 1024 on the v5e
    # (PR 26, two seeds). 2.5x above that. A layer that loses, swaps or
    # mis-addresses an expert is off by the routed part's whole spread
    # (planted there: 1.6 / 2.2 / 2.2; each must read over 10x this).
    "routed_expert_tol": 0.05,
    # One hyper-connection alone (models/hyper_connections.py: the
    # coefficient head, the Sinkhorn projection, both mixes) at Xing4.0's
    # widths (4 streams of 3584) on random bfloat16 streams, against the
    # same equations in float32. Two tolerances, because the cell's
    # ``correct`` cannot see the precision of the coefficients or of the
    # mixes at three layers (PERF.md section 2) and this can:
    # ``_f32_tol`` for what the program states as float32, the LARGEST
    # error of a coefficient (H_pre, H_post, H_res: numbers of 0 to 2)
    # and of a pre-mix value as a share of the pre-mix's rms; the v5e
    # reads the coefficients at 5.4e-7 / 1.07e-6 and the pre-mix at
    # 8.4e-7 / 1.5e-6 (64 / 1024 rows; my chip runs, PR 47, calls A and
    # E: its compiler hands the float32 sum to the consumer and drops the
    # bfloat16 store in between), and coefficients rounded to bfloat16
    # (2^-9 of a number up to 2), planted, read 0.0039 in both and must
    # read over 10x this;
    # ``_tol`` for the post-mix, which is STORED bfloat16: 2^-9 of the
    # largest of 0.9 M (64 rows) or 14.7 M (1024 rows) values, which lies
    # 5-6 rms out: 0.0239 / 0.0249 on the v5e (the same run). 2x above
    # that; a projection stopped after one iteration read 1.20 there and
    # must read over 10x this.
    "hyper_connection_f32_tol": 1e-4,
    "hyper_connection_tol": 0.05,
    # tp4_parity: bf16 at a depth one chip also holds (the tp=1 side;
    # the same 4 layers the tolerance above was measured at), prompts
    # inside one prefill bucket (the one-chip parity does the chunking).
    "tp_layers": 4,
    "tp_prompts": [300, 500],
    # Warm-up on, as a user gets it. The four-chip phases turn it off:
    # what they are for exists across chips, warm-up does not, and each
    # of their seconds is charged four times.
    "warmup": True,
}

OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")


class SmokeFailure(Exception):
    """A check failed; the run ends non-zero with no result line."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


# --------------------------------------------------------------- HTTP side


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_text(url: str, timeout: float = 60.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def http_json(url: str, timeout: float = 60.0):
    return json.loads(http_text(url, timeout))


def prompt_of(n_tokens: int, salt: int) -> str:
    """ASCII text that the byte tokenizer turns into exactly n_tokens
    ids (BOS + one per byte); ``salt`` makes prompts differ."""
    words = f"request {salt} asks the server about paged attention on a tpu; "
    return (words * (n_tokens // len(words) + 1))[:n_tokens - 1]


def stream_generate(base: str, prompt: str, max_tokens: int,
                    chat: bool = False, timeout: float = 600.0) -> dict:
    """One streaming request; checks the NDJSON framing and the terminal
    record's counters, returns that record."""
    body = {"model": "smoke", "temperature": 0, "max_tokens": max_tokens,
            "stream": True}
    if chat:
        body["messages"] = [{"role": "user", "content": prompt}]
    else:
        body["prompt"] = prompt
    req = urllib.request.Request(
        base + ("/api/chat" if chat else "/api/generate"),
        data=json.dumps(body).encode())
    lines = []
    with urllib.request.urlopen(req, timeout=timeout) as r:
        check(r.status == 200, f"HTTP {r.status}")
        ctype = r.headers.get("Content-Type", "")
        check(ctype.startswith("application/x-ndjson"),
              f"Content-Type {ctype!r} is not NDJSON")
        for raw in r:
            check(raw.endswith(b"\n"), "NDJSON line without a newline")
            lines.append(json.loads(raw))
    check(len(lines) >= 2, f"{len(lines)} NDJSON lines: no token streamed")
    *tokens, final = lines
    key = "message" if chat else "response"
    for t in tokens:
        check(t.get("done") is False and key in t,
              f"malformed token line {t}")
    check(final.get("done") is True, f"last line is not done: {final}")
    for field in ("total_duration", "prompt_eval_count",
                  "prompt_eval_duration", "eval_count", "eval_duration",
                  "done_reason"):
        check(field in final, f"done record lacks {field}")
    n = final["eval_count"]
    check(1 <= n <= max_tokens, f"eval_count {n} outside 1..{max_tokens}")
    check(final["done_reason"] != "length" or n == max_tokens,
          f"done_reason=length with eval_count {n} != {max_tokens}")
    # One line per token, plus at most one flushing a split UTF-8 tail.
    check(n <= len(tokens) <= n + 1,
          f"{len(tokens)} token lines for eval_count {n}")
    if not chat:
        check(len(final["context"]) == final["prompt_eval_count"] + n,
              "context length != prompt_eval_count + eval_count")
    return final


def metric_value(metrics: str, name: str) -> float:
    """Sum of a family's samples in a Prometheus text page."""
    total, seen = 0.0, False
    for line in metrics.splitlines():
        if line.startswith(name) and line[len(name)] in " {":
            total += float(line.rsplit(" ", 1)[1])
            seen = True
    check(seen, f"/metrics has no {name}")
    return total


class Server:
    """The real CLI as a child process; the child owns the chip."""

    def __init__(self, cfg: dict, name: str, extra: list):
        self.cfg, self.name = cfg, name
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        os.makedirs(OUT_DIR, exist_ok=True)
        self.log_path = os.path.join(OUT_DIR, f"{name}.log")
        cmd = [sys.executable, "-m", "tpu_inference.server",
               "--model", cfg["model"], "--platform", cfg["platform"],
               "--attn-backend", cfg["attn_backend"],
               "--max-pages-per-seq", str(cfg["max_pages_per_seq"]),
               "--port", str(self.port), *cfg["sizing"], *extra,
               *([] if cfg["warmup"] else ["--no-warmup"])]
        self.cmd = " ".join(cmd[1:])
        self._log = open(self.log_path, "wb")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(cmd, cwd=ROOT, stdout=self._log,
                                     stderr=subprocess.STDOUT)

    def log_tail(self, n: int = 2000) -> str:
        with open(self.log_path, "rb") as f:
            return f.read()[-n:].decode(errors="replace")

    def wait_ready(self) -> float:
        """Poll /healthz until 200; fails at once if the child exits
        (no TPU: its first line of business is --platform)."""
        deadline = self.t0 + self.cfg["boot_timeout_s"]
        while time.monotonic() < deadline:
            rc = self.proc.poll()
            check(rc is None, f"{self.name}: server exited rc={rc} before "
                              f"serving:\n{self.log_tail()}")
            try:
                http_json(self.base + "/healthz", timeout=5)
                return time.monotonic() - self.t0
            except (urllib.error.URLError, ConnectionError, TimeoutError):
                time.sleep(1.0)
        raise SmokeFailure(
            f"{self.name}: not serving after "
            f"{self.cfg['boot_timeout_s']}s:\n{self.log_tail()}")

    def device_facts(self, replicas: int = 1) -> list:
        """Per-replica device facts from /healthz, checked against what
        was asked for; /metrics must say the same."""
        hz = http_json(self.base + "/healthz")
        check(hz["status"] == "ok", f"/healthz status {hz['status']}")
        devs = [r["device"] for r in hz["replicas"]]
        check(len(devs) == replicas, f"{len(devs)} replicas, not {replicas}")
        for d in devs:
            check(d["platform"] == self.cfg["platform"],
                  f"replica runs on {d['platform']!r}, "
                  f"not {self.cfg['platform']!r}")
            check(d["attn_backend"] == self.cfg["attn_backend"],
                  f"attention backend {d['attn_backend']!r}")
            check(d["kind"] == devs[0]["kind"] and d["kind"],
                  "replicas on different device kinds")
        info = [l for l in http_text(self.base + "/metrics").splitlines()
                if l.startswith("tpu_inf_build_info{")]
        check(bool(info), "/metrics has no tpu_inf_build_info")
        for l in info:
            for label in (f'backend="{self.cfg["platform"]}"',
                          f'device_kind="{devs[0]["kind"]}"',
                          f'attn_backend="{self.cfg["attn_backend"]}"'):
                check(label in l, f"/metrics build_info lacks {label}: {l}")
        return devs

    def stop(self) -> float:
        """SIGTERM; the child must exit 0 inside its drain budget."""
        t = time.monotonic()
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(self.cfg["drain_timeout_s"])
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{self.name}: still running "
                               f"{self.cfg['drain_timeout_s']}s after SIGTERM")
        check(rc == 0, f"{self.name}: exit code {rc} after SIGTERM:\n"
                       f"{self.log_tail()}")
        return time.monotonic() - t

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()


def run_child(cfg: dict, name: str, timeout: float = 900.0) -> dict:
    """One jax-owning child of this file (``_child <name> <settings>``);
    its last stdout line is its JSON result."""
    os.makedirs(OUT_DIR, exist_ok=True)
    t0 = time.monotonic()
    with open(os.path.join(OUT_DIR, f"{name}.log"), "wb") as log:
        try:
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "_child", name,
                 json.dumps(cfg)],
                cwd=ROOT, stdout=subprocess.PIPE, stderr=log,
                timeout=timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{name}: no result after {timeout}s")
    out = p.stdout.decode(errors="replace")
    check(p.returncode == 0, f"{name}: child exit code {p.returncode}:\n"
                             f"{out[-3000:]}")
    rec = json.loads(out.strip().splitlines()[-1])
    rec["wall_s"] = round(time.monotonic() - t0, 1)
    return rec


# ------------------------------------------------------------ the phases


def phase_record(name: str, srv: Server, boot_s: float, devs: list,
                 **more) -> dict:
    d = devs[0]
    return {"phase": name, "ok": True, "cmd": srv.cmd,
            "wall_s": round(time.monotonic() - srv.t0, 1),
            "boot_s": round(boot_s, 1), "warmup_s": d["warmup_s"],
            "warmup_graphs": d["warmup_graphs"],
            "max_batch_size": d["max_batch_size"],
            "num_pages": d["num_pages"], "ladder": d["ladder"],
            "peak_bytes_in_use": d["peak_bytes_in_use"],
            "device": {"platform": d["platform"], "kind": d["kind"]},
            **more}


def phase_serve(cfg: dict) -> dict:
    srv = Server(cfg, "serve", ["--quant", cfg["quant"]])
    try:
        boot_s = srv.wait_ready()
        srv.device_facts()
        n = cfg["max_tokens"]
        # One prompt per prefill bucket, alone: the 1-step latency graph.
        first = None
        for i, length in enumerate(cfg["bucket_prompts"]):
            rec = stream_generate(srv.base, prompt_of(length, i), n)
            check(rec["prompt_eval_count"] == length,
                  f"prompt of {length} tokens counted "
                  f"{rec['prompt_eval_count']}")
            first = first or rec
        # Greedy repeat of the first prompt under the same conditions.
        again = stream_generate(srv.base,
                                prompt_of(cfg["bucket_prompts"][0], 0), n)
        check(again["context"] == first["context"],
              "greedy repeat of one prompt produced different tokens")
        # A burst wider than the base rung: batched prefill + fused-K
        # decode, and the ladder must climb.
        lens = cfg["bucket_prompts"]
        with concurrent.futures.ThreadPoolExecutor(cfg["burst"]) as pool:
            futs = [pool.submit(stream_generate, srv.base,
                                prompt_of(lens[i % len(lens)], 100 + i),
                                cfg["burst_tokens"])
                    for i in range(cfg["burst"])]
            burst = [f.result() for f in futs]
        switches = metric_value(http_text(srv.base + "/metrics"),
                                "tpu_inf_rung_switches_total")
        check(switches >= 1, "a burst past the base rung never moved the "
                             "decode ladder")
        chat = stream_generate(srv.base, "why page the kv cache?", n,
                               chat=True)
        long_tokens = None
        if cfg["long_prompt"]:
            # Past the sliding window: windowed kernels with a non-zero
            # window start, chunked prefill over five chunks, eviction.
            rec = stream_generate(srv.base,
                                  prompt_of(cfg["long_prompt"], 7), n)
            check(rec["prompt_eval_count"] == cfg["long_prompt"],
                  f"long prompt counted {rec['prompt_eval_count']}")
            long_tokens = rec["prompt_eval_count"] + rec["eval_count"]
        devs = srv.device_facts()       # after load: peak memory is real
        drain_s = srv.stop()
        return phase_record(
            "serve", srv, boot_s, devs,
            requests=len(cfg["bucket_prompts"]) + 2 + len(burst)
            + (1 if cfg["long_prompt"] else 0),
            tokens_out=(sum(r["eval_count"] for r in burst)
                        + chat["eval_count"]),
            rung_switches=switches, long_request_tokens=long_tokens,
            sigterm_exit_s=round(drain_s, 1))
    finally:
        srv.kill()


def phase_fleet(cfg: dict, dp: int = 1) -> dict:
    """--fleet subprocess: a router plus one worker process per chip. The
    workers can only reach their chips if the router never initialised a
    JAX backend, so serving at all is the proof."""
    name = "fleet" if dp == 1 else f"dp{dp}"
    srv = Server(cfg, name, ["--quant", cfg["quant"], "--fleet",
                             "subprocess", "--dp", str(dp)])
    try:
        boot_s = srv.wait_ready()
        srv.device_facts(replicas=dp)
        n = cfg["max_tokens"]
        n_req = 2 if dp == 1 else 2 * dp
        with concurrent.futures.ThreadPoolExecutor(n_req) as pool:
            futs = [pool.submit(stream_generate, srv.base,
                                prompt_of(cfg["bucket_prompts"][1], 200 + i),
                                n) for i in range(n_req)]
            recs = [f.result() for f in futs]
        devs = srv.device_facts(replicas=dp)
        hz = http_json(srv.base + "/healthz")
        check(hz.get("fleet") == "subprocess", "/healthz is not the fleet's")
        pids = {r["pid"] for r in hz["replicas"]}
        check(len(pids) == dp and os.getpid() not in pids
              and srv.proc.pid not in pids,
              f"workers are not {dp} processes of their own: {pids}")
        # One chip group per worker: each was spawned with different
        # chips visible, and since a chip belongs to one process at a
        # time, dp live workers that all served sit on dp different ones.
        chips = [d["visible_chips"] for d in devs]
        check(len(set(chips)) == dp and None not in chips,
              f"workers were not given chips of their own: {chips}")
        served = [r["routing"]["hits"] + r["routing"]["cold"]
                  for r in hz["replicas"]]
        check(all(n >= 1 for n in served),
              f"a worker served nothing: {served}")
        drain_s = srv.stop()
        return phase_record(name, srv, boot_s, devs, requests=len(recs),
                            worker_pids=sorted(pids), served=served,
                            worker_chips=chips,
                            sigterm_exit_s=round(drain_s, 1))
    finally:
        srv.kill()


def phase_tp4(cfg: dict) -> dict:
    """--tp 4 in bf16 at full depth: 14.5 GB of weights, which one 16 GB
    chip cannot hold beside a KV pool, sharded 3.6 GB a chip."""
    srv = Server(cfg, "tp4", ["--tp", "4"])
    try:
        boot_s = srv.wait_ready()
        devs = srv.device_facts()
        check(len(devs[0]["ids"]) == 4,
              f"KV pool sits on devices {devs[0]['ids']}, not on four")
        n = cfg["max_tokens"]
        length = cfg["bucket_prompts"][1]
        first = stream_generate(srv.base, prompt_of(length, 0), n)
        again = stream_generate(srv.base, prompt_of(length, 0), n)
        check(again["context"] == first["context"],
              "greedy repeat of one prompt produced different tokens")
        with concurrent.futures.ThreadPoolExecutor(4) as pool:
            futs = [pool.submit(stream_generate, srv.base,
                                prompt_of(length, 300 + i), n)
                    for i in range(4)]
            recs = [f.result() for f in futs]
        devs = srv.device_facts()
        drain_s = srv.stop()
        return phase_record("tp4", srv, boot_s, devs, requests=2 + len(recs),
                            pool_devices=devs[0]["ids"],
                            sigterm_exit_s=round(drain_s, 1))
    finally:
        srv.kill()


def run(chips: int, seed: int, cfg: dict) -> list:
    """The phases for ``chips`` in order, each printed as it passes."""
    cfg = dict(cfg, seed=seed)
    if chips == 1:
        phases = [lambda: phase_serve(cfg),
                  lambda: dict(run_child(cfg, "parity"), phase="parity"),
                  lambda: phase_fleet(cfg)]
    else:
        cfg = dict(cfg, warmup=False, parity_prompts=cfg["tp_prompts"])
        phases = [lambda: phase_tp4(cfg),
                  lambda: dict(run_child(cfg, "tp4_parity"),
                               phase="tp4_parity"),
                  lambda: phase_fleet(cfg, dp=4)]
    done = []
    for phase in phases:
        rec = phase()
        emit(rec)
        done.append(rec)
    return done


def result_device(phases: list) -> dict:
    """The device of the last line, as JAX reported it inside the phases:
    every phase on the same platform and kind; the count from the child
    that listed jax.devices()."""
    seen = {(p["device"]["platform"], p["device"]["kind"]) for p in phases}
    check(len(seen) == 1, f"phases ran on different devices: {seen}")
    platform, kind = seen.pop()
    count = next(p["device"]["count"] for p in phases
                 if "count" in p["device"])
    return {"platform": platform, "kind": kind, "count": count}


# --------------------------------------------- children (these import jax)


def _child_setup(cfg: dict):
    """Common start of a jax-owning child: platform, compile cache, and
    the refusal to run anywhere but where it was sent."""
    from tpu_inference.runtime import (enable_compile_cache,
                                       require_backend, select_platform)

    select_platform(cfg["platform"], cpu_devices=4)
    enable_compile_cache()
    require_backend(cfg["platform"])
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _build_engine(cfg: dict, layers: int, quant: str, mesh=None,
                  params=None):
    import dataclasses

    from tpu_inference.config import PRESETS, EngineConfig
    from tpu_inference.engine.engine import InferenceEngine

    mcfg = dataclasses.replace(PRESETS[cfg["model"]](), n_layers=layers)
    ecfg = EngineConfig(quant=quant, attn_backend=cfg["attn_backend"],
                        max_pages_per_seq=128, num_pages=512,
                        max_batch_size=8, enable_prefix_cache=False)
    return InferenceEngine(mcfg, ecfg, params=params, seed=cfg["seed"],
                           mesh=mesh)


def _engine_logits(eng, prompts: list, decode_steps: int):
    """Drive the engine's real path — (chunked) prefill, then fused-K
    paged decode — and read logits back off the pool it wrote.

    The serving graphs return sampled tokens only, so logits come from a
    probe built of the engine's own parts (its paged attention, model
    forward and unembed): one query at position p over the pool's first
    p+1 tokens. At p = prompt_len-1 every page it reads was written by
    the prefill graphs; at p = the last position, by prefill AND the
    decode graph's scatter writes. Returns, per prompt, the generated
    tokens and {position: logits [V] f32}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_inference.engine.engine import Sequence

    # A budget the run never reaches: a finished sequence would hand
    # its pages back before the probe reads them.
    seqs = [Sequence(request_id=i, prompt_tokens=list(p),
                     max_new_tokens=4 * decode_steps)
            for i, p in enumerate(prompts)]
    for s in seqs:
        eng.prefill(s)
    while any(len(s.generated) < decode_steps + 1 for s in seqs):
        eng.decode_steps()

    def probe(params, kv, token, pos, block_table):
        attn = eng._paged_attn(eng.model_cfg, block_table, pos[:, None],
                               jnp.ones((1, 1), bool), q_offset=pos,
                               kv_len=pos + 1)
        hidden, kv = eng.mod.forward_hidden(
            params, eng.model_cfg, token[:, None], pos[:, None], kv, attn)
        return kv, eng.mod.unembed(params, eng.model_cfg, hidden[:, 0])

    probe = jax.jit(probe, donate_argnums=(1,))
    out = []
    for s in seqs:
        stream = s.prompt_tokens + s.generated
        table = jnp.asarray(eng._block_table_array(s.pages))[None]
        logits = {}
        # The last token the checked stream has in KV is the one
        # before the last sampled token.
        for p in (len(s.prompt_tokens) - 1,
                  len(s.prompt_tokens) + decode_steps - 1):
            eng.kv, lg = probe(eng.params, eng.kv,
                               jnp.asarray([stream[p]], jnp.int32),
                               jnp.asarray([p], jnp.int32), table)
            logits[p] = np.asarray(lg[0], np.float32)
        out.append((s.generated[:decode_steps + 1], logits))
    return out


def _reference_logits(eng, streams: list):
    """The plain reference: one dense float32 forward over each whole
    token stream (models.common.make_dense_attn, no cache, no kernels,
    matmuls at "highest" precision), on the engine's own weights — the
    same int8 codes, or the same bf16 values, widened to f32."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_inference.models.common import make_dense_attn

    cfg32 = dataclasses.replace(eng.model_cfg, dtype=jnp.float32)
    width = max(len(s) for s in streams)
    toks = np.zeros((len(streams), width), np.int32)
    for i, s in enumerate(streams):
        toks[i, :len(s)] = s            # right pad: causal, so harmless
    pos = np.broadcast_to(np.arange(width, dtype=np.int32), toks.shape)

    def fwd(params, tokens, positions):
        logits, _ = eng.mod.forward(params, cfg32, tokens, positions, None,
                                    make_dense_attn(cfg32.sliding_window))
        return logits

    # Replicated inputs: under a mesh the params are sharded, and GSPMD
    # partitions this program too; the arithmetic stays f32 either way.
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(fwd)(eng.params, jnp.asarray(toks),
                                       jnp.asarray(pos)), np.float32)


def _compare(got: list, ref, prompts: list, tol: dict) -> dict:
    """Engine logits and greedy tokens against the reference. Errors are
    shares of the position's reference logit spread (std over the
    vocabulary); every position is measured before any is judged, so a
    failure still reports the whole table."""
    import numpy as np

    rms, peak, gaps, bad = [], [], [], []
    for i, (generated, logits) in enumerate(got):
        for p, lg in logits.items():
            r = ref[i, p]
            check(lg.shape == r.shape, f"logits shape {lg.shape}")
            check(bool(np.isfinite(lg).all()), f"non-finite logits at {p}")
            d = (lg - r) / np.std(r)
            rms.append(float(np.sqrt(np.mean(d * d))))
            peak.append(float(np.max(np.abs(d))))
            if rms[-1] > tol["rms"] or peak[-1] > tol["max"]:
                bad.append(f"prompt {i} position {p}: logit error rms "
                           f"{rms[-1]:.4f} max {peak[-1]:.4f}")
        # The serving graphs' own greedy tokens, judged on the
        # reference's logits: each must be the reference's top token, or
        # within the max-error tolerance of it (random weights leave
        # near-ties that bf16 may flip).
        for j, tok in enumerate(generated):
            r = ref[i, len(prompts[i]) - 1 + j]
            gaps.append(float((r.max() - r[tok]) / np.std(r)))
            if gaps[-1] > tol["max"]:
                bad.append(f"prompt {i} token {j}: sampled {gaps[-1]:.4f} "
                           "below the reference's best")
    res = {"logit_err_rms": round(max(rms), 5),
           "logit_err_max": round(max(peak), 5),
           "token_gap_max": round(max(gaps), 5),
           "checks": len(rms) + len(gaps), "tol": tol}
    check(not bad, f"{'; '.join(bad)} (of the logit spread; {res})")
    return res


def _attn_error(got, want) -> float:
    """Worst |got - want| of any query, as a share of that query's own
    output spread (over heads x dims): queries that attend to 10 tokens
    and to 4000 differ 20x in output scale."""
    import numpy as np

    check(str(got.dtype) == "float32", f"kernel output is {got.dtype}")
    got, want = np.asarray(got), np.asarray(want)
    check(bool(np.isfinite(got).all()), "non-finite kernel output")
    spread = np.std(want, axis=(-2, -1), keepdims=True)
    return float(np.max(np.abs(got - want) / spread))


def _kernel_errors(cfg: dict, mcfg, interpret: bool = False) -> dict:
    """Both Pallas kernels against dense float32 attention on the same
    random bf16 pool: the model's heads, window and 16-token pages;
    contexts short, long, and past the window (so the windowed page
    offset is exercised at a non-zero window start); one prefill chunk
    that starts behind the window's edge, and the doc cell's 1024-token
    chunk at offsets 0 and 3072. The pool is stacked, three
    layers of different contents, and the kernels read the middle one
    (they take the whole stack and address the layer themselves)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_inference.engine import kv_cache as kvc
    from tpu_inference.kernels.paged_attention import paged_attention
    from tpu_inference.kernels.prefill_attention import (
        paged_prefill_attention)
    from tpu_inference.models.common import dense_causal_attention

    hq, hkv, d = mcfg.n_heads, mcfg.n_kv_heads, mcfg.head_dim
    win, page = mcfg.sliding_window, 16
    span = (win or 4096) + 1024
    mp = span // page
    # The decode kernel walks 256-token blocks from the window's first
    # page: 1024 and win + 256 end on a block's last token (before and
    # after the window binds); 0 is an idle lane of the rung, which reads
    # nothing and must come back 0.
    kv_lens = np.array([100, span // 4 + 7, 0, 1024, (win or 4096) + 256,
                        span - 700, span - 1], np.int32)
    live = kv_lens > 0
    b = len(kv_lens)
    key = jax.random.split(jax.random.PRNGKey(cfg["seed"]), 4)
    layer = 1
    pool_shape = (3, b * mp + 1, page, hkv, d)
    k_pool = jax.random.normal(key[0], pool_shape, jnp.bfloat16)
    v_pool = jax.random.normal(key[1], pool_shape, jnp.bfloat16)
    tables = jnp.asarray(1 + np.arange(b * mp, dtype=np.int32).reshape(b, mp))
    k_all, v_all = kvc.gather_kv(kvc.KVPages(k=k_pool, v=v_pool), layer,
                                 tables)

    err = _attn_error
    out = {}
    with jax.default_matmul_precision("highest"):
        # bf16 values held in f32: the kernels return q's dtype, so the
        # operands are what serving feeds them (bf16-exact q, bf16 pool)
        # but the result is not rounded to bf16 on the way out.
        q = jax.random.normal(key[2], (b, hq, d),
                              jnp.bfloat16).astype(jnp.float32)
        got = paged_attention(q, k_pool, v_pool, layer, tables,
                              jnp.asarray(kv_lens), interpret=interpret,
                              sliding_window=win)
        seen = jnp.asarray(np.maximum(kv_lens, 1))
        want = dense_causal_attention(
            q[:, None], k_all, v_all, q_offset=seen - 1, kv_len=seen,
            sliding_window=win)[:, 0]
        check(not np.asarray(got)[~live].any(), "an idle lane's rows are not 0")
        out["decode"] = err(got[live], want[live])
        # Prefill: a fresh 512-token chunk, and one whose first query
        # sits 96 tokens before the context passes the window.
        s_len = 512
        q_off = np.array([0, (win or 4096) - 96], np.int32)
        lens = q_off + s_len
        qp = jax.random.normal(key[3], (2, s_len, hq, d),
                               jnp.bfloat16).astype(jnp.float32)
        got = paged_prefill_attention(
            qp, k_pool, v_pool, layer, tables[:2], jnp.asarray(lens),
            jnp.asarray(q_off), interpret=interpret, sliding_window=win)
        want = dense_causal_attention(
            qp, k_all[:2], v_all[:2],
            q_offset=jnp.asarray(q_off), kv_len=jnp.asarray(lens),
            sliding_window=win)
        out["prefill"] = err(got, want)
        # The doc cell's chunk: 1024 tokens whose query blocks see no
        # block of pages before their own (offset 0), and 1024 tokens
        # behind three chunks of a document (offset 3072 under Mistral's
        # 4096 window: 13 to 17 blocks of 256 tokens a query block).
        s_len = 1024
        q_off = np.array([0, (win or 4096) - s_len], np.int32)
        lens = q_off + s_len
        qp = jax.random.normal(key[3], (2, s_len, hq, d),
                               jnp.bfloat16).astype(jnp.float32)
        got = paged_prefill_attention(
            qp, k_pool, v_pool, layer, tables[:2], jnp.asarray(lens),
            jnp.asarray(q_off), interpret=interpret, sliding_window=win)
        want = dense_causal_attention(
            qp, k_all[:2], v_all[:2],
            q_offset=jnp.asarray(q_off), kv_len=jnp.asarray(lens),
            sliding_window=win)
        out["prefill_doc_chunk"] = err(got, want)
    return {k: round(v, 5) for k, v in out.items()}


def _mha_kernel_errors(cfg: dict, *, heads: int = 16, d: int = 128,
                       slots: int = 16, lanes: int = 12, ctx: int = 330,
                       rows: int = 384, interpret: bool = False) -> dict:
    """The two GQA kernels at an MHA shape (Ouro-2.6B's: 16 KV heads, one
    query head each, head size 128) against dense float32 attention: a
    stacked pool of ``slots`` (pass, layer) slots read at a slot past
    the first pass; decode at ``lanes`` lanes of about ``ctx`` tokens, one
    of them idle; a prefill chunk of ``rows`` rows at offset 0 and behind
    a cached prefix that ends inside a page block."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_inference.engine import kv_cache as kvc
    from tpu_inference.kernels.paged_attention import paged_attention
    from tpu_inference.kernels.prefill_attention import (
        paged_prefill_attention)
    from tpu_inference.models.common import dense_causal_attention

    page = 16
    q_off = np.array([0, ctx - 7], np.int32)
    mp = -(-(int(q_off[1]) + rows) // page)
    slot = slots // 2 + 1
    kv_lens = np.array([ctx + 5 * i for i in range(lanes)], np.int32)
    kv_lens[lanes // 2] = 0
    live = kv_lens > 0
    key = jax.random.split(jax.random.PRNGKey(cfg["seed"] + 1), 4)
    pool_shape = (slots, lanes * mp + 1, page, heads, d)
    k_pool = jax.random.normal(key[0], pool_shape, jnp.bfloat16)
    v_pool = jax.random.normal(key[1], pool_shape, jnp.bfloat16)
    tables = jnp.asarray(1 + np.arange(lanes * mp,
                                       dtype=np.int32).reshape(lanes, mp))
    k_all, v_all = kvc.gather_kv(kvc.KVPages(k=k_pool, v=v_pool), slot,
                                 tables)

    err = _attn_error
    out = {}
    with jax.default_matmul_precision("highest"):
        q = jax.random.normal(key[2], (lanes, heads, d),
                              jnp.bfloat16).astype(jnp.float32)
        got = paged_attention(q, k_pool, v_pool, slot, tables,
                              jnp.asarray(kv_lens), interpret=interpret)
        seen = jnp.asarray(np.maximum(kv_lens, 1))
        want = dense_causal_attention(q[:, None], k_all, v_all,
                                      q_offset=seen - 1, kv_len=seen)[:, 0]
        check(not np.asarray(got)[~live].any(),
              "an idle lane's rows are not 0")
        out["mha_decode"] = err(got[live], want[live])
        lens = q_off + rows
        qp = jax.random.normal(key[3], (2, rows, heads, d),
                               jnp.bfloat16).astype(jnp.float32)
        got = paged_prefill_attention(
            qp, k_pool, v_pool, slot, tables[:2], jnp.asarray(lens),
            jnp.asarray(q_off), interpret=interpret)
        want = dense_causal_attention(
            qp, k_all[:2], v_all[:2], q_offset=jnp.asarray(q_off),
            kv_len=jnp.asarray(lens))
        out["mha_prefill"] = err(got, want)
    return {k: round(v, 5) for k, v in out.items()}


def _mixed_kernel_errors(cfg: dict, *, d: int = 128, kv_heads: int = 8,
                         kinds=((72, 512), (48, 0)), slots: int = 9,
                         lanes: int = 6, ctx: int = 1400, rows: int = 512,
                         page: int = 16, interpret: bool = False) -> dict:
    """The two GQA kernels at the shapes of a stack of mixed kinds
    (Laguna-S-2.1's: 72 query heads over a window of 512 and 48 over the
    whole context, on 8 KV heads x 128: ``n_rep`` 9 and 6, a window of
    two 256-token blocks) against dense float32 attention: decode at
    ``lanes`` lanes from a few tokens to several windows long, one idle;
    a prefill chunk of ``rows`` rows at offset 0 and behind a prefix
    longer than the window that ends inside a page block. The pages
    behind a windowed lane's window are the trash page, as the engine
    leaves them (released while the sequence runs). ``page``: the tokens
    of a page, 16, or the 64 that 'auto' gives a pool of 4 KV heads."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_inference.engine import kv_cache as kvc
    from tpu_inference.kernels.paged_attention import paged_attention
    from tpu_inference.kernels.prefill_attention import (
        paged_prefill_attention)
    from tpu_inference.models.common import dense_causal_attention

    q_off = np.array([0, ctx - 7], np.int32)
    mp = -(-(int(q_off[1]) + rows) // page)
    slot = slots // 2 + 1
    kv_lens = np.array([3 + (ctx * i) // (lanes - 1) for i in range(lanes)],
                       np.int32)
    kv_lens[lanes // 2] = 0
    live = kv_lens > 0
    key = jax.random.split(jax.random.PRNGKey(cfg["seed"] + 2), 4)
    pool_shape = (slots, lanes * mp + 1, page, kv_heads, d)
    k_pool = jax.random.normal(key[0], pool_shape, jnp.bfloat16)
    v_pool = jax.random.normal(key[1], pool_shape, jnp.bfloat16)
    tables = 1 + np.arange(lanes * mp, dtype=np.int32).reshape(lanes, mp)
    k_all, v_all = kvc.gather_kv(kvc.KVPages(k=k_pool, v=v_pool), slot,
                                 jnp.asarray(tables))

    def released(tables, first_query, window):
        """The tables with each lane's pages behind its window zeroed."""
        if not window:
            return jnp.asarray(tables)
        out = tables.copy()
        for lane, q0 in enumerate(first_query):
            out[lane, :max(0, int(q0) - window) // page] = 0
        return jnp.asarray(out)

    out = {}
    with jax.default_matmul_precision("highest"):
        for heads, window in kinds:
            tag = f"h{heads}w{window}"
            q = jax.random.normal(key[2], (lanes, heads, d),
                                  jnp.bfloat16).astype(jnp.float32)
            seen = np.maximum(kv_lens, 1)
            got = paged_attention(
                q, k_pool, v_pool, slot, released(tables, seen - 1, window),
                jnp.asarray(kv_lens), interpret=interpret,
                sliding_window=window)
            want = dense_causal_attention(
                q[:, None], k_all, v_all, q_offset=jnp.asarray(seen - 1),
                kv_len=jnp.asarray(seen), sliding_window=window)[:, 0]
            check(not np.asarray(got)[~live].any(),
                  "an idle lane's rows are not 0")
            out[tag + "_decode"] = _attn_error(got[live], want[live])
            lens = q_off + rows
            qp = jax.random.normal(key[3], (2, rows, heads, d),
                                   jnp.bfloat16).astype(jnp.float32)
            got = paged_prefill_attention(
                qp, k_pool, v_pool, slot, released(tables[:2], q_off, window),
                jnp.asarray(lens), jnp.asarray(q_off), interpret=interpret,
                sliding_window=window)
            want = dense_causal_attention(
                qp, k_all[:2], v_all[:2], q_offset=jnp.asarray(q_off),
                kv_len=jnp.asarray(lens), sliding_window=window)
            out[tag + "_prefill"] = _attn_error(got, want)
    return {k: round(v, 5) for k, v in out.items()}


def _sambay_kernel_errors(cfg: dict, *, d_inner: int = 5120, n_state: int = 16,
                          lanes: int = 3, rows: int = 256,
                          pairs: dict = None,
                          interpret: bool = False) -> dict:
    """What a SambaY stack (Phi-4-mini-flash-reasoning) adds to the
    kernels: the selective-scan kernel at its scan width and state size
    against a ``lax.scan`` over time in float32 (rows of a whole chunk, a
    length that ends inside a time block, and none: its state must leave
    as it entered), as shares of the outputs' spread; and the two GQA
    kernels at its PAIR-head shapes (40 padded query heads on 10 heads x
    128, ``n_rep`` 4; a window of 512 and none)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_inference.kernels.selective_scan import (
        selective_scan, selective_scan_reference)

    ks = jax.random.split(jax.random.PRNGKey(cfg["seed"] + 4), 7)
    shape = (lanes, rows, d_inner)
    x = jax.random.normal(ks[0], shape, jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], shape) - 3.0)
    bm = jax.random.normal(ks[2], (lanes, rows, n_state))
    cm = jax.random.normal(ks[3], (lanes, rows, n_state))
    a_t = -jnp.exp(jnp.log(jnp.arange(1.0, n_state + 1))[:, None]
                   + 0.1 * jax.random.normal(ks[4], (n_state, d_inner)))
    d_skip = 1.0 + 0.1 * jax.random.normal(ks[5], (d_inner,))
    h0 = jax.random.normal(ks[6], (lanes, n_state, d_inner))
    lens = jnp.asarray(([rows, rows // 2 + 3] + [0] * lanes)[:lanes],
                       jnp.int32)
    args = (x.astype(jnp.float32), dt, bm, cm, a_t, d_skip, h0, lens)
    y, h = selective_scan(*args, interpret=interpret)
    yr, hr = selective_scan_reference(*args)
    live = (np.arange(rows)[None] < np.asarray(lens)[:, None])[..., None]
    check(float(jnp.abs(h[-1] - h0[-1]).max()) == 0.0 or lanes < 3,
          "a lane with no valid position changed its state")
    out = {"scan_y": float(np.abs(np.where(live, y - yr, 0)).max()
                           / np.std(np.asarray(yr)[:1])),
           "scan_h": float(jnp.abs(h - hr).max() / jnp.std(hr))}
    pairs = dict(dict(kv_heads=10, kinds=((40, 512), (40, 0)), slots=8),
                 **(pairs or {}))
    out.update({"pair_" + k: v for k, v in _mixed_kernel_errors(
        cfg, interpret=interpret, **pairs).items()})
    return {k: round(v, 6) for k, v in out.items()}


def _delta_rule_errors(cfg: dict, *, heads: int = 32, d: int = 128,
                       lanes: int = 3, rows: int = 1024, slots: int = 9,
                       step_lanes: int = 96, time_it: bool = True,
                       interpret: bool = False) -> dict:
    """What delta-rule layers (Ling-3.0-flash) add to the kernels, at the
    published head sizes (32 heads of a 128 x 128 float32 state): the
    chunk kernel over a whole engine chunk against the recurrence a token
    at a time (a whole row, one that ends inside a block, an idle one
    whose state must leave as it entered; half the channels' decay AT the
    gate's bound, e^-5 a token), then a second chunk from the states the
    first left (the state crosses the chunk boundary through its slot),
    and the one-token update against one step of the recurrence, each as
    a share of the reference's spread. With ``time_it``, microseconds a
    call of the one-token update at ``step_lanes`` lanes: the kernel (each
    state read once and written once, in place) beside the same update in
    plain XLA on gathered states, scattered back (what the engine runs off
    the kernel's backend), and of the chunk kernel over one row."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_inference.kernels import delta_rule as dr

    ks = jax.random.split(jax.random.PRNGKey(cfg["seed"] + 9), 8)

    def draw(n, s):
        unit = lambda x: x / jnp.linalg.norm(x, axis=-1,       # noqa: E731
                                             keepdims=True)
        shape = (n, s, heads, d)
        g = -5.0 * jax.nn.sigmoid(3.0 * jax.random.normal(ks[3], shape))
        g = jnp.where(jnp.arange(d) % 2 == 0, -5.0, g)     # at the bound
        # Keys and queries share a direction (k_i . k_j ~ 0.5), as a
        # deep layer's do: the block's triangular system is then far
        # from the identity.
        return (unit(jax.random.normal(ks[0], shape) + 1.0) * d ** -0.5,
                unit(jax.random.normal(ks[1], shape) + 1.0),
                jax.random.normal(ks[2], shape), g,
                jax.nn.sigmoid(jax.random.normal(ks[4], (n, s, heads))))

    q, k, v, g, beta = draw(lanes, rows)
    pool = jax.random.normal(ks[5], (2, slots, heads, d, d))
    at = jnp.arange(1, lanes + 1)
    lens = jnp.asarray(([rows, rows // 2 + 3] + [0] * lanes)[:lanes],
                       jnp.int32)
    flat = lambda a: a.reshape(a.shape[0], a.shape[1], -1)     # noqa: E731
    out = {}
    state = pool[1, at]
    for name in ("chunk", "next_chunk"):
        o, pool2 = dr.kda_chunk_prefill(
            pool, 1, at, jnp.where(lens > 0, at, 0),
            jnp.zeros((lanes,), bool), flat(q), flat(k), flat(v), flat(g),
            beta, lens, n_heads=heads, interpret=interpret)
        o_ref, s_ref = dr.kda_recurrence(q, k, v, g, beta, state, lens)
        live = (np.arange(rows)[None] < np.asarray(lens)[:, None])[
            ..., None, None]
        check(bool(jnp.isfinite(o).all()), "the chunk kernel overflowed")
        check(bool((pool2[0] == pool[0]).all()), "another layer's states "
              "changed")
        check(lanes < 3 or bool((pool2[1, at[-1]] == pool[1, at[-1]]).all()),
              "a lane with no valid position changed its state")
        out[name + "_o"] = float(
            np.abs(np.where(live, o.reshape(o_ref.shape) - o_ref, 0)).max()
            / np.std(np.asarray(o_ref)[:1]))
        out[name + "_s"] = float(jnp.abs(pool2[1, at] - s_ref).max()
                                 / jnp.std(s_ref))
        pool, state = pool2, s_ref
    one = lambda a: a[:, 0].reshape(lanes, -1)                 # noqa: E731
    o1, pool3 = dr.kda_step(pool, 1, at, at, one(q), one(k), one(v), one(g),
                            beta[:, 0], n_heads=heads, interpret=interpret)
    o_ref, s_ref = dr.kda_recurrence(q[:, :1], k[:, :1], v[:, :1], g[:, :1],
                                     beta[:, :1], state,
                                     jnp.ones((lanes,), jnp.int32))
    out["step_o"] = float(jnp.abs(o1.reshape(lanes, heads, d)
                                  - o_ref[:, 0]).max() / jnp.std(o_ref))
    out["step_s"] = float(jnp.abs(pool3[1, at] - s_ref).max()
                          / jnp.std(s_ref))
    out = {k: round(v, 7) for k, v in out.items()}
    if not time_it:
        return out

    def per_call_us(fn, *args, calls=20):
        res = fn(*args)
        jax.block_until_ready(res)
        t0 = time.perf_counter()
        for _ in range(calls):
            res = fn(*args)
        jax.block_until_ready(res)
        return round(1e6 * (time.perf_counter() - t0) / calls, 1)

    n = step_lanes
    big = jnp.zeros((1, n + 1, heads, d, d), jnp.float32)
    sq, sk, sv, sg, sb = (a[:, 0] for a in draw(n, 1))
    where = jnp.arange(1, n + 1)
    f2 = lambda a: a.reshape(n, -1)                            # noqa: E731

    @jax.jit
    def kernel(pool):
        return dr.kda_step(pool, 0, where, where, f2(sq), f2(sk), f2(sv),
                           f2(sg), sb, n_heads=heads, interpret=interpret)[1]

    @jax.jit
    def plain(pool):
        _, st = dr.kda_recurrence(sq[:, None], sk[:, None], sv[:, None],
                                  sg[:, None], sb[:, None], pool[0, where],
                                  jnp.ones((n,), jnp.int32))
        return pool.at[0, where].set(st)

    cq, ck, cv, cg, cb = draw(1, rows)

    @jax.jit
    def chunk(pool):
        return dr.kda_chunk_prefill(
            pool, 0, where[:1], where[:1], jnp.zeros((1,), bool), flat(cq),
            flat(ck), flat(cv), flat(cg), cb, jnp.full((1,), rows),
            n_heads=heads, interpret=interpret)[1]

    out["step_us_kernel"] = per_call_us(kernel, big)
    out["step_us_xla"] = per_call_us(plain, big)
    out["chunk_us"] = per_call_us(chunk, big)
    out["step_state_bytes"] = int(2 * n * heads * d * d * 4)
    return out


def _kv_rows_write_check(cfg: dict, *, pools=(("window", 8), ("full", 1)),
                         pages: int = 1024, page: int = 16, heads: int = 10,
                         d: int = 128, lanes: int = 64, reps: int = 200,
                         interpret: bool = False) -> dict:
    """A decode step's K / V write into merged-row pools
    (kernels/kv_rows_write.py) against the scatter it replaces
    (``kv_cache.write_kv_rows``) on the same random bf16 pools at cell
    7's shapes: Phi-4-mini-flash's 10 pair heads x 128 in 16-token pages
    of 160 rows, 64 lanes, the window kind's 8 slots and the full kind's
    one (``pages`` a pool: neither path's cost knows the pool's extent;
    the cell's are 6273 and 22104). A token a lane on a page of its own,
    one at its page's last position (the span clamped to the page's
    end), one at its first, three lanes without a token (the trash page,
    which is left out of the comparison: which of them lands last is
    nobody's business). ``<kind>_err``: elements that differ, at either
    end of the slots; ``<kind>_kernel_us`` / ``<kind>_scatter_us``: a
    call (K and V) inside a loop that carries the donated pools, the
    slot moving with the trip, as the difference of 2 x ``reps`` trips
    and ``reps``, the best of five."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_inference.engine import kv_cache as kvc
    from tpu_inference.kernels.kv_rows_write import kv_rows_write

    rng = np.random.default_rng(cfg["seed"])
    rows = page * heads
    at = rng.permutation(np.arange(1, pages))[:lanes]
    tok = rng.integers(0, page, lanes)
    tok[:2] = page - 1, 0
    starts = (at * page + tok) * heads
    starts[[3, 5, 6][:max(0, lanes - 3)]] = 0
    starts = jnp.asarray(starts, jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(cfg["seed"] & 0x7FFFFFFF), 4)
    new = [jax.random.normal(k, (lanes, heads, d), jnp.bfloat16)
           for k in keys[:2]]

    def kernel(slot, k, v):
        return kv_rows_write(k, v, slot, *new, starts, interpret=interpret)

    def scatter(slot, k, v):
        return tuple(kvc.write_kv_rows(pool, slot, x, starts)
                     for pool, x in zip((k, v), new))

    def per_trip_us(write, slots, fresh):
        run = jax.jit(lambda n, k, v: jax.lax.fori_loop(
            0, n, lambda i, c: write(jax.lax.rem(i, slots), *c), (k, v)),
            donate_argnums=(1, 2))
        best = {}
        for n in (reps, 2 * reps):
            took = []
            for _ in range(6):                  # the first one compiles
                pools = jax.block_until_ready(fresh())
                t0 = time.perf_counter()
                jax.block_until_ready(run(n, *pools))
                took.append(time.perf_counter() - t0)
            best[n] = min(took[1:])
        return round((best[2 * reps] - best[reps]) / reps * 1e6, 2)

    out = {}
    for kind, slots in pools:
        shape = (slots, pages, rows, d)
        fresh = jax.jit(lambda shape=shape: tuple(
            jax.random.normal(k, shape, jnp.bfloat16) for k in keys[2:]))
        differ = jax.jit(lambda a, b: sum(
            jnp.sum(x[:, 1:].view(jnp.uint16) != y[:, 1:].view(jnp.uint16))
            for x, y in zip(a, b)))
        out[f"{kind}_err"] = sum(
            int(differ(kernel(jnp.int32(slot), *fresh()),
                       scatter(jnp.int32(slot), *fresh())))
            for slot in {0, slots - 1})
        # (A write that did nothing must not read 0 against a scatter
        # that did nothing: the reference moves a token's rows a live
        # lane, less the few bf16 values that were there by chance.)
        moved = int(differ(fresh(), scatter(jnp.int32(0), *fresh())))
        wrote = 2 * int(jnp.sum(starts > 0)) * heads * d
        check(0.9 * wrote < moved <= wrote,
              f"the scatter moved {moved} elements of the {wrote} it wrote")
        if reps:
            out[f"{kind}_kernel_us"] = per_trip_us(kernel, slots, fresh)
            out[f"{kind}_scatter_us"] = per_trip_us(scatter, slots, fresh)
    return out


def _kda_tail_step_check(cfg: dict, *, lanes: int = 96, layers: int = 11,
                         heads: int = 32, d: int = 128, taps: int = 4,
                         reps: int = 200, interpret: bool = False) -> dict:
    """A delta-rule layer's one-token convolution
    (kernels/delta_rule.kda_tail_step) against the XLA form it replaces
    (a gather of the tails, the taps, ``take_along_axis``, a scatter) on
    the same random bf16 pool at cell 10's shapes: 96 lanes on 97 slots,
    11 layers, 3 x 32 heads x 128 = 12,288 channels, 4 taps. Every sixth
    lane has no valid token (its tail must stay, its write goes to the
    trash slot), every tenth is fresh (reads zeros), the slots are
    shuffled. ``tail_err``: elements of the pool that differ, the trash
    slot left out (which idle lane lands there last is nobody's
    business), at the first and the last layer; ``x_err``: the largest
    distance of x as a share of the reference's spread; ``kernel_us`` /
    ``xla_us``: a call inside a loop that carries the donated pool, the
    layer moving with the trip, as the difference of 2 x ``reps`` trips
    and ``reps``, the best of five."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_inference.engine.engine import PagedState
    from tpu_inference.engine.kv_cache import KVPages
    from tpu_inference.kernels import delta_rule as dr

    rng = np.random.default_rng(cfg["seed"])
    bf, c = jnp.bfloat16, 3 * heads * d
    keys = jax.random.split(jax.random.PRNGKey(cfg["seed"] & 0x7FFFFFFF), 3)
    slots = jnp.asarray(rng.permutation(np.arange(1, lanes + 1)), jnp.int32)
    lens = jnp.asarray(np.arange(lanes) % 6 != 3, jnp.int32)
    fresh = jnp.asarray(np.arange(lanes) % 10 == 4)
    slots_w = jnp.where(lens > 0, slots, 0)       # as PagedState's
    qkv = jax.random.normal(keys[0], (lanes, c), bf)
    conv_w = (taps ** -0.5 * jax.random.normal(keys[1], (taps, c))).astype(bf)
    shape = (layers, lanes + 1, taps - 1, 3 * heads, d)
    fresh_pool = jax.jit(lambda: jax.random.normal(keys[2], shape, bf))

    def kernel(layer, pool):
        return dr.kda_tail_step(pool, layer, slots, slots_w, lens, fresh,
                                qkv, conv_w, interpret=interpret)

    def xla(layer, pool):
        """What the engine runs off the kernel's backend."""
        state = PagedState(slots, lens[:, None] > 0,
                           jnp.where(fresh, 0, 1), False, False)
        x, kv = state.conv_step(layer, qkv[:, None], conv_w,
                                KVPages(k=None, v=None, conv=pool))
        return x[:, 0], kv.conv

    def per_trip_us(step):
        def trip(i, carry):
            x, pool = step(jax.lax.rem(i, layers), carry[1])
            return carry[0] + x[0, 0], pool

        run = jax.jit(lambda n, pool: jax.lax.fori_loop(
            0, n, trip, (jnp.float32(0), pool)), donate_argnums=(1,))
        best = {}
        for n in (reps, 2 * reps):
            took = []
            for _ in range(6):                  # the first one compiles
                pool = jax.block_until_ready(fresh_pool())
                t0 = time.perf_counter()
                jax.block_until_ready(run(n, pool))
                took.append(time.perf_counter() - t0)
            best[n] = min(took[1:])
        return round((best[2 * reps] - best[reps]) / reps * 1e6, 2)

    differ = jax.jit(lambda a, b: jnp.sum(
        a[:, 1:].view(jnp.uint16) != b[:, 1:].view(jnp.uint16)))
    out = {"tail_err": 0, "x_err": 0.0}
    for layer in sorted({0, layers - 1}):
        x, pool = jax.jit(kernel)(jnp.int32(layer), fresh_pool())
        x_ref, want = jax.jit(xla)(jnp.int32(layer), fresh_pool())
        out["tail_err"] += int(differ(pool, want))
        live = np.asarray(lens, bool)[:, None]
        out["x_err"] = max(out["x_err"], float(
            np.abs(np.where(live, x - x_ref, 0)).max() / np.std(x_ref)))
        # (A kernel that moved nothing must not read 0 against a form
        # that moved nothing: the reference changes a live lane's tail.)
        moved = int(differ(fresh_pool(), want))
        wrote = int(jnp.sum(lens)) * (taps - 1) * c
        check(0.9 * wrote < moved <= wrote,
              f"the XLA form moved {moved} elements of the {wrote} it wrote")
    out["x_err"] = round(out["x_err"], 9)
    if reps:
        out["kernel_us"] = per_trip_us(kernel)
        out["xla_us"] = per_trip_us(xla)
        out["bytes"] = int(lanes * c * (2 * (taps - 1) * 2 + 2 + 4))
    return out


def _latent_kernel_errors(cfg: dict, *, heads: int = 64, rank: int = 512,
                          rope: int = 64,
                          ctx=(100, 0, 5000, 8200, 0, 0, 10000, 3333),
                          chunk: int = 512, page: int = 16,
                          interpret: bool = False) -> dict:
    """mla_decode_attention / mla_prefill_attention against the dense
    float32 form (mla_attention_dense) on the same random bf16 latent
    pool, at layer 1 of a 3-layer stacked pool: Kimi-K2's 64 heads over
    one 512 + 64 entry (stored 640 wide), pages scattered, lanes of
    uneven contexts short to 10k with idle lanes (ctx 0: they must come
    back 0) among them, and a batch of chunks: one at offset 0, one row
    without a sequence, one behind a cached prefix of the second live
    lane's tokens. ``page``: the tokens of a page, 16, or the 64 that
    'auto' gives a latent pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_inference.kernels import mla_attention as mla

    width = -(-(rank + rope) // 128) * 128
    b = len(ctx)
    mp = -(-(max(ctx) + chunk) // page)
    key = jax.random.split(jax.random.PRNGKey(cfg["seed"]), 3)
    pool = jax.random.normal(key[0], (3, b * mp + 1, page, width),
                             jnp.bfloat16)
    tables = jnp.asarray(1 + np.random.RandomState(cfg["seed"] % 2**31)
                         .permutation(b * mp).astype(np.int32)
                         .reshape(b, mp))
    scale, layer = 0.13, 1
    live = np.asarray(ctx) > 0

    def err(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        check(bool(np.isfinite(got).all()), "non-finite kernel output")
        spread = np.std(want, axis=(-2, -1), keepdims=True)
        return float(np.max(np.abs(got - want) / spread))

    out = {}
    with jax.default_matmul_precision("highest"):
        lens = jnp.asarray(ctx, jnp.int32)
        q = jax.random.normal(key[1], (b, heads, rank + rope),
                              jnp.bfloat16).astype(jnp.float32)
        got = np.asarray(mla.mla_decode_attention(
            q, pool, layer, tables, lens, rank=rank, scale=scale,
            interpret=interpret))
        want = np.asarray(mla.mla_attention_dense(
            q[:, None], pool, layer, tables, lens, lens - 1, rank=rank,
            scale=scale))[:, 0]
        check(not got[~live].any(), "an idle lane's rows are not 0")
        out["latent_decode"] = err(got[live], want[live])
        first, second = np.flatnonzero(live)[:2]
        rows = jnp.asarray([first, np.flatnonzero(~live)[0], second])
        q_off = jnp.asarray([0, 0, ctx[second]], jnp.int32)
        kv_len = jnp.asarray([chunk, 0, ctx[second] + chunk], jnp.int32)
        qp = jax.random.normal(key[2], (3, chunk, heads, rank + rope),
                               jnp.bfloat16).astype(jnp.float32)
        got = np.asarray(mla.mla_prefill_attention(
            qp, pool, layer, tables[rows], kv_len, q_off, rank=rank,
            scale=scale, interpret=interpret))
        want = np.asarray(mla.mla_attention_dense(
            qp, pool, layer, tables[rows], kv_len, q_off, rank=rank,
            scale=scale))
        check(not got[1].any(), "a row without a sequence is not 0")
        out["latent_prefill"] = err(got[::2], want[::2])
    return {k: round(v, 5) for k, v in out.items()}


def _bf16_normal(key, shape):
    """Random bf16 weights, std 0.02, made on the device."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda k: (0.02 * jax.random.normal(
        k, shape, jnp.float32)).astype(jnp.bfloat16))(key)


def _expert_layer_attn(valid=None, interpret: bool = False):
    """What ``moe_ffn`` reads off its attention argument and nothing
    else: the Pallas backend, and the rows that hold a token."""
    def attn(*a):
        raise AssertionError("the expert layer calls no attention")
    attn.pallas, attn.interpret, attn.valid = True, interpret, valid
    return attn


def _spilled_routing(mcfg, n: int, live: int, tm: int, key):
    """(top_idx [n, k], gates [n, k] float32) that put every pair of the
    ``live`` first tokens on held experts and need more tiles than a
    round holds: k - 1 pairs of every token go, in turn, to a few
    ``heavy`` experts that each fill over one tile of ``tm`` rows, the
    last one, in turn, to the other held experts, whose partial tiles
    lie behind the heavy ones: in a later round than the token's other
    rows."""
    import jax
    import numpy as np

    k, held = mcfg.n_experts_per_tok, mcfg.n_local_experts
    heavy = min(held - 1, live * (k - 1) // (tm + 1))
    check(heavy >= k - 1, f"{live} tokens x {k} cannot spill {held} experts")
    i, j = np.arange(n)[:, None], np.arange(k - 1)[None, :]
    top = np.concatenate([(i * (k - 1) + j) % heavy,
                          heavy + i % (held - heavy)], axis=1)
    gates = jax.random.uniform(key, (n, k), minval=0.5, maxval=1.5)
    gates = gates / gates.sum(1, keepdims=True) * mcfg.routed_scaling_factor
    return (top + mcfg.ep_rank * held).astype(np.int32), gates


def _routed_expert_errors(cfg: dict, *, preset: str = "kimi-k2-ep32",
                          tokens=(32, 1024), idle: int = 5,
                          interpret: bool = False,
                          spill: bool = False) -> dict:
    """The expert layer as the engine runs it (``deepseek_v3.moe_ffn``:
    the router over all experts, the held pairs grouped into one-expert
    tiles, ``moe_grouped_experts_gate_up`` / ``_down`` addressing (layer,
    expert) in the stacked weights) at layer 1 of a 2-layer stack of the
    preset's widths, on random bf16 activations: a decode step's rows and
    a prefill chunk's, the last ``idle`` rows not holding a token. Against
    a plain float32 loop over the held experts with the SAME chosen
    experts and gates (so no pair changes expert between the two).
    ``routed_*``: the program's error. ``planted_*``: what a fault in
    that path reads by the same measure (the smallest over the row
    counts), to be far over the tolerance. A preset that holds EVERY
    expert of a layer with no shared one beside them
    (``smallthinker-21b-pp4``: 64 held, top-6, relu) runs the same
    check: 6 rows an expert at 64 tokens, 96 at 1024. ``spill``: the
    routing is not the router's but ``_spilled_routing``'s, handed to
    both sides: the layout's loop runs a second round, a token's rows
    lie in two rounds, and each round's combine gathers (a decode rung
    of Kimi's or Laguna's: ``spilled_<n>``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_inference.config import PRESETS
    from tpu_inference.kernels import moe_experts
    from tpu_inference.models import deepseek_v3 as dsv3

    mcfg = PRESETS[preset]()
    d, f, held = mcfg.d_model, mcfg.moe_d_ff, mcfg.n_local_experts
    fs, layer = f * mcfg.n_shared_experts, 1
    key = jax.random.split(jax.random.PRNGKey(cfg["seed"]), 10)

    mat = _bf16_normal
    experts = (mat(key[0], (2, held, d, f)), mat(key[1], (2, held, d, f)),
               mat(key[2], (2, held, f, d)))
    lp = {"w_router": mat(key[3], (d, mcfg.n_experts))}
    if mcfg.moe_scoring == "sigmoid":       # the selection bias is its
        lp["router_bias"] = 0.01 * jax.random.normal(key[4],
                                                     (mcfg.n_experts,))
    if fs:      # (a preset with no shared expert has no such leaves)
        lp.update(ws_gate=mat(key[5], (d, fs)), ws_up=mat(key[6], (d, fs)),
                  ws_down=mat(key[7], (fs, d)))
    act = moe_experts.ACTS[mcfg.moe_act]

    def expert(x, wg, wu, wd):
        return (act(x @ wg) * (x @ wu)) @ wd

    @jax.jit
    def _program(lp, h, valid, experts, moe_layer, routing):
        return dsv3.moe_ffn(mcfg, lp, experts, moe_layer, h,
                            _expert_layer_attn(valid, interpret),
                            routing=routing)

    def program(h, valid, experts, moe_layer, routing=None):
        return _program(lp, h, valid, experts, moe_layer, routing)

    @jax.jit
    def _plain(lp, experts, h, valid, routing):
        with jax.default_matmul_precision("highest"):
            x = h[:, 0].astype(jnp.float32)
            top, gates = (dsv3.route(mcfg, lp, h[:, 0]) if routing is None
                          else routing)
            first = mcfg.ep_rank * held
            y = (dsv3.swiglu(x, *(lp[k].astype(jnp.float32) for k in
                                  ("ws_gate", "ws_up", "ws_down")))
                 if fs else jnp.zeros_like(x))
            for e in range(held):
                ge = jnp.sum(jnp.where((top == first + e) & valid, gates,
                                       0.0), axis=1)
                y = y + ge[:, None] * expert(
                    x, *(w[layer, e].astype(jnp.float32) for w in experts))
            return y

    def plain(h, valid, routing=None):
        return _plain(lp, experts, h, valid, routing)

    def spilled(n, valid):
        """The routing that spills, and that it does: more tiles in use
        than a round's, every round gathering, a token in two rounds."""
        k = mcfg.n_experts_per_tok
        expected = dsv3.expected_local_pairs(mcfg, n)
        tm = moe_experts.round_layout(n, k, held, expected)[0]
        top, gates = _spilled_routing(mcfg, n, n - idle, tm, key[9])
        groups = moe_experts.group_pairs(
            jnp.where(valid, top - mcfg.ep_rank * held, held), gates, held,
            expected)
        check(groups.pair_row is not None, f"{n} rows do not gather")
        in_round = np.asarray(groups.pair_row)[:n - idle] // groups.round_rows
        check(int(groups.n_tiles) * tm > groups.round_rows
              and (in_round.min(1) < in_round.max(1)).any(),
              f"the routing of {n} rows does not spill: {in_round.max()}")
        return jnp.asarray(top), gates

    def err(got, want, valid):
        got = np.asarray(got, np.float32)[np.asarray(valid)[:, 0]]
        want = np.asarray(want, np.float32)[np.asarray(valid)[:, 0]]
        check(bool(np.isfinite(got).all()), "non-finite expert output")
        return float(np.max(np.abs(got - want))
                     / np.sqrt(np.mean(want * want)))

    out = {}
    planted = {"zero": [], "next_expert": [], "layer_0": []}
    pairs = 0
    for n in tokens:
        h = jax.random.normal(jax.random.fold_in(key[8], n), (n, 1, d),
                              jnp.bfloat16)
        valid = (jnp.arange(n) < n - idle)[:, None]
        routing = spilled(n, valid) if spill else None
        want = plain(h, valid, routing)
        got, stats = program(h, valid, experts, layer, routing)
        st = dict(zip(dsv3.MOE_STATS, np.asarray(stats)))
        check(st["tokens"] == n - idle
              and st["local_pairs"] == st["computed_pairs"],
              f"routing counts of {n} rows: {st}")
        pairs += int(st["local_pairs"])
        out[f"{'spilled' if spill else 'routed'}_{n}"] = err(
            got[:, 0], want, valid)
        rolled = tuple(jnp.roll(w, 1, axis=1) for w in experts)
        planted["zero"].append(err(
            plain(h, valid & False, routing), want, valid))
        planted["next_expert"].append(err(
            program(h, valid, rolled, layer, routing)[0][:, 0], want, valid))
        planted["layer_0"].append(err(
            program(h, valid, experts, 0, routing)[0][:, 0], want, valid))
    check(pairs > 0, "no pair was routed to a held expert")
    out.update({f"planted_{k}": min(v) for k, v in planted.items()})
    return {k: round(v, 5) for k, v in out.items()}


def _hyper_connection_errors(cfg: dict, *, preset: str = "xing4-29b-pp6",
                             rows=(64, 1024)) -> dict:
    """The hyper-connection alone at ``preset``'s widths, ``rows`` token
    rows a call (a decode rung, a prefill chunk), the program's own
    functions under jit against the equations in plain float32 at the
    highest matmul precision on the same values: the coefficients
    themselves, the pre-mix and the post-mix; and, planted, the
    coefficients rounded to bfloat16 before the mixes use them and the
    projection stopped after ONE iteration."""
    import jax
    import jax.numpy as jnp

    from tpu_inference.config import PRESETS
    from tpu_inference.models import hyper_connections as mhc

    mcfg = PRESETS[preset]()
    n, d, c = mcfg.hc_mult, mcfg.d_model, mhc.n_coeff(mcfg)
    key = jax.random.fold_in(jax.random.PRNGKey(cfg["seed"] & 0x7FFFFFFF), 47)
    k_phi, k_b, k_x, k_y = jax.random.split(key, 4)
    lp = {"hc_attn_phi": (jax.random.normal(k_phi, (n * d, c), jnp.float32)
                          / (n * d) ** 0.5).astype(mcfg.dtype),
          "hc_attn_b": 0.5 * jax.random.normal(k_b, (c,), jnp.float32),
          "hc_attn_alpha": jnp.ones((3,), jnp.float32)}

    def program(x, y, rounded=False):
        coef, err = mhc.coefficients(mcfg, lp, "attn", x)
        if rounded:
            # (bfloat16's 8 exponent and 7 mantissa bits, by the one op
            # no compiler folds away)
            coef = jax.lax.reduce_precision(coef, 8, 7)
        return (coef, mhc.pre_mix(mcfg, coef, x).astype(jnp.float32),
                mhc.post_mix(mcfg, coef, x, y).astype(jnp.float32), err)

    def plain(x, y, iters):
        with jax.default_matmul_precision("highest"):
            xf = x.astype(jnp.float32).reshape(-1, n, d)
            v = xf.reshape(-1, n * d)
            z = (v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                                   + mcfg.hc_eps)
                 ) @ lp["hc_attn_phi"].astype(jnp.float32) + lp["hc_attn_b"]
            pre = jax.nn.sigmoid(z[:, :n])
            post = 2.0 * jax.nn.sigmoid(z[:, n:2 * n])
            res = jnp.exp(jnp.clip(z[:, 2 * n:], -mcfg.hc_res_clamp,
                                   mcfg.hc_res_clamp)).reshape(-1, n, n)
            for _ in range(iters):
                res = res / (res.sum(1, keepdims=True) + mcfg.hc_eps)
                res = res / (res.sum(2, keepdims=True) + mcfg.hc_eps)
            h = jnp.einsum("tn,tnd->td", pre, xf)
            out = (jnp.einsum("tij,tjd->tid", res, xf)
                   + post[:, :, None] * y.reshape(-1, 1, d))
            coef = jnp.concatenate([pre, post, res.reshape(-1, n * n)], -1)
            return coef, h, out.reshape(-1, n * d)

    def err(got, want, scale=None):
        got = got.reshape(want.shape)
        return float(jnp.max(jnp.abs(got - want))
                     / (scale or jnp.sqrt(jnp.mean(want * want))))

    out = {}
    for t in rows:
        b, s = (t, 1) if t <= 64 else (1, t)
        x = jax.random.normal(jax.random.fold_in(k_x, t), (b, s, n * d),
                              jnp.float32).astype(mcfg.dtype)
        y = jax.random.normal(jax.random.fold_in(k_y, t), (b, s, d),
                              jnp.float32)
        coef, h, mixed, off = jax.jit(program)(x, y)
        want_coef, want_h, want = plain(x, y, mcfg.hc_sinkhorn_iters)
        out[f"coef_{t}"] = err(coef, want_coef, scale=1.0)
        out[f"pre_mix_{t}"] = err(h, want_h)
        out[f"post_mix_{t}"] = err(mixed, want)
        out[f"sum_err_ppm_{t}"] = round(1e6 * float(off))
    coef, h, _, _ = jax.jit(lambda x, y: program(x, y, True))(x, y)
    out["planted_coef_bf16"] = min(err(coef, want_coef, scale=1.0),
                                   err(h, want_h))
    out["planted_one_iteration"] = err(mixed, plain(x, y, 1)[2])
    return out


def check_hyper_connection(errs: dict, tol: float, f32_tol: float) -> None:
    def largest(*prefixes):
        return max(v for k, v in errs.items() if k.startswith(prefixes))

    check(largest("coef_", "pre_mix_") <= f32_tol
          and largest("post_mix_") <= tol,
          f"hyper-connection vs the equations in float32: {errs}")
    check(errs["planted_coef_bf16"] > 10 * f32_tol,
          f"coefficients rounded to bfloat16 read as sound: {errs}")
    check(errs["planted_one_iteration"] > 10 * tol,
          f"a projection stopped after one iteration reads as sound: {errs}")


def check_routed_experts(errs: dict, tol: float) -> None:
    for name, e in errs.items():
        if name.startswith(("routed_", "spilled_")):
            check(e <= tol, f"routed-expert layer vs the plain float32 "
                            f"loop: {errs}")
        else:
            check(e > 10 * tol, f"a planted fault in the routed-expert "
                                f"path reads like a sound layer: {errs}")


def _combine_costs(cfg: dict, *, shapes=(
        ("laguna-s-ep8", 32), ("laguna-s-ep8", 1024), ("laguna-s-ep8", 4096),
        ("kimi-k2-ep32", 32), ("kimi-k2-ep32", 1024)),
        reps: int = 100, interpret: bool = False) -> dict:
    """What ``moe_experts.SCATTERED_ROW_COST`` is set from: a round's two
    combines timed on this device at a preset's widths and a step
    program's rows (a decode rung, a chunk, four chunks), the routing
    uniform over all experts. Per shape, in microseconds:
    ``scatter_us`` / ``gather_us``, one round's combine ALONE
    (``y.at[tok].add(yr * gate)`` over ``round_rows`` rows against
    ``gathered_rows`` over ``pairs`` = T x k; indices and gates made to
    depend on the carried ``y``, so no part is hoisted out of the timed
    loop), ``row_cost`` = (scatter_us / round_rows) / (gather_us /
    pairs), and ``layer_scatter_us`` / ``layer_gather_us``, the whole
    layer (``moe_ffn`` without its shared expert: router, layout,
    kernels, combine) in a scan fed its own output, as a step program
    runs it, each form forced on the same layout. A time is the
    difference of a call of 2 x ``reps`` trips and one of ``reps``, the
    best of five, so what a call costs besides its trips is not in it.
    The forms are forced by setting the module's constant for the trace
    (nothing else can: no flag decides the path)."""
    import time

    import jax
    import jax.numpy as jnp

    from tpu_inference.config import PRESETS
    from tpu_inference.kernels import moe_experts
    from tpu_inference.models import deepseek_v3 as dsv3

    key = jax.random.PRNGKey(cfg["seed"])

    def forced(gather: bool, fn):
        """``fn`` traced with every layout made to gather, or none."""
        def call(*args):
            kept = moe_experts.SCATTERED_ROW_COST
            moe_experts.SCATTERED_ROW_COST = float("inf") if gather else 0.0
            try:
                return fn(*args)
            finally:
                moe_experts.SCATTERED_ROW_COST = kept
        return call

    def per_trip_us(step, carry, *args):
        """``step(carry, *args) -> carry`` in a loop of n trips."""
        run = jax.jit(lambda n, c, *a: jax.lax.fori_loop(
            0, n, lambda _, c: step(c, *a), c))
        best = {}
        for n in (reps, 2 * reps):
            jax.block_until_ready(run(n, carry, *args))
            took = []
            for _ in range(5):
                t0 = time.perf_counter()
                jax.block_until_ready(run(n, carry, *args))
                took.append(time.perf_counter() - t0)
            best[n] = min(took)
        return (best[2 * reps] - best[reps]) / reps * 1e6

    out = {}
    for preset, t in shapes:
        mcfg = PRESETS[preset]()
        k, held, d, f = (mcfg.n_experts_per_tok, mcfg.n_local_experts,
                         mcfg.d_model, mcfg.moe_d_ff)
        expected = dsv3.expected_local_pairs(mcfg, t)
        ks = jax.random.split(jax.random.fold_in(key, t), 8)
        top = jax.random.randint(ks[0], (t, k), 0, mcfg.n_experts)
        top_local = jnp.where(top < held, top, held).astype(jnp.int32)
        gates = jax.random.uniform(ks[1], (t, k), minval=0.5, maxval=1.5)
        groups = forced(True, moe_experts.group_pairs)(
            top_local, gates, held, expected)
        rr = groups.round_rows
        yr = jax.random.normal(ks[2], (rr, d), jnp.float32)
        y0 = jnp.zeros((t, d), jnp.float32)

        def bump(y):        # 0, but only the device knows
            return (y[0, 0] > 3e38).astype(jnp.int32)

        def scatter(y, yr, tok, gate):
            b = bump(y)
            return y.at[tok + b].add(yr * (gate + b)[:, None], mode="drop")

        def gather(y, yr, pair_row, pair_gate):
            b = bump(y)
            return y + moe_experts.gathered_rows(yr, groups._replace(
                pair_row=pair_row + b, pair_gate=pair_gate + b), 0)

        res = {"rows_round": rr, "pairs": t * k,
               "rounds": groups.row_token.shape[0] // rr,
               "scatter_us": per_trip_us(scatter, y0, yr,
                                         groups.row_token[:rr],
                                         groups.row_gate[:rr]),
               "gather_us": per_trip_us(gather, y0, yr, groups.pair_row,
                                        groups.pair_gate)}
        res["row_cost"] = (res["scatter_us"] / rr) / (res["gather_us"]
                                                     / (t * k))

        mat = _bf16_normal
        experts = (mat(ks[3], (2, held, d, f)), mat(ks[4], (2, held, d, f)),
                   mat(ks[5], (2, held, f, d)))
        lp = {"w_router": mat(ks[6], (d, mcfg.n_experts))}
        h0 = jax.random.normal(ks[7], (t, 1, d), jnp.bfloat16)
        attn = _expert_layer_attn(interpret=interpret)

        def layer(h, lp, experts):
            y, _ = dsv3.moe_ffn(mcfg, lp, experts, 1, h, attn)
            # Kept at the input's scale, and the features moved on by
            # one: a token routed to no held expert would get y = 0 and
            # route there for ever, and trip by trip the layer would
            # have less to do (my first chip run read 15 us a layer).
            out = (h + y) * jax.lax.rsqrt(
                jnp.mean(jnp.square((h + y).astype(jnp.float32)))
            ).astype(h.dtype)
            return jnp.roll(out, 1, axis=2)

        for form in ("scatter", "gather"):
            res[f"layer_{form}_us"] = per_trip_us(
                forced(form == "gather", layer), h0, lp, experts)
        out[f"{preset}_{t}"] = {k_: round(v, 3) if isinstance(v, float)
                                else v for k_, v in res.items()}
    return out


def _seeded_prompts(cfg: dict, vocab: int) -> list:
    import numpy as np

    rng = np.random.default_rng(cfg["seed"])
    return [rng.integers(1, vocab, size=n).tolist()
            for n in cfg["parity_prompts"]]


def child_parity(cfg: dict) -> dict:
    device = _child_setup(cfg)
    eng = _build_engine(cfg, cfg["parity_layers"], cfg["quant"])
    prompts = _seeded_prompts(cfg, eng.model_cfg.vocab_size)
    got = _engine_logits(eng, prompts, cfg["parity_decode_steps"])
    ref = _reference_logits(
        eng, [p + g[:-1] for p, (g, _) in zip(prompts, got)])
    res = _compare(got, ref, prompts, cfg["parity_tol"])
    info = eng.device_info()
    check(info["attn_backend"] == cfg["attn_backend"], "attention backend")
    if cfg["attn_backend"] == "pallas":
        res["kernel_err"] = _kernel_errors(cfg, eng.model_cfg)
        res["kernel_tol"] = cfg["kernel_tol"]
        check(max(res["kernel_err"].values()) <= cfg["kernel_tol"],
              f"Pallas kernel vs dense float32 attention: {res}")
        res["mha_kernel_err"] = _mha_kernel_errors(cfg)
        check(max(res["mha_kernel_err"].values()) <= cfg["kernel_tol"],
              f"Pallas kernels at the MHA shape vs dense float32: {res}")
        res["mixed_kernel_err"] = _mixed_kernel_errors(cfg)
        check(max(res["mixed_kernel_err"].values()) <= cfg["kernel_tol"],
              f"Pallas kernels at the mixed-kind shapes vs dense float32: "
              f"{res}")
        res["sambay_kernel_err"] = _sambay_kernel_errors(cfg)
        check(max(res["sambay_kernel_err"].values()) <= cfg["kernel_tol"],
              f"selective scan vs lax.scan / GQA kernels at the pair-head "
              f"shapes vs dense float32: {res}")
        # Delta-rule layers: the chunk kernel and the one-token update
        # at 32 heads of a 128 x 128 float32 state against the recurrence
        # a token at a time, and what a call of each costs.
        res["delta_rule_err"] = _delta_rule_errors(cfg)
        check(max(v for k, v in res["delta_rule_err"].items()
                  if k.endswith(("_o", "_s"))) <= cfg["delta_rule_tol"],
              f"delta-rule kernels vs the recurrence: {res}")
        # A delta-rule layer's one-token convolution: the tails the
        # gather, taps and scatter give, bit for bit, x to float32's
        # rounding, and what a call of each costs.
        res["kda_tail_step_err"] = _kda_tail_step_check(cfg)
        check(res["kda_tail_step_err"]["tail_err"] == 0
              and res["kda_tail_step_err"]["x_err"] <= cfg["kda_tail_tol"],
              f"kda_tail_step vs the XLA form it replaces: {res}")
        # A decode step's K / V write into merged-row pools: the pool
        # the scatter gives, bit for bit, and what a call of each costs.
        res["kv_rows_write_err"] = _kv_rows_write_check(cfg)
        check(all(v == 0 for k, v in res["kv_rows_write_err"].items()
                  if k.endswith("_err")),
              f"kv_rows_write vs the scatter it replaces: {res}")
        res["latent_kernel_err"] = _latent_kernel_errors(cfg)
        check(max(res["latent_kernel_err"].values())
              <= cfg["latent_kernel_tol"],
              f"latent-attention kernel vs its dense float32 form: {res}")
        res["routed_expert_err"] = _routed_expert_errors(cfg)
        check_routed_experts(res["routed_expert_err"],
                             cfg["routed_expert_tol"])
        # Every expert of a layer held (64, top-6, relu), and the GQA
        # kernels at that stack's shapes: 28 query heads on 4 KV heads,
        # a 4096-token window and none (the rope-less full layers).
        res["all_held_expert_err"] = _routed_expert_errors(
            cfg, preset="smallthinker-21b-pp4", tokens=(64, 1024))
        check_routed_experts(res["all_held_expert_err"],
                             cfg["routed_expert_tol"])
        # The widest decode rung of the two chips that hold few experts,
        # the routing piled so that a second round runs and a token's
        # rows lie in two: each round's combine gathers (PR 45).
        res["spilled_expert_err"] = {
            preset: _routed_expert_errors(cfg, preset=preset, tokens=(32,),
                                          spill=True)
            for preset in ("kimi-k2-ep32", "laguna-s-ep8")}
        for errs in res["spilled_expert_err"].values():
            check_routed_experts(errs, cfg["routed_expert_tol"])
        # Four residual streams of 3584 mixed by one hyper-connection:
        # the coefficients and the pre-mix held to float32.
        res["hyper_connection_err"] = _hyper_connection_errors(cfg)
        check_hyper_connection(res["hyper_connection_err"],
                               cfg["hyper_connection_tol"],
                               cfg["hyper_connection_f32_tol"])
        res["nope_window_kernel_err"] = _mixed_kernel_errors(
            cfg, kv_heads=4, kinds=((28, 4096), (28, 0)), lanes=4,
            ctx=5000, rows=1024)
        check(max(res["nope_window_kernel_err"].values())
              <= cfg["kernel_tol"],
              f"Pallas kernels at 28 heads on 4 KV heads, window 4096 and "
              f"none, vs dense float32: {res}")
        # The 64-token page that 'auto' gives the pools under 32 KB a
        # 16-token page (PR 48): [P, 64, 4, 128] and [P, 64, 640], at
        # the tolerances of the 16-token cases.
        res["wide_page_kernel_err"] = _mixed_kernel_errors(
            cfg, kv_heads=4, kinds=((28, 4096), (28, 0)), lanes=4,
            ctx=5000, rows=1024, page=64)
        check(max(res["wide_page_kernel_err"].values())
              <= cfg["kernel_tol"],
              f"Pallas kernels on 64-token pages of 4 KV heads vs dense "
              f"float32: {res}")
        res["wide_page_latent_kernel_err"] = _latent_kernel_errors(
            cfg, page=64)
        check(max(res["wide_page_latent_kernel_err"].values())
              <= cfg["latent_kernel_tol"],
              f"latent-attention kernel on 64-token pages vs its dense "
              f"float32 form: {res}")
    return {"ok": True, "layers": cfg["parity_layers"],
            "depth_cut": f"{cfg['parity_layers']} of the model's layers: "
                         "what a float32 reference fits beside on one chip",
            "prompt_tokens": cfg["parity_prompts"], **res,
            "peak_bytes_in_use": info["peak_bytes_in_use"],
            "device": device}


def child_tp4_parity(cfg: dict) -> dict:
    """tp=4 against tp=1 on the same seeded bf16 model (depth cut to what
    one chip holds), both against the float32 reference; and the shards
    really sit on four devices."""
    import jax
    import numpy as np

    device = _child_setup(cfg)
    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices, need 4")
    from tpu_inference.config import ParallelConfig
    from tpu_inference.models.registry import build_model
    from tpu_inference.parallel.mesh import build_mesh
    from tpu_inference.parallel.shardings import param_shardings

    mesh = build_mesh(ParallelConfig(tp=4), devices=jax.devices()[:4])
    prompts = None
    results = {}
    for name, m in (("tp4", mesh), ("tp1", None)):
        params = None
        if m is None:
            # The SAME weights as the tp=4 engine drew, bit for bit: both
            # through build_model's jitted init (jit and eager differ by
            # a bf16 ulp here and there), this side onto one device.
            one = build_mesh(ParallelConfig(tp=1), devices=jax.devices()[:1])
            params, _ = build_model(
                eng.model_cfg, cfg["seed"],
                shardings=param_shardings(eng.model_cfg, one))
            del eng
        eng = _build_engine(cfg, cfg["tp_layers"], "none", mesh=m,
                            params=params)
        prompts = prompts or _seeded_prompts(cfg, eng.model_cfg.vocab_size)
        if m is not None:
            # Placement, read off the arrays: every weight matrix and the
            # pool have one shard on each of the four devices, and a
            # tp-sharded one holds a quarter of it.
            # (wq is stored [L, N, K], a one-child node: its array)
            for label, arr in (("wq", jax.tree.leaves(
                                    eng.params["blocks"]["wq"])[0]),
                               ("w_down", eng.params["blocks"]["w_down"]),
                               ("kv pool", eng.kv.k)):
                devs = {s.device.id for s in arr.addressable_shards}
                check(len(devs) == 4, f"{label} sits on devices {devs}")
                shard = arr.addressable_shards[0].data
                check(shard.size * 4 == arr.size,
                      f"{label} shard {shard.shape} is not a quarter of "
                      f"{arr.shape}")
        got = _engine_logits(eng, prompts, cfg["parity_decode_steps"])
        ref = _reference_logits(
            eng, [p + g[:-1] for p, (g, _) in zip(prompts, got)])
        results[name] = (got, _compare(got, ref, prompts, cfg["parity_tol"]))
    # tp=4 vs tp=1 directly, at the prompts' last positions (the same
    # token stream on both sides). Two bf16 engines that differ in the
    # order of their partial sums: bf16's own noise again, same bounds.
    rms, peak = 0.0, 0.0
    for (_, l4), (_, l1), p in zip(results["tp4"][0], results["tp1"][0],
                                   prompts):
        d = (l4[len(p) - 1] - l1[len(p) - 1]) / np.std(l1[len(p) - 1])
        rms = max(rms, float(np.sqrt(np.mean(d * d))))
        peak = max(peak, float(np.max(np.abs(d))))
    tol = cfg["parity_tol"]
    check(rms <= tol["rms"] and peak <= tol["max"],
          f"tp=4 vs tp=1 logits: rms {rms:.4f} max {peak:.4f} of the spread")
    return {"ok": True, "layers": cfg["tp_layers"],
            "tp4_vs_ref": results["tp4"][1], "tp1_vs_ref": results["tp1"][1],
            "tp4_vs_tp1": {"logit_err_rms": round(rms, 5),
                           "logit_err_max": round(peak, 5)},
            "device": device}


def child_combine_costs(cfg: dict) -> dict:
    return {"device": _child_setup(cfg), "combine_costs": _combine_costs(cfg)}


CHILDREN = {"parity": child_parity, "tp4_parity": child_tp4_parity,
            "combine_costs": child_combine_costs}


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "_child":
        emit(CHILDREN[sys.argv[2]](json.loads(sys.argv[3])))
        return 0
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the cross-chip phases only (tp4, dp4)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--combine-costs", action="store_true",
                    help="only time the grouped expert layer's two "
                         "combines at Laguna's and Kimi's shapes (what "
                         "moe_experts.SCATTERED_ROW_COST is set from)")
    args = ap.parse_args()
    t0 = time.monotonic()
    if args.combine_costs:
        emit(run_child(dict(SETTINGS, seed=args.seed), "combine_costs",
                       timeout=1800.0))
        return 0
    try:
        phases = run(args.chips, args.seed, SETTINGS)
        device = result_device(phases)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    emit({"summary": {p["phase"]: p["wall_s"] for p in phases},
          "model": SETTINGS["model"], "seed": args.seed,
          "wall_s": round(time.monotonic() - t0, 1), "claim": None})
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
