#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts the server through its normal CLI as a child that owns the
chip(s), stays off JAX itself, sends the cell's traffic over HTTP (a warm
lap, then the measured window), stops the server, checks the program's
outputs against the plain reference (``parity.py``, a second child), and
prints one JSON result line last. With ``--trace 0`` the line carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics (and a
few seconds of the window are profiled in the server process).

No chip, fewer chips than the cell asks for, a platform other than the
configuration's, or any failure: non-zero exit and no result line.
Everything specific to a cell, configuration, traffic mix or per-layer
metric is a data file found by name (``manifest.py``); see README.md.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import glob  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import loadgen  # noqa: E402
import metrics as M  # noqa: E402
import traffic as T  # noqa: E402
from manifest import Manifest  # noqa: E402
from server import Server  # noqa: E402

TRACE_SECONDS = 3.0          # of the window, profiled in a traced run
TRACE_AT = 0.35              # where in the window the profile starts


class BenchFailure(Exception):
    pass


def say(**kv) -> None:
    """An earlier line of stdout (the driver reads only the last)."""
    print(json.dumps(kv), flush=True)


COMPARED = {}                # every number compared, beside its limit


def compare(**pairs) -> None:
    """``name=[number, limit]``: said at once, and kept for the result
    line's last key and the last lines of stderr."""
    COMPARED.update(pairs)
    say(compared=pairs)


def schedule(traffic: dict, seed: int, seconds: float) -> list:
    if traffic["loop"] == "open":
        return T.open_loop(traffic, seed, seconds)
    return T.closed_loop(traffic, seed)


def server_flags(cfg: dict, traffic: dict) -> list:
    """The configuration's server flags, with ``--ignore-eos`` where the
    MIX says ``"ignore_eos": true``: every answer of that mix runs to its
    ``num_predict``. (Greedy argmax over random weights reaches the byte
    tokenizer's EOS id in a few answers of a hundred, WHICH ones follows
    the seed, and each vacates a lane early: the seed then changes the
    work. The program has the switch for the whole server only.)"""
    flags = list(cfg["serving"]["flags"])
    if traffic.get("ignore_eos") and "--ignore-eos" not in flags:
        flags.append("--ignore-eos")
    return flags


def run_window(server: Server, traffic: dict, reqs: list, seconds: float,
               trace: bool) -> dict:
    """Warm lap + measured window against a serving server. Returns the
    client's records and everything scraped around the window."""
    snap = {}

    async def on_open():
        snap["log_open"] = server.log_size()
        snap["metrics_open"] = await asyncio.to_thread(server.metrics)

    async def on_close():
        # At the close, not once the profiler has returned: a trace that
        # takes longer to write than the rest of the window would add the
        # seconds after the close, in which the loop idles, to every
        # share of the loop's wall.
        snap["metrics_end"] = await asyncio.to_thread(server.metrics)
        snap["steps"] = await asyncio.to_thread(server.get_json,
                                                "/debug/steps")

    async def profile(t_open: float):
        await asyncio.sleep(max(0.0, t_open + TRACE_AT * seconds
                                - time.monotonic()))
        t0, unix0 = time.monotonic() - t_open, time.time()
        res = await asyncio.to_thread(
            server.post_json, "/debug/profile",
            {"seconds": min(TRACE_SECONDS, 0.5 * seconds)}, 300.0)
        res["start_s"], res["end_s"] = t0, time.monotonic() - t_open
        res["start_unix"], res["end_unix"] = unix0, time.time()
        return res

    out = loadgen.run(server.base, traffic["loop"], reqs,
                      float(traffic["warm_lap_s"]), seconds,
                      float(traffic.get("drain_s", 0.0)),
                      int(traffic.get("clients", 0)),
                      on_open=on_open, during=profile if trace else None,
                      on_close=on_close)
    for res in out["side"]:
        if isinstance(res, BaseException):
            raise BenchFailure(f"a scrape beside the window failed: {res!r}")
    snap["compiled_in_window"] = server.compiles_since(snap["log_open"])
    snap["compiles_in_window"] = len(snap["compiled_in_window"])
    snap["profile"] = out["side"][1] if trace else None
    snap["records"] = out["records"]
    snap["t_open"] = out["t_open"]
    snap["queue"] = out["queue"]
    return snap


def check_queue(queue: dict) -> None:
    """A closed loop's queue has to outlast the window: a client that
    found it empty left, the load was lighter than the cell's, and a
    faster program would read a LOWER ``out_tok_s``. No result then."""
    compare(queue_drawn=[queue["drawn"], queue["queued"]])
    if queue["dry_s"] is not None:
        raise BenchFailure(f"closed queue ran dry at {queue['dry_s']:.1f} s:"
                           f" {queue['drawn']} of {queue['queued']} drawn")


def child(cmd: list, env: dict, log_path: str, timeout: float) -> list:
    """Run a child of this benchmark to its end; its stdout's JSON lines."""
    with open(log_path, "wb") as log:
        p = subprocess.run(cmd, cwd=REPO, env=env, stdout=subprocess.PIPE,
                           stderr=log, timeout=timeout)
    out = p.stdout.decode(errors="replace")
    if p.returncode != 0:
        with open(log_path, "rb") as f:
            tail = f.read()[-3000:].decode(errors="replace")
        raise BenchFailure(f"{cmd[1]} exited {p.returncode}:\n{out[-2000:]}"
                           f"\n{tail}")
    return [json.loads(line) for line in out.splitlines()
            if line.startswith("{")]


def _written_in_call(path: str, profile: dict) -> bool:
    """Was this file last written between the end of the profiled seconds
    and the profile call's return?"""
    return (profile["start_unix"] + profile["seconds"] - 1.0
            <= os.path.getmtime(path) <= profile["end_unix"] + 1.0)


def own_trace(profile: dict) -> str:
    """The one trace file this run's profile call wrote. The server is
    told to profile into this run's own ``.bench_out/<cell>/profile``
    (``server.py``: ``--profile-dir``), but the directory it ANSWERS with
    is the one read, and a program that kept its default would share it
    with every other checkout's traced run. So: only a ``*.xplane.pb``
    written during this run's own call is taken; none, or more than one
    (somebody else's run overlapped), fails the run."""
    found = [path for path in glob.glob(
        os.path.join(profile["dir"], "**", "*.xplane.pb"), recursive=True)
        if _written_in_call(path, profile)]
    if len(found) != 1:
        raise BenchFailure(
            f"{len(found)} traces under {profile['dir']} were written "
            f"during this run's profile call, not 1: {found}")
    return found[0]


def reduce_trace(profile: dict, out_dir: str) -> dict:
    """The profiler's trace -> a small summary (a child, pinned to the
    CPU: it imports jax only to read the file). Then what this run's call
    wrote beside the trace (the profiler's session directory also gets a
    ``.trace.json.gz``) is removed, and the session directory if that
    leaves it empty: nothing else under the program's profile directory
    is touched."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    summary = os.path.join(out_dir, "trace_summary.json")
    trace = own_trace(profile)
    try:
        child([sys.executable, os.path.join(HERE, "trace_reduce.py"),
               trace, summary], env,
              os.path.join(out_dir, "trace_reduce.log"), 300.0)
    finally:
        remove_own_session(trace, profile)
    with open(summary) as f:
        return json.load(f)


def remove_own_session(trace: str, profile: dict) -> None:
    session = os.path.dirname(trace)
    for name in os.listdir(session):
        if _written_in_call(os.path.join(session, name), profile):
            os.remove(os.path.join(session, name))
    try:
        os.rmdir(session)
    except OSError:
        pass


def out_dir_of(man: Manifest, cell: dict) -> str:
    """``.bench_out/<cell>``: server log, blackbox, profile, parity log.
    A manifest other than the checkout's own (a test's copy) gets a
    directory of its own under it, so two rehearsals of one cell name
    running side by side share nothing."""
    parts = [REPO, ".bench_out"]
    if man.path != os.path.join(REPO, "BENCHMARK.json"):
        parts.append(hashlib.sha1(man.path.encode()).hexdigest()[:12])
    return os.path.join(*parts, cell["name"])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--manifest",
                    default=os.path.join(REPO, "BENCHMARK.json"))
    args = ap.parse_args()

    man = Manifest(args.manifest)
    cell = man.cell(args.workload)
    cfg = man.config(cell)
    traffic = T.load(man.traffic_path(cell))
    out_dir = out_dir_of(man, cell)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)

    flags = server_flags(cfg, traffic)
    server = Server(REPO, flags, out_dir)
    try:
        reqs = schedule(traffic, args.seed, args.seconds)
        boot_s = server.wait_ready()
        dev = server.device()
        if dev["platform"] != cfg["serving"]["platform"]:
            raise BenchFailure(f"server runs on {dev['platform']!r}, the "
                               f"configuration says "
                               f"{cfg['serving']['platform']!r}")
        if dev["count"] != cell["chips"]:
            raise BenchFailure(f"server uses {dev['count']} chips, the "
                               f"cell asks for {cell['chips']}")
        log_ready = server.log_size()
        win = run_window(server, traffic, reqs, args.seconds, bool(args.trace))
        if win["queue"] is not None:
            check_queue(win["queue"])
        warm_compiles = (len(server.compiles_since(log_ready))
                         - win["compiles_in_window"])
        dev = server.device()            # after load: the peak is real
    finally:
        rc = server.stop()
    if rc != 0:
        raise BenchFailure(f"server exit code {rc} after SIGTERM:\n"
                           f"{server.log_tail()}")
    ledger = server.ledger() if args.trace else []
    setup_s = win["t_open"] - T_PROCESS

    records = win["records"]
    drain_s = float(traffic.get("drain_s", 0.0))
    split = M.counted(records, traffic["loop"], args.seconds, drain_s)
    e2e = M.end_to_end(records, split, args.seconds)
    e2e["setup_s"] = setup_s
    say(window={"loop": traffic["loop"], "seconds": args.seconds,
                "offered": len([r for r in records if r["index"] >= 0]),
                "attempted": e2e["attempted"], "failed": e2e["failed"],
                "prompt_tokens": sum(r["prompt_tokens"] for r in split["ok"]),
                "answer_tokens": sum(r["answer_tokens"] for r in split["ok"]),
                "early_stop_share_pct": M.early_stop_share(split["ok"]),
                # One stalled run shows here before it shows in a mean.
                "ttft_max_s": max((M.ttft(r) for r in split["ok"]),
                                  default=None),
                "gap_max_s": max((g for r in split["ok"]
                                  for g in M.gaps(r)), default=None),
                # The client's account of its requests over the window.
                "clients_waiting_mean": M.in_flight_mean(
                    records, args.seconds, "waiting"),
                "streams_decoding_mean": M.in_flight_mean(
                    records, args.seconds, "decoding"),
                "boot_s": boot_s, "compiles_in_window":
                    win["compiles_in_window"],
                "compiles_in_warm_lap": warm_compiles,
                "compiled_in_window": win["compiled_in_window"][:12],
                # A traced run: when the profiler was asked and when it
                # had written its trace (it may outlast the window).
                "profile_s": [win["profile"]["start_s"],
                              win["profile"]["end_s"]]
                if win["profile"] else None,
                "errors": sorted({r["error"] for r in split["failed"]
                                  if r["error"]})[:5]})
    say(candidates={k: v for k, v in e2e.items()})

    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": dev["memory_peak_bytes"]}
    result = {"correct": False, "attempted": e2e["attempted"],
              "failed": e2e["failed"], "metrics": {}, "device": device}

    if args.trace:
        trace = reduce_trace(win["profile"], out_dir)
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"][:10],
                               "idle_gaps": trace["idle_gaps"][:10]}
        ctx = {"records": records, "ok": split["ok"],
               "failed": split["failed"], "traffic": traffic, "config": cfg,
               "cell": cell, "seconds": args.seconds, "drain_s": drain_s,
               "metrics_open": win["metrics_open"],
               "metrics_end": win["metrics_end"], "steps": win["steps"],
               "ledger": ledger,
               "compiles_in_window": win["compiles_in_window"],
               "profile": win["profile"], "trace": trace, "device": device,
               "peaks": man.peaks(dev["kind"]) if dev["platform"] != "cpu"
               else None}
        specs = []
        for m in man.metrics_of("per_layer", cell["name"]):
            spec = man.layer_metric(m["name"], cfg)
            specs.append(spec)
            value = man.reader(spec["reader"])(ctx, **spec.get("args", {}))
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        # How much of each account (the engine loop's wall) this cell's
        # metric files read between them: said, not part of ``correct``.
        for name, pct in M.accounts_read(specs, win["metrics_open"],
                                         win["metrics_end"]).items():
            compare(**{name + "_read_pct": [pct, 100.0]})
    else:
        for m in man.metrics_of("end_to_end", cell["name"]):
            if e2e.get(m["name"]) is None:
                raise BenchFailure(f"no value for {m['name']}: "
                                   f"{e2e['attempted']} requests counted")
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}

    served_ok = e2e["attempted"] > 0 and win["compiles_in_window"] == 0
    # A request the server counted differently than it was sent; under
    # --ignore-eos an answer that ends before its length is one.
    to_length = "--ignore-eos" in flags
    miscounted = [r["index"] for r in split["ok"]
                  if r["prompt_eval_count"] != r["prompt_tokens"]
                  or not ((r["answer_tokens"] if to_length else 1)
                          <= (r["eval_count"] or 0) <= r["answer_tokens"])
                  or len(r["token_s"]) not in (r["eval_count"],
                                               r["eval_count"] + 1)]
    compare(compiles_in_window=[win["compiles_in_window"], 0],
            miscounted_requests=[len(miscounted), 0])
    lines = child([sys.executable, os.path.join(HERE, "parity.py"),
                   "--manifest", man.path, "--workload", cell["name"],
                   "--seeds", str(args.seed)], dict(os.environ),
                  os.path.join(out_dir, "parity.log"), 1100.0)
    *seeds, summary = lines
    for s in seeds:
        compare(logit_err_rms=[s["rms"], s["limit"]["rms"]],
                logit_err_max=[s["max"], s["limit"]["max"]],
                token_gap=[s["token_gap"], s["limit"]["max"]],
                sizes_wrong=[s["sizes_wrong"], []],
                cached_tokens=s["cached_tokens"], probe=s["probe"])
    pdev = summary["device"]
    if (pdev["platform"], pdev["kind"]) != (dev["platform"], dev["kind"]):
        raise BenchFailure(f"parity ran on {pdev}, the server on {dev}")
    result["correct"] = bool(summary["ok"] and served_ok and not miscounted)
    result["compared"] = COMPARED
    print(json.dumps(result), flush=True)
    for name, pair in COMPARED.items():
        print(f"compared {name}: {json.dumps(pair)}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # noqa: BLE001 — the process boundary
        import traceback
        traceback.print_exc()
        print(f"bench/run.py failed: {e}", file=sys.stderr)
        sys.exit(1)
