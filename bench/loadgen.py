"""The load generator: one thread, one asyncio loop, streaming HTTP.

Sends a schedule from ``traffic.py`` to ``/api/generate`` and keeps, per
request, the arrival instant of every streamed token line on this
process's monotonic clock, relative to window open. Open loop: each
request sleeps until its due instant and is sent whatever the server is
doing. Closed loop: ``clients`` workers each send the next request of the
queue when their last one ended, until the window closes. The queue is
made longer than any window draws (``traffic.closed_loop``); a worker that
finds it empty before the close leaves, and the instant is kept under
``queue`` (``run.py`` fails such a run: its load was not the cell's).
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Awaitable, Callable, List, Optional

import aiohttp


def _record(req) -> dict:
    return {"index": req.index, "due_s": req.due_s, "sent_s": None,
            "token_s": [], "done_s": None, "failed_s": None,
            "eval_count": None, "prompt_eval_count": None,
            "done_reason": None, "error": None,
            "prompt_tokens": req.prompt_tokens,
            "answer_tokens": req.answer_tokens, "shared": req.shared}


async def _send(session: aiohttp.ClientSession, base: str, req, rec: dict,
                t_open: float) -> None:
    body = {"model": "bench", "prompt": req.prompt, "stream": True,
            "options": {"temperature": 0, "num_predict": req.answer_tokens}}
    rec["sent_s"] = time.monotonic() - t_open
    try:
        async with session.post(base + "/api/generate", json=body) as resp:
            if resp.status != 200:
                rec["error"] = f"HTTP {resp.status}"
                return
            async for raw in resp.content:
                now = time.monotonic() - t_open
                line = json.loads(raw)
                if line.get("done"):
                    rec["done_s"] = now
                    rec["eval_count"] = line.get("eval_count")
                    rec["prompt_eval_count"] = line.get("prompt_eval_count")
                    rec["done_reason"] = line.get("done_reason")
                elif "error" in line:
                    rec["error"] = str(line["error"])
                else:
                    rec["token_s"].append(now)
        if rec["done_s"] is None and rec["error"] is None:
            rec["error"] = "stream ended without a done record"
    except asyncio.CancelledError:
        raise
    except Exception as e:  # noqa: BLE001 — any failure is a failed request
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        if rec["error"] is not None:
            rec["failed_s"] = time.monotonic() - t_open


async def _sleep_until(t: float) -> None:
    delay = t - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)


async def _run(base: str, loop_kind: str, reqs: list, warm_lap_s: float,
               seconds: float, drain_s: float, clients: int,
               on_open: Optional[Callable[[], Awaitable[None]]],
               during: Optional[Callable[[float], Awaitable[None]]],
               on_close: Optional[Callable[[], Awaitable[None]]] = None
               ) -> dict:
    t_start = time.monotonic()
    t_open = t_start + warm_lap_s
    records = [_record(r) for r in reqs]
    queue_state = None
    conn = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=None, sock_read=300)
    async with aiohttp.ClientSession(connector=conn,
                                     timeout=timeout) as session:
        if loop_kind == "open":
            async def one(req, rec):
                await _sleep_until(t_open + req.due_s)
                await _send(session, base, req, rec, t_open)
            tasks = [asyncio.create_task(one(q, r))
                     for q, r in zip(reqs, records)]
        else:
            queue = iter(zip(reqs, records))
            queue_state = {"drawn": 0, "queued": len(reqs), "dry_s": None}

            async def client():
                while time.monotonic() < t_open + seconds:
                    try:
                        req, rec = next(queue)
                    except StopIteration:
                        if queue_state["dry_s"] is None:
                            queue_state["dry_s"] = time.monotonic() - t_open
                        return
                    queue_state["drawn"] += 1
                    await _send(session, base, req, rec, t_open)
            tasks = [asyncio.create_task(client()) for _ in range(clients)]
        side = []
        await _sleep_until(t_open)
        if on_open is not None:
            side.append(asyncio.create_task(on_open()))
        if during is not None:
            side.append(asyncio.create_task(during(t_open)))
        await _sleep_until(t_open + seconds)
        if loop_kind == "open":
            await asyncio.wait(tasks, timeout=drain_s)
        for t in tasks:
            if not t.done():
                t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        if on_close is not None:
            # Before waiting for ``during``: what outlasts the window (a
            # profiler still writing its trace) is no part of it.
            side.append(asyncio.create_task(on_close()))
        side_out = await asyncio.gather(*side, return_exceptions=True)
    return {"records": records, "t_open": t_open,
            "warm_lap_s": warm_lap_s, "side": side_out,
            "queue": queue_state}


def run(base: str, loop_kind: str, reqs: list, warm_lap_s: float,
        seconds: float, drain_s: float = 0.0, clients: int = 0,
        on_open=None, during=None, on_close=None) -> dict:
    """Run one warm lap + window. ``on_open`` (a coroutine function) runs
    at window open, ``during(t_open)`` beside the window, ``on_close`` once
    the window (and an open loop's drain) is over, whether ``during`` has
    returned or not; their results (or exceptions) come back under 'side'
    in that order; a closed loop's queue count
    ({drawn, queued, dry_s}) under 'queue'."""
    return asyncio.run(_run(base, loop_kind, reqs, warm_lap_s, seconds,
                            drain_s, clients, on_open, during, on_close))
