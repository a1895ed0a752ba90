"""Metric arithmetic over the client's own records. No program import.

A record is what ``loadgen`` kept for one request, all instants on the
client's monotonic clock relative to window open (negative = warm lap):

    index, due_s (None in a closed loop), sent_s, token_s (arrival of each
    streamed token line), done_s, eval_count, prompt_eval_count,
    done_reason, error, prompt_tokens, answer_tokens

Which records COUNT:
  open loop    the requests DUE inside [0, seconds). One not finished by
               seconds + drain_s, or refused, or broken, is ``failed``.
  closed loop  the requests that ENDED inside [0, seconds) (clients send
               back to back, so a window is a slice of a steady stream;
               what is still running when it closes is cut, not failed).
               One that ended in an error inside the window is ``failed``.
A failed request misses every limit.

``in_flight_mean`` (a request's life: waiting, decoding) counts EVERY
record for the part of it inside the window, counted or not.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional


def percentile(values: List[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile (q in 0..100), None if empty."""
    if not values:
        return None
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def mean(values: List[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None


def finished(rec: dict) -> bool:
    return (rec.get("error") is None and rec.get("done_s") is not None
            and len(rec.get("token_s") or ()) >= 1)


def counted(records: List[dict], loop: str, seconds: float,
            drain_s: float = 0.0) -> Dict[str, List[dict]]:
    """Split records into {'ok': [...], 'failed': [...]} by the rules in
    the module docstring; everything else is outside the window."""
    ok, failed = [], []
    for r in records:
        if loop == "open":
            if r["index"] < 0 or not (0.0 <= r["due_s"] < seconds):
                continue
            if finished(r) and r["done_s"] <= seconds + drain_s:
                ok.append(r)
            else:
                failed.append(r)
        else:
            end = r.get("done_s") if finished(r) else r.get("failed_s")
            if end is None or not (0.0 <= end < seconds):
                continue
            (ok if finished(r) else failed).append(r)
    return {"ok": ok, "failed": failed}


def start_of(rec: dict) -> float:
    """The instant latency is timed from: when the request was DUE in an
    open loop (a late generator or a stalled server both count), when it
    was sent in a closed loop."""
    return rec["due_s"] if rec.get("due_s") is not None else rec["sent_s"]


def ttft(rec: dict) -> float:
    return rec["token_s"][0] - start_of(rec)


def tpot(rec: dict) -> Optional[float]:
    """(last token - first token) / (tokens - 1); None for one token."""
    t = rec["token_s"]
    if len(t) < 2:
        return None
    return (t[-1] - t[0]) / (len(t) - 1)


def gaps(rec: dict) -> List[float]:
    t = rec["token_s"]
    return [b - a for a, b in zip(t, t[1:])]


def tokens_in_window(records: List[dict], seconds: float) -> int:
    """Output tokens that ARRIVED inside [0, seconds), of any request."""
    return sum(1 for r in records for t in (r.get("token_s") or ())
               if 0.0 <= t < seconds)


def _overlap(a: float, b: float, seconds: float) -> float:
    return max(0.0, min(b, seconds) - max(a, 0.0))


def in_flight_mean(records: List[dict], seconds: float,
                   phase: str) -> Optional[float]:
    """Time average over [0, seconds) of the requests in ``phase``, from
    EVERY record (a request that straddles the open or the close counts
    for the part inside): 'waiting' = sent and no token yet, 'decoding' =
    between its first and its last token. A request that was still
    running when the window closed (no done record, no error: the
    client cut it) held its phase to the close. With the clients between
    two requests these close a closed loop's account: decoding + waiting
    + turn-round = clients, and ``out_tok_s`` ~ decoding / mean gap."""
    if phase not in ("waiting", "decoding"):
        raise ValueError(f"in_flight_mean knows no phase {phase!r}")
    if seconds <= 0:
        return None
    total = 0.0
    for r in records:
        if r.get("sent_s") is None:
            continue
        tokens = r.get("token_s") or ()
        end = r["done_s"] if r.get("done_s") is not None \
            else r.get("failed_s")
        if phase == "waiting":
            a = r["sent_s"]
            b = tokens[0] if tokens else (seconds if end is None else end)
        elif tokens:
            a, b = tokens[0], seconds if end is None else tokens[-1]
        else:
            continue
        total += _overlap(a, b, seconds)
    return total / seconds


def met_limits(rec: dict, ttft_limit_s: float, tpot_limit_s: float) -> bool:
    if not finished(rec):
        return False
    tp = tpot(rec)
    return ttft(rec) <= ttft_limit_s and (tp is None or tp <= tpot_limit_s)


def early_stop_share(ok: List[dict]) -> float:
    """Share of finished requests that ended before num_predict (a stray
    EOS under random weights), in percent."""
    if not ok:
        return 0.0
    early = sum(1 for r in ok if (r.get("eval_count") or 0)
                < r["answer_tokens"])
    return 100.0 * early / len(ok)


def end_to_end(records: List[dict], split: Dict[str, List[dict]],
               seconds: float) -> Dict[str, Optional[float]]:
    """Every end-to-end candidate the client can compute, from all the
    records and their ``counted`` split; the manifest says which of them
    a cell reports."""
    ok = split["ok"]
    tp = [x for x in (tpot(r) for r in ok) if x is not None]
    all_gaps = [g for r in ok for g in gaps(r)]
    return {
        "ttft_mean_s": mean([ttft(r) for r in ok]),
        "gap_p99_s": percentile(all_gaps, 99.0),
        "tpot_p50_s": percentile(tp, 50.0),
        "out_tok_s": tokens_in_window(records, seconds) / seconds,
        "attempted": len(ok) + len(split["failed"]),
        "failed": len(split["failed"]),
    }


def accounts_read(specs: List[dict], metrics_open: Dict[str, float],
                  metrics_end: Dict[str, float]) -> Dict[str, float]:
    """A metric file may say ``"account": {"name", "total"}``: its
    numerator (``args.num``, a /metrics family) is one part of a
    partition of ``total``. For each account, the percent of the total's
    delta over the window that the given files' numerators read between
    them: 100 when every part is read by some metric, less when the
    program keeps a part nobody reads."""
    def delta(family):
        return metrics_end.get(family, 0.0) - metrics_open.get(family, 0.0)

    parts: Dict[str, set] = {}
    totals: Dict[str, str] = {}
    for spec in specs:
        acc = spec.get("account")
        if acc:
            totals[acc["name"]] = acc["total"]
            parts.setdefault(acc["name"], set()).add(spec["args"]["num"])
    return {name: 100.0 * sum(map(delta, nums)) / delta(totals[name])
            for name, nums in parts.items() if delta(totals[name]) > 0}
