"""The engine's step ledger (GET /debug/steps, taken when the window
closed; the ledger's own window is its last 60 s, so a shorter run's
reading includes the end of the warm lap).

  decode_batch_mean    sequences per decode dispatch
  decode_step_ms       decode dispatch wall / decode steps, where steps
                       per dispatch = tokens / sequences of the dispatch
"""


def _fleet(ctx):
    rep = ctx["steps"].get("fleet") or {}
    return rep if rep.get("enabled") else None


def read(ctx, what):
    rep = _fleet(ctx)
    if rep is None:
        return None
    kinds = rep["kinds"]
    occ = rep.get("rung_occupancy", {})
    disp = sum(v["dispatches"] for v in occ.values())
    slots = sum(v["dispatches"] * v["mean_slots"] for v in occ.values())
    if what == "decode_batch_mean":
        return slots / disp if disp else None
    if what == "decode_step_ms":
        d = kinds.get("decode")
        if not d or not d["tokens"] or not disp:
            return None
        steps = d["tokens"] / (slots / disp)
        return 1000.0 * d["device_s"] / steps
    raise ValueError(f"steps knows no {what!r}")
