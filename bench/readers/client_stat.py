"""A statistic of the client's own records (the load generator's clock)."""

import metrics as M     # bench/ is on sys.path wherever a reader is loaded


IN_FLIGHT = {"streams_decoding_mean": "decoding",
             "clients_waiting_mean": "waiting"}


def read(ctx, stat):
    ok, failed = ctx["ok"], ctx["failed"]
    traffic = ctx["traffic"]
    if stat == "ttft_mean_s":
        return M.mean([M.ttft(r) for r in ok])
    if stat == "ttft_p90_s":
        return M.percentile([M.ttft(r) for r in ok], 90.0)
    if stat in IN_FLIGHT:
        return M.in_flight_mean(ctx["records"], ctx["seconds"],
                                IN_FLIGHT[stat])
    if stat == "gen_late_p99_s":
        late = [r["sent_s"] - r["due_s"] for r in ok + failed
                if r.get("due_s") is not None and r["sent_s"] is not None]
        return M.percentile(late, 99.0)
    if stat == "slo_met_share":
        n = len(ok) + len(failed)
        if not n:
            return None
        met = sum(1 for r in ok if M.met_limits(
            r, traffic["ttft_limit_s"], traffic["tpot_limit_s"]))
        return 100.0 * met / n
    raise ValueError(f"client_stat knows no {stat!r}")
