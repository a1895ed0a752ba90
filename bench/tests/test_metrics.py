"""Metric arithmetic: window membership, drain, failures, percentiles."""

import pytest

import metrics as M


def rec(index, due, tokens, done=None, error=None, answer=4, sent=None):
    return {"index": index, "due_s": due,
            "sent_s": due if sent is None else sent, "token_s": tokens,
            "done_s": done, "failed_s": None if error is None else done,
            "eval_count": len(tokens), "prompt_eval_count": 10,
            "done_reason": "length", "error": error,
            "prompt_tokens": 10, "answer_tokens": answer}


def test_open_loop_counts_requests_due_inside_the_window():
    recs = [rec(-1, -0.5, [0.1, 0.2], 0.2),          # warm lap
            rec(0, 0.0, [0.3, 0.4], 0.4),
            rec(1, 9.9, [10.5, 10.6], 10.6),         # due inside, ends in drain
            rec(2, 10.0, [10.5], 10.5)]              # due at the close: out
    s = M.counted(recs, "open", 10.0, drain_s=2.0)
    assert [r["index"] for r in s["ok"]] == [0, 1] and not s["failed"]


def test_open_loop_unfinished_after_drain_is_failed():
    recs = [rec(0, 1.0, [1.5, 1.6], 13.0),           # done after drain
            rec(1, 2.0, [2.5], None),                # never done
            rec(2, 3.0, [], 3.1, error="HTTP 503")]  # refused
    s = M.counted(recs, "open", 10.0, drain_s=2.0)
    assert not s["ok"] and len(s["failed"]) == 3
    e = M.end_to_end(recs, s, 10.0)
    assert e["attempted"] == 3 and e["failed"] == 3


def test_closed_loop_counts_requests_that_end_inside_the_window():
    recs = [rec(0, None, [-3.0, 0.5], 0.5, sent=-4.0),   # began in warm lap
            rec(1, None, [1.0, 2.0], 2.0, sent=0.6),
            rec(2, None, [9.0, 10.5], 10.5, sent=8.0),   # cut by the close
            rec(3, None, [], 4.0, error="boom", sent=3.0)]
    s = M.counted(recs, "closed", 10.0)
    assert [r["index"] for r in s["ok"]] == [0, 1]
    assert [r["index"] for r in s["failed"]] == [3]


def test_ttft_is_timed_from_the_due_instant_not_the_send():
    r = rec(0, 1.0, [1.8, 1.9], 1.9, sent=1.3)
    assert M.ttft(r) == pytest.approx(0.8)
    c = rec(0, None, [1.8, 1.9], 1.9, sent=1.3)
    assert M.ttft(c) == pytest.approx(0.5)


def test_tpot_gaps_and_tokens_in_window():
    r = rec(0, 0.0, [1.0, 1.0, 1.0, 1.4, 1.4, 1.8], 1.8)
    assert M.tpot(r) == pytest.approx(0.8 / 5)
    assert M.gaps(r) == pytest.approx([0, 0, 0.4, 0, 0.4])
    assert M.tpot(rec(0, 0.0, [1.0], 1.0)) is None
    late = rec(1, 9.0, [9.5, 9.9, 10.2], 10.2)
    assert M.tokens_in_window([r, late], 10.0) == 8
    e = M.end_to_end([r, late],
                     M.counted([r, late], "open", 10.0, 1.0), 10.0)
    assert e["out_tok_s"] == pytest.approx(0.8)
    assert e["gap_p99_s"] is not None and e["tpot_p50_s"] is not None


def test_a_failed_request_misses_every_limit():
    good = rec(0, 0.0, [0.2, 0.25], 0.25)
    slow = rec(1, 0.0, [1.5, 1.55], 1.55)
    dead = rec(2, 0.0, [], None, error="x")
    assert M.met_limits(good, 1.0, 0.08)
    assert not M.met_limits(slow, 1.0, 0.08)
    assert not M.met_limits(dead, 1.0, 0.08)


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 2.5), (100, 4.0),
                                    (99, 3.97)])
def test_percentile(q, want):
    assert M.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)
    assert M.percentile([], q) is None


def test_early_stop_share():
    a = rec(0, 0.0, [0.1, 0.2], 0.2, answer=4)       # 2 of 4: early
    b = rec(1, 0.0, [0.1, 0.2, 0.3, 0.4], 0.4, answer=4)
    assert M.early_stop_share([a, b]) == pytest.approx(50.0)
