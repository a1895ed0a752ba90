"""One packed operand a dispatch (engine/staging.py): the layout is
exact, the key folded in the graph is the eager one, a dispatch makes
one transfer, and scheduling modes stay byte-equal on it."""

import jax
import numpy as np
import pytest

from tpu_inference import config as cfgs
from tpu_inference.engine import staging
from tpu_inference.engine.engine import InferenceEngine, Sequence

ENGINE_KW = dict(page_size=8, num_pages=96, max_pages_per_seq=12,
                 max_batch_size=4, prefill_buckets=(16, 32),
                 decode_steps_per_call=4, max_prefill_batch=2,
                 chunked_prefill_size=16, enable_prefix_cache=False)


def _engine(preset="tiny-llama", **kw):
    return InferenceEngine(cfgs.PRESETS[preset](vocab_size=256),
                           cfgs.EngineConfig(**{**ENGINE_KW, **kw}), seed=3)


def _bits(x) -> np.ndarray:
    return np.asarray(x).view(np.int32)


# (a) unpack(pack(fields)) is bit-identical, float bits included.

@pytest.mark.parametrize("layout,lanes", [
    (staging.decode_layout(6), 2),
    (staging.decode_layout(6), 8),
    (staging.prefill_layout(32, 6), 2),        # a batched prefill
    (staging.prefill_layout(16, 12), 1),       # a chunk, a table a kind
], ids=["decode-b2", "decode-b8", "prefill-2x32", "chunk-1x16"])
def test_layout_round_trip_is_bit_exact(layout, lanes):
    rng = np.random.default_rng(lanes + layout.width)
    packed = layout.blank(lanes)
    views = layout.views(packed)
    want = {}
    for name, view in views.items():
        if view.dtype == np.float32:
            # Sampling values and odd bit patterns alike: subnormals,
            # negative zero, the largest finite float.
            vals = rng.choice(np.asarray(
                [0.0, -0.0, 0.8, 0.95, 1.0, 1.3, 1e-6, 1e-45, 3.4028235e38],
                np.float32), size=view.shape)
        else:
            vals = rng.integers(-2**31, 2**31 - 1, size=view.shape,
                                dtype=np.int64).astype(np.int32)
        view[...] = vals
        want[name] = vals.copy()
    assert set(want) == set(layout.cols)
    # Every column belongs to exactly one field.
    covered = sorted(c for c0, w, _ in layout.cols.values()
                     for c in range(c0, c0 + max(1, w)))
    assert covered == list(range(layout.width))
    got = jax.jit(layout.unpack)(jax.device_put(packed))
    for name, vals in want.items():
        assert got[name].dtype == vals.dtype, name
        assert got[name].shape == vals.shape, name
        assert np.array_equal(_bits(got[name]), _bits(vals)), name
    # The host's own views read the same array back.
    again = layout.views(np.asarray(jax.device_put(packed)).copy())
    for name, vals in want.items():
        assert np.array_equal(_bits(again[name]), _bits(vals)), name


def test_blank_lanes_are_inert_and_the_bucket_reads_off_the_width():
    d = staging.decode_layout(5).views(staging.decode_layout(5).blank(3))
    assert not d["allowed"].any() and (d["eos_ids"] == -1).all()
    assert (d["seeds"] == -1).all() and (d["windows"] == -1).all()
    assert (d["top_ps"] == 1.0).all() and (d["rpens"] == 1.0).all()
    assert not d["temps"].any() and not d["bts"].any()
    for bucket in (16, 512):
        layout = staging.prefill_layout(bucket, 7)
        assert staging.prefill_bucket(layout.width, 7) == bucket
        p = layout.views(layout.blank(2))
        assert (p["prompt_len"] == 1).all() and not p["block_table"].any()
        assert p["tokens"].shape == (2, bucket)


# (b) the key folded in the graph is the eager one, in the eager order.

def test_key_folded_in_the_graph_equals_the_eager_fold():
    eng = _engine()
    for n in (1, 7, 2**31 - 1):
        want = jax.random.fold_in(eng._base_key, n)
        d = eng._decode_layout.blank(4)
        eng._decode_layout.views(d)["step"][:] = n
        got = jax.jit(eng._decode_operands)(eng._base_key, d, None)[5]
        assert np.array_equal(np.asarray(got), np.asarray(want))
        layout = eng._prefill_layout(16)
        p = layout.blank(2)
        layout.views(p)["step"][:] = n
        got = jax.jit(eng._prefill_operands)(eng._base_key, p)[4]
        assert np.array_equal(np.asarray(got), np.asarray(want))
    # The programs that still take a key get the same stream.
    before = eng._step_count
    assert np.array_equal(
        np.asarray(eng._next_key()),
        np.asarray(jax.random.fold_in(eng._base_key, before + 1)))
    assert eng._next_step() == before + 2


class _Puts:
    """Records what an engine hands _put_operands, call by call."""

    def __init__(self, eng, on_put=None):
        self.calls = []
        real = eng._put_operands

        def spy(*hosts):
            self.calls.append([h.copy() for h in hosts])
            if on_put is not None:
                on_put(*hosts)
            return real(*hosts)
        eng._put_operands = spy


def _steps(eng, host):
    layout = (eng._decode_layout if host.shape[1] == eng._decode_layout.width
              else eng._prefill_layout(staging.prefill_bucket(
                  host.shape[1], eng.bt_width)))
    step = layout.views(host)["step"]
    assert (step == step[0]).all()
    return int(step[0])


def _counts(eng):
    tel = eng.telemetry
    return tel.stage_transfers.value, tel.stage_dispatches.value


# (c) one transfer a dispatch (two for a hybrid call), and (b)'s order:
# a hybrid call draws the decode half's number before the chunk's.

def test_a_dispatch_is_one_transfer_and_a_hybrid_two():
    eng = _engine(hybrid_prefill=True)
    puts = _Puts(eng)
    t0, d0 = _counts(eng)
    n0 = eng._step_count
    eng.prefill_many([Sequence(request_id=i, prompt_tokens=[3 + i, 5, 9],
                               max_new_tokens=40) for i in range(2)])
    assert _counts(eng) == (t0 + 1, d0 + 1)            # a batched prefill
    eng.decode_steps()
    assert _counts(eng) == (t0 + 2, d0 + 2)            # a fused-K decode
    eng.decode_steps(max_steps=1)
    assert _counts(eng) == (t0 + 3, d0 + 3)            # the one-step program
    long = Sequence(request_id=9, prompt_tokens=list(range(1, 41)),
                    max_new_tokens=4)
    eng.prefill_begin(long)
    eng.prefill_step(long)
    assert _counts(eng) == (t0 + 4, d0 + 4)            # a serial chunk
    eng.decode_steps_pipelined(long)
    assert _counts(eng) == (t0 + 6, d0 + 5)            # a hybrid call
    assert eng.hybrid_steps_total == 1
    assert [len(c) for c in puts.calls] == [1, 1, 1, 1, 2]
    numbers = [[_steps(eng, h) for h in call] for call in puts.calls]
    chunk, decode = numbers[-1]
    assert numbers[:-1] == [[n0 + 1], [n0 + 2], [n0 + 3], [n0 + 4]]
    assert (decode, chunk) == (n0 + 5, n0 + 6)
    assert puts.calls[-1][0].shape[0] == 1             # the chunk's operand
    assert puts.calls[-1][1].shape[0] == eng.decode_rung


# (d) scheduling modes stay byte-equal on the packed path.

def _requests():
    out = []
    for i in range(5):
        kw = (dict(temperature=0.8, top_p=0.9, top_k=40, seed=1234 + i,
                   repeat_penalty=1.3, repeat_last_n=32) if i % 2 == 0
              else dict(temperature=0.0, repeat_penalty=1.2))
        out.append(Sequence(
            request_id=i, max_new_tokens=10 + 2 * i,
            prompt_tokens=[(7 * i + 3 * j) % 250 + 1
                           for j in range(5 + 9 * i)], **kw))
    return out


def _serve(**kw):
    """Three requests up front, two more arriving between decode rounds
    (the second through an incremental prefill: it rides hybrid calls
    where the engine has them)."""
    eng = _engine(max_batch_size=6, **kw)
    reqs = _requests()
    eng.prefill_many(reqs[:3])
    late, pending, turn = reqs[3:], None, 0
    while not all(s.done for s in reqs):
        turn += 1
        assert turn < 200, "no progress"
        if turn == 2:
            eng.prefill(late.pop())
        if turn == 4:
            pending = late.pop()
            eng.prefill_begin(pending)
        if pending is not None and not eng.engine_cfg.hybrid_prefill:
            if eng.prefill_step(pending):
                pending = None
        eng.decode_steps_pipelined(pending)
        if pending is not None and pending.prefill_prompt is None:
            pending = None
        for s in reqs:
            if s.done and s.slot >= 0:
                eng.release(s)
    eng.drain_pipeline()
    assert all(s.done for s in reqs)
    return {s.request_id: list(s.generated) for s in reqs}, eng


@pytest.fixture(scope="module")
def serial_streams():
    return _serve()[0]


@pytest.mark.parametrize("mode", [
    dict(stage_host_reuse=False),
    dict(decode_pipeline_depth=2),
    dict(decode_pipeline_depth=3, stage_host_reuse=False),
    dict(hybrid_prefill=True),
    dict(hybrid_prefill=True, decode_pipeline_depth=2),
], ids=["rebuild", "depth2", "depth3-rebuild", "hybrid", "hybrid-depth2"])
def test_modes_are_byte_equal_on_the_packed_path(serial_streams, mode):
    got, eng = _serve(**mode)
    assert got == serial_streams
    assert all(len(v) >= 10 for v in got.values())
    if mode.get("hybrid_prefill"):
        assert eng.hybrid_steps_total > 0
    # Deeper than 1 the fused-K program takes a carry and no other
    # variant of it is compiled; at depth 1 it takes none.
    assert bool(eng._null_carry) == (mode.get("decode_pipeline_depth", 1) > 1)
    assert eng._decode_multi_jit._cache_size() == 1


# (e) what the persistent operand holds is what a rebuild would stage:
# a slot that changes owner, a block-table row that grows, a row whose
# window-kind pages were released (a two-kind table).

def test_reused_rows_reach_the_device_as_a_rebuild_stages_them():
    eng = _engine("tiny-laguna", max_batch_size=2, decode_steps_per_call=4)
    assert eng.bt_width == 2 * eng.max_pages           # [full | window]
    checked = {"owner": 0, "grew": 0, "released": 0, "dispatches": 0}
    last = {}                      # slot -> (request, pages held) last time

    def check(host):
        """At the hand-off: host state is what the dispatch was staged
        from (grants made, nothing folded yet)."""
        if host.shape[1] != eng._decode_layout.width:
            return                                      # a prefill's
        f = eng._decode_layout.views(host)
        for slot, seq in enumerate(eng.slots):
            if seq is None or f["allowed"][slot] == 0:
                continue
            top_k, seed = eng._sampling_arrays(seq)
            rpen, rlast = eng._penalty_arrays(seq)
            want_bt = eng._block_table_array(seq.pages)
            assert np.array_equal(f["bts"][slot], want_bt)
            assert f["tokens"][slot] == seq.last_token
            assert f["ctx"][slot] == seq.ctx_len
            assert _bits(f["temps"][slot]) == _bits(np.float32(
                seq.temperature))
            assert _bits(f["top_ps"][slot]) == _bits(np.float32(seq.top_p))
            assert _bits(f["rpens"][slot]) == _bits(np.float32(rpen))
            assert (f["top_ks"][slot], f["seeds"][slot],
                    f["rlasts"][slot]) == (top_k, seed, rlast)
            if rpen != 1.0:
                assert np.array_equal(f["windows"][slot],
                                      eng._penalty_window_row(seq))
            was = last.get(slot)
            if was is not None:
                checked["owner"] += was[0] != seq.request_id
                checked["grew"] += (was[0] == seq.request_id
                                    and len(seq.pages) > was[1])
            window = want_bt[eng.max_pages:eng.max_pages
                             + len(seq.pages.window)]
            checked["released"] += bool(len(window)
                                        and (window == 0).any()
                                        and window[-1] != 0)
            last[slot] = (seq.request_id, len(seq.pages))
        checked["dispatches"] += 1

    def request(i, n, **kw):
        return Sequence(request_id=i, max_new_tokens=n,
                        prompt_tokens=[(5 * i + j) % 250 + 1
                                       for j in range(6 + i)], **kw)

    waiting = [request(2, 12, temperature=0.7, seed=5, top_k=20),
               request(3, 8)]
    eng.prefill_many([
        request(0, 40, temperature=0.9, seed=11, repeat_penalty=1.4),
        request(1, 6, temperature=0.0)])
    _Puts(eng, on_put=check)
    while eng.active_sequences() or waiting:
        eng.decode_steps()
        for s in [s for s in eng.slots if s is not None and s.done]:
            eng.release(s)
            if waiting:
                eng.prefill(waiting.pop(0))
    assert checked["owner"] >= 2 and checked["grew"] >= 3
    assert checked["released"] >= 3 and checked["dispatches"] >= 8
