"""The harness is driven by data: a later PR adds a configuration, a
traffic mix and a per-layer metric as NEW files plus manifest entries,
editing no file that is there. Shown in a temporary copy.

One ``per_layer`` entry a reading (PR 50): the names every configuration
brought for the readings all of them have became one entry each, read by
the reader the cell's configuration names under ``readings``. ``MOVED``
freezes what the manifest before PR 50 read under each old name, so that
a ledger line from before it can still be matched to its reading."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, REPO


def test_root_manifest_names_files_that_exist():
    from manifest import Manifest

    man = Manifest(os.path.join(REPO, "BENCHMARK.json"))
    for cell in man.data["workloads"]:
        cfg = man.config(cell)
        assert cfg["name"] == cell["config"]
        assert os.path.exists(man.traffic_path(cell))
        assert os.path.exists(os.path.join(
            BENCH, "references", cfg["reference"] + ".py"))
        e2e = {m["name"] for m in man.metrics_of("end_to_end", cell["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = man.metrics_of("per_layer", cell["name"])
        assert layer
        for m in layer:
            spec = man.layer_metric(m["name"], cfg)
            assert callable(man.reader(spec["reader"]))
            assert m["moves"] in e2e, (cell["name"], m["name"], m["moves"])
    used = {c["config"] for c in man.data["workloads"]}
    assert used == {c["name"] for c in man.data["configs"]}


def test_unknown_names_are_errors():
    from manifest import Manifest, ManifestError

    man = Manifest(os.path.join(REPO, "BENCHMARK.json"))
    with pytest.raises(ManifestError):
        man.cell("no-such-cell")
    with pytest.raises(ManifestError):
        man.peaks("TPU v9 imaginary")
    assert man.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_a_new_cell_needs_only_new_files(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(BENCH, copy / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (copy / "bench").rglob("*")
              if p.is_file()}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        data = json.load(f)

    # A new configuration with a probe of its own, a traffic mix, a
    # per-layer metric and a reader.
    cfg = json.loads((copy / "bench/configs/mistral-7b-int8.json").read_text())
    cfg["name"] = "newmodel-int8"
    cfg["parity"]["probe"] = "lastrow"
    cfg["readings"] = {"decode_hbm_roofline": {
        "reader": "answer_tokens", "args": {"scale": 2.0}}}
    (copy / "bench/probes/lastrow.py").write_text(
        "ENGINE = {'max_batch_size': 4}\n"
        "def make(eng):\n"
        "    return lambda seq, p: [eng, seq, p]\n")
    (copy / "bench/configs/newmodel-int8.json").write_text(json.dumps(cfg))
    mix = json.loads((copy / "bench/traffic/chat.json").read_text())
    mix["rate"] = 1.5
    (copy / "bench/traffic/chat-slow.json").write_text(json.dumps(mix))
    (copy / "bench/layer_metrics/answer_tokens_mean.json").write_text(
        json.dumps({"reader": "answer_tokens", "args": {"scale": 1.0}}))
    (copy / "bench/readers/answer_tokens.py").write_text(
        "def read(ctx, scale):\n"
        "    ok = ctx['ok']\n"
        "    return scale * sum(r['answer_tokens'] for r in ok) / len(ok) "
        "if ok else None\n")
    data["configs"].append({"name": "newmodel-int8", "source": "x",
                            "file": "bench/configs/newmodel-int8.json",
                            "reduced": [], "why": "test"})
    data["workloads"].append({"name": "newmodel-int8_chat-slow",
                              "config": "newmodel-int8",
                              "traffic": "chat-slow", "chips": 1,
                              "why": "test"})
    data["per_layer"].append({"name": "answer_tokens_mean", "unit": "tokens",
                              "better": "higher", "source": "host_clock",
                              "layer": "client", "moves": "tpot_p50_s",
                              "workloads": ["newmodel-int8_chat-slow"]})
    # A reading the benchmark has: the cell joins its entry's list, and
    # its configuration says who reads it there.
    for m in data["per_layer"]:
        if m["name"] == "decode_hbm_roofline":
            m["workloads"].append("newmodel-int8_chat-slow")
    (copy / "BENCHMARK.json").write_text(json.dumps(data))

    probe = (
        "import sys, json; sys.path.insert(0, 'bench')\n"
        "from manifest import Manifest\n"
        "import traffic as T\n"
        "m = Manifest('BENCHMARK.json')\n"
        "c = m.cell('newmodel-int8_chat-slow')\n"
        "t = T.load(m.traffic_path(c))\n"
        "names = [x['name'] for x in m.metrics_of('per_layer', c['name'])]\n"
        "spec = m.layer_metric('answer_tokens_mean', m.config(c))\n"
        "own = m.layer_metric('decode_hbm_roofline', m.config(c))\n"
        "old = m.layer_metric('decode_hbm_roofline',"
        " m.config(m.cell('mistral-7b-int8_chat-steady')))\n"
        "v = m.reader(spec['reader'])({'ok': [{'answer_tokens': 8},"
        " {'answer_tokens': 4}]}, **spec['args'])\n"
        "import parity\n"
        "p = parity.load_probe(m.config(c)['parity']['probe'])\n"
        "print(json.dumps([p.make('e')('s', 3),"
        " parity.probe_engine(p, ['max_batch_size'])]))\n"
        "print(json.dumps([m.config(c)['name'], t['rate'],"
        " 'answer_tokens_mean' in names, 'prefix_hit_share' in names, v,"
        " len(T.open_loop(t, 1, 48)), 'decode_hbm_roofline' in names,"
        " own['reader'], old['reader']]))\n")
    out = subprocess.run([sys.executable, "-c", probe], cwd=copy,
                         capture_output=True, text=True, check=True)
    probed, rest = out.stdout.strip().splitlines()[-2:]
    assert json.loads(probed) == [["e", "s", 3], {"max_batch_size": 4}]
    name, rate, has_new, has_steady_only, value, n, *reading = json.loads(rest)
    assert reading == [True, "answer_tokens", "kernels"]
    assert (name, rate, has_new, has_steady_only, value) == (
        "newmodel-int8", 1.5, True, False, 6.0)
    assert n == 72 + round(mix["warm_lap_s"] * 72 / 48)
    after = {p: p.read_bytes() for p in (copy / "bench").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert all(after[p] == b for p, b in before.items()), \
        "adding a cell edited a file that was there"


# -- one entry a reading (PR 50) ------------------------------------------

CELLS = ["mistral-7b-int8_chat-steady", "qwen2-7b-int8_sysprompt-steady",
         "mistral-7b-int8_doc-batch", "kimi-k2-ep32-bf16_doc-reask-batch",
         "ouro-2.6b-bf16_reason-batch", "laguna-s-ep8-bf16_code-mixed-batch",
         "phi4-mini-flash-bf16_reason-long-batch",
         "smallthinker-21b-pp4-bf16_reason-wide-batch",
         "xing4-29b-pp6-bf16_agent-tools-batch"]

_MOE = "tpu_inf_moe_"
_PAIRS = {"num": _MOE + "local_pairs_total", "den": _MOE + "tokens_total"}
_DROPPED = {"num": _MOE + "dropped_pairs_total"}
_DISTINCT = {"num": _MOE + "distinct_experts_total",
             "den": _MOE + "decode_layer_steps_total"}
_PREEMPT = {"num": "tpu_inf_preemptions_total"}


def _load(scale):
    return {"num": _MOE + "busiest_expert_pairs_total",
            "den": _MOE + "local_pairs_total", "scale": scale}


def _pool(kind):
    return {"what": "pool_live_share", "kind": kind}


# (name before PR 50, its cells as indices of CELLS, name since, and the
# reader and args the file ``layer_metrics/<old>.json`` held then)
MOVED = [
    ("prefill_ms_per_ktok.batch", "2", "prefill_ms_per_ktok.batch", "kernels",
     {"what": "prefill_ms_per_ktok", "kernel": "paged_prefill_attention"}),
    ("decode_attn_roofline", "0124", "decode_attn_roofline", "kernels",
     {"what": "decode_attn", "kernel": "paged_attention"}),
    ("prefill_attn_roofline.batch", "2", "prefill_attn_roofline.batch",
     "kernels", {"what": "prefill_attn",
                 "kernel": "paged_prefill_attention"}),
    ("decode_hbm_roofline", "012", "decode_hbm_roofline", "kernels",
     {"what": "decode_hbm", "kernel": "paged_attention"}),
    ("mla_decode_attn_roofline", "38", "decode_attn_roofline", "mla_moe",
     {"what": "mla_decode_attn"}),
    ("mla_prefill_attn_roofline", "38", "prefill_attn_roofline.batch",
     "mla_moe", {"what": "mla_prefill_attn"}),
    ("moe_experts_roofline", "3", "moe_experts_decode_roofline", "mla_moe",
     {"what": "moe_experts"}),
    ("mla_moe_decode_hbm_roofline", "3", "decode_hbm_roofline", "mla_moe",
     {"what": "mla_moe_decode_hbm"}),
    ("mla_prefill_ms_per_ktok", "3", "prefill_ms_per_ktok.batch", "mla_moe",
     {"what": "mla_prefill_ms_per_ktok"}),
    ("moe_local_pairs_per_token", "378", "moe_local_pairs_per_token",
     "metrics_delta", _PAIRS),
    ("moe_dropped_pairs", "378", "moe_dropped_pairs", "metrics_delta",
     _DROPPED),
    ("moe_expert_load_max_over_mean", "3", "moe_expert_load_max_over_mean",
     "metrics_delta", _load(12.0)),
    ("moe_decode_distinct_experts", "378", "moe_decode_distinct_experts",
     "metrics_delta", _DISTINCT),
    ("looped_decode_hbm_roofline", "4", "decode_hbm_roofline", "looped",
     {"what": "looped_decode_hbm"}),
    ("looped_prefill_attn_roofline", "4", "prefill_attn_roofline.batch",
     "looped", {"what": "looped_prefill_attn"}),
    ("looped_prefill_ms_per_ktok", "4", "prefill_ms_per_ktok.batch",
     "looped", {"what": "looped_prefill_ms_per_ktok"}),
    ("preemptions_in_window", "478", "preemptions_in_window",
     "metrics_delta", _PREEMPT),
    ("mixed_window_decode_attn_roofline", "5", "window_decode_attn_roofline",
     "mixed", {"what": "window_decode_attn"}),
    ("mixed_full_decode_attn_roofline", "5", "decode_attn_roofline", "mixed",
     {"what": "full_decode_attn"}),
    ("mixed_prefill_attn_roofline", "5", "prefill_attn_roofline.batch",
     "mixed", {"what": "prefill_attn"}),
    ("mixed_moe_experts_roofline", "5", "moe_experts_decode_roofline",
     "mixed", {"what": "moe_experts"}),
    ("mixed_decode_hbm_roofline", "5", "decode_hbm_roofline", "mixed",
     {"what": "decode_hbm"}),
    ("mixed_prefill_ms_per_ktok", "5", "prefill_ms_per_ktok.batch", "mixed",
     {"what": "prefill_ms_per_ktok"}),
    ("kv_window_pool_live_share", "5", "kv_window_pool_live_share", "mixed",
     _pool("window")),
    ("kv_full_pool_live_share", "5", "kv_full_pool_live_share", "mixed",
     _pool("full")),
    ("kv_window_pages_released_per_s", "5", "kv_window_pages_released_per_s",
     "mixed", {"what": "released_per_s"}),
    ("moe_local_pairs_per_token.mixed", "5", "moe_local_pairs_per_token",
     "metrics_delta", _PAIRS),
    ("moe_dropped_pairs.mixed", "5", "moe_dropped_pairs", "metrics_delta",
     _DROPPED),
    ("moe_decode_distinct_experts.mixed", "5", "moe_decode_distinct_experts",
     "metrics_delta", _DISTINCT),
    ("preemptions_in_window.mixed", "5", "preemptions_in_window",
     "metrics_delta", _PREEMPT),
    ("sambay_shared_decode_attn_roofline", "6", "decode_attn_roofline",
     "sambay", {"what": "shared_decode_attn"}),
    ("sambay_window_decode_attn_roofline", "6", "window_decode_attn_roofline",
     "sambay", {"what": "window_decode_attn"}),
    ("sambay_prefill_attn_roofline", "6", "prefill_attn_roofline.batch",
     "sambay", {"what": "prefill_attn"}),
    ("sambay_decode_hbm_roofline", "6", "decode_hbm_roofline", "sambay",
     {"what": "decode_hbm"}),
    ("sambay_prefill_ms_per_ktok", "6", "prefill_ms_per_ktok.batch", "sambay",
     {"what": "prefill_ms_per_ktok"}),
    ("sambay_full_pool_live_share", "6", "kv_full_pool_live_share", "sambay",
     _pool("full")),
    ("sambay_window_pool_live_share", "6", "kv_window_pool_live_share",
     "sambay", _pool("window")),
    ("sambay_window_pages_released_per_s", "6",
     "kv_window_pages_released_per_s", "sambay",
     {"what": "released_per_s"}),
    ("preemptions_in_window.sambay", "6", "preemptions_in_window",
     "metrics_delta", _PREEMPT),
    ("thinker_moe_experts_decode_roofline", "7",
     "moe_experts_decode_roofline", "smallthinker",
     {"what": "moe_experts_decode"}),
    ("thinker_moe_experts_prefill_roofline", "7",
     "moe_experts_prefill_roofline", "smallthinker",
     {"what": "moe_experts_prefill"}),
    ("thinker_decode_attn_roofline", "7", "decode_attn_roofline",
     "smallthinker", {"what": "decode_attn"}),
    ("thinker_prefill_attn_roofline", "7", "prefill_attn_roofline.batch",
     "smallthinker", {"what": "prefill_attn"}),
    ("thinker_decode_hbm_roofline", "7", "decode_hbm_roofline",
     "smallthinker", {"what": "decode_hbm"}),
    ("thinker_prefill_ms_per_ktok", "7", "prefill_ms_per_ktok.batch",
     "smallthinker", {"what": "prefill_ms_per_ktok"}),
    ("thinker_full_pool_live_share", "7", "kv_full_pool_live_share",
     "smallthinker", _pool("full")),
    ("thinker_expert_load_max_over_mean", "7",
     "moe_expert_load_max_over_mean", "metrics_delta", _load(64.0)),
    ("xing_decode_hbm_roofline", "8", "decode_hbm_roofline", "xing_mhc",
     {"what": "decode_hbm"}),
    ("xing_moe_experts_decode_roofline", "8", "moe_experts_decode_roofline",
     "xing_mhc", {"what": "moe_experts_decode"}),
    ("xing_prefill_ms_per_ktok", "8", "prefill_ms_per_ktok.batch", "mla_moe",
     {"what": "mla_prefill_ms_per_ktok"}),
]
# Gone with no reading of the same spec in their place: the log-line count
# beside the program's own counter ``xla_compiles_in_window`` (``run.py``
# still holds the log-line count to 0 inside ``correct``), and the page
# COUNT of one cell that stood in for the page's tokens, now read.
REMOVED = {"compiles_in_window": "xla_compiles_in_window",
           "kv_pool_pages": "kv_page_tokens"}


@pytest.fixture(scope="module")
def man():
    from manifest import Manifest

    return Manifest(os.path.join(REPO, "BENCHMARK.json"))


def _cells_of(man, entry):
    return set(entry.get("workloads",
                         [c["name"] for c in man.data["workloads"]]))


@pytest.mark.parametrize("old,cells,new,reader,args", MOVED,
                         ids=[row[0] for row in MOVED])
def test_an_old_name_is_read_as_before_under_its_new_name(
        man, old, cells, new, reader, args):
    assert [c["name"] for c in man.data["workloads"]] == CELLS
    names = [m["name"] for m in man.data["per_layer"]]
    assert names.count(new) == 1 and (old == new or old not in names)
    for i in cells:
        cell = man.cell(CELLS[int(i)])
        listed = [m["name"] for m in man.metrics_of("per_layer",
                                                    cell["name"])]
        assert new in listed, (cell["name"], new)
        spec = man.layer_metric(new, man.config(cell))
        assert (spec["reader"], spec.get("args", {})) == (reader, args), \
            (old, cell["name"])


def test_a_merged_entry_lists_exactly_the_cells_of_the_names_it_replaced(man):
    want = {}
    for _, cells, new, _, _ in MOVED:
        want.setdefault(new, set()).update(CELLS[int(i)] for i in cells)
    for m in man.data["per_layer"]:
        if m["name"] in want:
            assert "workloads" in m, m["name"]
            assert set(m["workloads"]) == want[m["name"]], m["name"]
            assert len(set(m["workloads"])) == len(m["workloads"])
    names = {m["name"] for m in man.data["per_layer"]}
    for gone, instead in REMOVED.items():
        assert gone not in names and instead in names
    assert _cells_of(man, man._entry("per_layer", "kv_page_tokens")) \
        == set(CELLS)


def test_every_per_layer_name_has_a_file_of_its_reading(man):
    """``tests/test_loop_clock.py`` opens one for every name of every
    cell: the file is the reading's default, whoever reads it in a cell."""
    files = {f[:-len(".json")]
             for f in os.listdir(os.path.join(BENCH, "layer_metrics"))}
    for m in man.data["per_layer"]:
        assert {m["name"], m["name"].split(".")[0]} & files, m["name"]
    # ... and no file is left that no name reads.
    stems = {m["name"].split(".")[0] for m in man.data["per_layer"]}
    assert files <= stems | {m["name"] for m in man.data["per_layer"]}


def test_no_reading_is_entered_twice_for_one_cell(man):
    """Two entries of one reading (``<reading>`` and ``<reading>.<suffix>``)
    that move the same end-to-end metric share no cell: that was how one
    counter came to stand under two names."""
    seen = {}
    for m in man.data["per_layer"]:
        key = (m["name"].split(".")[0], m["moves"])
        cells = _cells_of(man, m)
        for other, theirs in seen.setdefault(key, []):
            assert not cells & theirs, (m["name"], other, cells & theirs)
        seen[key].append((m["name"], cells))


def test_per_layer_leaves_room_for_the_next_configuration(man):
    assert len(man.data["per_layer"]) <= 95        # of the contract's 128
    for m in man.data["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200 and m["layer"].isprintable()


def _config_files():
    for root in (os.path.join(BENCH, "configs"),
                 os.path.join(BENCH, "tests", "rehearsal", "configs")):
        for name in sorted(os.listdir(root)):
            with open(os.path.join(root, name)) as f:
                yield name, json.load(f)


def test_a_configurations_readings_name_readers_and_readings_that_exist(man):
    stems = {m["name"].split(".")[0] for m in man.data["per_layer"]}
    for name, cfg in _config_files():
        for reading, spec in cfg.get("readings", {}).items():
            assert reading in stems, (name, reading)
            assert callable(man.reader(spec["reader"])), (name, reading)
            assert isinstance(spec.get("args", {}), dict)
    # A real configuration's reading is listed for one of its cells: a
    # reader nobody asks is not carried.
    for c in man.data["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            readings = json.load(f).get("readings", {})
        cells = {w["name"] for w in man.data["workloads"]
                 if w["config"] == c["name"]}
        for reading in readings:
            assert any(cells & _cells_of(man, m)
                       for m in man.data["per_layer"]
                       if m["name"].split(".")[0] == reading), \
                (c["name"], reading)
    # The rehearsal's twin of a configuration reads as the configuration.
    by_reference = {}
    for name, cfg in _config_files():
        spec = {k: (v["reader"], v.get("args", {}))
                for k, v in cfg.get("readings", {}).items()}
        assert by_reference.setdefault(cfg["reference"], spec) == spec, name


def test_the_configurations_reading_comes_before_the_readings_file(man):
    file_spec = man.layer_metric("decode_hbm_roofline")
    assert file_spec["reader"] == "kernels"
    assert man.layer_metric("decode_hbm_roofline", {"name": "x"}) \
        == file_spec
    own = {"reader": "looped", "args": {"what": "looped_decode_hbm"}}
    split = {"reader": "mixed", "args": {"what": "decode_hbm"}}
    cfg = {"readings": {"prefill_ms_per_ktok": own,
                        "prefill_ms_per_ktok.batch": split}}
    assert man.layer_metric("prefill_ms_per_ktok.steady", cfg) == own
    assert man.layer_metric("prefill_ms_per_ktok.batch", cfg) == split
    assert man.layer_metric("ttft_mean_s.watch", cfg)["reader"] \
        == "client_stat"
