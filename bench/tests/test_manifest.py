"""The harness is driven by data: a later PR adds a configuration, a
traffic mix and a per-layer metric as NEW files plus manifest entries,
editing no file that is there. Shown in a temporary copy."""

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
            spec = man.layer_metric(m["name"])
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

    # A new configuration, traffic mix, per-layer metric and reader.
    cfg = json.loads((copy / "bench/configs/mistral-7b-int8.json").read_text())
    cfg["name"] = "newmodel-int8"
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
    (copy / "BENCHMARK.json").write_text(json.dumps(data))

    probe = (
        "import sys, json; sys.path.insert(0, 'bench')\n"
        "from manifest import Manifest\n"
        "import traffic as T\n"
        "m = Manifest('BENCHMARK.json')\n"
        "c = m.cell('newmodel-int8_chat-slow')\n"
        "t = T.load(m.traffic_path(c))\n"
        "names = [x['name'] for x in m.metrics_of('per_layer', c['name'])]\n"
        "spec = m.layer_metric('answer_tokens_mean')\n"
        "v = m.reader(spec['reader'])({'ok': [{'answer_tokens': 8},"
        " {'answer_tokens': 4}]}, **spec['args'])\n"
        "print(json.dumps([m.config(c)['name'], t['rate'],"
        " 'answer_tokens_mean' in names, 'prefix_hit_share' in names, v,"
        " len(T.open_loop(t, 1, 48))]))\n")
    out = subprocess.run([sys.executable, "-c", probe], cwd=copy,
                         capture_output=True, text=True, check=True)
    name, rate, has_new, has_steady_only, value, n = json.loads(out.stdout)
    assert (name, rate, has_new, has_steady_only, value) == (
        "newmodel-int8", 1.5, True, False, 6.0)
    assert n == 72 + round(mix["warm_lap_s"] * 72 / 48)
    after = {p: p.read_bytes() for p in (copy / "bench").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert all(after[p] == b for p, b in before.items()), \
        "adding a cell edited a file that was there"
