"""BENCHMARK.json -> the files of one cell, found by name.

The harness knows no cell, configuration, traffic mix or per-layer metric
by name. A later PR adds one by adding files and one manifest entry:

    configuration   the ``file`` its ``configs`` entry names
    traffic mix     <root>/traffic/<traffic>.json
    per-layer       the cell's configuration's ``readings[<reading>]``, else
    metric          <root>/layer_metrics/<reading>.json: either names a
                    reader, <root>/readers/<reader>.py, and its arguments
    probe           <root>/probes/<probe>.py, named by the configuration's
                    ``parity.probe`` (none named: ``refeed``)

One ``per_layer`` entry a reading, whatever the architecture: a
configuration that computes the reading another way (a latent pool, a pool
a kind, a looped stack) names its reader under ``readings`` in its own
file, which the PR that adds the configuration brings; the file under
``layer_metrics`` is the reading's default. A per-layer metric
``<reading>.<suffix>`` (one quantity split by the end-to-end metric it
moves in different cells, or a ``.watch`` candidate) reads ``<reading>``:
one spec a reading and configuration. Unit, layer, ``moves`` and cells of
each name are the manifest entry's, where the contract puts them.

``<root>`` is the manifest's first ``paths`` entry. A test manifest may
carry ``_root`` to keep tiny configurations and mixes of its own; what it
does not have there is taken from the real root.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional


class ManifestError(Exception):
    pass


class ProbeError(Exception):
    """A probe that cannot give the engine's logits says why: no file of
    its name, a program without its surface, a position nobody kept."""


def load_module(path: str):
    """A Python file found by name (a reader, a reference) as a module."""
    name = "bench_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    def __init__(self, path: str):
        self.path = os.path.abspath(path)
        # The checkout: the directory that holds this benchmark's root.
        self.repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(self.path) as f:
            self.data = json.load(f)
        self.root = os.path.join(self.repo, self.data["paths"][0])
        self.roots = [self.root]
        if self.data.get("_root"):
            self.roots.insert(0, os.path.join(self.repo, self.data["_root"]))

    def _find(self, *parts: str) -> str:
        for root in self.roots:
            p = os.path.join(root, *parts)
            if os.path.exists(p):
                return p
        raise ManifestError(f"no {os.path.join(*parts)} under "
                            f"{[os.path.relpath(r, self.repo) for r in self.roots]}")

    def _entry(self, section: str, name: str) -> dict:
        for e in self.data[section]:
            if e["name"] == name:
                return e
        names = [e["name"] for e in self.data[section]]
        raise ManifestError(f"no {name!r} in {section}: {names}")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, cell: dict) -> dict:
        entry = self._entry("configs", cell["config"])
        with open(os.path.join(self.repo, entry["file"])) as f:
            cfg = json.load(f)
        cfg["_entry"] = entry
        return cfg

    def traffic_path(self, cell: dict) -> str:
        return self._find("traffic", cell["traffic"] + ".json")

    def metrics_of(self, section: str, cell_name: str) -> List[dict]:
        """The metrics of ``section`` this cell reports: those with no
        ``workloads`` key, or whose key lists the cell."""
        return [m for m in self.data[section]
                if "workloads" not in m or cell_name in m["workloads"]]

    def layer_metric(self, name: str, cfg: Optional[dict] = None) -> dict:
        """``{"reader": ..., "args": {...}}`` of one per-layer metric: what
        the configuration ``cfg`` (as ``config`` returns it) says under
        ``readings``, else the reading's file; each is asked for the whole
        name first, then for its stem before the first ``.``."""
        stem = name.split(".")[0]
        readings = (cfg or {}).get("readings", {})
        for n in (name, stem):
            if n in readings:
                return readings[n]
        try:
            path = self._find("layer_metrics", name + ".json")
        except ManifestError:
            path = self._find("layer_metrics", stem + ".json")
        with open(path) as f:
            return json.load(f)

    def reader(self, name: str) -> Callable[..., Optional[float]]:
        return load_module(self._find("readers", name + ".py")).read

    def peaks(self, device_kind: str) -> Dict[str, Any]:
        with open(self._find("peaks.json")) as f:
            table = json.load(f)
        if device_kind not in table:
            raise ManifestError(
                f"device kind {device_kind!r} is not in peaks.json "
                f"({sorted(k for k in table if not k.startswith('_'))}): "
                "an unknown chip has no roofline")
        return table[device_kind]
