"""The system under test as a child process: the server's normal CLI.

The child owns the chip(s) for its lifetime; this process never imports
JAX. Everything the benchmark learns from the program comes over HTTP
(/healthz, /metrics, /debug/steps, /debug/profile), from the child's log,
or from the flight recorder's capture it writes under ``--blackbox-dir``
(inside this run's output directory) when it exits.
"""

from __future__ import annotations

import glob
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional


class ServerError(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def parse_metrics(text: str) -> Dict[str, float]:
    """Prometheus text -> {family: sum of its replica-labelled samples}.
    Fleet-level duplicates (samples with no ``replica`` label of a family
    that also has labelled ones) are not added twice."""
    labelled: Dict[str, float] = {}
    bare: Dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, val = line.rpartition(" ")
        try:
            v = float(val)
        except ValueError:
            continue
        name, _, labels = head.partition("{")
        if "_bucket" in name or 'q="' in labels:
            continue
        if 'replica="' in labels:
            labelled[name] = labelled.get(name, 0.0) + v
        else:
            bare[name] = bare.get(name, 0.0) + v
    bare.update(labelled)
    return bare


class Server:
    def __init__(self, repo: str, flags: List[str], out_dir: str,
                 boot_timeout_s: float = 1100.0):
        self.repo, self.out_dir = repo, out_dir
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        os.makedirs(out_dir, exist_ok=True)
        self.log_path = os.path.join(out_dir, "server.log")
        self.boot_timeout_s = boot_timeout_s
        env = dict(os.environ)
        # Every compile is a log line: compiles_in_window counts them.
        env["JAX_LOG_COMPILES"] = "1"
        cmd = [sys.executable, "-m", "tpu_inference.server", *flags,
               "--port", str(self.port), "--debug",
               "--blackbox-dir", os.path.join(out_dir, "blackbox"),
               "--step-ledger-depth", "16384"]
        self._log = open(self.log_path, "wb")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(cmd, cwd=repo, env=env,
                                     stdout=self._log,
                                     stderr=subprocess.STDOUT)

    # ------------------------------------------------------------ HTTP
    def get_text(self, path: str, timeout: float = 30.0) -> str:
        with urllib.request.urlopen(self.base + path, timeout=timeout) as r:
            return r.read().decode()

    def get_json(self, path: str, timeout: float = 30.0):
        return json.loads(self.get_text(path, timeout))

    def metrics(self) -> Dict[str, float]:
        return parse_metrics(self.get_text("/metrics"))

    def post_json(self, path: str, body: dict, timeout: float = 120.0):
        req = urllib.request.Request(
            self.base + path, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read().decode())

    # ------------------------------------------------------- lifecycle
    def log_tail(self, n: int = 3000) -> str:
        with open(self.log_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")

    def log_size(self) -> int:
        return os.path.getsize(self.log_path)

    def compiles_since(self, offset: int) -> List[str]:
        """The functions the child compiled after byte ``offset`` of its
        log (jax logs 'Compiling <fn> with global shapes ...' once per
        XLA compile request, a persistent-cache hit included)."""
        names = []
        with open(self.log_path, "rb") as f:
            f.seek(offset)
            for line in f:
                _, found, rest = line.partition(b"Compiling ")
                if found:
                    names.append(rest.split(b" with ")[0].decode(
                        errors="replace")[:80])
        return names

    def wait_ready(self) -> float:
        deadline = self.t0 + self.boot_timeout_s
        while time.monotonic() < deadline:
            rc = self.proc.poll()
            if rc is not None:
                raise ServerError(f"server exited rc={rc} before serving:\n"
                                  f"{self.log_tail()}")
            try:
                self.get_json("/healthz", timeout=5)
                return time.monotonic() - self.t0
            except (urllib.error.URLError, ConnectionError, TimeoutError,
                    OSError):
                time.sleep(0.5)
        raise ServerError(f"server not serving after {self.boot_timeout_s}s:"
                          f"\n{self.log_tail()}")

    def device(self) -> dict:
        """What the replicas really run on, from /healthz."""
        hz = self.get_json("/healthz")
        devs = [r["device"] for r in hz["replicas"]]
        kinds = {(d["platform"], d["kind"]) for d in devs}
        if len(kinds) != 1:
            raise ServerError(f"replicas on different devices: {kinds}")
        peaks = [d.get("peak_bytes_in_use") for d in devs]
        return {"platform": devs[0]["platform"], "kind": devs[0]["kind"],
                "count": sum(len(d["ids"]) for d in devs),
                "memory_peak_bytes": max((p for p in peaks if p), default=0),
                "replicas": devs}

    def ledger(self) -> List[dict]:
        """The engine's per-dispatch records (unix ``ts``, ``kind``,
        ``chunk_tokens``, ``kv_read_tokens``, ...) of the whole run, from
        the capture each replica's flight recorder wrote at exit; [] if
        there is none. Call after ``stop``."""
        steps: List[dict] = []
        for path in glob.glob(os.path.join(self.out_dir, "blackbox", "*",
                                           "capture-*-atexit.json")):
            with open(path) as f:
                steps.extend(json.load(f).get("steps") or [])
        return sorted(steps, key=lambda r: r["ts"])

    def stop(self, timeout_s: float = 40.0) -> Optional[int]:
        """SIGTERM, wait, then kill whatever is left. Always returns with
        the child (and its process group's log handle) gone."""
        rc = self.proc.poll()
        if rc is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                rc = self.proc.wait(timeout_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                rc = self.proc.wait()
        self._log.close()
        return rc
