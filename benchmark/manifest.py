"""``BENCHMARK.json`` and the files it names.

A cell is an entry of ``workloads``: its configuration is
``benchmark/configs/<config>.json``, its traffic mix
``benchmark/workloads/<traffic>.json``.  The configuration names its
``family`` (``benchmark/families/<family>.py``, the adapter that builds the
system under test, with the plain reference beside it), the mix names its
``kind`` (``benchmark/traffic/<kind>.py``, the generator that reads it), and
a per-layer metric is read by ``benchmark/layer_metrics/<name>.py``.  A later
PR adds a cell, a configuration, a mix or a metric by adding files and
entries; nothing here lists them.  ``benchmark/held_out/`` (a cell's entries
kept out of ``BENCHMARK.json``, and why) is read by the tests alone.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List

BENCH_DIR = "benchmark"
_NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
_PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
         "end_to_end", "per_layer"}
_SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
MAX_BOUND = 0.1
MAX_FOUR_CHIP_SHARE = 0.25


class ManifestError(ValueError):
    pass


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


class Manifest:
    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.data = load_json(os.path.join(self.root, "BENCHMARK.json"))

    # ------------------------------------------------------------ look-ups
    def path(self, *parts: str) -> str:
        return os.path.join(self.root, *parts)

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json; have "
                            f"{[w['name'] for w in self.data['workloads']]}")

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.data["configs"]:
            if c["name"] == name:
                return load_json(self.path(c["file"]))
        raise ManifestError(f"no config {name!r} in BENCHMARK.json")

    def mix(self, traffic: str) -> Dict[str, Any]:
        return load_json(self.path(BENCH_DIR, "workloads", traffic + ".json"))

    def metrics_for(self, group: str, cell: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` entries a cell reports: those
        that list it under ``workloads``, and those that list no cells."""
        return [m for m in self.data[group]
                if "workloads" not in m or cell in m["workloads"]]

    def module(self, kind: str, name: str):
        """``benchmark/<kind>/<name>.py``, loaded from its file: a metric's
        name may hold dots, which no import statement could spell."""
        path = self.path(BENCH_DIR, kind, name + ".py")
        if not os.path.isfile(path):
            raise ManifestError(f"{kind} {name!r} has no file {path}")
        mod_name = "benchmark_{}_{}".format(kind, re.sub(r"\W", "_", name))
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    # ---------------------------------------------------------- validation
    def problems(self) -> List[str]:
        """Every way this manifest breaks the benchmark's contract that can
        be seen without running anything; empty when it holds."""
        d, bad = self.data, []
        if set(d) != _KEYS:
            bad.append(f"keys {sorted(d)} != {sorted(_KEYS)}")
            return bad
        paths = d["paths"]
        if not 1 <= len(paths) <= 16 or any(
                not _PATH.match(p) or p.startswith("/") or ".." in p.split("/")
                for p in paths):
            bad.append(f"paths {paths}")
        if not 1 <= len(d["command"]) <= 32:
            bad.append("command length")
        for arg in d["command"]:
            if arg.startswith("/") or ".." in arg.split("/"):
                bad.append(f"command argument {arg!r} leaves the repo")
            if os.path.exists(self.path(arg)) and not self._under_paths(arg):
                bad.append(f"command names {arg!r} outside paths")
        if not (isinstance(d["run_seconds"], int)
                and 1 <= d["run_seconds"] <= 51):
            bad.append(f"run_seconds {d['run_seconds']!r}")
        names: List[str] = []
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            names += [e.get("name", "") for e in d[group]]
        for n in names:
            if not _NAME.match(n):
                bad.append(f"name {n!r}")
        dup = {n for n in names if names.count(n) > 1}
        if dup:
            bad.append(f"names used twice: {sorted(dup)}")
        bad += self._config_problems() + self._cell_problems() \
            + self._metric_problems()
        for p in paths:
            for dirpath, _, files in os.walk(self.path(p)):
                if "__pycache__" in dirpath:
                    continue
                for f in files:
                    rel = os.path.relpath(os.path.join(dirpath, f), self.root)
                    if not _PATH.match(rel):
                        bad.append(f"file name {rel!r}")
        return bad

    def _under_paths(self, rel: str) -> bool:
        rel = os.path.normpath(rel)
        return any(rel == p or rel.startswith(os.path.normpath(p) + os.sep)
                   for p in self.data["paths"])

    def _config_problems(self) -> List[str]:
        d, bad, files = self.data, [], []
        if not 1 <= len(d["configs"]) <= 24:
            bad.append("number of configs")
        used = {w["config"] for w in d["workloads"]}
        for c in d["configs"]:
            if not {"name", "source", "file", "reduced", "why"} <= set(c):
                bad.append(f"config {c.get('name')}: keys")
                continue
            if c["name"] not in used:
                bad.append(f"config {c['name']} is used by no cell")
            if not self._under_paths(c["file"]) \
                    or not os.path.isfile(self.path(c["file"])):
                bad.append(f"config {c['name']}: file {c['file']}")
                continue
            files.append(c["file"])
            body = load_json(self.path(c["file"]))
            family = body.get("family")
            if not family or not os.path.isfile(
                    self.path(BENCH_DIR, "families", family + ".py")):
                bad.append(f"config {c['name']}: family {family!r}")
            if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
                bad.append(f"config {c['name']}: reduced differs from its file")
            if len(c["why"]) > 200:
                bad.append(f"config {c['name']}: why over 200 characters")
        if len(set(files)) != len(files):
            bad.append("two configs share a file")
        return bad

    def _cell_problems(self) -> List[str]:
        d, bad, pairs = self.data, [], []
        cells = d["workloads"]
        if not 2 <= len(cells) <= 24:
            bad.append("number of workloads")
        configs = {c["name"] for c in d["configs"]}
        for w in cells:
            if not {"name", "config", "traffic", "chips", "why"} <= set(w):
                bad.append(f"workload {w.get('name')}: keys")
                continue
            if w["config"] not in configs:
                bad.append(f"workload {w['name']}: config {w['config']}")
            if w["chips"] not in (1, 4):
                bad.append(f"workload {w['name']}: chips {w['chips']}")
            if len(w["why"]) > 200:
                bad.append(f"workload {w['name']}: why over 200 characters")
            pairs.append((w["config"], w["traffic"]))
            mix_path = self.path(BENCH_DIR, "workloads", w["traffic"] + ".json")
            if not os.path.isfile(mix_path):
                bad.append(f"workload {w['name']}: no traffic file {mix_path}")
                continue
            kind = load_json(mix_path).get("kind")
            if not kind or not os.path.isfile(
                    self.path(BENCH_DIR, "traffic", kind + ".py")):
                bad.append(f"workload {w['name']}: traffic kind {kind!r}")
        if len(set(pairs)) != len(pairs):
            bad.append("a pair of config and traffic appears twice")
        four = sum(1 for w in cells if w.get("chips") == 4)
        if four > max(1, int(len(cells) * MAX_FOUR_CHIP_SHARE)):
            bad.append(f"{four} four-chip cells of {len(cells)}")
        return bad

    def _metric_problems(self) -> List[str]:
        d, bad = self.data, []
        cells = [w["name"] for w in d["workloads"]]
        e2e = {m["name"]: m for m in d["end_to_end"]}
        if not 1 <= len(d["end_to_end"]) <= 16 \
                or not 1 <= len(d["per_layer"]) <= 128:
            bad.append("number of metrics")
        if "setup_s" not in e2e or "workloads" in e2e.get("setup_s", {}):
            bad.append("setup_s must be an end-to-end metric of every cell")
        for m in d["end_to_end"]:
            if not {"name", "unit", "better", "bound", "source"} <= set(m):
                bad.append(f"metric {m.get('name')}: keys")
                continue
            if m["source"] not in ("host_clock", "device_trace"):
                bad.append(f"metric {m['name']}: source {m['source']}")
            if not 0.01 <= m["bound"] <= MAX_BOUND:
                bad.append(f"metric {m['name']}: bound {m['bound']}")
        for m in d["per_layer"]:
            if not {"name", "unit", "better", "source", "layer",
                    "moves"} <= set(m):
                bad.append(f"metric {m.get('name')}: keys")
                continue
            if m["source"] not in _SOURCES:
                bad.append(f"metric {m['name']}: source {m['source']}")
            if m["moves"] not in e2e:
                bad.append(f"metric {m['name']} moves unknown {m['moves']}")
            if not os.path.isfile(self.path(BENCH_DIR, "layer_metrics",
                                            m["name"] + ".py")):
                bad.append(f"metric {m['name']} has no reader file")
        for m in d["end_to_end"] + d["per_layer"]:
            if m.get("better") not in ("higher", "lower"):
                bad.append(f"metric {m.get('name')}: better")
            if not _UNIT.match(str(m.get("unit", ""))):
                bad.append(f"metric {m.get('name')}: unit {m.get('unit')!r}")
            for c in m.get("workloads", []):
                if c not in cells:
                    bad.append(f"metric {m['name']}: unknown cell {c}")
        for c in cells:
            mine = {m["name"] for m in self.metrics_for("end_to_end", c)}
            if len(mine - {"setup_s"}) < 1:
                bad.append(f"cell {c} has no end-to-end metric but setup_s")
            layer = self.metrics_for("per_layer", c)
            if not layer:
                bad.append(f"cell {c} has no per-layer metric")
            for m in layer:
                if m.get("moves") in e2e and m["moves"] not in mine:
                    bad.append(f"cell {c}: {m['name']} moves {m['moves']}, "
                               f"which the cell does not report")
        return bad
