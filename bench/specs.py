"""``BENCHMARK.json`` and the files it names, found by name.

A cell is ``workloads/<name>.json``, its configuration ``configs/<name>.json``,
its traffic mix ``traffic/<name>.json`` and each per-layer metric a reader
``metrics/<name>.py`` with ``read(measurements) -> float | None``.  Adding a
cell or a metric adds files; no existing file changes.  ``validate`` holds a
``BENCHMARK.json`` to the benchmark's rules of form.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

__all__ = ["BENCH_DIR", "ROOT", "Specs", "validate"]

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
E2E_SOURCES = ("host_clock", "device_trace")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source", "workloads"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves",
              "workloads"}


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Specs:
    """The benchmark's specifications under one bench directory."""

    def __init__(self, bench_dir: str = BENCH_DIR,
                 benchmark_path: str | None = None):
        self.bench_dir = bench_dir
        self.benchmark = _load_json(benchmark_path or os.path.join(
            os.path.dirname(bench_dir), "BENCHMARK.json"))

    def _file(self, kind: str, name: str, ext: str = ".json") -> str:
        if not NAME.match(name):
            raise ValueError(f"illegal {kind} name {name!r}")
        path = os.path.join(self.bench_dir, kind, name + ext)
        if not os.path.isfile(path):
            raise FileNotFoundError(f"no {kind} file {path}")
        return path

    def workload(self, name: str) -> dict:
        """The cell's file, checked against its entry in BENCHMARK.json."""
        entry = {w["name"]: w for w in self.benchmark["workloads"]}.get(name)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        cell = _load_json(self._file("workloads", name))
        for key in ("config", "traffic", "chips"):
            if cell.get(key) != entry[key]:
                raise ValueError(f"workload {name!r}: {key} is "
                                 f"{cell.get(key)!r} in its file and "
                                 f"{entry[key]!r} in BENCHMARK.json")
        return cell

    def config(self, name: str) -> dict:
        return _load_json(self._file("configs", name))

    def traffic(self, name: str) -> dict:
        return _load_json(self._file("traffic", name))

    def metrics_for(self, cell: str, kind: str) -> list:
        """The metric entries of ``kind`` ("end_to_end" or "per_layer")
        that the cell reports."""
        return [m for m in self.benchmark[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str):
        """The ``read`` function of ``metrics/<metric>.py``."""
        path = self._file("metrics", metric, ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + re.sub(r"\W", "_", metric), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def _check_names(errors, what, items):
    names = [i.get("name") for i in items]
    for n in names:
        if not isinstance(n, str) or not NAME.match(n):
            errors.append(f"{what}: illegal name {n!r}")
    if len(set(names)) != len(names):
        errors.append(f"{what}: duplicate names")
    return set(n for n in names if isinstance(n, str))


def _line(errors, what, text):
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        errors.append(f"{what}: needs 1 to 200 characters on one line")


def validate(bench: dict, root: str = ROOT) -> list:
    """Every breach of the benchmark's rules of form, as messages."""
    errors = []
    if set(bench) != TOP_KEYS:
        errors.append(f"top-level keys {sorted(bench)} are not "
                      f"{sorted(TOP_KEYS)}")
    paths = bench.get("paths", [])
    if not 1 <= len(paths) <= 16:
        errors.append("paths: 1 to 16 directories")
    for p in paths:
        if (not PATH.match(p) or p.startswith("/") or ".." in p.split("/")
                or not os.path.isdir(os.path.join(root, p))):
            errors.append(f"paths: {p!r} is no directory of the repo")
    command = bench.get("command", [])
    if not 1 <= len(command) <= 32:
        errors.append("command: 1 to 32 words")
    for word in command:
        _line(errors, "command", word)
        if word.startswith("/") or ".." in word.split("/"):
            errors.append(f"command: {word!r} leaves the repo")
        if os.path.exists(os.path.join(root, word)) and not any(
                word == p or word.startswith(p.rstrip("/") + "/")
                for p in paths):
            errors.append(f"command: {word!r} is outside paths")
    secs = bench.get("run_seconds")
    if not (isinstance(secs, int) and 1 <= secs <= 51):
        errors.append("run_seconds: a whole number from 1 to 51")
    configs = bench.get("configs", [])
    cells = bench.get("workloads", [])
    e2e = bench.get("end_to_end", [])
    layers = bench.get("per_layer", [])
    for what, items, lo, hi in (("configs", configs, 1, 24),
                                ("workloads", cells, 1, 24),
                                ("end_to_end", e2e, 1, 16),
                                ("per_layer", layers, 1, 128)):
        if not lo <= len(items) <= hi:
            errors.append(f"{what}: {lo} to {hi} entries")
    config_names = _check_names(errors, "configs", configs)
    cell_names = _check_names(errors, "workloads", cells)
    e2e_names = _check_names(errors, "end_to_end", e2e)
    layer_names = _check_names(errors, "per_layer", layers)
    if e2e_names & layer_names:
        errors.append("a metric name is used twice")
    files = set()
    for c in configs:
        if set(c) != CONFIG_KEYS:
            errors.append(f"config {c.get('name')}: keys {sorted(c)}")
        _line(errors, f"config {c.get('name')} source", c.get("source"))
        _line(errors, f"config {c.get('name')} why", c.get("why"))
        f = c.get("file", "")
        if f in files or not any(f.startswith(p.rstrip("/") + "/")
                                 for p in paths) \
                or not os.path.isfile(os.path.join(root, f)):
            errors.append(f"config {c.get('name')}: file {f!r}")
        files.add(f)
        reduced = c.get("reduced", [])
        if len(reduced) > 16 or not all(NAME.match(k) for k in reduced):
            errors.append(f"config {c.get('name')}: reduced {reduced}")
    used_configs = set()
    pairs = set()
    for w in cells:
        if set(w) != CELL_KEYS:
            errors.append(f"workload {w.get('name')}: keys {sorted(w)}")
        if w.get("config") not in config_names:
            errors.append(f"workload {w.get('name')}: unknown config")
        used_configs.add(w.get("config"))
        if not NAME.match(str(w.get("traffic"))):
            errors.append(f"workload {w.get('name')}: illegal traffic")
        if w.get("chips") not in (1, 4):
            errors.append(f"workload {w.get('name')}: chips 1 or 4")
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            errors.append(f"workload {w.get('name')}: pair {pair} twice")
        pairs.add(pair)
        _line(errors, f"workload {w.get('name')} why", w.get("why"))
    if config_names - used_configs:
        errors.append(f"configs no cell uses: {config_names - used_configs}")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 2):
        errors.append(f"{four} cells on 4 chips of {len(cells)}")
    for m in e2e + layers:
        what = f"metric {m.get('name')}"
        if not UNIT.match(str(m.get("unit"))):
            errors.append(f"{what}: illegal unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            errors.append(f"{what}: better is lower or higher")
        for cell in m.get("workloads", []):
            if cell not in cell_names:
                errors.append(f"{what}: unknown workload {cell!r}")
    for m in e2e:
        what = f"end_to_end {m.get('name')}"
        if not set(m) <= E2E_KEYS or not E2E_KEYS - {"workloads"} <= set(m):
            errors.append(f"{what}: keys {sorted(m)}")
        if m.get("source") not in E2E_SOURCES:
            errors.append(f"{what}: source {m.get('source')!r}")
        bound = m.get("bound")
        if not (isinstance(bound, (int, float)) and 0.01 <= bound <= 0.25):
            errors.append(f"{what}: bound {bound!r} not in [0.01, 0.25]")
    if "setup_s" not in e2e_names:
        errors.append("end_to_end: setup_s is missing")
    for m in layers:
        what = f"per_layer {m.get('name')}"
        if (not set(m) <= LAYER_KEYS
                or not LAYER_KEYS - {"workloads"} <= set(m)):
            errors.append(f"{what}: keys {sorted(m)}")
        if m.get("source") not in SOURCES:
            errors.append(f"{what}: source {m.get('source')!r}")
        if m.get("moves") not in e2e_names:
            errors.append(f"{what}: moves {m.get('moves')!r}, no "
                          f"end-to-end metric")
        _line(errors, f"{what} layer", m.get("layer"))
        if m.get("name", "").endswith("_roofline") and m.get("unit") != "%":
            errors.append(f"{what}: a roofline share is in %")
    for w in cells:
        name = w.get("name")
        reported = [m for m in e2e
                    if "workloads" not in m or name in m["workloads"]]
        if len(reported) < 2:
            errors.append(f"workload {name}: reports setup_s and no other "
                          f"end-to-end metric")
        if not any("workloads" not in m or name in m["workloads"]
                   for m in layers):
            errors.append(f"workload {name}: reports no per-layer metric")
    for m in layers:
        for cell in m.get("workloads", []):
            moved = [e for e in e2e if e.get("name") == m.get("moves")]
            if moved and "workloads" in moved[0] \
                    and cell not in moved[0]["workloads"]:
                errors.append(f"per_layer {m.get('name')}: {cell} does not "
                              f"report {m.get('moves')}")
    return errors
