"""BENCHMARK.json and the files it names: rules of form, discovery by name,
and the entry point's refusal without a chip."""

import copy
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import specs  # noqa: E402

ROOT = specs.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_benchmark_json_keeps_every_rule():
    assert specs.validate(BENCH) == []


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_name_and_unit_are_legal(metric):
    assert specs.NAME.match(metric["name"])
    assert specs.UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_moves_names_an_end_to_end_metric(metric):
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("metric", [m for m in METRICS if "workloads" in m],
                         ids=lambda m: m["name"])
def test_workloads_lists_name_existing_cells(metric):
    assert metric["workloads"] and set(metric["workloads"]) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    spec = specs.Specs()
    w = spec.workload(cell)
    cfg = spec.config(w["config"])
    entry = {c["name"]: c for c in BENCH["configs"]}[w["config"]]
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"]
    assert cfg["source"] == entry["source"]
    assert entry["file"] == f"bench/configs/{w['config']}.json"
    assert w["rhs_per_solve"] == 1
    assert isinstance(spec.traffic(w["traffic"])["field_seed"], int)
    assert w["limits"]["residual_rel"] > 0 and w["limits"]["error_rel"] > 0
    for m in spec.metrics_for(cell, "per_layer"):
        assert callable(spec.reader(m["name"]))


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_dropped_in_cell_and_metric_are_found_without_edits(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), bench,
                    ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    before = _digest(bench)
    new = copy.deepcopy(BENCH)
    (bench / "configs" / "extra.json").write_text(json.dumps(
        {"name": "extra", "elements": [2, 2, 2]}))
    (bench / "traffic" / "burst.json").write_text(json.dumps(
        {"field_seed": 2}))
    (bench / "workloads" / "extra.burst.json").write_text(json.dumps(
        {"name": "extra.burst", "config": "extra", "traffic": "burst",
         "chips": 1, "limits": {"residual_rel": 1, "error_rel": 1}}))
    (bench / "metrics" / "answer_count.py").write_text(
        "def read(m):\n    return float(len(m.iterations))\n")
    new["configs"].append({"name": "extra", "source": "tiny", "reduced": [],
                           "file": "bench/configs/extra.json", "why": "x"})
    new["workloads"].append({"name": "extra.burst", "config": "extra",
                             "traffic": "burst", "chips": 1, "why": "x"})
    new["per_layer"].append({"name": "answer_count", "unit": "solves",
                             "better": "higher", "source": "program_counter",
                             "layer": "PCG solver", "moves": "solve_s",
                             "workloads": ["extra.burst"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(new))
    assert specs.validate(new, str(tmp_path)) == []
    spec = specs.Specs(str(bench), str(path))
    assert spec.workload("extra.burst")["config"] == "extra"
    assert spec.config("extra")["elements"] == [2, 2, 2]
    assert spec.traffic("burst")["field_seed"] == 2
    names = [m["name"] for m in spec.metrics_for("extra.burst", "per_layer")]
    assert "answer_count" in names and "exchange_ms" not in names
    assert "answer_count" not in [
        m["name"] for m in spec.metrics_for(CELLS[0], "per_layer")]

    class M:
        iterations = [3, 4]

    assert spec.reader("answer_count")(M()) == 2.0
    after = _digest(bench)
    assert {k: after[k] for k in before} == before


def _breach(change):
    bench = copy.deepcopy(BENCH)
    change(bench)
    return specs.validate(bench)


BREACHES = {
    "name_with_space": lambda b: b["per_layer"][0].update(name="pcg iters"),
    "unit_with_space": lambda b: b["per_layer"][1].update(unit="ms per it"),
    "moves_unknown": lambda b: b["per_layer"][2].update(moves="ttft_p95_ms"),
    "workloads_unknown": lambda b: b["per_layer"][6].update(
        workloads=["nekbone_p9.solve"]),
    "bound_too_loose": lambda b: b["end_to_end"][0].update(bound=0.3),
    "bound_too_tight": lambda b: b["end_to_end"][0].update(bound=0.005),
    "no_setup_s": lambda b: b["end_to_end"].pop(1),
    "extra_key": lambda b: b["per_layer"][0].update(why="x"),
    "chips_three": lambda b: b["workloads"][0].update(chips=3),
    "source_unknown": lambda b: b["per_layer"][0].update(source="guess"),
    "e2e_from_counter": lambda b: b["end_to_end"][0].update(
        source="program_counter"),
    "run_seconds_too_long": lambda b: b.update(run_seconds=52),
    "path_leaves_repo": lambda b: b.update(paths=["../bench"]),
    "command_outside_paths": lambda b: b.update(
        command=["python3", "chip_smoke.py"]),
    "config_unused": lambda b: b["configs"].append(
        dict(b["configs"][0], name="unused", file="bench/peaks.json")),
    "pair_twice": lambda b: b["workloads"].append(
        dict(b["workloads"][0], name="again")),
    "roofline_not_percent": lambda b: b["per_layer"][4].update(unit="frac"),
    "why_too_long": lambda b: b["workloads"][0].update(why="w" * 201),
}


@pytest.mark.parametrize("name", sorted(BREACHES))
def test_validate_names_each_breach(name):
    assert _breach(BREACHES[name]), name


def _run_entry(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    proc = _run_entry(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_run_with_only_the_benchmark_files_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_entry(str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
