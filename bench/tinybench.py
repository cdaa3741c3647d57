"""A small copy of the benchmark's tree for the CPU tests.

``make(tmp)`` writes ``BENCHMARK.json`` and a bench directory under ``tmp``
with the real metric readers and traffic mix, and configurations cut to a
size that interpret-mode Pallas solves in well under a second: the cells
``tiny.solve`` (one device) and ``tiny_x4.solve`` (four).  Their limits sit
between what sound runs and the control read at that size.
"""

from __future__ import annotations

import json
import os
import shutil

from bench.specs import BENCH_DIR, Specs

__all__ = ["CONFIG", "make"]

CONFIG = {
    "equation": "poisson", "d": 1, "order": 3, "elements": [2, 2, 2],
    "lengths": [1.0, 1.0, 1.0], "warp_amplitude": 0.08,
    "dirichlet": "all six faces", "variant": "trilinear",
    "backend": "pallas", "precision": "float32", "preconditioner": "jacobi",
    "tol": 1e-6, "max_iter": 200,
}
LIMITS = {"residual_rel": 3e-6, "error_rel": 5e-6}


def make(tmp: str) -> Specs:
    bench = os.path.join(tmp, "bench")
    for sub in ("configs", "workloads", "traffic"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    shutil.copytree(os.path.join(BENCH_DIR, "metrics"),
                    os.path.join(bench, "metrics"), dirs_exist_ok=True)
    shutil.copy(os.path.join(BENCH_DIR, "traffic", "solve.json"),
                os.path.join(bench, "traffic", "solve.json"))
    real = json.load(open(os.path.join(os.path.dirname(BENCH_DIR),
                                       "BENCHMARK.json")))
    configs, cells = [], []
    for name, chips, elements in (("tiny", 1, [2, 2, 2]),
                                  ("tiny_x4", 4, [4, 2, 2])):
        cfg = dict(CONFIG, name=name, elements=elements,
                   lengths=[e / 2.0 for e in elements])
        with open(os.path.join(bench, "configs", name + ".json"), "w") as f:
            json.dump(cfg, f)
        cell = {"name": name + ".solve", "config": name, "traffic": "solve",
                "chips": chips, "why": f"tiny {name} for the CPU tests"}
        with open(os.path.join(bench, "workloads", cell["name"] + ".json"),
                  "w") as f:
            json.dump(dict(cell, rhs_per_solve=1,
                           exchange="psum" if chips > 1 else None,
                           grid=None, limits=LIMITS), f)
        configs.append({"name": name, "source": "tiny", "file":
                        f"bench/configs/{name}.json", "reduced": [],
                        "why": "tiny"})
        cells.append(cell)
    per_layer = [dict(m, workloads=["tiny_x4.solve"])
                 if "workloads" in m else m for m in real["per_layer"]]
    bench_json = dict(real, configs=configs, workloads=cells,
                      per_layer=per_layer)
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench_json, f)
    return Specs(bench, path)
