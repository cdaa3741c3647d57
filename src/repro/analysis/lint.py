"""Performance-contract lint: `python -m repro.analysis.lint`.

A registry of the repo's REAL entry points — dense solve, sharded
psum/neighbour solves at 2/4 devices, the reduced-width bf16/int8 wires,
the bf16_x32 refined solve, the bucketed solve service, and all five
axhelm variants — each bound to the contract suite that machine-checks
its invariants (see `repro.analysis.contracts` and DESIGN.md
"Performance contracts").

The CLI lowers/compiles every registered entry, evaluates its contracts,
prints a human summary, optionally writes a JSON report, and exits
nonzero on any violation — the blocking CI step.

    python -m repro.analysis.lint                  # everything
    python -m repro.analysis.lint --list           # registry
    python -m repro.analysis.lint --only dense_poisson,psum_solve_2dev
    python -m repro.analysis.lint --json report.json

Registering a new entry point: add a builder returning
``[(EntryArtifacts, [contracts...]), ...]`` and decorate it with
``@entry(name, description)``.  Builders import jax lazily so `main()`
can force 4 simulated host devices BEFORE the backend initializes.

This module imports no jax at module scope on purpose.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Tuple

N_DEVICES = 4  # simulated host devices the sharded entries need

Check = Tuple["EntryArtifacts", List["Contract"]]  # noqa: F821


@dataclass
class Entry:
    name: str
    description: str
    build: Callable[[], List[Check]]


REGISTRY: Dict[str, Entry] = {}


def entry(name: str, description: str):
    def deco(fn):
        REGISTRY[name] = Entry(name, description, fn)
        return fn
    return deco


def ensure_host_devices(n: int = N_DEVICES) -> bool:
    """Force `n` simulated CPU devices.  Must run before jax imports;
    returns False (and touches nothing) when it is already too late."""
    if "jax" in sys.modules:
        import jax
        return jax.device_count() >= n
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            f"{flags} --xla_force_host_platform_device_count={n}".strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    return True


# ------------------------------------------------------- shared builders ---


def _mesh(nx=3, ny=3, nz=2, order=3, deform=True):
    from repro.core import mesh_gen
    mesh = mesh_gen.box_mesh(nx, ny, nz, order)
    return mesh_gen.deform_trilinear(mesh, seed=3) if deform else mesh


def _lower(fn, *args):
    """(lowered_text, compiled_text, jaxpr) for one jit entry."""
    import jax
    lo = jax.jit(fn).lower(*args)
    return lo.as_text(), lo.compile().as_text(), jax.make_jaxpr(fn)(*args)


def _no_collectives_census():
    from repro.analysis import contracts as C
    from repro.analysis.hlo_ir import COLLECTIVES
    return C.CollectiveCensus(exact={k: 0 for k in COLLECTIVES})


def _sharded_solve_checks(name, exchange, devices, nrhs=1):
    """op + solve artifacts and the census suites for one sharded config."""
    import jax
    import jax.numpy as jnp
    from repro.analysis import contracts as C
    from repro.core import nekbone
    from repro.distributed.context import make_solver_ctx

    if jax.device_count() < devices:
        raise RuntimeError(
            f"{name}: needs {devices} devices, backend has "
            f"{jax.device_count()} — run via `python -m "
            f"repro.analysis.lint` so the host-device flag lands first")
    mesh = _mesh()
    ctx = make_solver_ctx(devices=devices, nrhs=nrhs, exchange=exchange)
    sh = nekbone.setup_problem(mesh, variant="trilinear",
                               dtype=jnp.float32, shard_ctx=ctx)
    ns = int(sh.partition.n_shared)
    shape = (mesh.n_global, nrhs) if nrhs > 1 else (mesh.n_global,)
    B = jnp.zeros(shape, jnp.float32)
    lo_op, co_op, jx_op = _lower(sh.op, B)
    lo_sv, co_sv, jx_sv = _lower(lambda b: sh.run_pcg(b, 1e-6, 300), B)
    base = [C.NoF64Leak(), C.NoHostTransfer()]
    if exchange == "psum":
        op_census = C.CollectiveCensus(
            exact={"collective-permute": 0},
            matchers=[C.interface_allreduce(ns, nrhs=nrhs, exact=1)])
        sv_census = C.CollectiveCensus(
            exact={"collective-permute": 0},
            matchers=[C.interface_allreduce(ns, nrhs=nrhs, exact=2)])
    else:
        rounds = 2 * len(sh.partition.nbr_offsets)
        op_census = C.CollectiveCensus(
            exact={"collective-permute": rounds},
            matchers=[C.interface_allreduce(ns, exact=0)])
        sv_census = C.CollectiveCensus(
            exact={"collective-permute": 2 * rounds},
            matchers=[C.interface_allreduce(ns, exact=0)])
    return [
        (C.EntryArtifacts(f"{name}:op", lowered_text=lo_op,
                          compiled_text=co_op, jaxpr=jx_op),
         [op_census] + base),
        (C.EntryArtifacts(f"{name}:solve", lowered_text=lo_sv,
                          compiled_text=co_sv, jaxpr=jx_sv),
         [sv_census, C.AccumulationDtype()] + base),
    ]


# --------------------------------------------------------------- entries ---


@entry("dense_poisson",
       "single-device trilinear Poisson solve: zero collectives, fp32 "
       "accumulation, no f64, no host transfers")
def _dense_poisson() -> List[Check]:
    import jax.numpy as jnp
    from repro.analysis import contracts as C
    from repro.core import nekbone

    mesh = _mesh(2, 2, 1)
    prob = nekbone.setup_problem(mesh, variant="trilinear",
                                 dtype=jnp.float32)
    b = jnp.ones((mesh.n_global,), jnp.float32)
    lo, co, jx = _lower(
        lambda b: nekbone.solve(prob, b, tol=1e-6, max_iter=200), b)
    art = C.EntryArtifacts("dense_poisson:solve", lowered_text=lo,
                           compiled_text=co, jaxpr=jx)
    return [(art, [_no_collectives_census(), C.AccumulationDtype(),
                   C.NoF64Leak(), C.NoHostTransfer()])]


@entry("psum_solve_2dev",
       "sharded psum solve, 2 devices: ONE interface all-reduce per "
       "apply, two per solve, zero permutes")
def _psum2() -> List[Check]:
    return _sharded_solve_checks("psum_solve_2dev", "psum", 2)


@entry("psum_solve_4dev",
       "sharded psum solve, 4 devices, nrhs=4: the batch rides ONE "
       "interface all-reduce per apply")
def _psum4() -> List[Check]:
    return _sharded_solve_checks("psum_solve_4dev", "psum", 4, nrhs=4)


@entry("neighbour_solve_2dev",
       "neighbour (ppermute) solve, 2 devices: 2 permutes per offset per "
       "apply, ZERO interface all-reduces")
def _nbr2() -> List[Check]:
    return _sharded_solve_checks("neighbour_solve_2dev", "neighbour", 2)


@entry("neighbour_solve_4dev",
       "neighbour solve, 4 devices, nrhs=4: same permute counts as "
       "nrhs=1, ZERO interface all-reduces")
def _nbr4() -> List[Check]:
    return _sharded_solve_checks("neighbour_solve_4dev", "neighbour", 4,
                                 nrhs=4)


def _wire_checks(name, compress, require):
    import jax
    import jax.numpy as jnp
    from repro.analysis import contracts as C
    from repro.core import nekbone
    from repro.distributed.context import make_solver_ctx

    mesh = _mesh()
    ctx = make_solver_ctx(devices=4, exchange="neighbour",
                          compress=compress)
    sh = nekbone.setup_problem(mesh, variant="trilinear",
                               dtype=jnp.float32, shard_ctx=ctx,
                               precision="bf16_x32")
    ns = int(sh.partition.n_shared)
    b = jnp.zeros((mesh.n_global,), jnp.float32)
    lo = jax.jit(lambda b: sh.run_refined(b, 1e-5, 300)).lower(b)
    art = C.EntryArtifacts(f"{name}:refined_solve",
                           lowered_text=lo.as_text(),
                           compiled_text=lo.compile().as_text())
    # the compiled wire WIDTH is deliberately unchecked: CPU hoists the
    # lossless converts across its permutes (see the mixed-precision gate)
    suite = [
        C.WireWidth(require=require),
        C.CollectiveCensus(min_counts={"collective-permute": 1},
                           matchers=[C.interface_allreduce(ns, exact=0)]),
        C.NoF64Leak(), C.NoHostTransfer(),
    ]
    return [(art, suite)]


@entry("neighbour_wire_bf16_4dev",
       "bf16-compressed halo wire: lowered permutes ship bf16, zero "
       "interface all-reduces")
def _wire_bf16() -> List[Check]:
    return _wire_checks("neighbour_wire_bf16_4dev", "bf16", {"bf16"})


@entry("neighbour_wire_int8_4dev",
       "int8-compressed halo wire: lowered permutes ship s8 payloads, "
       "zero interface all-reduces")
def _wire_int8() -> List[Check]:
    return _wire_checks("neighbour_wire_int8_4dev", "int8", {"s8"})


@entry("bf16_x32_refine_dense",
       "dense mixed-precision refined solve: bf16 storage, >= fp32 "
       "accumulation everywhere in the jaxpr")
def _refine_dense() -> List[Check]:
    import jax.numpy as jnp
    from repro.analysis import contracts as C
    from repro.core import nekbone

    mesh = _mesh(2, 2, 1)
    prob = nekbone.setup_problem(mesh, variant="trilinear",
                                 dtype=jnp.float32, precision="bf16_x32")
    b = jnp.ones((mesh.n_global,), jnp.float32)
    lo, co, jx = _lower(
        lambda b: nekbone.solve(prob, b, tol=1e-5, max_iter=200), b)
    art = C.EntryArtifacts("bf16_x32_refine_dense:solve", lowered_text=lo,
                           compiled_text=co, jaxpr=jx)
    return [(art, [_no_collectives_census(), C.AccumulationDtype(),
                   C.NoF64Leak(), C.NoHostTransfer()])]


@entry("service_buckets",
       "bucketed solve service: after warmup a randomized request stream "
       "compiles ZERO new solves")
def _service() -> List[Check]:
    import jax.numpy as jnp
    import numpy as np
    from repro.analysis import contracts as C
    from repro.core import nekbone
    from repro.serving.solve_service import SolveRequest, SolveService

    mesh = _mesh(2, 2, 1)
    prob = nekbone.setup_problem(mesh, variant="trilinear",
                                 dtype=jnp.float32)
    svc = SolveService(prob, max_batch=4, tol=1e-6, max_iter=200)
    warm = svc.warmup()
    rng = np.random.default_rng(0)
    depth_rng = np.random.default_rng(1)
    uid = 0
    for _ in range(4):
        for _ in range(int(depth_rng.integers(1, svc.max_batch + 1))):
            b = nekbone.rhs_from_solution(
                prob, jnp.asarray(rng.standard_normal(mesh.n_global),
                                  jnp.float32))
            svc.submit(SolveRequest(uid=uid, b=b))
            uid += 1
        svc.step()
    svc.run_until_drained()
    art = C.EntryArtifacts("service_buckets:stream",
                           meta={"traces_before": warm,
                                 "traces_after": svc.trace_count,
                                 "requests": uid})
    return [(art, [C.NoRetrace()])]


def _axhelm_checks(variant: str) -> List[Check]:
    import jax
    import jax.numpy as jnp
    from repro.analysis import contracts as C
    from repro.core import nekbone
    from repro.kernels.axhelm import tune

    helm = variant == "merged"
    # parallelepiped geometry must stay affine — no trilinear deformation
    mesh = _mesh(2, 2, 1, deform=variant != "parallelepiped")
    n1 = mesh.order + 1
    e_total = len(mesh.verts)
    eb = tune.get_block_elems(variant, n1, 1, jnp.float32,
                              helmholtz=helm, e_total=e_total,
                              interpret=True)
    # the bf16 reference operator drives the AccumulationDtype check: the
    # sum-factorization dots must accumulate in f32 even at bf16 storage
    prob = nekbone.setup_problem(mesh, variant=variant, helmholtz=helm,
                                 dtype=jnp.bfloat16, backend="reference")
    x = jnp.ones((mesh.n_global,), jnp.bfloat16)
    jx = jax.make_jaxpr(prob.op)(x)
    art = C.EntryArtifacts(f"axhelm_{variant}:op_bf16", jaxpr=jx)
    return [(art, [
        C.AccumulationDtype(),
        C.VmemBudget(variant, n1, 1, jnp.float32, eb, helmholtz=helm),
        C.VmemBudget(variant, n1, 1, jnp.bfloat16,
                     tune.get_block_elems(variant, n1, 1, jnp.bfloat16,
                                          helmholtz=helm, e_total=e_total,
                                          interpret=True),
                     helmholtz=helm),
    ])]


for _variant in ("precomputed", "trilinear", "parallelepiped", "merged",
                 "partial"):
    entry(f"axhelm_{_variant}",
          f"axhelm[{_variant}]: dispatched block fits the v3 VMEM model; "
          f"bf16 reference op accumulates in fp32")(
        lambda v=_variant: _axhelm_checks(v))


# ------------------------------------------------------------------- CLI ---


def run_entry(e: Entry) -> dict:
    from repro.analysis.contracts import check_suite
    t0 = time.monotonic()
    row = {"entry": e.name, "description": e.description,
           "status": "pass", "violations": [], "checks": 0}
    try:
        for art, suite in e.build():
            row["checks"] += len(suite)
            for v in check_suite(art, suite):
                row["violations"].append(
                    {"contract": v.contract, "artifact": v.entry,
                     "message": v.message})
    except Exception as exc:  # an entry that cannot build is a failure
        row["status"] = "error"
        row["error"] = f"{type(exc).__name__}: {exc}"
    if row["violations"]:
        row["status"] = "fail"
    row["seconds"] = round(time.monotonic() - t0, 2)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="machine-check the solver's performance contracts")
    ap.add_argument("--only", default="",
                    help="comma-separated entry names (default: all)")
    ap.add_argument("--list", action="store_true",
                    help="list registered entries and exit")
    ap.add_argument("--json", default="",
                    help="write the JSON report to this path")
    args = ap.parse_args(argv)

    if args.list:
        for e in REGISTRY.values():
            print(f"{e.name:26s} {e.description}")
        return 0

    names = [n for n in args.only.split(",") if n] or list(REGISTRY)
    unknown = [n for n in names if n not in REGISTRY]
    if unknown:
        print(f"unknown entries: {', '.join(unknown)}; "
              f"try --list", file=sys.stderr)
        return 2

    ensure_host_devices()
    rows = []
    for n in names:
        row = run_entry(REGISTRY[n])
        rows.append(row)
        mark = {"pass": "ok  ", "fail": "FAIL", "error": "ERR "}[
            row["status"]]
        print(f"[{mark}] {row['entry']:26s} {row['checks']:2d} checks  "
              f"{row['seconds']:6.2f}s")
        for v in row["violations"]:
            print(f"       - [{v['contract']}] {v['artifact']}: "
                  f"{v['message']}")
        if row["status"] == "error":
            print(f"       ! {row['error']}")
    report = {
        "entries": rows,
        "passed": sum(r["status"] == "pass" for r in rows),
        "failed": sum(r["status"] != "pass" for r in rows),
    }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"report -> {args.json}")
    print(f"{report['passed']}/{len(rows)} entries clean")
    return 1 if report["failed"] else 0


if __name__ == "__main__":
    sys.exit(main())
