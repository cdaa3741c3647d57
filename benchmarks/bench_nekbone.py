"""Paper Table 6 analogue: end-to-end Nekbone PCG per variant/equation.

Reports GFLOPS (Nekbone useful-FLOP counting), GDOFS (dofs * iters / s),
iteration count, and final error — and checks the iteration-invariance that
the paper uses as its correctness evidence.  CPU wall numbers: relative.

Also emits weak/strong-scaling rows for the element-sharded solve
(`setup_problem(shard_ctx=...)`) and a multi-RHS sweep (`solve` on
(Ng, nrhs) stacked RHS blocks): strong scaling holds the mesh fixed while
the device count grows; weak scaling grows the element count with the
devices; the nrhs sweep shows the paper-model bytes per RHS falling as the
batch amortizes the per-element geometry traffic.  Every sharded scaling
configuration is measured under BOTH interface exchanges (mesh-wide psum
and the overlapped neighbour ppermute path), under every requested shard
grid (`--grids slab,auto,2x2x1,...` — box decompositions shrink the
per-shard interface surface the slab partition pays), and carries the
partition's surface metrics (per-shard shared-dof counts,
interface-element fraction).  A dedicated surface section compares the
(2,2,1) box against the (4,1,1) slab on a 6x6x6 mesh at 4 shards — the
box must record strictly fewer per-shard shared dofs and a lower
interface-element fraction at identical (±1) iteration counts, under both
exchanges.  Results land in BENCH_nekbone.json:

    {"table6": [...], "scaling": [...], "multirhs": [...], "surface": [...]}

On the CPU, device counts beyond the visible devices are simulated by
re-running this script in a subprocess with JAX_PLATFORMS=cpu and
--xla_force_host_platform_device_count (the parent process must keep its
1-device backend).  On an accelerator every row runs in this process,
which holds the chips: there the sharded rows need that many devices.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import benchio
from repro.core import mesh_gen, nekbone

OUT_JSON = "BENCH_nekbone.json"

# merge-don't-clobber keys: a subset run (--smoke, --no-*) re-measures only
# its own configurations; rows of other configurations (including other
# mesh sizes — elements/dofs are part of the identity) must survive
ROW_KEYS = {
    "table6": ("equation", "variant"),
    "scaling": ("mode", "devices", "variant", "exchange", "grid_spec",
                "elements", "dofs"),
    "surface": ("grid_spec", "exchange", "devices", "variant", "order"),
    "multirhs": ("nrhs", "variant", "equation"),
    "precision": ("equation", "precision", "regime", "dofs"),
}


def _timed_solve(prob, b, tol, max_iter=400):
    solve = jax.jit(lambda bb: nekbone.solve(prob, bb, tol=tol,
                                             max_iter=max_iter))
    res = solve(b)
    jax.block_until_ready(res.x)
    t0 = time.perf_counter()
    res = solve(b)
    jax.block_until_ready(res.x)
    return res, time.perf_counter() - t0


def rows(nx: int = 4, order: int = 7, tol: float = 1e-8):
    mesh = mesh_gen.deform_trilinear(mesh_gen.box_mesh(nx, nx, nx, order),
                                     seed=1)
    rng = np.random.default_rng(0)
    x_true = jnp.asarray(rng.standard_normal(mesh.n_global), jnp.float32)
    out = []
    for helm in (False, True):
        variants = ["precomputed", "trilinear",
                    "merged" if helm else "partial", "parallelepiped"]
        for variant in variants:
            use_mesh = mesh
            if variant == "parallelepiped":
                use_mesh = mesh_gen.deform_affine(
                    mesh_gen.box_mesh(nx, nx, nx, order), seed=2)
            prob = nekbone.setup_problem(use_mesh, variant=variant,
                                         helmholtz=helm, dtype=jnp.float32)
            b = nekbone.rhs_from_solution(prob, x_true)
            res, dt = _timed_solve(prob, b, tol)
            iters = int(res.iterations)
            ref = x_true if helm else jnp.where(
                jnp.asarray(use_mesh.boundary), 0.0, x_true)
            err = float(jnp.linalg.norm(res.x - ref)
                        / jnp.linalg.norm(ref))
            flops = nekbone.flop_count(use_mesh, 1, helm, iters)
            out.append({
                "equation": "helmholtz" if helm else "poisson",
                "variant": variant,
                "gflops": flops / dt / 1e9,
                "gdofs": use_mesh.n_global * iters / dt / 1e9,
                "iters": iters,
                "error": err,
                "wall_s": dt,
            })
    return out


def _surface_metrics(part) -> dict:
    """Partition-quality surface metrics: how many interface dofs each
    shard actually touches, and how much of the element volume sits on
    the surface — the quantities a box decomposition shrinks."""
    per_shard = [int(c) for c in part.shared_present.sum(axis=1)]
    return {
        "grid": list(part.grid),
        "shared_dofs": int(part.n_shared),
        "shared_dofs_per_shard": per_shard,
        "max_shared_dofs_per_shard": max(per_shard),
        "iface_elem_frac": float(part.iface_counts.sum())
        / int(part.elem_counts.sum()),
        "neighbour_offsets": list(part.nbr_offsets),
    }


def scaling_rows(device_counts=(1, 2, 4), nx: int = 3, order: int = 4,
                 tol: float = 1e-6, variant: str = "trilinear",
                 exchanges=("psum", "neighbour"), grids=("slab",)):
    """Weak + strong scaling of the sharded solve (run with enough devices).

    Strong: the (nx, nx, nx) mesh is fixed; devices split its elements.
    Weak:   the mesh grows to (nx * devices, nx, nx) — constant elements
            per device.

    Every sharded configuration is measured once per interface-exchange
    implementation (`exchanges`) and once per shard-grid spec (`grids`,
    `parse_grid_arg` syntax: "slab", "auto", "2x2x1", ...; explicit grids
    that do not multiply to the device count are skipped), so the exchange
    cost shows up as a row pair and the box-vs-slab surface difference as
    a row pair at equal shard count.  Each sharded row records the
    partition-quality surface metrics — per-shard shared-dof counts and
    the interface-element fraction — that the box decomposition shrinks.
    """
    from repro.distributed.context import make_solver_ctx, parse_grid_arg

    out = []
    for mode in ("strong", "weak"):
        for s in device_counts:
            shape = (nx, nx, nx) if mode == "strong" else (nx * s, nx, nx)
            mesh = mesh_gen.deform_trilinear(
                mesh_gen.box_mesh(*shape, order), seed=1)
            # seeded per mesh, NOT drawn from a sequential stream: every
            # strong-scaling device count must solve the SAME system, or
            # the iteration-parity check below compares different RHS
            # (whose counts legitimately differ by a few) and reports a
            # phantom sharding regression
            x_true = jnp.asarray(
                np.random.default_rng(0).standard_normal(mesh.n_global),
                jnp.float32)
            # the s=1 baseline has no partition: always run exactly one
            # unsharded row, whatever grids were requested
            seen_grids = set()
            for gspec in (grids if s > 1 else ("slab",)):
                grid = parse_grid_arg(gspec) if s > 1 else None
                if isinstance(grid, tuple) and int(np.prod(grid)) != s:
                    # an explicit box only fits its own device count — say
                    # so instead of silently shrinking coverage
                    print(f"# scaling: skipping grid {gspec} at {s} "
                          f"device(s) (needs {int(np.prod(grid))})")
                    continue
                # specs that resolve to the same partition (e.g. "auto"
                # picking the slab on an elongated mesh) would re-measure
                # identical solves — run each resolved grid once
                resolved = mesh_gen.normalize_grid(grid, mesh.shape, s) \
                    if s > 1 else None
                if resolved in seen_grids:
                    print(f"# scaling: grid {gspec} at {s} device(s) "
                          f"resolves to already-measured {resolved}")
                    continue
                seen_grids.add(resolved)
                for exchange in (exchanges if s > 1 else exchanges[:1]):
                    ctx = make_solver_ctx(devices=s, exchange=exchange,
                                          grid=grid) if s > 1 else None
                    prob = nekbone.setup_problem(mesh, variant=variant,
                                                 dtype=jnp.float32,
                                                 shard_ctx=ctx)
                    b = nekbone.rhs_from_solution(prob, x_true)
                    res, dt = _timed_solve(prob, b, tol)
                    iters = int(res.iterations)
                    flops = nekbone.flop_count(mesh, 1, False, iters)
                    row = {
                        "mode": mode,
                        "devices": s,
                        "variant": variant,
                        "exchange": exchange if s > 1 else "none",
                        "grid_spec": gspec if s > 1 else "none",
                        "elements": len(mesh.verts),
                        "dofs": mesh.n_global,
                        "iters": iters,
                        "wall_s": dt,
                        "gflops": flops / dt / 1e9,
                        "gdofs": mesh.n_global * iters / dt / 1e9,
                    }
                    if ctx is not None:
                        part = prob.partition
                        row.update(_surface_metrics(part))
                        row["shared_frac"] = part.n_shared / mesh.n_global
                    out.append(row)
    return out


def surface_rows(order: int = 2, tol: float = 1e-6,
                 variant: str = "trilinear"):
    """Box-vs-slab surface comparison on a 6x6x6 mesh at 4 shards.

    The acceptance configuration for the box decomposition: the (2,2,1)
    box partition must record strictly fewer per-shard shared dofs and a
    lower interface-element fraction than the (4,1,1) slab, while the
    solves stay within ±1 PCG iteration — under BOTH interface exchanges.
    Needs 4 visible devices (the bench main re-runs in a subprocess with
    forced host devices when short).
    """
    from repro.distributed.context import make_solver_ctx, parse_grid_arg

    mesh = mesh_gen.deform_trilinear(mesh_gen.box_mesh(6, 6, 6, order),
                                     seed=1)
    rng = np.random.default_rng(0)
    x_true = jnp.asarray(rng.standard_normal(mesh.n_global), jnp.float32)
    out = []
    for gspec in ("slab", "2x2x1"):
        for exchange in ("psum", "neighbour"):
            ctx = make_solver_ctx(devices=4, exchange=exchange,
                                  grid=parse_grid_arg(gspec))
            prob = nekbone.setup_problem(mesh, variant=variant,
                                         dtype=jnp.float32, shard_ctx=ctx)
            b = nekbone.rhs_from_solution(prob, x_true)
            res, dt = _timed_solve(prob, b, tol)
            row = {
                "mesh": [6, 6, 6],
                "order": order,
                "devices": 4,
                "variant": variant,
                "exchange": exchange,
                "grid_spec": gspec,
                "elements": len(mesh.verts),
                "dofs": mesh.n_global,
                "iters": int(res.iterations),
                "wall_s": dt,
            }
            row.update(_surface_metrics(prob.partition))
            out.append(row)
    return out


def _check_surface(rows):
    """Machine-check the box-vs-slab acceptance on the surface rows."""
    print("# surface: grid,exchange,iters,max_shared/shard,iface_frac")
    for r in rows:
        print(f"bench_nekbone_surface,{r['grid_spec']},{r['exchange']},"
              f"{r['iters']},{r['max_shared_dofs_per_shard']},"
              f"{r['iface_elem_frac']:.3f}")
    for exchange in ("psum", "neighbour"):
        slab = next(r for r in rows if r["exchange"] == exchange
                    and r["grid_spec"] == "slab")
        box = next(r for r in rows if r["exchange"] == exchange
                   and r["grid_spec"] != "slab")
        assert box["max_shared_dofs_per_shard"] \
            < slab["max_shared_dofs_per_shard"], (slab, box)
        assert box["iface_elem_frac"] < slab["iface_elem_frac"], (slab, box)
        assert abs(box["iters"] - slab["iters"]) <= 1, (slab, box)
    print("# box < slab surface (both exchanges), iteration parity: OK")


def multirhs_rows(nrhs_list=(1, 2, 4, 8), nx: int = 3, order: int = 4,
                  tol: float = 1e-6, variant: str = "trilinear",
                  helm: bool = False):
    """Block-PCG nrhs sweep on a fixed mesh (single device).

    Per row: per-column iteration counts, wall per solve and per RHS, and
    the paper-model traffic per RHS (`core.paper_roofline.axhelm_cost` with
    the nrhs extension): geometry is loaded/recomputed once per element per
    operator application regardless of nrhs, so bytes/RHS decreases toward
    the X+Y floor as the batch grows — the solver-level analogue of the
    paper's recomputation trade.
    """
    from repro.core.paper_roofline import axhelm_cost

    mesh = mesh_gen.deform_trilinear(mesh_gen.box_mesh(nx, nx, nx, order),
                                     seed=1)
    prob = nekbone.setup_problem(mesh, variant=variant, helmholtz=helm,
                                 dtype=jnp.float32)
    rng = np.random.default_rng(0)
    # ONE solution pool: column j is the same RHS in every row, so its
    # iteration count must be batch-size-invariant (checked in main)
    x_all = jnp.asarray(
        rng.standard_normal((mesh.n_global, max(nrhs_list))), jnp.float32)
    b_all = nekbone.rhs_from_solution(prob, x_all)
    out = []
    for nrhs in nrhs_list:
        b = b_all[:, :nrhs]
        res, dt = _timed_solve(prob, b, tol)
        iters = [int(i) for i in np.atleast_1d(np.asarray(res.iterations))]
        cost = axhelm_cost(order, 1, helm, variant, fp_size=4, nrhs=nrhs)
        out.append({
            "nrhs": nrhs,
            "variant": variant,
            "equation": "helmholtz" if helm else "poisson",
            "elements": len(mesh.verts),
            "dofs": mesh.n_global,
            "iters": iters,
            "wall_s": dt,
            "wall_per_rhs_s": dt / nrhs,
            "model_bytes_per_elem": cost.m_bytes,
            "model_bytes_per_rhs": cost.m_bytes / nrhs,
            "model_intensity": cost.f_tot / cost.m_bytes,
        })
    return out


def precision_rows(shape=(3, 3, 2), order: int = 3,
                   precisions=("fp32", "bf16_x32"),
                   variant: str = "trilinear"):
    """fp32 vs bf16_x32 (fp32 iterative refinement around bf16 inner
    sweeps) on one Dirichlet-masked mesh, both equations.

    Two operating points per (equation, precision): "single_sweep" — a
    tolerance within one inner sweep's reach, the paper's bf16 MXU
    operating point, where refinement must match the fp32 iteration
    count ±2 — and "tight" — an absolute 1e-4, where extra refinement
    sweeps are the honest price of the narrow operator.  Dirichlet
    masking keeps the systems inside refinement's convergence envelope
    (kappa_eff * eps_bf16 < 1; see core/DESIGN.md).

    The single-sweep overhead is the inner sweep's 0.5x target-safety
    factor (`core.pcg.refine` aims the bf16 sweep at tol/2 so recurrence
    -vs-true residual drift cannot force a second sweep): it costs
    ``its(tol/2) - its(tol)`` extra iterations, ~1-2 on this mesh's
    convergence curve, more where the curve is shallow — which is why
    the parity gate pins THIS mesh rather than any mesh.  Each bf16_x32
    row records `beats_fp32_wall` against its fp32 twin;
    `_check_precision` asserts the strict wall win only where the MXU
    exists (TPU) — CPU bf16 is emulated, so there the bool is recorded,
    not asserted.
    """
    mesh = mesh_gen.deform_trilinear(mesh_gen.box_mesh(*shape, order),
                                     seed=1)
    rng = np.random.default_rng(0)
    b = rng.standard_normal(mesh.n_global).astype(np.float32)
    b[np.asarray(mesh.boundary)] = 0.0
    b = jnp.asarray(b / np.linalg.norm(b) * 30.0)
    out = []
    for helm in (False, True):
        for prec in precisions:
            prob = nekbone.setup_problem(
                mesh, variant=variant, helmholtz=helm, dirichlet=True,
                dtype=jnp.float32,
                precision=None if prec == "fp32" else prec)
            for regime, tol in (("single_sweep", 0.1 * 30.0),
                                ("tight", 1e-4)):
                res, dt = _timed_solve(prob, b, tol)
                out.append({
                    "equation": "helmholtz" if helm else "poisson",
                    "precision": prec,
                    "regime": regime,
                    "variant": variant,
                    "elements": len(mesh.verts),
                    "dofs": mesh.n_global,
                    "iters": int(res.iterations),
                    "status": int(res.status),
                    "true_residual": float(jnp.linalg.norm(
                        b - prob.op(res.x))),
                    "wall_s": dt,
                })
    for r in out:
        if r["precision"] == "fp32":
            continue
        base = next(q for q in out if q["precision"] == "fp32"
                    and (q["equation"], q["regime"])
                    == (r["equation"], r["regime"]))
        r["iters_fp32"] = base["iters"]
        r["beats_fp32_wall"] = r["wall_s"] < base["wall_s"]
    return out


def _check_precision(rows):
    """Machine-check the mixed-precision acceptance on the sweep rows."""
    print("# precision: eq,precision,regime,iters,wall_s,true_residual")
    for r in rows:
        print(f"bench_nekbone_precision,{r['equation']},{r['precision']},"
              f"{r['regime']},{r['iters']},{r['wall_s']:.4f},"
              f"{r['true_residual']:.2e}")
    on_tpu = jax.default_backend() == "tpu"
    for r in rows:
        assert r["status"] == 0, r          # every row must converge
        if r["precision"] == "fp32":
            continue
        if r["regime"] == "single_sweep":
            assert abs(r["iters"] - r["iters_fp32"]) <= 2, r
        if on_tpu:
            assert r["beats_fp32_wall"], r  # the MXU must pay for itself
    print("# single-sweep iteration parity (both equations)"
          + (", bf16_x32 < fp32 wall: OK" if on_tpu
             else "; wall win recorded (CPU, not asserted): OK"))


def _check_scaling(sc):
    """Print the scaling rows and machine-check the parity evidence."""
    print("# scaling: mode,devices,exchange,grid,elements,dofs,iters,"
          "wall_s,gflops")
    for r in sc:
        print(f"bench_nekbone_scaling,{r['mode']},{r['devices']},"
              f"{r['exchange']},{r.get('grid_spec', 'none')},"
              f"{r['elements']},{r['dofs']},{r['iters']},"
              f"{r['wall_s']:.4f},{r['gflops']:.2f}")
    # sharding must not change the iteration count (parity evidence):
    # every strong-scaling run — psum AND neighbour exchange, every shard
    # grid — within +-1 of the fewest-devices run
    strong = sorted((r for r in sc if r["mode"] == "strong"),
                    key=lambda r: r["devices"])
    assert strong, "no scaling rows produced — check --devices/--grids"
    base = strong[0]["iters"]
    for r in strong:
        assert abs(r["iters"] - base) <= 1, (base, r)
    print("# strong-scaling iteration parity (both exchanges): OK")
    # auto-vs-slab surface report at equal shard count.  NOT an assert:
    # "auto" minimizes the TOTAL cut-face count (slab included as a
    # candidate), which tracks — but does not bound — the per-shard MAX
    # shared-dof count recorded here; on small or non-divisible meshes the
    # unbalanced chunks can push one auto shard a few dofs above the
    # slab's worst (e.g. a (3,3,3) mesh at 6 shards: auto (3,2,1) maxes at
    # 77 vs the slab's 74).  The guaranteed, machine-checked gate lives in
    # `_check_surface` on its validated chunky-mesh configuration.
    pairs = []
    for r in sc:
        if r.get("grid_spec") != "auto":
            continue
        for q in sc:
            if q.get("grid_spec") == "slab" \
                    and (q["mode"], q["devices"], q["exchange"]) \
                    == (r["mode"], r["devices"], r["exchange"]):
                pairs.append((r["max_shared_dofs_per_shard"],
                              q["max_shared_dofs_per_shard"]))
    if pairs:
        better = sum(a < b for a, b in pairs)
        tied = sum(a == b for a, b in pairs)
        print(f"# auto-vs-slab max shared dofs/shard: {better} better, "
              f"{tied} tied, {len(pairs) - better - tied} worse of "
              f"{len(pairs)} pairs")


def _child_rows(child_flag, forced_devices, *extra_args):
    """Re-run this file on `forced_devices` simulated host devices; collect
    its JSON rows.

    CPU only: this process already holds its backend, so a child that
    needed an accelerator would find it taken.  On a chip the rows run in
    this process, which then needs that many devices.
    """
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"these rows need {forced_devices} devices but "
            f"{jax.device_count()} {jax.default_backend()} device(s) are "
            f"visible; simulated host devices exist on the CPU only")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count="
                          f"{forced_devices}")
    env.setdefault("PYTHONPATH", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))
    cmd = [sys.executable, os.path.abspath(__file__), child_flag,
           *extra_args]
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=3600)
    if out.returncode != 0:
        raise RuntimeError(f"bench child failed:\n{out.stderr[-4000:]}")
    return [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("{")]


def _scaling_via_subprocess(device_counts, nx, order, tol, grids):
    return _child_rows("--scaling-child", max(device_counts),
                       "--devices", ",".join(map(str, device_counts)),
                       "--nx", str(nx), "--order", str(order),
                       "--tol", str(tol), "--grids", ",".join(grids))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", default="1,2,4",
                    help="comma-separated device counts for the scaling rows")
    ap.add_argument("--nx", type=int, default=3)
    ap.add_argument("--order", type=int, default=4)
    ap.add_argument("--tol", type=float, default=1e-6)
    ap.add_argument("--no-scaling", action="store_true")
    ap.add_argument("--grids", default="slab",
                    help="comma-separated shard-grid specs for the scaling "
                         "rows: slab, auto, or explicit boxes like 2x2x1 "
                         "(explicit boxes run only at their own device "
                         "count)")
    ap.add_argument("--nrhs", default="1,2,4,8",
                    help="comma-separated RHS-batch widths for the "
                         "multi-RHS sweep (block-PCG)")
    ap.add_argument("--no-multirhs", action="store_true")
    ap.add_argument("--no-surface", action="store_true")
    ap.add_argument("--precisions", default="fp32,bf16_x32",
                    help="comma-separated precisions for the mixed-"
                         "precision sweep (fp32, bf16_x32)")
    ap.add_argument("--no-precision", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: scaling rows (incl. the neighbour-"
                         "exchange and box-grid rows) on a small mesh plus "
                         "the 6x6x6 box-vs-slab surface gate, skip table6 "
                         "and the multi-RHS sweep")
    ap.add_argument("--scaling-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--surface-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    device_counts = tuple(int(s) for s in args.devices.split(","))
    nrhs_list = tuple(int(s) for s in args.nrhs.split(","))
    grids = tuple(s for s in args.grids.split(",") if s)
    precisions = tuple(s for s in args.precisions.split(",") if s)

    if args.scaling_child:
        for r in scaling_rows(device_counts, args.nx, args.order, args.tol,
                              grids=grids):
            print(json.dumps(r))
        return
    if args.surface_child:
        for r in surface_rows(tol=args.tol):
            print(json.dumps(r))
        return

    def _surface():
        if jax.device_count() >= 4:
            return surface_rows(tol=args.tol)
        return _child_rows("--surface-child", 4, "--tol", str(args.tol))

    if args.smoke:
        sc = _scaling_via_subprocess(device_counts, args.nx, args.order,
                                     args.tol, grids) \
            if jax.device_count() < max(device_counts) \
            else scaling_rows(device_counts, args.nx, args.order, args.tol,
                              grids=grids)
        _check_scaling(sc)
        payload = {"scaling": sc}
        if not args.no_surface:
            payload["surface"] = _surface()
            _check_surface(payload["surface"])
        if not args.no_precision:
            payload["precision"] = precision_rows(precisions=precisions)
            _check_precision(payload["precision"])
        benchio.merge_payload(OUT_JSON, payload, row_keys=ROW_KEYS)
        print(f"# smoke: wrote {OUT_JSON} ({len(sc)} scaling rows, "
              f"exchanges: {sorted({r['exchange'] for r in sc})}, "
              f"grids: {sorted({r['grid_spec'] for r in sc})})")
        return

    print("# bench_nekbone (Table 6 analogue): eq,variant,gflops,gdofs,"
          "iters,error")
    rs = rows()
    for r in rs:
        print(f"bench_nekbone,{r['equation']},{r['variant']},"
              f"{r['gflops']:.2f},{r['gdofs']:.4f},{r['iters']},"
              f"{r['error']:.2e}")
    # the paper's invariance claim, machine-checked (trilinear-mesh variants)
    for eq in ("poisson", "helmholtz"):
        iters = {r["iters"] for r in rs if r["equation"] == eq
                 and r["variant"] != "parallelepiped"}
        assert max(iters) - min(iters) <= 1, (eq, iters)
    print("# iteration-invariance across variants: OK")

    payload = {"table6": rs}
    if not args.no_scaling:
        if jax.device_count() >= max(device_counts):
            sc = scaling_rows(device_counts, args.nx, args.order, args.tol,
                              grids=grids)
        else:
            sc = _scaling_via_subprocess(device_counts, args.nx, args.order,
                                         args.tol, grids)
        payload["scaling"] = sc
        _check_scaling(sc)
    if not args.no_surface:
        payload["surface"] = _surface()
        _check_surface(payload["surface"])
    if not args.no_multirhs:
        mr = multirhs_rows(nrhs_list, args.nx, args.order, args.tol)
        payload["multirhs"] = mr
        print("# multirhs: nrhs,iters,wall_s,wall_per_rhs_s,"
              "model_bytes_per_rhs")
        for r in mr:
            print(f"bench_nekbone_multirhs,{r['nrhs']},"
                  f"{max(r['iters'])},{r['wall_s']:.4f},"
                  f"{r['wall_per_rhs_s']:.4f},"
                  f"{r['model_bytes_per_rhs']:.0f}")
        # batching must amortize geometry traffic (the acceptance gate) and
        # must not perturb convergence: column j carries the SAME RHS in
        # every row, so its iteration count may move by at most 1 as the
        # batch around it grows (fp reduction-order wiggle only)
        bpr = [r["model_bytes_per_rhs"] for r in mr]
        assert all(b1 > b2 for b1, b2 in zip(bpr, bpr[1:])), bpr
        by_col = {}
        for r in mr:
            for j, it in enumerate(r["iters"]):
                by_col.setdefault(j, []).append(it)
        for j, its in by_col.items():
            assert max(its) - min(its) <= 1, (j, its)
        print("# multi-RHS bytes/RHS decreasing + per-column iteration "
              "parity: OK")
    if not args.no_precision:
        payload["precision"] = precision_rows(precisions=precisions)
        _check_precision(payload["precision"])
    benchio.merge_payload(OUT_JSON, payload, row_keys=ROW_KEYS)
    print(f"# wrote {OUT_JSON}")


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
