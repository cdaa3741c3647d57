"""Paper Figures 9-10 analogue: measured axhelm variant performance.

Times every paper variant through BOTH backends — the pure-jnp reference and
the Pallas kernels (interpret mode off-TPU) — and reports, per row:

  us/element, effective GFLOPS (P_eff = F_ax / t: charges recalculation time
  but not recalculation FLOPs), total GFLOPS, the paper's modeled
  bytes/element (Table 4 geometry traffic + X/Y/lambda), operational
  intensity, and the modeled v5e roofline ceiling R_eff with the fraction of
  it actually achieved.

On CPU the wall numbers are for RELATIVE comparison between variants and
backends; the bytes/intensity/R_eff columns are the machine-independent
paper model.  Results land in BENCH_axhelm.json so the perf trajectory is
tracked across PRs.

Run:  PYTHONPATH=src python benchmarks/bench_axhelm.py
          [--quick] [--n 7] [--e 512] [--d 1] [--autotune]
          [--backends reference pallas] [--out BENCH_axhelm.json]
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import benchio

from repro.core import axhelm as ax, mesh_gen
from repro.core.paper_roofline import PLATFORMS, axhelm_cost, roofline
from repro.core.spectral import basis

POISSON_VARIANTS = ("precomputed", "trilinear", "parallelepiped", "partial")
HELMHOLTZ_VARIANTS = ("precomputed", "trilinear", "parallelepiped", "merged")

COLUMNS = ("equation", "variant", "backend", "nrhs", "us_per_elem",
           "p_eff_gflops", "p_tot_gflops", "model_bytes_per_elem",
           "model_bytes_per_rhs", "model_intensity",
           "model_r_eff_gflops_v5e", "roofline_frac_v5e")


def _time(fn, *args, iters: int = 5) -> float:
    jax.block_until_ready(fn(*args))   # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def rows(n: int = 7, e: int = 512, d: int = 1,
         backends=("reference", "pallas"), iters: int = 5,
         block_elems=None, nrhs_list=(1,)):
    """Returns (rows, info) — info carries the ACTUAL element count (the
    requested e is rounded to the 8x8xnz box mesh).

    `nrhs_list` sweeps the RHS-batch width: nrhs>1 rows time ONE batched
    apply over (E, nrhs, d, N1^3) — every column reuses the element's
    geometry load/recomputation, so the modeled bytes/RHS falls toward the
    X+Y floor while the measured us/elem grows sublinearly in nrhs.
    """
    b = basis(n)
    nz = max(1, e // 64)
    box = mesh_gen.box_mesh(8, 8, nz, n)
    tri_mesh = mesh_gen.deform_trilinear(box, seed=1)
    par_mesh = mesh_gen.deform_affine(box, seed=2)
    e = len(tri_mesh.verts)
    rng = np.random.default_rng(0)
    lam0 = jnp.ones((e, b.n1, b.n1, b.n1), jnp.float32)
    lam1 = jnp.full((e, b.n1, b.n1, b.n1), 0.1, jnp.float32)
    # fp_size=4 throughout: these runs are fp32, so the modeled traffic and
    # the R_eff ceiling must use the same word size or the roofline
    # fraction compares fp32 measurements against a bf16-traffic ceiling.
    v5e = dataclasses.replace(PLATFORMS["v5e"], fp_size=4)

    def field(nrhs):
        if nrhs > 1:
            shape = (e, nrhs, d, b.n1, b.n1, b.n1)
        else:
            shape = (e, b.n1, b.n1, b.n1) if d == 1 \
                else (e, d, b.n1, b.n1, b.n1)
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    xs = {nrhs: field(nrhs) for nrhs in nrhs_list}
    out = []
    for helm in (False, True):
        for vname in (HELMHOLTZ_VARIANTS if helm else POISSON_VARIANTS):
            mesh = par_mesh if vname == "parallelepiped" else tri_mesh
            verts = jnp.asarray(mesh.verts, jnp.float32)
            kw = dict(lam0=lam0, lam1=lam1) if helm else {}
            for backend in backends:
                op = ax.make_axhelm(vname, b, verts, helmholtz=helm,
                                    dtype=jnp.float32, backend=backend,
                                    block_elems=block_elems, **kw)
                for nrhs in nrhs_list:
                    cost = axhelm_cost(n, d, helm, vname, fp_size=4,
                                       nrhs=nrhs)
                    model = roofline(v5e, n, d, helm, vname, nrhs=nrhs)
                    t = _time(jax.jit(op.apply), xs[nrhs], iters=iters)
                    p_eff = cost.f_ax * e / t / 1e9
                    out.append({
                        "equation": "helmholtz" if helm else "poisson",
                        "variant": vname,
                        "backend": op.backend,
                        "nrhs": nrhs,
                        "us_per_elem": t / e * 1e6,
                        "p_eff_gflops": p_eff,
                        "p_tot_gflops": cost.f_tot * e / t / 1e9,
                        "model_bytes_per_elem": cost.m_bytes,
                        "model_bytes_per_rhs": cost.m_bytes / nrhs,
                        "model_intensity": cost.f_tot / cost.m_bytes,
                        "model_r_eff_gflops_v5e": model["r_eff"] / 1e9,
                        "roofline_frac_v5e": p_eff / (model["r_eff"] / 1e9),
                    })
    return out, {"e": e, "n": n, "d": d, "nrhs_list": list(nrhs_list)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=7)
    ap.add_argument("--e", type=int, default=512)
    ap.add_argument("--d", type=int, default=1, choices=[1, 3])
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--backends", nargs="+",
                    default=["reference", "pallas"],
                    choices=["reference", "pallas", "auto"])
    ap.add_argument("--autotune", action="store_true",
                    help="run the kernels/axhelm/tune.py block sweep per "
                         "configuration before timing the pallas backend")
    ap.add_argument("--nrhs", default="1",
                    help="comma-separated RHS-batch widths to sweep "
                         "(e.g. 1,2,4,8); widths > 1 time the batched "
                         "kernels sharing one geometry set per element")
    ap.add_argument("--quick", action="store_true",
                    help="small problem for CI smoke (n=3, e=64, 2 iters)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_axhelm.json"))
    args = ap.parse_args()
    if args.quick:
        args.n, args.e, args.iters = min(args.n, 3), min(args.e, 64), 2
    nrhs_list = tuple(int(s) for s in args.nrhs.split(","))

    r, info = rows(n=args.n, e=args.e, d=args.d,
                   backends=tuple(args.backends), iters=args.iters,
                   block_elems="auto" if args.autotune else None,
                   nrhs_list=nrhs_list)

    print("# bench_axhelm: " + ",".join(COLUMNS))
    for row in r:
        print("bench_axhelm," + ",".join(
            f"{row[c]:.3f}" if isinstance(row[c], float) else str(row[c])
            for c in COLUMNS))

    # stamp each row with the problem size it was measured at, so a
    # --quick smoke run merges in BESIDE the full-size rows instead of
    # replacing them (benchio merges by the full configuration key)
    for row in r:
        row.update({"n": info["n"], "e": info["e"], "d": info["d"]})
    payload = {
        "bench": "axhelm",
        "jax_backend": jax.default_backend(),
        # info, not args: the mesh rounds the requested e to the 8x8xnz box
        "config": {**info, "iters": args.iters, "autotune": args.autotune},
        "rows": r,
    }
    out = os.path.abspath(args.out)
    benchio.merge_payload(out, payload, row_keys={
        "rows": ("equation", "variant", "backend", "nrhs", "n", "e", "d")})
    print(f"# wrote {out}")


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
