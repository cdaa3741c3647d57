"""End-to-end Nekbone driver (the paper's own workload, Table 6 style).

Solves Poisson/Helmholtz on a box of trilinear elements with PCG and the
chosen axhelm variant; prints GFLOPS / GDOFS / iterations / error.

Run:  PYTHONPATH=src python examples/nekbone_solve.py \
          [--elements 4 4 4] [--order 7] [--variant trilinear] \
          [--equation poisson] [--d 1] [--precision float32] \
          [--backend auto] [--block-elems N|auto] [--devices N] [--nrhs R] \
          [--exchange psum|neighbour] [--grid slab|auto|PXxPYxPZ]
          [--stagnation-window W] [--inject MODE@ITER] [--resilient]

--backend auto drives the Pallas axhelm kernel inside the PCG while_loop
(interpret mode off-TPU) for fp32/bf16 and the jnp reference for fp64;
--block-elems auto runs the per-configuration block autotuner first.
--devices N shards the elements over N devices (shard_map element
partition + interface-dof exchange; on a CPU-only host missing devices are
simulated via --xla_force_host_platform_device_count).
--exchange neighbour swaps the mesh-wide interface psum for per-neighbour
ppermute rounds that overlap with interior-element compute (DESIGN.md).
--grid picks the element-partition shard grid: slab (1-D, the default),
auto (smallest-surface factorization of the device count), or an explicit
PXxPYxPZ box — a box decomposition shrinks the per-shard shared-dof
surface from a full mesh cross-section to a sub-box surface.
--nrhs R solves R stacked right-hand sides in one block-PCG: one operator
application, one interface exchange and one batched dot per iteration for
the whole block — geometry traffic is amortized over the batch.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, "src")


def _parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--elements", type=int, nargs=3, default=[4, 4, 4])
    ap.add_argument("--order", type=int, default=7)
    ap.add_argument("--variant", default="trilinear",
                    choices=["precomputed", "trilinear", "parallelepiped",
                             "merged", "partial"])
    ap.add_argument("--equation", default="poisson",
                    choices=["poisson", "helmholtz"])
    ap.add_argument("--d", type=int, default=1, choices=[1, 3])
    ap.add_argument("--precision", default="float32",
                    choices=["float32", "float64"])
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "reference", "pallas"],
                    help="element kernel: pallas (TPU kernels; interpret "
                         "mode off-TPU), reference (pure jnp), or auto")
    ap.add_argument("--block-elems", default=None,
                    help="Pallas VMEM block size (int), or 'auto' to "
                         "autotune per (variant, N, d, dtype)")
    ap.add_argument("--devices", type=int, default=1,
                    help="shard the solve over N devices (1 = the exact "
                         "single-device path)")
    ap.add_argument("--exchange", default="psum",
                    choices=["psum", "neighbour"],
                    help="interface-dof exchange on the sharded solve: one "
                         "mesh-wide psum (default), or per-neighbour "
                         "ppermute rounds overlapped with interior-element "
                         "compute")
    ap.add_argument("--grid", default="slab",
                    help="element-partition shard grid: 'slab' (1-D), "
                         "'auto' (smallest-surface factorization), or an "
                         "explicit box like '2x2x1' (must multiply to "
                         "--devices)")
    ap.add_argument("--nrhs", type=int, default=1,
                    help="solve R stacked right-hand sides with block-PCG "
                         "(1 = the exact single-RHS path)")
    ap.add_argument("--tol", type=float, default=1e-8)
    ap.add_argument("--max-iter", type=int, default=400)
    ap.add_argument("--stagnation-window", type=int, default=0,
                    help="flag the solve STAGNATED when the residual makes "
                         "no new minimum for this many iterations (0 = "
                         "off)")
    ap.add_argument("--resilient", action="store_true",
                    help="run through resilience.retry.solve_resilient: "
                         "true-residual verification plus the restart -> "
                         "backend -> precision escalation ladder; prints "
                         "the per-attempt audit trail")
    ap.add_argument("--inject", default=None, metavar="MODE@ITER",
                    help="fault-injection demo: corrupt one operator "
                         "application, e.g. 'nan@3', 'bitflip@2', "
                         "'drop_exchange@5' (sharded only).  Watch the "
                         "status turn non-CONVERGED — and recovery happen "
                         "with --resilient")
    return ap.parse_args()


def main():
    # parse (and set XLA_FLAGS for --devices) before jax initializes devices
    args = _parse_args()
    if args.devices > 1 and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import compile_cache

    compile_cache.enable()
    block_elems = args.block_elems
    if block_elems is not None and block_elems != "auto":
        block_elems = int(block_elems)

    if args.precision == "float64":
        jax.config.update("jax_enable_x64", True)
    dtype = jnp.dtype(args.precision)
    helm = args.equation == "helmholtz"

    from repro.core import mesh_gen, nekbone
    from repro.distributed.context import make_solver_ctx, parse_grid_arg
    from repro.resilience import SolveStatus
    from repro.resilience.inject import FaultSpec

    fault = None
    if args.inject is not None:
        mode, _, it = args.inject.partition("@")
        fault = FaultSpec(mode=mode, iteration=int(it) if it else 3)

    nx, ny, nz = args.elements
    mesh = mesh_gen.box_mesh(nx, ny, nz, args.order)
    if args.variant == "parallelepiped":
        mesh = mesh_gen.deform_affine(mesh, seed=2)
    else:
        mesh = mesh_gen.deform_trilinear(mesh, seed=3)
    e = len(mesh.verts)
    # called unconditionally: at --devices 1 it returns None (the exact
    # unsharded path) and WARNS if --exchange/--grid would be dropped
    shard_ctx = make_solver_ctx(devices=args.devices, nrhs=args.nrhs,
                                exchange=args.exchange,
                                grid=parse_grid_arg(args.grid))
    n_shards = shard_ctx.n_shards if shard_ctx is not None else 1
    print(f"mesh: E={e} N={args.order} dofs={mesh.n_global} "
          f"variant={args.variant} eq={args.equation} d={args.d} "
          f"devices={n_shards} nrhs={args.nrhs} exchange={args.exchange}")

    prob = nekbone.setup_problem(mesh, variant=args.variant, d=args.d,
                                 helmholtz=helm, dtype=dtype,
                                 backend=args.backend,
                                 block_elems=block_elems,
                                 shard_ctx=shard_ctx, nrhs=args.nrhs)
    print(f"backend={prob.backend}")
    if shard_ctx is not None:
        part = prob.partition
        iface_frac = float(part.iface_counts.sum()) / e
        print(f"partition: shards={part.n_shards} grid={part.grid} "
              f"elems/shard={[int(c) for c in part.elem_counts]} "
              f"local_dofs={part.n_local} shared_dofs={part.n_shared} "
              f"({part.n_shared / mesh.n_global:.1%} of field exchanged) "
              f"max_shared/shard={int(part.shared_present.sum(axis=1).max())} "
              f"iface_elems={iface_frac:.1%} "
              f"neighbour_offsets={list(part.nbr_offsets)}")
    rng = np.random.default_rng(0)
    shape = (mesh.n_global,) if args.d == 1 else (mesh.n_global, args.d)
    if args.nrhs > 1:
        shape = shape + (args.nrhs,)
    x_true = jnp.asarray(rng.standard_normal(shape), dtype)
    b = nekbone.rhs_from_solution(prob, x_true)

    if args.resilient:
        from repro.resilience.retry import RetryPolicy, solve_resilient

        policy = RetryPolicy(stagnation_window=args.stagnation_window)
        t0 = time.perf_counter()
        report = solve_resilient(prob, b, policy, tol=args.tol,
                                 max_iter=args.max_iter, fault=fault)
        jax.block_until_ready(report.x)
        dt = time.perf_counter() - t0
        for a in report.attempts:
            sts = [SolveStatus(int(s)).name
                   for s in np.atleast_1d(np.asarray(a.status))]
            print(f"attempt rung={a.rung} "
                  f"columns={[int(c) for c in a.columns]} "
                  f"status={sts} true_residual="
                  f"{np.array2string(np.atleast_1d(a.true_residual), precision=2)}")
        print(f"resilient: converged={report.converged} "
              f"rung={list(report.rung)}")
        res = report
    else:
        solve = jax.jit(lambda bb: nekbone.solve(
            prob, bb, tol=args.tol, max_iter=args.max_iter,
            stagnation_window=args.stagnation_window, fault=fault))
        res = solve(b)
        jax.block_until_ready(res.x)
        t0 = time.perf_counter()
        res = solve(b)
        jax.block_until_ready(res.x)
        dt = time.perf_counter() - t0

    iters_all = [int(i) for i in np.atleast_1d(np.asarray(res.iterations))]
    iters = max(iters_all)
    mask_b = jnp.asarray(mesh.boundary).reshape(
        (mesh.n_global,) + (1,) * (x_true.ndim - 1))
    ref = x_true if helm else jnp.where(mask_b, 0.0, x_true)
    err = float(jnp.linalg.norm(res.x - ref) / jnp.linalg.norm(ref))
    # useful FLOPs: each column pays for the iterations it actually ran
    flops = sum(nekbone.flop_count(mesh, args.d, helm, it)
                for it in iters_all)
    status = [SolveStatus(int(s)).name
              for s in np.atleast_1d(np.asarray(res.status))]
    msg = (f"status={status if len(status) > 1 else status[0]} "
           f"iters={iters} error={err:.2e} wall={dt:.3f}s "
           f"GFLOPS={flops / dt / 1e9:.2f} "
           f"GDOFS={mesh.n_global * args.d * sum(iters_all) / dt / 1e9:.4f}")
    if args.nrhs > 1:
        msg += (f" iters/column={iters_all} "
                f"wall/rhs={dt / args.nrhs:.3f}s")
    print(msg)


if __name__ == "__main__":
    main()
