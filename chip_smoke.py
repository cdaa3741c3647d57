"""Chip smoke test: the Nekbone solve of `configs/nekbone.py` on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py            # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4  # four chips: phase (e) only

Every phase runs in this one process (a chip belongs to one process; a
child started after JAX holds it would find it taken):

  (a) device   the first JAX device must be a TPU, else exit non-zero;
  (b) kernels  one compiled Pallas apply of each axhelm variant on the
               config's mesh, against the plain reference;
  (c) solve    the config's solve on the Pallas and on the reference
               backend: both CONVERGED, iterations within ±1, error against
               the manufactured solution printed; the compiled Pallas solve
               must hold the kernel (`tpu_custom_call`);
  (d) service  `SolveService` warms up and answers 8 requests at the
               config's size: no errors, all CONVERGED on the first rung,
               no trace after warmup;
  (e) sharded  the config's problem on 4 devices with the psum and the
               neighbour (grid "auto") exchange, against the 1-device solve
               in the same process; with each sharded problem alive, every
               device must hold at least its share of the elements: in the
               problem's per-shard arrays and in its bytes in use.

The element block size comes from the VMEM model (no timed sweep, no tune
cache), the backend is passed explicitly, and no phase goes through the
resilience ladder, so nothing can leave the chip or the kernel unnoticed.
Compile and wall times are printed for information; they are no benchmark.
A failed phase makes the run exit non-zero.  The last line of standard
output is, on success, one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

VARIANTS = ("precomputed", "trilinear", "parallelepiped", "merged",
            "partial")
# max |y - y_ref| / max |y_ref| of one fp32 apply (the kernels and the
# reference contract in different orders)
KERNEL_RTOL = 1e-4


class SmokeFailure(AssertionError):
    pass


def _require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _say(*parts):
    print(*parts, flush=True)


def check_device(chips: int):
    """(a): the device JAX found, or SystemExit naming what it found."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found platform "
                         f"{d.platform!r} ({d.device_kind}, {len(devs)} "
                         f"device(s))")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, JAX found {len(devs)} {d.device_kind}")
    _say(f"(a) device: platform={d.platform} kind={d.device_kind} "
         f"count={len(devs)}")
    return d, len(devs)


def config_meshes(cfg):
    """The config's trilinear mesh, and its affine twin (parallelepiped)."""
    from repro.core import mesh_gen

    box = mesh_gen.box_mesh(*cfg.elements, cfg.order)
    return (mesh_gen.deform_trilinear(box, seed=3),
            mesh_gen.deform_affine(box, seed=2))


def _block(cfg, mesh, nrhs=1, e_total=None, variant=None, helmholtz=None):
    import jax.numpy as jnp

    from repro.kernels.axhelm import tune

    variant = variant or cfg.variant
    helm = cfg.helmholtz if helmholtz is None else helmholtz
    return tune.model_block_elems(
        variant, cfg.order + 1, cfg.d, jnp.dtype(cfg.precision), helm,
        e_total=e_total or len(mesh.verts), nrhs=nrhs)


def phase_kernels(cfg, mesh, mesh_affine, interpret: bool):
    """(b): each variant's Pallas apply against `kops.reference`."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import axhelm as core_ax
    from repro.core.spectral import basis
    from repro.kernels.axhelm import ops as kops

    b = basis(cfg.order)
    dt = jnp.dtype(cfg.precision)
    n1 = b.n1
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (len(mesh.verts), n1, n1, n1)), dt)
    bad = []
    for variant in VARIANTS:
        helm = variant == "merged"           # merged is Helmholtz-only
        m = mesh_affine if variant == "parallelepiped" else mesh
        lam = dict(lam0=1.0, lam1=0.1) if helm else {}
        eb = _block(cfg, m, variant=variant, helmholtz=helm)
        elem_ops, apply, _ = core_ax.make_axhelm_elem_ops(
            variant, b, jnp.asarray(m.verts, dt), helmholtz=helm, dtype=dt,
            backend="pallas", block_elems=eb, interpret=interpret, **lam)
        y = apply(x, elem_ops)
        y_ref = kops.reference(x, b, variant, elem_ops["geom"],
                               elem_ops.get("lam0"), elem_ops.get("lam1"),
                               helmholtz=helm)
        err = float(jnp.max(jnp.abs(y - y_ref)) / jnp.max(jnp.abs(y_ref)))
        ok = bool(np.isfinite(err)) and err <= KERNEL_RTOL
        _say(f"(b) kernel {variant}: E={len(m.verts)} block_elems={eb} "
             f"max_rel_err={err:.3e} (limit {KERNEL_RTOL:g}) "
             f"{'ok' if ok else 'FAIL'}")
        if not ok:
            bad.append(variant)
    _require(not bad, f"kernel parity failed for {bad}")


def _manufacturer(prob):
    """seed -> (b, x_ref): b = A x_true for a standard-normal x_true, and
    x_true with the Dirichlet dofs zeroed (what the solve should return).
    One jitted operator serves every seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import nekbone

    ng = prob.mesh.n_global
    shape = (ng,) if prob.d == 1 else (ng, prob.d)
    rhs = jax.jit(lambda x: nekbone.rhs_from_solution(prob, x))

    def make(seed):
        x_true = jnp.asarray(
            np.random.default_rng(seed).standard_normal(shape),
            prob.diag.dtype)
        if prob.mask is not None:
            m = jnp.asarray(prob.mask).reshape((ng,) + (1,) * (len(shape)
                                                               - 1))
            return rhs(x_true), jnp.where(m, 0.0, x_true)
        return rhs(x_true), x_true

    return make


def _timed_solve(cfg, prob, b):
    """Compile and run the jitted solve twice: (result, compiled,
    compile_s, first_s, warm_s)."""
    import jax

    from repro.core import nekbone

    def fn(bb):
        return nekbone.solve(prob, bb, tol=cfg.tol, max_iter=cfg.max_iter)

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(b).compile()
    t1 = time.perf_counter()
    res = jax.block_until_ready(compiled(b))
    t2 = time.perf_counter()
    res = jax.block_until_ready(compiled(b))
    t3 = time.perf_counter()
    return res, compiled, t1 - t0, t2 - t1, t3 - t2


def _eager_solve(cfg, prob, b):
    """Two calls of `nekbone.solve` as a user makes them, outside any
    jit: (result, first_call_s, warm_s).  The sharded runners take their
    per-shard arrays as arguments; an enclosing jit would capture them as
    constants of its own program."""
    import jax

    from repro.core import nekbone

    t0 = time.perf_counter()
    res = jax.block_until_ready(
        nekbone.solve(prob, b, tol=cfg.tol, max_iter=cfg.max_iter))
    t1 = time.perf_counter()
    res = jax.block_until_ready(
        nekbone.solve(prob, b, tol=cfg.tol, max_iter=cfg.max_iter))
    return res, t1 - t0, time.perf_counter() - t1


def _rel(a, b):
    import jax.numpy as jnp

    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def phase_solve(cfg, mesh, interpret: bool):
    """(c): the config's solve, Pallas against reference."""
    import jax.numpy as jnp

    from repro.core import nekbone
    from repro.resilience.status import SolveStatus

    dt = jnp.dtype(cfg.precision)
    common = dict(variant=cfg.variant, d=cfg.d, helmholtz=cfg.helmholtz,
                  dtype=dt)
    probs = {
        "pallas": nekbone.setup_problem(
            mesh, backend="pallas", block_elems=_block(cfg, mesh),
            interpret=interpret, **common),
        "reference": nekbone.setup_problem(mesh, backend="reference",
                                           **common),
    }
    b, x_ref = _manufacturer(probs["reference"])(0)
    iters = {}
    for backend, prob in probs.items():
        _require(prob.backend == backend,
                 f"asked for backend {backend!r}, got {prob.backend!r}")
        res, compiled, t_c, t_1, t_w = _timed_solve(cfg, prob, b)
        status = SolveStatus(int(res.status)).name
        iters[backend] = int(res.iterations)
        has_kernel = "tpu_custom_call" in compiled.as_text()
        _say(f"(c) solve backend={prob.backend} variant={cfg.variant} "
             f"E={len(mesh.verts)} N={cfg.order} dofs={mesh.n_global} "
             f"status={status} iters={iters[backend]} "
             f"residual={float(res.residual):.3e} (tol {cfg.tol:g}) "
             f"error={_rel(res.x, x_ref):.3e} tpu_custom_call={has_kernel}")
        _say(f"(c)   informational: compile_s={t_c:.2f} "
             f"first_call_s={t_1:.3f} warm_wall_s={t_w:.3f}")
        _require(status == "CONVERGED", f"{backend} solve ended {status}")
        if not interpret:
            # the compiled kernel, not the interpreter, for pallas; none
            # at all for the reference contractions
            _require(has_kernel == (backend == "pallas"),
                     f"{backend} solve: tpu_custom_call={has_kernel}")
    _require(abs(iters["pallas"] - iters["reference"]) <= 1,
             f"iterations differ by more than 1: {iters}")


def phase_service(cfg, mesh, interpret: bool, n_requests: int = 8,
                  max_batch: int = 4):
    """(d): `SolveService` at the config's size."""
    import jax.numpy as jnp

    from repro.core import nekbone
    from repro.resilience.retry import RetryPolicy
    from repro.serving.solve_service import SolveRequest, SolveService

    prob = nekbone.setup_problem(
        mesh, variant=cfg.variant, d=cfg.d, helmholtz=cfg.helmholtz,
        dtype=jnp.dtype(cfg.precision), backend="pallas",
        block_elems=_block(cfg, mesh, nrhs=max_batch), interpret=interpret,
        nrhs=max_batch)
    # no rung past the first: a fallback would leave the kernel unnoticed
    policy = RetryPolicy(restart=False, backend_fallback=False,
                         precision_fallback=False)
    svc = SolveService(prob, policy=policy, max_batch=max_batch,
                       tol=cfg.tol, max_iter=cfg.max_iter)
    t0 = time.perf_counter()
    warm = svc.warmup()
    t_warm = time.perf_counter() - t0
    _say(f"(d) service backend={prob.backend} max_batch={max_batch} "
         f"buckets={list(svc.cache.buckets)} warmup_traces={warm} "
         f"(informational: warmup_s={t_warm:.2f})")
    make = _manufacturer(prob)
    reqs = []
    for uid in range(n_requests):
        b, _ = make(100 + uid)
        reqs.append(SolveRequest(uid=uid, b=b))
        svc.submit(reqs[-1])
    t0 = time.perf_counter()
    svc.run_until_drained()
    t_serve = time.perf_counter() - t0
    bad = []
    for r in reqs:
        rep = r.report
        ok = (r.done and r.error is None and rep is not None
              and rep.converged and tuple(rep.rung) == ("initial",))
        _say(f"(d)   request {r.uid}: "
             + (f"error={r.error}" if rep is None else
                f"converged={rep.converged} rung={rep.rung[0]} "
                f"iters={int(rep.iterations[0])} "
                f"true_residual={float(rep.true_residual[0]):.3e} "
                f"wall_s={r.wall_s:.3f}")
             + ("" if ok else " FAIL"))
        if not ok:
            bad.append(r.uid)
    post = svc.trace_count - warm
    _say(f"(d) service answered {len(reqs) - len(bad)}/{len(reqs)}; "
         f"post_warmup_traces={post} (informational: serve_s={t_serve:.2f})")
    _require(not bad, f"requests failed: {bad}")
    _require(post == 0, f"{post} trace(s) after warmup")


def phase_sharded(cfg, mesh, interpret: bool, devices: int = 4):
    """(e): the element-sharded solve against the 1-device solve."""
    import jax
    import jax.numpy as jnp

    from repro.core import nekbone
    from repro.distributed.context import make_solver_ctx
    from repro.resilience.retry import RetryPolicy
    from repro.resilience.status import SolveStatus

    dt = jnp.dtype(cfg.precision)
    e = len(mesh.verts)

    def setup(ctx):
        e_shard = e if ctx is None else -(-e // devices)
        return nekbone.setup_problem(
            mesh, variant=cfg.variant, d=cfg.d, helmholtz=cfg.helmholtz,
            dtype=dt, backend="pallas", interpret=interpret, shard_ctx=ctx,
            block_elems=_block(cfg, mesh, e_total=e_shard))

    one = setup(None)
    b, x_ref = _manufacturer(one)(0)
    apply_one = jax.jit(one.op)
    # a sharded answer comes back to the first device before the 1-device
    # operator sees it: a Mosaic kernel cannot be partitioned implicitly
    first = jax.devices()[0]
    bar = RetryPolicy().verify_factor * max(
        cfg.tol, float(jnp.finfo(dt).eps) * float(jnp.linalg.norm(b)))

    def report(label, prob):
        res, t_1, t_w = _eager_solve(cfg, prob, b)
        status = SolveStatus(int(res.status)).name
        x = jax.device_put(res.x, first)
        true_res = float(jnp.linalg.norm(b - apply_one(x)))
        _say(f"(e) {label}: status={status} iters={int(res.iterations)} "
             f"true_residual={true_res:.3e} error={_rel(x, x_ref):.3e} "
             f"(informational: first_call_s={t_1:.2f} "
             f"warm_wall_s={t_w:.3f})")
        _require(status == "CONVERGED", f"{label} ended {status}")
        _require(true_res <= bar, f"{label}: true residual {true_res:.3e} "
                 f"above the acceptance bar {bar:.3e}")
        return int(res.iterations), x

    # a device's share of one element-local field; each shard's element
    # index map alone is that large
    share = -(-e // devices) * (cfg.order + 1) ** 3 * dt.itemsize
    devs = jax.devices()[:devices]
    _say(f"(e) sharded solve: E={e} N={cfg.order} dofs={mesh.n_global} "
         f"devices={devices} backend={one.backend} acceptance bar "
         f"||b - A x|| <= {bar:.3e}")
    base_it, base_x = report("1 device", one)
    for exchange, grid in (("psum", None), ("neighbour", "auto")):
        ctx = make_solver_ctx(devices=devices, exchange=exchange, grid=grid)
        prob = setup(ctx)
        part = prob.partition
        its, x = report(f"{devices} devices exchange={exchange} "
                        f"grid={part.grid}", prob)
        d_it = abs(its - base_it)
        # both answers pass the bar, so A (x_s - x_1) is within twice it
        d_res = float(jnp.linalg.norm(apply_one(x - base_x)))
        _say(f"(e)   vs 1 device: |d iters|={d_it} "
             f"||A (x_s - x_1)||={d_res:.3e} "
             f"elems/shard={[int(c) for c in part.elem_counts]}")
        _require(d_it <= 1, f"{exchange}: iterations differ by {d_it}")
        _require(d_res <= 2 * bar, f"{exchange}: solutions differ by "
                 f"{d_res:.3e} through A (limit {2 * bar:.3e})")
        _check_resident(exchange, prob, devs, share)


def _check_resident(label, prob, devs, share):
    """With `prob` alive, every device holds at least `share` bytes of its
    per-shard arrays, and (on a TPU) at least that many bytes in use."""
    import jax

    held = {d: 0 for d in devs}
    for leaf in jax.tree.leaves(prob.shard_arrays):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    for d in devs:
        stats = d.memory_stats() or {}
        in_use = int(stats.get("bytes_in_use", 0))
        _say(f"(e)   {label} device {d.id}: per-shard arrays {held[d]} "
             f"bytes, bytes_in_use={in_use} "
             f"peak_bytes_in_use={int(stats.get('peak_bytes_in_use', 0))} "
             f"(share of one element field {share})")
        _require(held[d] >= share, f"{label}: device {d.id} holds "
                 f"{held[d]} bytes of the problem, under its share {share}")
        if d.platform == "tpu":
            _require(in_use >= share, f"{label}: device {d.id} has "
                     f"{in_use} bytes in use, under its share {share}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: phases (a)-(d) on one chip; 4: the sharded "
                         "solve on four chips against one (phase (e))")
    args = ap.parse_args(argv)

    from repro import compile_cache
    from repro.configs.nekbone import CONFIG

    cache = compile_cache.enable()
    dev, count = check_device(args.chips)
    _say(f"compile cache: {cache}")
    cfg = CONFIG
    _say(f"config: {dataclasses.asdict(cfg)}")
    mesh, mesh_affine = config_meshes(cfg)
    if args.chips == 4:
        phases = [("sharded", lambda: phase_sharded(cfg, mesh, False, 4))]
    else:
        phases = [
            ("kernels", lambda: phase_kernels(cfg, mesh, mesh_affine, False)),
            ("solve", lambda: phase_solve(cfg, mesh, False)),
            ("service", lambda: phase_service(cfg, mesh, False)),
        ]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception as exc:             # reported, and the run fails
            failed.append(name)
            traceback.print_exc()
            _say(f"phase {name} FAILED: {type(exc).__name__}: {exc}")
        _say(f"phase {name}: {'FAILED' if name in failed else 'ok'} "
             f"(informational: {time.perf_counter() - t0:.1f}s)")
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
