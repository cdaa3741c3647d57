"""Solve-service latency under a replayed request stream.

Replays a seeded Poisson arrival stream of right-hand sides against
`serving.solve_service.SolveService` — the bucketed, padded, batched
production loop — and reports what a service operator actually watches:
per-request wall-clock latency percentiles (p50/p95/p99), the
queue-vs-solve split, sustained throughput, and the compilation-cache
behaviour (traces paid at warmup vs traces paid while serving).

The headline gate is machine-checked here, not eyeballed: after the
one-time bucket-ladder warmup, serving the whole randomized-depth stream
must compile ZERO new solves (`post_warmup_traces == 0` — every packed
block replays a warm bucket).  `--smoke` runs one small configuration
under that gate for CI.

Results land in BENCH_serve.json via the benchio merge layer: a smoke row
re-measures only its own configuration and never clobbers full-run rows.

    {"serve": [{"max_batch": ..., "rate": ..., "p50_ms": ..., ...}]}

CPU wall numbers: relative, not roofline claims.
"""

from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np

import benchio
from repro.core import mesh_gen, nekbone
from repro.serving.solve_service import SolveRequest, SolveService

OUT_JSON = "BENCH_serve.json"

# a configuration's identity: everything that changes the measured numbers
ROW_KEYS = {
    "serve": ("max_batch", "rate", "requests", "nx", "order", "variant",
              "dtype"),
}


def _percentiles(xs_s):
    xs = np.asarray(xs_s, np.float64) * 1e3
    return {f"p{p}_ms": round(float(np.percentile(xs, p)), 4)
            for p in (50, 95, 99)}


def serve_row(*, nx: int, order: int, max_batch: int, rate: float,
              n_requests: int, tol: float = 1e-6, seed: int = 0) -> dict:
    """Warm the bucket ladder, replay one seeded Poisson stream, report.

    Arrivals are Poisson(`rate`) new requests per service step, so queue
    depths wander over 1..max_batch (and beyond — the service drains at
    most `max_batch` per step) exactly like a bursty client population.
    """
    mesh = mesh_gen.deform_trilinear(mesh_gen.box_mesh(nx, nx, 1, order),
                                     seed=3)
    prob = nekbone.setup_problem(mesh, variant="trilinear",
                                 dtype=jnp.float32)
    svc = SolveService(prob, max_batch=max_batch, tol=tol, max_iter=300)
    warm = svc.warmup()

    rng = np.random.default_rng(seed)
    reqs = []
    depths = []
    t0 = time.perf_counter()
    while len(reqs) < n_requests or svc.queue:
        for _ in range(min(int(rng.poisson(rate)),
                           n_requests - len(reqs))):
            b = nekbone.rhs_from_solution(
                prob, jnp.asarray(rng.standard_normal(mesh.n_global),
                                  jnp.float32))
            req = SolveRequest(uid=len(reqs), b=b)
            svc.submit(req)
            reqs.append(req)
        served = svc.step()
        if served:
            depths.append(served)
    elapsed = time.perf_counter() - t0

    assert all(r.done for r in reqs)
    row = {
        "max_batch": max_batch, "rate": rate, "requests": n_requests,
        "nx": nx, "order": order, "variant": "trilinear",
        "dtype": "float32", "dofs": int(mesh.n_global),
        "warmup_traces": warm,
        "post_warmup_traces": svc.trace_count - warm,
        "batch_depths": sorted(set(depths)),
        "converged": int(sum(r.report.converged for r in reqs)),
        "errors": svc.errors,
        "throughput_rps": round(n_requests / elapsed, 3),
    }
    row.update(_percentiles([r.wall_s for r in reqs]))
    row["queue_p50_ms"] = round(
        float(np.percentile([r.queue_s for r in reqs], 50)) * 1e3, 4)
    row["solve_p50_ms"] = round(
        float(np.percentile([r.solve_s for r in reqs], 50)) * 1e3, 4)
    return row


def check_rows(rows):
    """The serving contract, machine-checked on every run."""
    for r in rows:
        assert r["post_warmup_traces"] == 0, (
            f"trace gate violated: serving {r['requests']} requests at "
            f"max_batch={r['max_batch']} compiled "
            f"{r['post_warmup_traces']} new solves after warmup — {r}")
        assert r["converged"] == r["requests"] and r["errors"] == 0, r
        assert len(r["batch_depths"]) > 1, (
            f"stream was not mixed-depth, gate is vacuous: {r}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=60)
    ap.add_argument("--tol", type=float, default=1e-6)
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: one small configuration, 50 requests, "
                         "assert the zero-trace-after-warmup gate")
    args = ap.parse_args()

    if args.smoke:
        rows = [serve_row(nx=2, order=3, max_batch=8, rate=3.0,
                          n_requests=50, tol=args.tol)]
    else:
        rows = [serve_row(nx=3, order=4, max_batch=mb, rate=rate,
                          n_requests=args.requests, tol=args.tol)
                for mb in (4, 8) for rate in (2.0, 6.0)]
    check_rows(rows)
    benchio.merge_payload(OUT_JSON, {"serve": rows}, row_keys=ROW_KEYS)
    for r in rows:
        print(f"# max_batch={r['max_batch']} rate={r['rate']}: "
              f"p50={r['p50_ms']}ms p95={r['p95_ms']}ms "
              f"p99={r['p99_ms']}ms {r['throughput_rps']} req/s, "
              f"traces {r['warmup_traces']}+{r['post_warmup_traces']}")
    print(f"# wrote {OUT_JSON} ({len(rows)} serve rows, zero-trace gate "
          f"held)")


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
