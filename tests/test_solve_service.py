"""Production solve service: bucketed jit-cache batching with padded RHS.

Covers the bucket ladder + trace-count gate (warmup then randomized queue
depths compile NOTHING new), padded-column masking (padding never flips a
real column's status and never reaches a report), bit-parity of bucketed
serving vs direct `solve_resilient` calls, submit-time validation, the
batch-loss regression (a raising solve fails the offender, not the
batch), and per-request latency metrics.

Single-device coverage; the retry-level rebuild-nrhs regression lives in
tests/test_resilience.py next to the rest of the ladder suite.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.contracts import NoRetrace
from repro.core import mesh_gen, nekbone
from repro.resilience.retry import RetryPolicy, solve_resilient
from repro.resilience.status import SolveStatus
from repro.serving import solve_service
from repro.serving.bucket_cache import (BucketedSolveCache, bucket_sizes,
                                        problem_key)
from repro.serving.solve_service import SolveRequest, SolveService

TOL = 1e-6


@pytest.fixture(scope="module")
def poisson():
    mesh = mesh_gen.deform_trilinear(mesh_gen.box_mesh(2, 2, 1, 3), seed=3)
    prob = nekbone.setup_problem(mesh, variant="trilinear",
                                 dtype=jnp.float32)
    return mesh, prob


def _rhs(prob, rng):
    return nekbone.rhs_from_solution(
        prob, jnp.asarray(rng.standard_normal(prob.mesh.n_global),
                          jnp.float32))


# --------------------------------------------------------------------------
# bucket ladder + cache keys
# --------------------------------------------------------------------------

def test_bucket_ladder_shapes():
    assert bucket_sizes(1) == (1,)
    assert bucket_sizes(4) == (1, 2, 4)
    assert bucket_sizes(8) == (1, 2, 4, 8)
    # a non-power-of-two cap caps the ladder at itself: a full queue never
    # pads past the service's own batch limit
    assert bucket_sizes(6) == (1, 2, 4, 6)
    with pytest.raises(ValueError, match="max_batch"):
        bucket_sizes(0)


def test_cache_key_separates_rebuilt_problems(poisson):
    mesh, prob = poisson
    k = problem_key(prob)
    assert k == problem_key(prob)  # deterministic
    ref = nekbone.setup_problem(mesh, variant="trilinear",
                                dtype=jnp.bfloat16)
    assert problem_key(ref) != k   # dtype is part of the key
    cache = BucketedSolveCache(max_batch=4, tol=TOL)
    assert cache.bucket_for(3) == 4
    assert cache.bucket_for(4) == 4
    assert cache.bucket_for(9) == 9  # beyond the ladder: unbucketed


# --------------------------------------------------------------------------
# the trace-count gate
# --------------------------------------------------------------------------

def test_warmup_then_randomized_depths_trace_nothing(poisson):
    """The tentpole acceptance: after warming the bucket ladder, a stream
    of randomized queue depths 1..max_batch compiles ZERO new solves."""
    _, prob = poisson
    svc = SolveService(prob, max_batch=8, tol=TOL, max_iter=200)
    warm = svc.warmup()
    # one solver per bucket + the verify operator at each bucket shape
    assert warm == 2 * len(svc.cache.buckets)
    rng = np.random.default_rng(0)
    depth_rng = np.random.default_rng(1)
    reqs = []
    while len(reqs) < 20:
        for _ in range(int(depth_rng.integers(1, svc.max_batch + 1))):
            req = SolveRequest(uid=len(reqs), b=_rhs(prob, rng))
            svc.submit(req)
            reqs.append(req)
        svc.step()
    svc.run_until_drained()
    violations = NoRetrace.counts(warm, svc.trace_count,
                                  "randomized-depths")
    assert not violations, [str(v) for v in violations]
    assert all(r.done and r.report.converged for r in reqs)


def test_unwarmed_service_traces_on_demand(poisson):
    """Without warmup the first request of a bucket width pays the trace —
    the cache still converges to the warmed steady state."""
    _, prob = poisson
    svc = SolveService(prob, max_batch=2, tol=TOL, max_iter=200)
    rng = np.random.default_rng(2)
    for uid in range(2):
        svc.submit(SolveRequest(uid=uid, b=_rhs(prob, rng)))
    svc.step()
    first = svc.trace_count
    assert first > 0
    for uid in range(2, 4):
        svc.submit(SolveRequest(uid=uid, b=_rhs(prob, rng)))
    svc.step()
    # same bucket: replayed, not retraced
    assert not NoRetrace.counts(first, svc.trace_count, "unwarmed-repeat")


# --------------------------------------------------------------------------
# padding semantics + bit parity
# --------------------------------------------------------------------------

def test_bucketed_single_request_bit_parity(poisson):
    """A bucketed single request returns bit-identical answers to a direct
    `solve_resilient` call on the same problem."""
    _, prob = poisson
    rng = np.random.default_rng(3)
    b = _rhs(prob, rng)
    svc = SolveService(prob, max_batch=8, tol=TOL, max_iter=200)
    svc.warmup()
    req = SolveRequest(uid=0, b=b)
    svc.submit(req)
    svc.step()
    ref = solve_resilient(prob, b, tol=TOL, max_iter=200)
    assert req.report.converged and ref.converged
    np.testing.assert_array_equal(np.asarray(req.report.x),
                                  np.asarray(ref.x))
    assert int(req.report.iterations[0]) == int(ref.iterations[0])


def test_padded_columns_are_bit_neutral(poisson):
    """3 requests pack into bucket 4 (one zero-padded column): every real
    column is bit-identical to the direct block solve at the bucket width
    (the same compiled shape, a zero fourth column), agrees with the
    unpadded 3-column solve to the solve tolerance, and per-request
    reports carry length-1 arrays (padding never reaches a SolveReport).

    Bit identity across the two widths is no claim: they are two compiled
    programs, free to round differently."""
    _, prob = poisson
    rng = np.random.default_rng(4)
    bs = [_rhs(prob, rng) for _ in range(3)]
    svc = SolveService(prob, max_batch=4, tol=TOL, max_iter=200)
    svc.warmup()
    reqs = [SolveRequest(uid=i, b=b) for i, b in enumerate(bs)]
    for r in reqs:
        svc.submit(r)
    assert svc.step() == 3
    padded = solve_resilient(
        prob, jnp.stack(bs + [jnp.zeros_like(bs[0])], axis=-1), tol=TOL,
        max_iter=200)
    unpadded = solve_resilient(prob, jnp.stack(bs, axis=-1), tol=TOL,
                               max_iter=200)
    assert int(padded.iterations[3]) == 0        # the pad never iterates
    # the acceptance bar of solve_resilient: each answer's true residual
    # is within verify_factor * max(tol, eps ||b||), so two answers to one
    # b differ by at most twice that through A
    eps = float(jnp.finfo(jnp.float32).eps)
    for j, req in enumerate(reqs):
        x = np.asarray(req.report.x)
        np.testing.assert_array_equal(x, np.asarray(padded.x[..., j]))
        assert int(req.report.iterations[0]) == int(padded.iterations[j])
        assert abs(int(req.report.iterations[0])
                   - int(unpadded.iterations[j])) <= 1
        bar = RetryPolicy().verify_factor * max(
            TOL, eps * float(jnp.linalg.norm(bs[j])))
        diff = prob.op(jnp.asarray(x) - unpadded.x[..., j])
        assert float(jnp.linalg.norm(diff)) <= 2 * bar
        assert req.report.status.shape == (1,)
        assert len(req.report.rung) == 1


def test_padded_column_never_flips_a_real_columns_status(poisson):
    """A failing real column (NaN RHS — rejected nowhere: shape and dtype
    are valid) packed with healthy ones and a padded column: the failure
    stays structured on ITS request, siblings converge with untouched
    status, and the retry subset re-enters through warm buckets (zero new
    traces even on the failure path)."""
    _, prob = poisson
    rng = np.random.default_rng(5)
    good = [SolveRequest(uid=i, b=_rhs(prob, rng)) for i in range(2)]
    bad = SolveRequest(uid=9, b=jnp.full(prob.mesh.n_global, jnp.nan,
                                         jnp.float32))
    svc = SolveService(prob, max_batch=4, tol=TOL, max_iter=200)
    warm = svc.warmup()
    for r in (good[0], bad, good[1]):
        svc.submit(r)
    assert svc.step() == 3
    assert not NoRetrace.counts(warm, svc.trace_count, "failure-path")
    for r in good:
        assert r.done and r.error is None and r.report.converged
        assert int(r.report.status[0]) == SolveStatus.CONVERGED
    # the NaN request fails STRUCTURED (diverged through initial+restart),
    # done=True, no exception, batch-mates unharmed
    assert bad.done and bad.error is None
    assert not bad.report.converged
    assert int(bad.report.status[0]) == SolveStatus.DIVERGED
    assert [a.rung for a in bad.report.attempts] == ["initial", "restart"]


# --------------------------------------------------------------------------
# submit-time validation (at the door, not mid-step)
# --------------------------------------------------------------------------

def test_submit_rejects_batched_rhs(poisson):
    mesh, prob = poisson
    svc = SolveService(prob)
    with pytest.raises(ValueError, match="single"):
        svc.submit(SolveRequest(uid=0, b=jnp.zeros((mesh.n_global, 2))))


def test_submit_rejects_wrong_length_at_the_door(poisson):
    """Regression: a wrong-LENGTH rank-1 b used to pass submit and make
    `jnp.stack` throw mid-step, taking down its batch-mates.  Now the
    offender is rejected at submit and the good requests serve clean."""
    mesh, prob = poisson
    svc = SolveService(prob, max_batch=4, tol=TOL, max_iter=200)
    rng = np.random.default_rng(6)
    ok = SolveRequest(uid=0, b=_rhs(prob, rng))
    svc.submit(ok)
    with pytest.raises(ValueError, match="dofs"):
        svc.submit(SolveRequest(uid=1,
                                b=jnp.zeros(mesh.n_global + 5,
                                            jnp.float32)))
    assert len(svc.queue) == 1
    svc.step()  # the accepted request is unaffected
    assert ok.done and ok.report.converged


def test_submit_rejects_uncastable_dtype(poisson):
    _, prob = poisson
    svc = SolveService(prob)
    with pytest.raises(TypeError, match="cast"):
        svc.submit(SolveRequest(
            uid=0, b=np.array(["x"] * prob.mesh.n_global, dtype=object)))
    assert not svc.queue


# --------------------------------------------------------------------------
# batch-loss regression: pop on success, isolate the offender
# --------------------------------------------------------------------------

def test_raising_solve_fails_offender_not_batch(poisson, monkeypatch):
    """Regression: `step` used to pop the batch BEFORE solving, so an
    exception lost every request in it.  A solve that raises now fails
    only the offending request (structured ``error``, ``done=True``);
    batch-mates get their answers and the queue drains."""
    _, prob = poisson
    real = solve_service.solve_resilient

    def flaky(problem, b, *args, **kwargs):
        if bool(jnp.isnan(b).any()):
            raise RuntimeError("mid-solve explosion")
        return real(problem, b, *args, **kwargs)

    monkeypatch.setattr(solve_service, "solve_resilient", flaky)
    svc = SolveService(prob, max_batch=4, tol=TOL, max_iter=200)
    rng = np.random.default_rng(7)
    good = [SolveRequest(uid=i, b=_rhs(prob, rng)) for i in range(2)]
    bad = SolveRequest(uid=9, b=jnp.full(prob.mesh.n_global, jnp.nan,
                                         jnp.float32))
    for r in (good[0], bad, good[1]):
        svc.submit(r)
    assert svc.step() == 3
    assert not svc.queue  # nothing silently lost, nothing stuck
    for r in good:
        assert r.done and r.error is None and r.report.converged
    assert bad.done and bad.report is None
    assert "mid-solve explosion" in bad.error
    assert svc.errors == 1 and svc.served == 2


def test_raising_rebuild_fails_request_structured():
    """The satellite's scenario end-to-end with a real ladder: a bf16
    problem whose precision:float32 rung REBUILD raises.  The request
    comes back done with the exception recorded — not an exception out of
    `step`, not a vanished queue entry."""
    mesh = mesh_gen.deform_trilinear(mesh_gen.box_mesh(2, 2, 1, 3), seed=3)
    prob = nekbone.setup_problem(mesh, variant="trilinear",
                                 dtype=jnp.bfloat16)

    def bad_rebuild(backend=None, dtype=None, nrhs=None):
        raise RuntimeError("rebuild exploded")

    svc = SolveService(prob, max_batch=2, tol=1e-6, max_iter=50,
                       rebuild=bad_rebuild)
    req = SolveRequest(uid=0, b=jnp.full(mesh.n_global, jnp.nan,
                                         jnp.bfloat16))
    svc.submit(req)
    assert svc.step() == 1
    assert not svc.queue
    assert req.done and req.report is None
    assert "rebuild exploded" in req.error
    assert svc.errors == 1


# --------------------------------------------------------------------------
# per-request latency metrics (the early-return contract)
# --------------------------------------------------------------------------

def test_per_request_latency_metrics(poisson):
    _, prob = poisson
    svc = SolveService(prob, max_batch=4, tol=TOL, max_iter=200)
    svc.warmup()
    rng = np.random.default_rng(8)
    reqs = [SolveRequest(uid=i, b=_rhs(prob, rng)) for i in range(3)]
    for r in reqs:
        svc.submit(r)
    svc.step()
    iters = [int(r.report.iterations[0]) for r in reqs]
    for r in reqs:
        assert r.queue_s >= 0
        assert r.solve_s > 0
        assert r.wall_s == pytest.approx(r.queue_s + r.solve_s)
    # early return: a request's solve share scales with ITS column's
    # iteration count — the earliest-converging column has the smallest
    # attributed solve time, the slowest carries the block
    order_by_iters = np.argsort(iters)
    solve_s = [reqs[j].solve_s for j in order_by_iters]
    assert solve_s == sorted(solve_s)
    slowest = reqs[int(order_by_iters[-1])]
    assert all(r.solve_s <= slowest.solve_s + 1e-12 for r in reqs)


def test_drain_steps_and_served_counter(poisson):
    """The skeleton's drain contract survives the rewrite: 3 requests at
    max_batch=2 drain in 2 steps, every report verifies."""
    _, prob = poisson
    svc = SolveService(prob, max_batch=2, tol=TOL, max_iter=200)
    rng = np.random.default_rng(9)
    bs = [_rhs(prob, rng) for _ in range(3)]
    reqs = [SolveRequest(uid=i, b=b) for i, b in enumerate(bs)]
    for r in reqs:
        svc.submit(r)
    assert svc.run_until_drained() == 2
    assert svc.served == 3 and not svc.queue
    for req, b in zip(reqs, bs):
        r = np.asarray(b, np.float64) - np.asarray(
            prob.op(req.report.x), np.float64)
        assert float(np.sqrt((r * r).sum())) < 10 * TOL
