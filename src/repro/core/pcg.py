"""Preconditioned conjugate gradients (Nekbone's PCG, Figure 2).

The operator is supplied as a closure `A(x)` over global dofs (gather o
axhelm o scatter).  Preconditioners: COPY (none) and JACOBI (inverse
diagonal).  The loop is a `jax.lax.while_loop`, so the whole solve is a
single XLA computation — steppable under pjit on the production mesh.

`pcg_block` is the multi-RHS path: nrhs stacked right-hand sides advance
through one batched iteration with per-column alpha/beta (each column runs
its own mathematically independent CG — the operator is RHS-independent, so
batching changes reduction order only) and a converged-column mask that
freezes finished columns while the rest keep iterating.

Health monitoring lives INSIDE the loop: every iteration checks the carried
``rr`` for NaN/Inf (a poisoned operator/field stops a column within one
iteration instead of spinning to ``max_iter``), an optional stagnation
window (no new residual minimum for N counted iterations), and the Lanczos
breakdown guard.  All three piggyback on the ``rr``/``p.Ap`` scalars the
iteration already reduces, so on the sharded solve they add ZERO extra
collectives (HLO-gated in tests/test_resilience_sharded.py).  The outcome
is reported as a `resilience.status.SolveStatus` code in
``PCGResult.status``.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.resilience.status import SolveStatus, classify

__all__ = ["PCGResult", "pcg", "pcg_block", "refine", "owned_dot"]

# at the default precision a TPU computes an fp32 vdot in bf16
HIGHEST = jax.lax.Precision.HIGHEST


def _up(u: jnp.ndarray) -> jnp.ndarray:
    """Upcast sub-fp32 floats for reduction accumulation.

    The PCG inner products feed the tolerance check, alpha/beta, and the
    stagnation/divergence flags; accumulating them at the ITERATE dtype
    hands those consumers 8-bit-mantissa scalars on a bf16 solve (a sum of
    a few thousand like-magnitude bf16 terms stops absorbing new terms
    entirely).  fp32 and wider pass through untouched, so full-precision
    solves stay bit-identical.
    """
    if jnp.issubdtype(u.dtype, jnp.floating) and u.dtype.itemsize < 4:
        return u.astype(jnp.float32)
    return u


def owned_dot(weight: jnp.ndarray, axis_name: Optional[str] = None,
              batched: bool = False
              ) -> Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]:
    """A `dot` for `pcg`/`pcg_block` on element-sharded fields.

    `weight` is the per-shard ownership indicator (1.0 where this shard owns
    the dof, 0.0 on ghost/padding/trash slots), so interface dofs — which
    are replicated on every shard that touches them — are counted exactly
    once; `axis_name` psums the partial reductions across shards.  Inside
    `shard_map` this makes every PCG inner product a single scalar psum,
    which is all the communication the iteration adds on top of the gather.

    With `batched=True` the trailing axis of u/v is an RHS batch: the
    reduction runs over every axis EXCEPT the last and returns per-column
    dots of shape (nrhs,) — still one psum, just of an (nrhs,) buffer.

    Reduced-precision operands are accumulated in fp32 (see `_up`): the
    psum'd partials stay fp32 scalars, so the collective count is
    unchanged and fp32/fp64 fields reduce bit-identically to before.
    """

    def dot(u: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
        w = weight if u.ndim == weight.ndim else weight.reshape(
            weight.shape + (1,) * (u.ndim - weight.ndim))
        prod = jnp.where(w, _up(u) * _up(v), 0)
        if batched:
            part = jnp.sum(prod, axis=tuple(range(prod.ndim - 1)))
        else:
            part = jnp.sum(prod)
        if axis_name is None:
            return part
        with obs.scope("exchange"):
            return jax.lax.psum(part, axis_name)

    return dot


class PCGResult(NamedTuple):
    """Outcome of a PCG solve.

    ``status`` is a `resilience.status.SolveStatus` code (int32 scalar for
    :func:`pcg`, per-column (nrhs,) for :func:`pcg_block`) saying WHY the
    solve stopped; ``breakdown`` is kept as the boolean view of the
    BREAKDOWN case for existing callers.

    `breakdown` flags a Lanczos breakdown: the iteration hit ``p.Ap <= 0``
    while the (column's) residual was still above tolerance — the operator
    is not SPD on the Krylov space (rank-deficient direction), so CG cannot
    advance.  A column whose carried ``rr`` turns NaN/Inf is DIVERGED, and
    one that makes no new residual minimum for ``stagnation_window``
    counted iterations is STAGNATED.  In every non-CONVERGED case the
    affected solve/column is FROZEN at its last *finite* iterate — a
    diverged step is rolled back before the poison reaches ``x`` — so
    `x` is always a valid restart point and ``residual`` reports where it
    stalled, not convergence.

    Both flag fields are ALWAYS boolean/int arrays (never Python None):
    `pcg`/`pcg_block`/the sharded runner all populate them, and the
    defaults below are concrete zero-dim numpy scalars so even a manually
    constructed result has a uniform field presence between the
    single-device and sharded paths.
    """

    x: jnp.ndarray
    iterations: jnp.ndarray
    residual: jnp.ndarray          # final sqrt(r.r) (last finite iterate)
    initial_residual: jnp.ndarray
    breakdown: jnp.ndarray = np.bool_(False)   # bool / (nrhs,) bool
    status: jnp.ndarray = np.int32(SolveStatus.MAXITER)  # SolveStatus codes


def _iter_op(a_op):
    """Adapt `a_op` to the (x, iteration) calling convention.

    The fault-injection harness (`resilience.inject`) needs to know WHICH
    operator application it is corrupting, so operators built with a
    `FaultSpec` advertise ``takes_iteration = True`` and receive the
    carried iteration counter (-1 for the initial-residual application).
    Plain operators are wrapped to ignore it — the counter is already in
    the loop state, so threading it is free.
    """
    if getattr(a_op, "takes_iteration", False):
        return a_op

    def wrapped(x, it):
        del it
        return a_op(x)

    return wrapped


_INIT_ITER = -1  # iteration index of the initial-residual application


def pcg(a_op: Callable[[jnp.ndarray], jnp.ndarray],
        b: jnp.ndarray,
        x0: Optional[jnp.ndarray] = None,
        precond: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
        tol: float = 1e-8,
        max_iter: int = 200,
        dot: Optional[Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]] = None,
        stagnation_window: int = 0,
        ) -> PCGResult:
    """Solve A x = b with (preconditioned) CG.

    `dot` may be overridden (e.g. with a mesh-weighted/psum'd inner product on
    a sharded solve); defaults to the plain full contraction.

    `stagnation_window` > 0 additionally stops the solve with
    ``SolveStatus.STAGNATED`` when ``rr`` makes no new minimum for that many
    counted iterations (0 — the default — disables the check, keeping the
    iteration trace bit-identical to the unmonitored loop; the NaN/Inf and
    breakdown checks are always on and only fire on already-poisoned
    solves).

    Reductions accumulate in fp32 even on reduced-precision iterates (the
    default dot upcasts, `owned_dot` does the same): ``rr``/``rz``/``p.Ap``
    — and everything derived from them — are fp32 scalars on a bf16 solve,
    while the iterate vectors stay at the solve dtype (alpha/beta are cast
    back before the axpy updates, so the while_loop carry is dtype-stable).
    """
    if dot is None:
        def dot(u, v):
            return jnp.vdot(_up(u), _up(v), precision=HIGHEST)
    if precond is None:
        def precond(r):
            return r
    dot = obs.scoped("vec.dot", dot)
    precond = obs.scoped("vec.precond", precond)
    a2 = _iter_op(a_op)

    with obs.scope("vec.update"):
        x = jnp.zeros_like(b) if x0 is None else x0
    ax = a2(x, jnp.asarray(_INIT_ITER, jnp.int32))
    with obs.scope("vec.update"):
        r = b - ax
    z = precond(r)
    p = z
    rz = dot(r, z)
    rr = dot(r, r)
    with obs.scope("vec.update"):
        r0 = jnp.sqrt(rr)
        tol2 = (tol * tol)
    window = jnp.asarray(stagnation_window, jnp.int32)
    win_on = window > 0

    # rr = dot(r, r) is carried in the state: the reduction happens in the
    # body where r is produced, and cond reads the carried scalar — cond is
    # free of cross-element communication (and the trailing evaluation at
    # loop exit costs nothing), instead of re-reducing r on every check.
    # The health flags (div/stag) read the same carried scalar, so the
    # checks add no reductions at all.
    def cond(state):
        _, _, _, _, _, rr, it, brk, div, stag, _, _ = state
        healthy = ~brk & ~div & ~stag
        return jnp.logical_and(it < max_iter,
                               jnp.logical_and(rr > tol2, healthy))

    def body(state):
        # A(p) outside the update's scope: its own layers name its work
        ap = a2(state[3], state[6])
        with obs.scope("vec.update"):
            return advance(state, ap)

    def advance(state, ap):
        x, r, z, p, rz, rr, it, brk, div, stag, stall, best = state
        pap = dot(p, ap)
        # Lanczos breakdown guard: p.Ap <= 0 with the residual still above
        # tolerance means A is not SPD along p (rank-deficient direction) —
        # alpha would be garbage (or inf/nan), so FREEZE the iterate at its
        # last value, flag it, and let cond exit; silently substituting a
        # denominator would keep "converging" to a wrong answer.
        bad = pap <= 0.0
        alpha = jnp.where(bad, 0.0, rz / jnp.where(bad, 1.0, pap))
        step = alpha.astype(x.dtype)   # fp32 scalar -> iterate dtype
        x_new = x + step * p
        r_new = r - step * ap
        z_new = precond(r_new)
        rz_new = dot(r_new, z_new)
        rr_new = dot(r_new, r_new)
        # divergence: the carried rr went non-finite THIS iteration (a NaN
        # anywhere in A(p) reaches rr through the dots) — roll the whole
        # step back so x stays the last finite iterate, flag, and exit.
        hurt = ~jnp.isfinite(rr_new)
        div = div | hurt
        x = jnp.where(hurt, x, x_new)
        r = jnp.where(hurt, r, r_new)
        z = jnp.where(hurt, z, z_new)
        rz2 = jnp.where(hurt, rz, rz_new)
        rr2 = jnp.where(hurt, rr, rr_new)
        beta = jnp.where(bad | hurt, 0.0,
                         rz_new / jnp.where(rz != 0, rz, 1.0))
        p = jnp.where(bad | hurt, p, z + beta.astype(p.dtype) * p)
        advanced = ~bad & ~hurt
        # stagnation: count iterations since the last new rr minimum
        improved = rr2 < best
        stall = jnp.where(improved, 0,
                          stall + jnp.where(advanced, 1, 0).astype(jnp.int32))
        best = jnp.minimum(best, rr2)
        stag = stag | (win_on & advanced & (stall >= window) & (rr2 > tol2))
        # a frozen/rolled-back iteration did not advance: don't count it
        return (x, r, z, p, rz2, rr2,
                it + jnp.where(advanced, 1, 0).astype(jnp.int32), bad, div,
                stag, stall, best)

    state = (x, r, z, p, rz, rr, jnp.array(0, dtype=jnp.int32),
             jnp.array(False), jnp.array(False), jnp.array(False),
             jnp.array(0, jnp.int32), rr)
    (x, r, _, _, _, rr, it, brk, div, stag, _, _) = \
        jax.lax.while_loop(obs.scoped("vec.update", cond), body, state)
    with obs.scope("vec.update"):
        status = classify(rr, tol2, brk, div, stag)
        return PCGResult(x, it, jnp.sqrt(rr), r0, brk, status)


def pcg_block(a_op: Callable[[jnp.ndarray], jnp.ndarray],
              b: jnp.ndarray,
              x0: Optional[jnp.ndarray] = None,
              precond: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
              tol: float = 1e-8,
              max_iter: int = 200,
              dot: Optional[Callable[[jnp.ndarray, jnp.ndarray],
                                     jnp.ndarray]] = None,
              stagnation_window: int = 0,
              ) -> PCGResult:
    """Solve A X = B for nrhs stacked right-hand sides (trailing axis).

    Each column runs the SAME iteration as :func:`pcg` with its own
    alpha/beta — the operator is applied once per iteration to the whole
    block, so the gather's interface exchange and the element kernels'
    geometry loads are amortized over every column.  A column whose carried
    ``rr`` has met the tolerance is *frozen* (its alpha is masked to zero
    and its search direction stops updating), so late-converging columns
    cannot perturb finished ones.  The same freeze applies to the
    unhealthy cases, each with its own `SolveStatus` code per column: a
    Lanczos breakdown (``p.Ap <= 0`` while active), a DIVERGED column
    (carried ``rr`` NaN/Inf — its step is rolled back so ``x`` keeps the
    last finite iterate), and — when ``stagnation_window`` > 0 — a
    STAGNATED column (no new rr minimum for that many counted iterations).
    Healthy columns keep iterating; the loop runs until every column is
    converged, flagged, or ``max_iter``.

    `dot(u, v)` must reduce to per-column values of shape (nrhs,) — the
    default contracts every axis except the last; on a sharded solve pass
    ``owned_dot(weight, axis, batched=True)``.  Returns a `PCGResult` whose
    ``iterations``/``residual``/``initial_residual``/``status`` are
    per-column (nrhs,) arrays; ``iterations`` counts the iterations each
    column actually advanced before its freeze.
    """
    if dot is None:
        def dot(u, v):
            uv = _up(u) * _up(v)
            return jnp.sum(uv, axis=tuple(range(uv.ndim - 1)))
    if precond is None:
        def precond(r):
            return r
    dot = obs.scoped("vec.dot", dot)
    precond = obs.scoped("vec.precond", precond)
    a2 = _iter_op(a_op)

    with obs.scope("vec.update"):
        x = jnp.zeros_like(b) if x0 is None else x0
    ax = a2(x, jnp.asarray(_INIT_ITER, jnp.int32))
    with obs.scope("vec.update"):
        r = b - ax
    z = precond(r)
    p = z
    rz = dot(r, z)
    rr = dot(r, r)
    with obs.scope("vec.update"):
        r0 = jnp.sqrt(rr)
        tol2 = (tol * tol)
    nrhs = b.shape[-1]
    window = jnp.asarray(stagnation_window, jnp.int32)
    win_on = window > 0

    def cond(state):
        _, _, _, _, _, rr, it, brk, div, stag, _, _ = state
        live = (rr > tol2) & ~brk & ~div & ~stag
        return jnp.logical_and(it[-1] < max_iter, jnp.any(live))

    def body(state):
        # A(p) outside the update's scope: its own layers name its work
        ap = a2(state[3], state[6][-1])
        with obs.scope("vec.update"):
            return advance(state, ap)

    def advance(state, ap):
        x, r, z, p, rz, rr, it, brk, div, stag, stall, best = state
        active = (rr > tol2) & ~brk & ~div & ~stag  # (nrhs,) live columns
        pap = dot(p, ap)
        # Lanczos breakdown on an ACTIVE column: p.Ap <= 0 while its
        # residual is still above tolerance means A is not SPD along that
        # column's direction — its alpha would be garbage (the old guard
        # silently computed rz/1.0 and kept "iterating" toward a wrong x).
        # Freeze the column at its last iterate and flag it; the healthy
        # columns keep going.
        bad = active & (pap <= 0.0)
        brk = brk | bad
        active = active & ~bad
        # masked columns get alpha = 0: x, r, p freeze exactly where they
        # converged/broke (the where-guards keep 0/0 NaNs out of dead
        # columns)
        alpha = jnp.where(active, rz / jnp.where(pap > 0, pap, 1.0), 0.0)
        step = alpha.astype(x.dtype)   # fp32 per-column -> iterate dtype
        x_new = x + step * p
        r_new = r - step * ap
        z_new = precond(r_new)
        rz_new = dot(r_new, z_new)
        rr_new = dot(r_new, r_new)
        # divergence: an active column's rr went non-finite this iteration
        # (a NaN in its slice of A(p) reaches its per-column dot).  Roll
        # THAT column's step back — x keeps its last finite iterate for
        # the recovery restart — and flag it; siblings are untouched
        # because alpha/beta are per-column.
        hurt = active & ~jnp.isfinite(rr_new)
        div = div | hurt
        x = jnp.where(hurt, x, x_new)
        r = jnp.where(hurt, r, r_new)
        z = jnp.where(hurt, z, z_new)
        rz2 = jnp.where(hurt, rz, rz_new)
        rr2 = jnp.where(hurt, rr, rr_new)
        beta = jnp.where(active & ~hurt,
                         rz_new / jnp.where(rz != 0, rz, 1.0), 0.0)
        p = jnp.where(active & ~hurt, z + beta.astype(p.dtype) * p, p)
        advanced = active & ~hurt
        # stagnation: per-column count of iterations since a new rr minimum
        improved = rr2 < best
        stall = jnp.where(improved, 0, stall + advanced.astype(jnp.int32))
        best = jnp.minimum(best, rr2)
        stag = stag | (win_on & advanced & (stall >= window) & (rr2 > tol2))
        it = it.at[-1].add(1)
        return (x, r, z, p, rz2, rr2,
                it.at[:nrhs].add(advanced.astype(jnp.int32)), brk, div,
                stag, stall, best)

    # it carries (nrhs,) per-column counts plus one trailing global counter
    it0 = jnp.zeros((nrhs + 1,), jnp.int32)
    state = (x, r, z, p, rz, rr, it0, jnp.zeros((nrhs,), bool),
             jnp.zeros((nrhs,), bool), jnp.zeros((nrhs,), bool),
             jnp.zeros((nrhs,), jnp.int32), rr)
    (x, r, _, _, _, rr, it, brk, div, stag, _, _) = \
        jax.lax.while_loop(obs.scoped("vec.update", cond), body, state)
    with obs.scope("vec.update"):
        status = classify(rr, tol2, brk, div, stag)
        return PCGResult(x, it[:nrhs], jnp.sqrt(rr), r0, brk, status)


def refine(a_hi, a_lo, b: jnp.ndarray,
           x0: Optional[jnp.ndarray] = None,
           precond: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
           tol: float = 1e-8,
           max_iter: int = 200,
           dot: Optional[Callable[[jnp.ndarray, jnp.ndarray],
                                  jnp.ndarray]] = None,
           batched: bool = False,
           lo_dtype=jnp.bfloat16,
           inner_tol: float = 0.03,
           inner_window: int = 5,
           max_outer: int = 40,
           stall_limit: int = 1) -> PCGResult:
    """Mixed-precision iterative refinement: fp32 outer, `lo_dtype` inner.

    The Haidar-et-al recipe adapted to matrix-free PCG: the outer loop
    keeps the solution ``x``, the TRUE residual ``r = b - A_hi(x)`` and
    the correction accumulation in fp32 (``a_hi`` is the full-precision
    operator), while each sweep solves the correction system
    ``A d = r / ||r||`` with an inner :func:`pcg`/:func:`pcg_block` run on
    the reduced-precision operator ``a_lo`` — iterates, operator and
    preconditioner all at ``lo_dtype``, reductions in fp32 (see `_up`).
    Normalizing the inner RHS per column keeps the bf16 dynamic range
    centred whatever the outer residual's magnitude, and the correction is
    scaled back in fp32 (``x += d * ||r||``).

    Per-column semantics match :func:`pcg_block`: with ``batched=True``
    every scalar below is an (nrhs,) array, a converged/flagged column's
    inner RHS is zeroed — the inner solve freezes it at iteration 0 — and
    its fp32 state stops moving.  A sweep whose recomputed true residual
    does not IMPROVE a column is rolled back for that column (the sweep is
    deterministic, so re-trying the same sweep cannot help): after
    ``stall_limit`` consecutive non-improving sweeps the column is flagged
    ``STAGNATED`` — the escape hatch `resilience.retry`'s
    ``precision:float32`` rung catches.  A non-finite recomputed ``rr``
    rolls back likewise and flags ``DIVERGED`` immediately.

    Each sweep's inner stop is ADAPTIVE: on the unit-normalized RHS the
    reduction still needed is ``tol / ||r||``, so that (with a small
    safety factor, floored at ``inner_tol`` and capped at 0.3) is the
    sweep's target.  The ``inner_tol`` floor defaults to a few times the
    bf16 operator discrepancy (~2^-8): the TRUE-residual gain a sweep can
    buy saturates near ``eps_lo * kappa_eff`` however deep the inner
    drills, so drilling past the floor burns reduced-precision iterations
    that purchase nothing (measured on the bench mesh: floor 0.03 beats
    floor 0.001 by ~20% total iterations at tight tolerances).
    A first sweep that can reach ``tol`` outright therefore runs exactly
    as deep as a plain fp32 solve would and the refinement adds no extra
    iterations; when ``tol`` is below the reduced-precision floor, later
    sweeps only buy the factor they are asked for instead of re-running to
    the floor every time.  ``inner_window`` is the inner stagnation window
    that exits a sweep at the attainable floor instead of burning the
    iteration budget there.  The one `dot` serves both precisions (it
    upcasts).  ``iterations`` in the returned result counts TOTAL inner
    iterations per column — the number of reduced-precision operator
    applications, the quantity comparable to a plain fp32 solve's count —
    and the loop stops when it reaches ``max_iter`` (or after
    ``max_outer`` sweeps).
    """
    if dot is None:
        if batched:
            def dot(u, v):
                uv = _up(u) * _up(v)
                return jnp.sum(uv, axis=tuple(range(uv.ndim - 1)))
        else:
            def dot(u, v):
                return jnp.vdot(_up(u), _up(v), precision=HIGHEST)
    runner = pcg_block if batched else pcg
    inner_dot = dot
    dot = obs.scoped("vec.dot", dot)
    with obs.scope("vec.update"):
        b32 = jnp.asarray(b, jnp.float32)
        x = jnp.zeros_like(b32) if x0 is None \
            else jnp.asarray(x0, jnp.float32)
    ax = a_hi(x)
    with obs.scope("vec.update"):
        r = (b32 - ax).astype(jnp.float32)
    rr = dot(r, r)
    with obs.scope("vec.update"):
        r0 = jnp.sqrt(rr)
    with obs.scope("vec.update"):
        tol2 = tol * tol
    it_shape = rr.shape  # () or (nrhs,)
    mi = jnp.asarray(max_iter, jnp.int32)

    def cond(state):
        x, r, rr, it, sweeps, div, stag, stall = state
        live = (rr > tol2) & ~div & ~stag
        return (sweeps < max_outer) & (jnp.max(it) < mi) & jnp.any(live)

    def body(state):
        x, r, rr, it, sweeps, div, stag, stall = state
        with obs.scope("vec.update"):
            active = (rr > tol2) & ~div & ~stag
            rnorm = jnp.sqrt(rr)
            safe = jnp.where(active & (rnorm > 0), rnorm, 1.0)
            # frozen columns get a zero inner RHS: their inner column has
            # r0 = 0, converges at iteration 0, and block-PCG's freeze
            # keeps it from perturbing live columns
            r_hat = jnp.where(active, r / safe, 0.0).astype(lo_dtype)
            # adaptive inner target: the reduction this sweep still needs
            # is tol/||r|| per column; take the tightest active column
            # (with a 0.5 safety factor so the fp32 true residual lands
            # below tol despite the lo/hi operator discrepancy), floored
            # at the attainable per-sweep depth and capped well under 1
            maxr = jnp.max(jnp.where(active, rnorm, 0.0))
            itol = jnp.clip(
                0.5 * jnp.sqrt(tol2) / jnp.where(maxr > 0, maxr, 1.0),
                inner_tol, 0.3)
            inner_max = jnp.maximum(mi - jnp.max(it), 1)
        res = runner(a_lo, r_hat, precond=precond, tol=itol,
                     max_iter=inner_max, dot=inner_dot,
                     stagnation_window=inner_window)
        with obs.scope("vec.update"):
            d = res.x.astype(jnp.float32) * jnp.where(active, rnorm, 0.0)
            x_new = x + d
        ax_new = a_hi(x_new)
        with obs.scope("vec.update"):
            return advance(state, active, res, x_new, ax_new)

    def advance(state, active, res, x_new, ax_new):
        x, r, rr, it, sweeps, div, stag, stall = state
        r_new = (b32 - ax_new).astype(jnp.float32)
        rr_new = dot(r_new, r_new)
        hurt = active & ~jnp.isfinite(rr_new)
        div = div | hurt
        # a finite sweep that did not improve its column is rolled back
        # too: the sweep is a deterministic function of (r, a_lo), so
        # keeping the worse iterate would only compound, and re-running
        # from the old one reproduces the failure — count the stall
        worse = active & ~hurt & (rr_new >= rr)
        keep = hurt | worse
        x = jnp.where(keep, x, x_new)
        r = jnp.where(keep, r, r_new)
        rr2 = jnp.where(keep, rr, rr_new)
        stall = jnp.where(active & ~keep, 0,
                          stall + worse.astype(jnp.int32))
        stag = stag | (worse & (stall >= stall_limit))
        it = it + jnp.where(active, res.iterations, 0).astype(jnp.int32)
        return (x, r, rr2, it, sweeps + 1, div, stag, stall)

    state = (x, r, rr, jnp.zeros(it_shape, jnp.int32),
             jnp.asarray(0, jnp.int32), jnp.zeros(it_shape, bool),
             jnp.zeros(it_shape, bool), jnp.zeros(it_shape, jnp.int32))
    x, r, rr, it, _, div, stag, _ = jax.lax.while_loop(
        obs.scoped("vec.update", cond), body, state)
    with obs.scope("vec.update"):
        brk = jnp.zeros(it_shape, bool)
        status = classify(rr, tol2, brk, div, stag)
        return PCGResult(x, it, jnp.sqrt(rr), r0, brk, status)
