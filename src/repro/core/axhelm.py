"""The axhelm operator: element-local Y^(e) = A^(e) X^(e), all paper variants.

A^(e) = D^T [lam0 * G] D  (+ Helmholtz: + diag(lam1 * Gwj)), applied matrix-
free by sum factorization.  The variants differ ONLY in where the geometric
factors come from — the paper's central idea:

  precomputed     paper Alg. 2 — read 6(+1) factor arrays from memory
                  (the original Nekbone/NekRS kernel, our baseline).
  parallelepiped  paper Alg. 4 — 7 scalars per *element*, zero-cost recalc.
  trilinear       paper Alg. 3 — 24 scalars (8 vertices) per element,
                  low-cost analytic recalculation at every node.
  merged          paper §4.1.1 (Helmholtz) — trilinear recalc with gScale/gwj
                  folded into the lambda fields (Lam2, Lam3): no division,
                  no determinant in the hot loop.
  partial         paper §4.1.2 (Poisson) — trilinear recalc of adj(K) only;
                  gScale (containing the division) is re-read from memory.

Shapes: x is (E, N1, N1, N1) for a scalar field (d = 1) or
(E, d, N1, N1, N1) for a vector field; factors broadcast over d.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax.numpy as jnp

from repro import obs
from repro.core import geometry, sumfact
from repro.core.geometry import GeomFactors, JT_SCALE
from repro.core.spectral import SpectralBasis

__all__ = [
    "VARIANTS",
    "BACKENDS",
    "axhelm_precomputed",
    "axhelm_trilinear",
    "axhelm_parallelepiped",
    "axhelm_merged",
    "axhelm_partial",
    "setup_merged_lambdas",
    "setup_partial_gscale",
    "element_diagonal",
    "make_axhelm",
    "make_axhelm_elem_ops",
]

VARIANTS = ("precomputed", "trilinear", "parallelepiped", "merged", "partial")


def _expand(a: Optional[jnp.ndarray], x: jnp.ndarray) -> Optional[jnp.ndarray]:
    """Broadcast a per-node factor (E, N1, N1, N1[, 6]) against x's batch
    axes — (E, d, N1^3) vector fields and (E, nrhs, d, N1^3) RHS-batched
    fields insert one and two singleton axes respectively; one factor set
    per element serves every column."""
    if a is None or jnp.ndim(a) == 0 or x.ndim == 4:
        return a
    return a.reshape(a.shape[:1] + (1,) * (x.ndim - 4) + a.shape[1:])


def _core(x: jnp.ndarray, g: jnp.ndarray, dhat: jnp.ndarray,
          lam0: Optional[jnp.ndarray] = None,
          mass: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Shared contraction core: y = D^T (lam0 * G) D x (+ mass * x).

    g: (..., N1, N1, N1, 6) packed [g00,g01,g02,g11,g12,g22];
    lam0/mass: optional (..., N1, N1, N1) pointwise fields.
    """
    xr, xs, xt = sumfact.grad_ref(x, dhat)
    g00, g01, g02 = g[..., 0], g[..., 1], g[..., 2]
    g11, g12, g22 = g[..., 3], g[..., 4], g[..., 5]
    gxr = g00 * xr + g01 * xs + g02 * xt
    gxs = g01 * xr + g11 * xs + g12 * xt
    gxt = g02 * xr + g12 * xs + g22 * xt
    if lam0 is not None:
        gxr, gxs, gxt = lam0 * gxr, lam0 * gxs, lam0 * gxt
    y = sumfact.grad_ref_transpose(gxr, gxs, gxt, dhat)
    if mass is not None:
        y = y + mass * x
    return y


def axhelm_precomputed(x: jnp.ndarray, factors: GeomFactors, dhat: jnp.ndarray,
                       lam0: Optional[jnp.ndarray] = None,
                       lam1: Optional[jnp.ndarray] = None,
                       helmholtz: bool = False) -> jnp.ndarray:
    """Paper Algorithm 2: factors read from (pre-assembled) arrays."""
    mass = None
    if helmholtz:
        mass = factors.gwj if lam1 is None else lam1 * factors.gwj
    return _core(x, _expand(factors.g, x), dhat,
                 lam0=_expand(lam0, x), mass=_expand(mass, x))


def axhelm_trilinear(x: jnp.ndarray, verts: jnp.ndarray, basis: SpectralBasis,
                     dhat: jnp.ndarray,
                     lam0: Optional[jnp.ndarray] = None,
                     lam1: Optional[jnp.ndarray] = None,
                     helmholtz: bool = False) -> jnp.ndarray:
    """Paper Algorithm 3: on-the-fly analytic recalculation (trilinear)."""
    factors = geometry.factors_trilinear(verts, basis)
    return axhelm_precomputed(x, factors, dhat, lam0, lam1, helmholtz)


def axhelm_parallelepiped(x: jnp.ndarray, verts: jnp.ndarray,
                          basis: SpectralBasis, dhat: jnp.ndarray,
                          lam0: Optional[jnp.ndarray] = None,
                          lam1: Optional[jnp.ndarray] = None,
                          helmholtz: bool = False) -> jnp.ndarray:
    """Paper Algorithm 4: constant-J elements, 7 scalars per element."""
    factors = geometry.factors_parallelepiped(verts, basis)
    return axhelm_precomputed(x, factors, dhat, lam0, lam1, helmholtz)


def setup_merged_lambdas(verts: jnp.ndarray, basis: SpectralBasis,
                         lam0: jnp.ndarray, lam1: jnp.ndarray):
    """Precompute Lam2 = gScale*lam0 and Lam3 = gwj*lam1 (paper §4.1.1).

    Done once before the solve; the hot kernel then avoids the determinant
    and the division entirely.
    """
    jt = geometry.jacobian_trilinear(verts, basis, unscaled=True)
    det = jnp.linalg.det(jt)
    w3 = jnp.asarray(basis.w3, dtype=verts.dtype)
    gscale = JT_SCALE * w3 / det
    gwj = (JT_SCALE ** 3) * w3 * det
    return gscale * lam0, gwj * lam1


def setup_partial_gscale(verts: jnp.ndarray, basis: SpectralBasis) -> jnp.ndarray:
    """Precompute gScale = w3/(8 det(Jt)) for partial recalculation (§4.1.2)."""
    jt = geometry.jacobian_trilinear(verts, basis, unscaled=True)
    w3 = jnp.asarray(basis.w3, dtype=verts.dtype)
    return JT_SCALE * w3 / jnp.linalg.det(jt)


def _adjugate_factors(verts: jnp.ndarray, basis: SpectralBasis) -> jnp.ndarray:
    """adj(K~) of the unscaled Jacobian, packed (..., N1,N1,N1, 6).

    This is the division-free part of Algorithm 3 shared by the merged and
    partial variants (single implementation: geometry.adjugate6).
    """
    return geometry.adjugate6(
        geometry.jacobian_trilinear(verts, basis, unscaled=True))


def axhelm_merged(x: jnp.ndarray, verts: jnp.ndarray, basis: SpectralBasis,
                  dhat: jnp.ndarray, lam2: jnp.ndarray,
                  lam3: jnp.ndarray) -> jnp.ndarray:
    """Paper §4.1.1 (Helmholtz): G = adj(K~) * Lam2, mass = Lam3."""
    adj = _adjugate_factors(verts, basis)
    g = adj * lam2[..., None]
    return _core(x, _expand(g, x), dhat, mass=_expand(lam3, x))


def axhelm_partial(x: jnp.ndarray, verts: jnp.ndarray, basis: SpectralBasis,
                   dhat: jnp.ndarray, gscale: jnp.ndarray) -> jnp.ndarray:
    """Paper §4.1.2 (Poisson): recompute adj(K~), re-read gScale from memory."""
    adj = _adjugate_factors(verts, basis)
    return _core(x, _expand(adj * gscale[..., None], x), dhat)


def element_diagonal(factors: GeomFactors, dhat: jnp.ndarray,
                     lam0: Optional[jnp.ndarray] = None,
                     lam1: Optional[jnp.ndarray] = None,
                     helmholtz: bool = False) -> jnp.ndarray:
    """Closed-form diag(A^(e)) via sum factorization (for Jacobi/PCG).

    diag(kji) = sum_m Dhat(m,i)^2 g'00(k,j,m) + sum_m Dhat(m,j)^2 g'11(k,m,i)
              + sum_m Dhat(m,k)^2 g'22(m,j,i)
              + 2 Dhat(i,i) Dhat(j,j) g'01 + 2 Dhat(i,i) Dhat(k,k) g'02
              + 2 Dhat(j,j) Dhat(k,k) g'12   (all at (k,j,i))
              (+ lam1 * gwj for Helmholtz),
    with g' = lam0 * g — lam0 lives INSIDE the contraction (it is evaluated
    at the summation node n, not at the diagonal node).
    """
    g = factors.g
    if lam0 is not None:
        g = g * lam0[..., None]
    d2 = dhat * dhat
    dd = jnp.diagonal(dhat)
    hi = sumfact.HIGHEST
    diag = jnp.einsum("mi,...m->...i", d2, g[..., 0], precision=hi)
    diag = diag + jnp.einsum("mj,...mi->...ji", d2, g[..., 3], precision=hi)
    diag = diag + jnp.einsum("mk,...mji->...kji", d2, g[..., 5], precision=hi)
    di = dd[None, None, :]
    dj = dd[None, :, None]
    dk = dd[:, None, None]
    diag = diag + 2.0 * (di * dj * g[..., 1] + di * dk * g[..., 2]
                         + dj * dk * g[..., 4])
    if helmholtz:
        diag = diag + (factors.gwj if lam1 is None else lam1 * factors.gwj)
    return diag


class AxhelmOp(NamedTuple):
    """A ready-to-apply element operator plus its setup products."""

    apply: Callable[[jnp.ndarray], jnp.ndarray]
    factors: Optional[GeomFactors]  # precomputed factors when available
    variant: str
    helmholtz: bool
    backend: str = "reference"


BACKENDS = ("reference", "pallas", "auto")


def _resolve_backend(backend: Optional[str], dtype) -> str:
    """Map a backend choice (None means "reference") to a concrete
    implementation.

    "auto" picks the Pallas kernels whenever the dtype fits the MXU (fp32 /
    bf16 — the kernels accumulate in fp32; off-TPU they run in interpret
    mode so CPU CI exercises the same code path) and falls back to the
    pure-jnp reference for fp64, which the TPU MXU cannot compute anyway.
    """
    if backend is None:
        backend = "reference"
    if backend not in BACKENDS:
        raise ValueError(f"unknown axhelm backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    if backend == "auto":
        backend = "reference" if jnp.dtype(dtype).itemsize > 4 else "pallas"
    return backend


def _node_field(a, dtype, node_shape) -> Optional[jnp.ndarray]:
    """Broadcast an optional scalar/field lambda to a per-node (E, N1^3)
    array (the Pallas kernels take per-node operands only)."""
    if a is None:
        return None
    return jnp.broadcast_to(jnp.asarray(a, dtype=dtype), node_shape)


def _pallas_operands(variant: str, basis: SpectralBasis, verts, factors,
                     lam0, lam1, dtype):
    """Per-variant (geom, lam0, lam1) operand assembly for the Pallas
    kernels — shared by the closure-style and operand-style entry points."""
    node_shape = verts.shape[:-2] + (basis.n1,) * 3
    l0 = _node_field(lam0, dtype, node_shape)
    l1 = _node_field(lam1, dtype, node_shape)

    if variant == "precomputed":
        # planar (E, 7, N1, N1, N1): one lane-dense plane per factor
        geom = jnp.concatenate([jnp.moveaxis(factors.g, -1, 1),
                                factors.gwj[:, None]], axis=1)
    elif variant == "parallelepiped":
        from repro.kernels.axhelm.ref import gelem_from_verts
        geom = gelem_from_verts(verts)
    elif variant == "merged":
        geom = verts
        l0, l1 = setup_merged_lambdas(
            verts, basis,
            jnp.ones(node_shape, dtype) if l0 is None else l0,
            jnp.ones(node_shape, dtype) if l1 is None else l1)
    elif variant == "partial":
        geom = verts
        l0, l1 = setup_partial_gscale(verts, basis), None
    else:  # trilinear
        geom = verts
    return geom, l0, l1


def _validate_setup(variant: str, basis: SpectralBasis, verts, lam0, lam1,
                    helmholtz: bool) -> None:
    """Shared argument validation for BOTH axhelm entry points.

    `make_axhelm` and `make_axhelm_elem_ops` funnel through here (and
    through one operand-assembly dispatch below), so unknown variants,
    wrong-equation variants, and mis-shaped operands fail identically from
    either — by construction, not by parity testing.
    """
    if variant not in VARIANTS:
        raise ValueError(f"unknown axhelm variant {variant!r}; expected one "
                         f"of {VARIANTS}")
    if variant == "merged" and not helmholtz:
        raise ValueError("merged scalar factors apply to Helmholtz only")
    if variant == "partial" and helmholtz:
        raise ValueError("partial recalculation applies to Poisson only")
    if jnp.ndim(verts) != 3 or jnp.shape(verts)[-2:] != (8, 3):
        raise ValueError(
            f"axhelm setup: verts must be (E, 8, 3) trilinear element "
            f"vertices, got shape {jnp.shape(verts)}")
    node_shape = jnp.shape(verts)[:-2] + (basis.n1,) * 3
    for name, lam in (("lam0", lam0), ("lam1", lam1)):
        if lam is None or jnp.ndim(lam) == 0:
            continue
        if jnp.shape(lam) != node_shape:
            raise ValueError(
                f"axhelm setup: {name} must be a scalar or a per-node "
                f"(E, N1, N1, N1) field of shape {node_shape}, got "
                f"{jnp.shape(lam)}")


def _setup_factors(variant: str, basis: SpectralBasis, verts, coords,
                   dtype, elem_ops) -> GeomFactors:
    """The `GeomFactors` carried on `AxhelmOp` (Jacobi diagonal and other
    setup products) — reused from `elem_ops` when already assembled."""
    if variant == "precomputed":
        if "g" in elem_ops:                      # reference operands
            return GeomFactors(elem_ops["g"], elem_ops["gwj"])
        if "geom" in elem_ops:                   # pallas planar [g6, gwj]
            geom = jnp.moveaxis(elem_ops["geom"], 1, -1)
            return GeomFactors(geom[..., :6], geom[..., 6])
        if coords is None:
            coords = geometry.node_coords(verts, basis)
        return geometry.factors_discrete(jnp.asarray(coords, dtype=dtype),
                                         basis)
    if variant == "parallelepiped":
        return geometry.factors_parallelepiped(verts, basis)
    return geometry.factors_trilinear(verts, basis)


def make_axhelm(variant: str, basis: SpectralBasis, verts: jnp.ndarray,
                coords: Optional[jnp.ndarray] = None,
                lam0: Optional[jnp.ndarray] = None,
                lam1: Optional[jnp.ndarray] = None,
                helmholtz: bool = False,
                dtype=jnp.float64,
                backend: Optional[str] = None,
                block_elems=None,
                interpret: Optional[bool] = None) -> AxhelmOp:
    """Build an axhelm closure for a mesh (one-time setup outside the solve).

    A thin closure over :func:`make_axhelm_elem_ops` — the closure- and
    operand-style entry points share ONE dispatch/validation/operand-assembly
    path, so they cannot drift (they used to be parallel implementations
    kept in sync only by the op-parity tests).

    `coords` (physical node coordinates) is required for the `precomputed`
    variant on general meshes; for trilinear meshes it is derived from verts.

    `backend` selects the element-kernel implementation: "reference" (pure
    jnp, any dtype), "pallas" (the TPU kernels in repro.kernels.axhelm;
    interpret mode off-TPU), or "auto" (pallas for fp32/bf16, reference for
    fp64).  Default: "reference".
    `block_elems`/`interpret` are forwarded to the Pallas path (see
    kernels/axhelm/ops.axhelm; block_elems="auto" invokes the autotuner).
    """
    verts = jnp.asarray(verts, dtype=dtype)
    elem_ops, elem_apply, backend_used = make_axhelm_elem_ops(
        variant, basis, verts, lam0=lam0, lam1=lam1, helmholtz=helmholtz,
        dtype=dtype, backend=backend, block_elems=block_elems,
        interpret=interpret, coords=coords)
    factors = _setup_factors(variant, basis, verts, coords, dtype, elem_ops)

    def apply(x):
        return elem_apply(x, elem_ops)

    return AxhelmOp(apply, factors, variant, helmholtz, backend_used)


def make_axhelm_elem_ops(variant: str, basis: SpectralBasis,
                         verts: jnp.ndarray,
                         lam0: Optional[jnp.ndarray] = None,
                         lam1: Optional[jnp.ndarray] = None,
                         helmholtz: bool = False,
                         dtype=jnp.float32,
                         backend: Optional[str] = None,
                         block_elems=None,
                         interpret: Optional[bool] = None,
                         coords: Optional[jnp.ndarray] = None):
    """Operand-style axhelm: `(elem_ops, apply, backend)` with
    apply(x, elem_ops) — the ONE setup path both entry points share.

    The per-element setup products (factors, Lam2/Lam3, gScale, vertices)
    are returned as a dict of arrays with a leading element axis instead of
    being closed over.  That is what the element-sharded solve needs:
    `shard_map` partitions `elem_ops` (and x) over the device mesh and
    `apply` runs unchanged on each shard's block — closures cannot be
    sharded, operands can.  Scalar lambdas and the basis stay closed over
    (replicated constants).  `apply` accepts scalar (E, N1^3), vector
    (E, d, N1^3) and RHS-batched (E, nrhs, d, N1^3) fields on both
    backends; every batch column reuses the element's single factor set.
    """
    _validate_setup(variant, basis, verts, lam0, lam1, helmholtz)
    backend = _resolve_backend(backend, dtype)
    if backend == "pallas" and jnp.dtype(dtype).itemsize > 4:
        import warnings

        warnings.warn(
            "axhelm backend='pallas' computes in fp32 (no fp64 MXU); "
            f"requested dtype {jnp.dtype(dtype).name} will not gain "
            "precision — use backend='reference' for fp64 solves, or "
            "loosen the PCG tolerance to fp32 levels (>= ~1e-6)",
            stacklevel=3)
    verts = jnp.asarray(verts, dtype=dtype)
    node_shape = verts.shape[:-2] + (basis.n1,) * 3

    if backend == "pallas":
        factors = None
        if variant == "precomputed":
            if coords is None:
                coords = geometry.node_coords(verts, basis)
            factors = geometry.factors_discrete(
                jnp.asarray(coords, dtype=dtype), basis)
        geom, l0, l1 = _pallas_operands(variant, basis, verts, factors,
                                        lam0, lam1, dtype)
        elem_ops = {"geom": geom}
        if l0 is not None:
            elem_ops["lam0"] = l0
        if l1 is not None:
            elem_ops["lam1"] = l1
        kw = {} if variant in ("merged", "partial") else {
            "helmholtz": helmholtz}
        from repro.kernels.axhelm import ops as kops

        # decided at setup: a compiled kernel asked for off a TPU fails
        # here, not at the first apply inside a solve
        interpret = kops.resolve_interpret(interpret)

        def apply(x, elem_ops):
            return kops.axhelm(x, basis, variant, elem_ops["geom"],
                               lam0=elem_ops.get("lam0"),
                               lam1=elem_ops.get("lam1"),
                               block_elems=block_elems, interpret=interpret,
                               **kw)
        return elem_ops, obs.scoped("axhelm", apply), backend

    dhat = jnp.asarray(basis.dhat, dtype=dtype)
    # Per-element lambda FIELDS ride in elem_ops — they have an element
    # axis, so the sharded solve can partition them like any other setup
    # product; scalars stay closed over (replicated constants).  `apply`
    # reads elem_ops first and falls back to the closed-over scalar.
    lam_ops = {}
    lam0_s, lam1_s = lam0, lam1
    if variant in ("precomputed", "trilinear", "parallelepiped"):
        if lam0 is not None and jnp.ndim(lam0) > 0:
            lam_ops["lam0"], lam0_s = jnp.asarray(lam0, dtype=dtype), None
        if lam1 is not None and jnp.ndim(lam1) > 0:
            lam_ops["lam1"], lam1_s = jnp.asarray(lam1, dtype=dtype), None
    if variant == "precomputed":
        if coords is None:
            coords = geometry.node_coords(verts, basis)
        factors = geometry.factors_discrete(jnp.asarray(coords, dtype=dtype),
                                            basis)
        elem_ops = {"g": factors.g, "gwj": factors.gwj, **lam_ops}

        def apply(x, elem_ops):
            f = GeomFactors(elem_ops["g"], elem_ops["gwj"])
            return axhelm_precomputed(x, f, dhat,
                                      elem_ops.get("lam0", lam0_s),
                                      elem_ops.get("lam1", lam1_s),
                                      helmholtz)
    elif variant == "trilinear":
        elem_ops = {"verts": verts, **lam_ops}

        def apply(x, elem_ops):
            return axhelm_trilinear(x, elem_ops["verts"], basis, dhat,
                                    elem_ops.get("lam0", lam0_s),
                                    elem_ops.get("lam1", lam1_s), helmholtz)
    elif variant == "parallelepiped":
        elem_ops = {"verts": verts, **lam_ops}

        def apply(x, elem_ops):
            return axhelm_parallelepiped(x, elem_ops["verts"], basis, dhat,
                                         elem_ops.get("lam0", lam0_s),
                                         elem_ops.get("lam1", lam1_s),
                                         helmholtz)
    elif variant == "merged":
        l0 = jnp.broadcast_to(jnp.asarray(
            1.0 if lam0 is None else lam0, dtype=dtype), node_shape)
        l1 = jnp.broadcast_to(jnp.asarray(
            1.0 if lam1 is None else lam1, dtype=dtype), node_shape)
        lam2, lam3 = setup_merged_lambdas(verts, basis, l0, l1)
        elem_ops = {"verts": verts, "lam2": lam2, "lam3": lam3}

        def apply(x, elem_ops):
            return axhelm_merged(x, elem_ops["verts"], basis, dhat,
                                 elem_ops["lam2"], elem_ops["lam3"])
    else:  # partial
        elem_ops = {"verts": verts,
                    "gscale": setup_partial_gscale(verts, basis)}

        def apply(x, elem_ops):
            return axhelm_partial(x, elem_ops["verts"], basis, dhat,
                                  elem_ops["gscale"])
    return elem_ops, obs.scoped("axhelm", apply), backend
