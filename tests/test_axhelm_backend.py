"""Backend dispatch: the Pallas kernels as the production solve path.

make_axhelm(backend="pallas") must match the jnp reference for every paper
variant (≤1e-4 rel in fp32), and setup_problem(backend="pallas") must drive
the PCG while_loop to the same iteration count (±1) as the reference."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import axhelm as core_ax
from repro.core import mesh_gen, nekbone
from repro.core.spectral import basis

ALL_CASES = [
    ("precomputed", False), ("trilinear", False),
    ("parallelepiped", False), ("partial", False),
    ("precomputed", True), ("trilinear", True),
    ("parallelepiped", True), ("merged", True),
]


def _mesh(variant, n=3, dims=(2, 2, 1), seed=1):
    box = mesh_gen.box_mesh(*dims, n)
    if variant == "parallelepiped":
        return mesh_gen.deform_affine(box, seed=seed)
    return mesh_gen.deform_trilinear(box, seed=seed)


@pytest.mark.parametrize("variant,helm", ALL_CASES)
@pytest.mark.parametrize("d", [1, 3])
def test_pallas_backend_matches_reference(rng, variant, helm, d):
    n = 3
    b = basis(n)
    mesh = _mesh(variant, n)
    verts = jnp.asarray(mesh.verts, jnp.float32)
    e = verts.shape[0]
    node = (e, b.n1, b.n1, b.n1)
    shape = node if d == 1 else (e, d) + (b.n1,) * 3
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    kw = {}
    if helm:
        kw = dict(lam0=jnp.asarray(1 + 0.3 * rng.random(node), jnp.float32),
                  lam1=jnp.asarray(0.5 + 0.2 * rng.random(node), jnp.float32),
                  helmholtz=True)
    ops = {be: core_ax.make_axhelm(variant, b, verts, dtype=jnp.float32,
                                   backend=be, **kw)
           for be in ("reference", "pallas")}
    assert ops["pallas"].backend == "pallas"
    y_ref = ops["reference"].apply(x)
    y_pal = ops["pallas"].apply(x)
    rel = float(jnp.linalg.norm(y_pal - y_ref) / jnp.linalg.norm(y_ref))
    assert rel <= 1e-4, (variant, helm, d, rel)


def test_auto_backend_resolution():
    b = basis(2)
    verts = jnp.asarray(_mesh("trilinear", 2).verts, jnp.float32)
    op32 = core_ax.make_axhelm("trilinear", b, verts, dtype=jnp.float32,
                               backend="auto")
    assert op32.backend == "pallas"
    op64 = core_ax.make_axhelm("trilinear", b, verts, dtype=jnp.float64,
                               backend="auto")
    assert op64.backend == "reference"  # no fp64 MXU
    with pytest.raises(ValueError):
        core_ax.make_axhelm("trilinear", b, verts, backend="cuda")


def test_backend_env_default(monkeypatch):
    """The default backend is "reference" whatever the environment says:
    no variable switches the element kernel behind the caller's back."""
    b = basis(2)
    verts = jnp.asarray(_mesh("trilinear", 2).verts, jnp.float32)
    monkeypatch.setenv("REPRO_AXHELM_BACKEND", "pallas")
    op = core_ax.make_axhelm("trilinear", b, verts, dtype=jnp.float32)
    assert op.backend == "reference"
    op = core_ax.make_axhelm("trilinear", b, verts, dtype=jnp.float32,
                             backend="pallas")
    assert op.backend == "pallas"


# ---------------------------------------------------------------------------
# Unified setup path: make_axhelm is a thin closure over make_axhelm_elem_ops
# — the two entry points must agree BY CONSTRUCTION (bit-identical apply)
# and raise identical validation errors from the one shared path.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("variant,helm", ALL_CASES)
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_make_axhelm_matches_elem_ops_by_construction(rng, variant, helm,
                                                      backend):
    """Closure-style and operand-style applies are the same code path, so
    their outputs are BIT-identical (not just close) on every variant and
    backend — the drift the op-parity tests used to guard is now
    impossible by construction."""
    n = 3
    b = basis(n)
    mesh = _mesh(variant, n)
    verts = jnp.asarray(mesh.verts, jnp.float32)
    e = verts.shape[0]
    x = jnp.asarray(rng.standard_normal((e, b.n1, b.n1, b.n1)), jnp.float32)
    kw = {}
    if helm:
        node = (e, b.n1, b.n1, b.n1)
        kw = dict(lam0=jnp.asarray(1 + 0.3 * rng.random(node), jnp.float32),
                  lam1=jnp.asarray(0.5 + 0.2 * rng.random(node),
                                   jnp.float32),
                  helmholtz=True)
    op = core_ax.make_axhelm(variant, b, verts, dtype=jnp.float32,
                             backend=backend, **kw)
    elem_ops, elem_apply, backend_used = core_ax.make_axhelm_elem_ops(
        variant, b, verts, dtype=jnp.float32, backend=backend, **kw)
    assert op.backend == backend_used == backend
    y_closure = op.apply(x)
    y_operand = elem_apply(x, elem_ops)
    assert bool(jnp.all(y_closure == y_operand)), (variant, helm, backend)
    # the batched layout flows through both styles identically as well
    xb = jnp.asarray(rng.standard_normal((e, 2, 1, b.n1, b.n1, b.n1)),
                     jnp.float32)
    assert bool(jnp.all(op.apply(xb) == elem_apply(xb, elem_ops)))


@pytest.mark.parametrize("entry", ["make_axhelm", "make_axhelm_elem_ops"])
def test_shared_path_validation_errors(rng, entry):
    """Unknown variants, wrong-equation variants, and mis-shaped operands
    raise the same ValueError from BOTH entry points (one shared
    _validate_setup)."""
    b = basis(2)
    verts = jnp.asarray(_mesh("trilinear", 2).verts, jnp.float32)
    e = verts.shape[0]
    make = getattr(core_ax, entry)
    with pytest.raises(ValueError, match="unknown axhelm variant"):
        make("spectral", b, verts, dtype=jnp.float32)
    with pytest.raises(ValueError, match="Helmholtz only"):
        make("merged", b, verts, dtype=jnp.float32, helmholtz=False)
    with pytest.raises(ValueError, match="Poisson only"):
        make("partial", b, verts, dtype=jnp.float32, helmholtz=True)
    with pytest.raises(ValueError, match=r"verts must be \(E, 8, 3\)"):
        make("trilinear", b, verts.reshape(-1, 3), dtype=jnp.float32)
    bad_lam = jnp.ones((e, 2, 2, 2), jnp.float32)  # wrong node shape
    with pytest.raises(ValueError, match="lam0 must be a scalar or"):
        make("trilinear", b, verts, dtype=jnp.float32, helmholtz=True,
             lam0=bad_lam)
    # scalars and correctly shaped fields still pass
    make("trilinear", b, verts, dtype=jnp.float32, helmholtz=True,
         lam0=jnp.asarray(2.0), lam1=jnp.ones((e, b.n1, b.n1, b.n1),
                                              jnp.float32))


@pytest.mark.parametrize("variant,helm", [("trilinear", False),
                                          ("partial", False),
                                          ("merged", True)])
def test_nekbone_solve_convergence_pallas(rng, variant, helm):
    """The acceptance gate: same PCG iteration count (±1) through the
    Pallas while_loop body as through the reference operator."""
    mesh = mesh_gen.deform_trilinear(mesh_gen.box_mesh(2, 2, 2, 3), seed=3)
    x_true = jnp.asarray(rng.standard_normal(mesh.n_global), jnp.float32)
    out = {}
    for be in ("reference", "pallas"):
        prob = nekbone.setup_problem(mesh, variant=variant, helmholtz=helm,
                                     dtype=jnp.float32, backend=be)
        assert prob.backend == be
        b_rhs = nekbone.rhs_from_solution(prob, x_true)
        res = nekbone.solve(prob, b_rhs, tol=1e-6, max_iter=300)
        ref = x_true if helm else jnp.where(jnp.asarray(mesh.boundary), 0.0,
                                            x_true)
        err = float(jnp.linalg.norm(res.x - ref) / jnp.linalg.norm(ref))
        out[be] = (int(res.iterations), err)
    it_ref, err_ref = out["reference"]
    it_pal, err_pal = out["pallas"]
    assert abs(it_pal - it_ref) <= 1, out
    assert err_pal < 1e-4 and err_ref < 1e-4, out
    assert it_pal < 300, out  # actually converged, not max_iter'd
