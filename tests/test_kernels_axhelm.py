"""Pallas axhelm kernels vs the pure-jnp oracle: shape/dtype/variant sweeps
(interpret mode on CPU, per the assignment)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import geometry, mesh_gen
from repro.core.spectral import basis
from repro.kernels.axhelm import ops as kops
from repro.kernels.axhelm import ref as kref


def _mesh_verts(n, nx=2, ny=2, nz=1, seed=1, dtype=jnp.float32):
    mesh = mesh_gen.deform_trilinear(mesh_gen.box_mesh(nx, ny, nz, n),
                                     seed=seed)
    return jnp.asarray(mesh.verts, dtype)


def _geom_precomputed(verts, b):
    coords = geometry.node_coords(verts, b)
    f = geometry.factors_discrete(coords, b)
    # the kernels' planar layout: (E, 7, N1, N1, N1)
    return jnp.concatenate([jnp.moveaxis(f.g, -1, 1), f.gwj[:, None]],
                           axis=1)


def test_compiled_kernel_off_tpu_raises(rng):
    """interpret=False demands the compiled kernel: off a TPU it raises,
    naming the backend, instead of quietly interpreting — from the kernel
    wrapper and from the solver's setup alike."""
    from repro.core import nekbone

    b = basis(2)
    verts = _mesh_verts(2)
    x = jnp.asarray(rng.standard_normal((verts.shape[0],) + (b.n1,) * 3),
                    jnp.float32)
    with pytest.raises(RuntimeError, match="'cpu'"):
        kops.axhelm(x, b, "trilinear", verts, interpret=False)
    mesh = mesh_gen.box_mesh(2, 2, 1, 2)
    with pytest.raises(RuntimeError, match="interpret=False"):
        nekbone.setup_problem(mesh, variant="trilinear", backend="pallas",
                              block_elems=8, interpret=False)
    # the default interprets off a TPU, so the CPU tests run the kernels
    y = kops.axhelm(x, b, "trilinear", verts)
    np.testing.assert_allclose(y, kops.reference(x, b, "trilinear", verts),
                               rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("n", [2, 3, 7])
@pytest.mark.parametrize("d", [1, 3])
@pytest.mark.parametrize("variant", ["precomputed", "trilinear"])
@pytest.mark.parametrize("helm", [False, True])
def test_kernel_matches_oracle(rng, n, d, variant, helm):
    b = basis(n)
    verts = _mesh_verts(n)
    e = verts.shape[0]
    geom = verts if variant == "trilinear" else _geom_precomputed(verts, b)
    shape = (e, b.n1, b.n1, b.n1) if d == 1 else (e, d, b.n1, b.n1, b.n1)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    kw = {}
    if helm:
        kw = dict(
            lam0=jnp.asarray(1 + 0.3 * rng.random((e, b.n1, b.n1, b.n1)),
                             jnp.float32),
            lam1=jnp.asarray(0.5 + 0.2 * rng.random((e, b.n1, b.n1, b.n1)),
                             jnp.float32),
            helmholtz=True)
    y = kops.axhelm(x, b, variant, geom, **kw)
    y_ref = kops.reference(x, b, variant, geom, **kw)
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=1e-4)


def test_parallelepiped_kernel(rng):
    b = basis(5)
    mesh = mesh_gen.deform_affine(mesh_gen.box_mesh(3, 1, 1, 5), seed=2)
    verts = jnp.asarray(mesh.verts, jnp.float32)
    gelem = kref.gelem_from_verts(verts)
    x = jnp.asarray(rng.standard_normal((3, b.n1, b.n1, b.n1)), jnp.float32)
    y = kops.axhelm(x, b, "parallelepiped", gelem)
    np.testing.assert_allclose(
        y, kops.reference(x, b, "parallelepiped", gelem), rtol=2e-5,
        atol=1e-4)


@pytest.mark.parametrize("e_total", [1, 3, 5, 16])
def test_element_padding(rng, e_total):
    """E not divisible by the block size exercises the pad/slice path."""
    b = basis(3)
    verts = _mesh_verts(3, nx=4, ny=2, nz=2)[:e_total]
    x = jnp.asarray(rng.standard_normal((e_total, b.n1, b.n1, b.n1)),
                    jnp.float32)
    y = kops.axhelm(x, b, "trilinear", verts, block_elems=4)
    y_ref = kops.reference(x, b, "trilinear", verts)
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=1e-4)
    assert not np.any(np.isnan(np.asarray(y)))


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 0.05)])
def test_dtype_sweep(rng, dtype, rtol):
    b = basis(3)
    verts = _mesh_verts(3, dtype=dtype)
    x = jnp.asarray(rng.standard_normal((4, b.n1, b.n1, b.n1)), dtype)
    y = kops.axhelm(x, b, "trilinear", verts)
    y_ref = kops.reference(
        x.astype(jnp.float32), b, "trilinear", verts.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(y, np.float32), y_ref, rtol=rtol,
                               atol=rtol)


@pytest.mark.parametrize("block_elems", [1, 2, 8])
def test_block_size_invariance(rng, block_elems):
    """Results must not depend on the VMEM block size (pure tiling knob)."""
    b = basis(3)
    verts = _mesh_verts(3)
    x = jnp.asarray(rng.standard_normal((4, b.n1, b.n1, b.n1)), jnp.float32)
    y = kops.axhelm(x, b, "trilinear", verts, block_elems=block_elems)
    y_ref = kops.axhelm(x, b, "trilinear", verts, block_elems=4)
    np.testing.assert_allclose(y, y_ref, rtol=1e-6, atol=1e-6)


def _merged_operands(verts, b, rng):
    """(Lam2, Lam3) from random lambda fields (paper §4.1.1 setup)."""
    from repro.core import axhelm as core_ax
    e = verts.shape[0]
    node = (e, b.n1, b.n1, b.n1)
    lam0 = jnp.asarray(1 + 0.3 * rng.random(node), jnp.float32)
    lam1 = jnp.asarray(0.5 + 0.2 * rng.random(node), jnp.float32)
    return core_ax.setup_merged_lambdas(verts, b, lam0, lam1), (lam0, lam1)


def _partial_operand(verts, b):
    from repro.core import axhelm as core_ax
    return core_ax.setup_partial_gscale(verts, b)


@pytest.mark.parametrize("n", [2, 3, 7])
@pytest.mark.parametrize("d", [1, 3])
@pytest.mark.parametrize("variant", ["merged", "partial"])
def test_merged_partial_kernel_matches_oracle(rng, n, d, variant):
    b = basis(n)
    verts = _mesh_verts(n)
    e = verts.shape[0]
    shape = (e, b.n1, b.n1, b.n1) if d == 1 else (e, d, b.n1, b.n1, b.n1)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    if variant == "merged":
        (lam2, lam3), _ = _merged_operands(verts, b, rng)
        kw = dict(lam0=lam2, lam1=lam3)
    else:
        kw = dict(lam0=_partial_operand(verts, b))
    y = kops.axhelm(x, b, variant, verts, **kw)
    y_ref = kops.reference(x, b, variant, verts, **kw)
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("e_total", [1, 3, 5])
@pytest.mark.parametrize("variant", ["merged", "partial"])
def test_merged_partial_padding(rng, e_total, variant):
    """Non-divisible E exercises the ref-cube vertex padding for the new
    variants (dead elements must not produce NaNs)."""
    b = basis(3)
    verts = _mesh_verts(3, nx=4, ny=2, nz=2)[:e_total]
    x = jnp.asarray(rng.standard_normal((e_total, b.n1, b.n1, b.n1)),
                    jnp.float32)
    if variant == "merged":
        (lam2, lam3), _ = _merged_operands(verts, b, rng)
        kw = dict(lam0=lam2, lam1=lam3)
    else:
        kw = dict(lam0=_partial_operand(verts, b))
    y = kops.axhelm(x, b, variant, verts, block_elems=4, **kw)
    y_ref = kops.reference(x, b, variant, verts, **kw)
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=1e-4)
    assert not np.any(np.isnan(np.asarray(y)))


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 0.05)])
@pytest.mark.parametrize("variant", ["merged", "partial"])
def test_merged_partial_dtype_sweep(rng, dtype, rtol, variant):
    b = basis(3)
    verts32 = _mesh_verts(3)
    if variant == "merged":
        (l0, l1), _ = _merged_operands(verts32, b, rng)
        kw32 = dict(lam0=l0, lam1=l1)
    else:
        kw32 = dict(lam0=_partial_operand(verts32, b))
    x = jnp.asarray(rng.standard_normal((4, b.n1, b.n1, b.n1)), dtype)
    kw = {k: v.astype(dtype) for k, v in kw32.items()}
    y = kops.axhelm(x, b, variant, verts32.astype(dtype), **kw)
    y_ref = kops.reference(x.astype(jnp.float32), b, variant, verts32, **kw32)
    np.testing.assert_allclose(np.asarray(y, np.float32), y_ref, rtol=rtol,
                               atol=rtol)


def test_merged_partial_match_core_operator(rng):
    """merged == the fp64-validated Helmholtz operator; partial == the
    Poisson one (the §4.1 algebra is exact, only fp32 roundoff differs)."""
    from repro.core import axhelm as core_ax
    b = basis(4)
    verts = _mesh_verts(4)
    e = verts.shape[0]
    x = jnp.asarray(rng.standard_normal((e, b.n1, b.n1, b.n1)), jnp.float32)

    (lam2, lam3), (lam0, lam1) = _merged_operands(verts, b, rng)
    y_m = kops.axhelm(x, b, "merged", verts, lam0=lam2, lam1=lam3)
    y_core = core_ax.make_axhelm("precomputed", b, verts, lam0=lam0,
                                 lam1=lam1, helmholtz=True,
                                 dtype=jnp.float32).apply(x)
    np.testing.assert_allclose(y_m, y_core, rtol=2e-4, atol=2e-4)

    y_p = kops.axhelm(x, b, "partial", verts,
                      lam0=_partial_operand(verts, b))
    y_core_p = core_ax.make_axhelm("partial", b, verts,
                                   dtype=jnp.float32).apply(x)
    np.testing.assert_allclose(y_p, y_core_p, rtol=2e-4, atol=2e-4)


def test_merged_partial_operand_validation(rng):
    b = basis(2)
    verts = _mesh_verts(2)
    x = jnp.asarray(rng.standard_normal((verts.shape[0],) + (b.n1,) * 3),
                    jnp.float32)
    with pytest.raises(ValueError):
        kops.axhelm(x, b, "merged", verts)           # missing Lam2/Lam3
    gs = _partial_operand(verts, b)
    with pytest.raises(ValueError):
        kops.axhelm(x, b, "partial", verts, lam0=gs, lam1=gs)  # stray lam1


def _variant_operands(variant, verts, b, rng, helm):
    """(geom, kwargs) for any of the five variants (helm only where legal)."""
    if variant == "precomputed":
        geom = _geom_precomputed(verts, b)
    elif variant == "parallelepiped":
        geom = kref.gelem_from_verts(verts)
    else:
        geom = verts
    e = verts.shape[0]
    node = (e, b.n1, b.n1, b.n1)
    if variant == "merged":
        (lam2, lam3), _ = _merged_operands(verts, b, rng)
        return geom, dict(lam0=lam2, lam1=lam3)
    if variant == "partial":
        return geom, dict(lam0=_partial_operand(verts, b))
    kw = {}
    if helm:
        kw = dict(lam0=jnp.asarray(1 + 0.3 * rng.random(node), jnp.float32),
                  lam1=jnp.asarray(0.5 + 0.2 * rng.random(node),
                                   jnp.float32),
                  helmholtz=True)
    return geom, kw


@pytest.mark.parametrize("variant,helm", [
    ("precomputed", False), ("trilinear", False), ("parallelepiped", False),
    ("partial", False), ("merged", True), ("precomputed", True)])
@pytest.mark.parametrize("d", [1, 3])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 0.05)])
def test_batched_matches_vmapped_single_rhs(rng, variant, helm, d, dtype,
                                            tol):
    """Property (all five variants, fp32/bf16, d=1/3): one batched kernel
    call on (E, nrhs, d, N1^3) == vmapping the single-RHS kernel over the
    RHS axis — the batch reuses one geometry set per element but computes
    every column exactly as the unbatched kernel would."""
    import jax

    n, nrhs = 3, 3
    b = basis(n)
    mesh_fn = mesh_gen.deform_affine if variant == "parallelepiped" \
        else mesh_gen.deform_trilinear
    mesh = mesh_fn(mesh_gen.box_mesh(2, 2, 1, n), seed=1)
    verts = jnp.asarray(mesh.verts, jnp.float32)
    e = verts.shape[0]
    geom, kw = _variant_operands(variant, verts, b, rng, helm)
    geom = geom.astype(dtype)
    kw = {k: (v.astype(dtype) if hasattr(v, "astype") else v)
          for k, v in kw.items()}
    x = jnp.asarray(rng.standard_normal((e, nrhs, d, b.n1, b.n1, b.n1)),
                    dtype)

    def single(xcol):                       # (E, d, N1^3) -> (E, d, N1^3)
        return kops.axhelm(xcol, b, variant, geom, block_elems=2, **kw)

    y_batched = kops.axhelm(x, b, variant, geom, block_elems=2, **kw)
    y_vmapped = jax.vmap(single, in_axes=1, out_axes=1)(x)
    assert y_batched.shape == x.shape
    np.testing.assert_allclose(np.asarray(y_batched, np.float32),
                               np.asarray(y_vmapped, np.float32),
                               rtol=tol, atol=tol)
    # and the batched oracle agrees too
    y_ref = kops.reference(
        x.astype(jnp.float32), b, variant,
        geom.astype(jnp.float32),
        **{k: (v.astype(jnp.float32) if hasattr(v, "astype") else v)
           for k, v in kw.items()})
    np.testing.assert_allclose(np.asarray(y_batched, np.float32), y_ref,
                               rtol=max(tol, 2e-5), atol=max(tol, 1e-4))


def test_batched_scalar_layout(rng):
    """(E, nrhs, 1, N1^3) batched scalar == stacking single scalar calls."""
    b = basis(3)
    verts = _mesh_verts(3)
    e = verts.shape[0]
    x = jnp.asarray(rng.standard_normal((e, 4, 1, b.n1, b.n1, b.n1)),
                    jnp.float32)
    y = kops.axhelm(x, b, "trilinear", verts, block_elems=2)
    y_loop = jnp.stack([kops.axhelm(x[:, r, 0], b, "trilinear", verts,
                                    block_elems=2)
                        for r in range(4)], axis=1)[:, :, None]
    np.testing.assert_allclose(y, y_loop, rtol=1e-6, atol=1e-6)


def test_kernel_agrees_with_core_solver_path(rng):
    """Kernel path == the fp64-validated core operator (fp32 tolerance)."""
    from repro.core import axhelm as core_ax
    b = basis(4)
    verts = _mesh_verts(4)
    x = jnp.asarray(rng.standard_normal((4, b.n1, b.n1, b.n1)), jnp.float32)
    y_core = core_ax.make_axhelm("trilinear", b, verts,
                                 dtype=jnp.float32).apply(x)
    y_kern = kops.axhelm(x, b, "trilinear", verts)
    np.testing.assert_allclose(y_kern, y_core, rtol=2e-4, atol=2e-4)
