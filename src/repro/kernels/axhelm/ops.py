"""Jit'd public wrappers for the Pallas axhelm kernels.

Handles layout normalization ((E, N1^3) scalar, (E, d, N1^3) vector, and
(E, nrhs, d, N1^3) RHS-batched fields, all flattened to the kernel's
lane-dense (E, nrhs*d*N1^3) rows), element padding to the block size,
operand assembly per variant, and interpret-mode selection: interpret
mode off-TPU so the kernels validate on CPU, and an error when a compiled
kernel is asked for where there is no TPU."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import geometry
from repro.core.spectral import SpectralBasis, basis as make_basis
from repro.kernels.axhelm import ref as ref_mod
from repro.kernels.axhelm import tune
from repro.kernels.axhelm.kernel import build_axhelm_call, kernel_constants
from repro.kernels.axhelm.tune import default_block_elems  # noqa: F401

__all__ = ["axhelm", "reference", "default_block_elems"]

# Variants whose geometry operand is the (E, 8, 3) vertex block and whose
# factors are recalculated in-kernel from the trilinear Jacobian.
_VERTS_VARIANTS = ("trilinear", "merged", "partial")

# The precomputed geometry operand is planar: (E, 7, N1, N1, N1) holding
# [g00, g01, g02, g11, g12, g22, gwj], one lane-dense plane per factor.
GEOM_PLANES = 7


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None -> compiled on a TPU, interpreted elsewhere (the CPU tests).
    An explicit ``interpret=False`` off-TPU raises instead of quietly
    interpreting, so a chip run cannot pass without running the kernel."""
    if interpret is None:
        return not _on_tpu()
    if not interpret and not _on_tpu():
        raise RuntimeError(
            f"axhelm: a compiled Pallas kernel (interpret=False) needs a "
            f"TPU backend, but JAX's default backend is "
            f"{jax.default_backend()!r}")
    return bool(interpret)


@functools.partial(jax.jit, static_argnames=(
    "variant", "helmholtz", "block_elems", "interpret", "n"))
def _axhelm_impl(x, geom, lam0, lam1, *, variant, helmholtz, block_elems,
                 interpret, n):
    b = make_basis(n)
    n3 = b.n1 ** 3
    e_total, cols = x.shape[0], x.shape[1] * x.shape[2]
    eb = block_elems
    pad = (-e_total) % eb

    def rows(a, width, fill=None):
        """(E, ...) -> (E + pad, width); dead rows take `fill` (zeros)."""
        if a is None:
            return None
        a = a.reshape(e_total, width)
        if pad == 0:
            return a
        tail = (jnp.zeros((pad, width), a.dtype) if fill is None else
                jnp.broadcast_to(jnp.asarray(fill, a.dtype), (pad, width)))
        return jnp.concatenate([a, tail], axis=0)

    if variant in _VERTS_VARIANTS:
        # pad with the reference cube so det(J) != 0 in dead elements
        geom_p = rows(geom, 24, geometry.reference_cube().reshape(24))
    elif variant == "parallelepiped":
        geom_p = rows(geom, 7, jnp.array([1.0, 0, 0, 1, 0, 1, 1]))
    else:
        geom_p = rows(geom, GEOM_PLANES * n3)

    call, _ = build_axhelm_call(
        variant, e_total=e_total + pad, n1=b.n1, cols=cols, block_elems=eb,
        helmholtz=helmholtz, has_lam0=lam0 is not None,
        has_lam1=lam1 is not None, out_dtype=x.dtype, interpret=interpret)
    operands = kernel_constants(variant, b, x.dtype)
    operands += [geom_p, rows(x, cols * n3)]
    operands += [rows(lam, n3) for lam in (lam0, lam1) if lam is not None]
    y = call(*operands)
    return y[:e_total].reshape(x.shape)


def axhelm(x: jnp.ndarray, basis: SpectralBasis, variant: str,
           geom: jnp.ndarray,
           lam0: Optional[jnp.ndarray] = None,
           lam1: Optional[jnp.ndarray] = None,
           helmholtz: bool = False,
           block_elems=None,
           interpret: Optional[bool] = None) -> jnp.ndarray:
    """Apply axhelm via the Pallas kernel.

    x:    (E, N1,N1,N1) scalar field, (E, d, N1,N1,N1) vector field, or
          (E, nrhs, d, N1,N1,N1) RHS-batched field — nrhs right-hand sides
          share one geometry load/recomputation per element (batched scalar
          fields are (E, nrhs, 1, N1,N1,N1)).
    geom: variant-dependent —
          precomputed:    (E, 7, N1,N1,N1)   planar [g00..g22, gwj]
          trilinear:      (E, 8, 3)          vertices
          parallelepiped: (E, 7)             per-element scalars
          merged:         (E, 8, 3)          vertices; lam0=Lam2, lam1=Lam3
                          (setup_merged_lambdas products, paper §4.1.1)
          partial:        (E, 8, 3)          vertices; lam0=gScale
                          (setup_partial_gscale product, paper §4.1.2)
    block_elems: int for a fixed VMEM block (compiled, a multiple of 8 or
          at least the element count), None for the cached/heuristic
          choice, or "auto" to run the tune.py sweep once per configuration
          — tune.py resolves both to a block the compiled kernel takes.
    interpret: None runs the compiled kernel on a TPU and the interpreter
          elsewhere; False demands the compiled kernel and raises off-TPU.
    """
    if variant == "merged":
        if lam0 is None or lam1 is None:
            raise ValueError("merged requires lam0=Lam2 and lam1=Lam3 "
                             "(see core.axhelm.setup_merged_lambdas)")
        helmholtz = True
    elif variant == "partial":
        if lam0 is None or lam1 is not None:
            raise ValueError("partial requires lam0=gScale and lam1=None "
                             "(see core.axhelm.setup_partial_gscale)")
        helmholtz = False
    if x.ndim not in (4, 5, 6):
        raise ValueError(
            f"axhelm: x must be (E, N1,N1,N1), (E, d, N1,N1,N1) or "
            f"(E, nrhs, d, N1,N1,N1), got shape {x.shape}")
    interpret = resolve_interpret(interpret)
    in_ndim = x.ndim
    if in_ndim == 4:                       # scalar -> (E, 1, 1, N1^3)
        x = x[:, None, None]
    elif in_ndim == 5:                     # vector -> (E, 1, d, N1^3)
        x = x[:, None]
    n1 = basis.n1
    nrhs, d = x.shape[1], x.shape[2]
    if isinstance(block_elems, str):
        if block_elems != "auto":
            raise ValueError(f"block_elems must be an int, None or 'auto', "
                             f"got {block_elems!r}")
        eb = tune.get_block_elems(variant, n1, d, x.dtype,
                                  helmholtz=helmholtz, e_total=x.shape[0],
                                  autotune_now=True, interpret=interpret,
                                  nrhs=nrhs)
    elif block_elems is None:
        eb = tune.get_block_elems(variant, n1, d, x.dtype,
                                  helmholtz=helmholtz, e_total=x.shape[0],
                                  interpret=interpret, nrhs=nrhs)
    else:
        eb = int(block_elems)
    y = _axhelm_impl(x, geom, lam0, lam1, variant=variant,
                     helmholtz=helmholtz, block_elems=eb,
                     interpret=interpret, n=basis.n)
    if in_ndim == 4:
        return y[:, 0, 0]
    return y[:, 0] if in_ndim == 5 else y


def reference(x, basis: SpectralBasis, variant: str, geom, lam0=None,
              lam1=None, helmholtz=False):
    """Dispatch to the pure-jnp oracle with the same operand convention
    (including the RHS-batched (E, nrhs, d, N1^3) layout)."""
    squeeze = x.ndim == 4
    if squeeze:
        x = x[:, None]
    dt = x.dtype
    dhat = jnp.asarray(basis.dhat, dtype=dt)
    xi = jnp.asarray(basis.points, dtype=dt)
    w3 = jnp.asarray(basis.w3, dtype=dt)
    if variant == "precomputed":
        g = jnp.moveaxis(geom, 1, -1)              # planar -> packed
        y = ref_mod.axhelm_precomputed(x, g[..., :6], g[..., 6], dhat,
                                       lam0, lam1, helmholtz)
    elif variant == "trilinear":
        y = ref_mod.axhelm_trilinear(x, geom, xi, w3, dhat, lam0, lam1,
                                     helmholtz)
    elif variant == "parallelepiped":
        y = ref_mod.axhelm_parallelepiped(x, geom, w3, dhat, lam0, lam1,
                                          helmholtz)
    elif variant == "merged":
        y = ref_mod.axhelm_merged(x, geom, xi, dhat, lam0, lam1)
    elif variant == "partial":
        y = ref_mod.axhelm_partial(x, geom, xi, dhat, lam0)
    else:
        raise ValueError(variant)
    return y[:, 0] if squeeze else y
