"""Sum-factorization tensor contractions (paper Definition 1, Eq. 5).

Fields on an element are stored as arrays of shape ``(..., N1, N1, N1)`` with
axis order ``(k, j, i)`` so that flattening the last three axes reproduces the
paper's linearization ``i + j*N1 + k*N1**2`` (i fastest).

Each contraction multiplies the (N1, N1) differentiation matrix against one
tensor axis — O(N1^4) FLOPs per element instead of the O(N1^6) of a full
``D_r @ x`` — the paper's "fundamental source of HOSFEM's high performance".
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["grad_ref", "grad_ref_transpose", "apply_dr", "apply_ds", "apply_dt"]

HIGHEST = jax.lax.Precision.HIGHEST


def _einsum(subscripts: str, dhat: jnp.ndarray, x: jnp.ndarray):
    """Contraction with >= fp32 accumulation, like the Pallas kernels.

    For sub-fp32 float inputs (the bf16 twin operator) the dot must not
    accumulate at the storage width — the `AccumulationDtype` contract
    forbids it everywhere — so accumulate in f32 and round once at the
    end.  HIGHEST precision: a TPU otherwise runs an fp32 contraction as
    one bf16 pass (the CPU computes every precision the same way)."""
    out_dt = jnp.promote_types(dhat.dtype, x.dtype)
    if jnp.issubdtype(out_dt, jnp.floating) and jnp.finfo(out_dt).bits < 32:
        return jnp.einsum(subscripts, dhat, x, precision=HIGHEST,
                          preferred_element_type=jnp.float32).astype(out_dt)
    return jnp.einsum(subscripts, dhat, x, precision=HIGHEST)


def apply_dr(x: jnp.ndarray, dhat: jnp.ndarray) -> jnp.ndarray:
    """y(..., k, j, i) = sum_m Dhat(i, m) x(..., k, j, m)."""
    return _einsum("im,...m->...i", dhat, x)


def apply_ds(x: jnp.ndarray, dhat: jnp.ndarray) -> jnp.ndarray:
    """y(..., k, j, i) = sum_m Dhat(j, m) x(..., k, m, i)."""
    return _einsum("jm,...mi->...ji", dhat, x)


def apply_dt(x: jnp.ndarray, dhat: jnp.ndarray) -> jnp.ndarray:
    """y(..., k, j, i) = sum_m Dhat(k, m) x(..., m, j, i)."""
    return _einsum("km,...mji->...kji", dhat, x)


def grad_ref(x: jnp.ndarray, dhat: jnp.ndarray):
    """Reference-space gradient (y_r, y_s, y_t) = (D_r x, D_s x, D_t x)."""
    return apply_dr(x, dhat), apply_ds(x, dhat), apply_dt(x, dhat)


def grad_ref_transpose(gr: jnp.ndarray, gs: jnp.ndarray, gt: jnp.ndarray,
                       dhat: jnp.ndarray) -> jnp.ndarray:
    """y = D_r^T gr + D_s^T gs + D_t^T gt (the adjoint contractions)."""
    y = _einsum("mi,...m->...i", dhat, gr)
    y = y + _einsum("mj,...mi->...ji", dhat, gs)
    y = y + _einsum("mk,...mji->...kji", dhat, gt)
    return y
