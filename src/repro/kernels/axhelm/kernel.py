"""Pallas TPU kernels for axhelm (all geometric-factor variants).

TPU adaptation of the paper's GPU kernels (see DESIGN.md §3).  Every block
is lane-dense: the N1^3 nodes of an element lie along the last (lane) axis,
so at N1 = 8 an element-field row is 512 = 4 x 128 lanes and the element
axis fills the sublanes.

  * the CUDA "one 2D thread block per element" becomes a 1-D Pallas grid
    over *blocks of EB elements*; each grid step holds an (EB, C*N1^3) slab
    of X in VMEM, where the C = nrhs*d columns of an element sit side by
    side on the lane axis.  Every column reuses the SAME geometry block
    (read once for precomputed/parallelepiped, or recomputed once per
    element for the on-the-fly variants), so geometry traffic per RHS falls
    as 1/nrhs (DESIGN.md §4a),
  * the Tensor-Core WMMA contractions become MXU matmuls against Kronecker
    factors of D̂ (DESIGN.md §3): the r and s contractions act within one
    128-lane chunk, the t contraction across the whole row, and the
    columns are stacked on the matmul's row axis,
  * `__constant__` D̂_N becomes those Kronecker operands, broadcast to every
    grid step (index_map -> block 0),
  * the on-the-fly trilinear recalculation (paper Algorithm 3) runs *inside*
    the kernel on the (EB, 24) vertex block: each vertex coordinate is a
    static (EB, 1) column, and the reference's own Algorithm 3 arithmetic
    on those columns and nine node-weight planes gives the nine unscaled
    Jacobian planes J~ at every node, so geometry traffic drops from
    (6+isHelm)*N1^3 words/element to 24 words/element, exactly the paper's
    trade,
  * the merged (§4.1.1) and partial (§4.1.2) variants reuse the same
    in-kernel Jacobian but stop at adj(K~) — no division and no
    determinant in the hot loop; the 1/det lives in the precomputed
    Lam2/gScale operand carried in the lam0/lam1 slots (DESIGN.md §4).

Compute is fp32 (TPU has no fp64 MXU; DESIGN.md §7): every operand is cast
to fp32 in VMEM and every matmul runs at fp32 contract precision.
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.geometry import JT_SCALE
from repro.core.spectral import SpectralBasis

__all__ = ["build_axhelm_call", "kernel_constants", "chunk_width",
           "Operand"]

_F32 = jnp.float32
_LANES = 128


class Operand(NamedTuple):
    """One pallas_call operand: its name, full shape, and whether it is a
    per-element block (True) or a constant broadcast to every grid step."""

    name: str
    shape: Tuple[int, ...]
    per_elem: bool


def chunk_width(n1: int) -> int:
    """Lane width of the r/s contraction chunk: the smallest multiple of
    128 lanes that holds whole (j, i) planes and divides N1^3 (128 at
    N1 = 8, two planes), or the whole row when none exists."""
    n3, plane = n1 ** 3, n1 * n1
    w = int(np.lcm(_LANES, plane))
    return w if n3 % w == 0 else n3


def _sumfact_matrices(dhat: np.ndarray):
    """Kronecker factors of D̂ in row-vector form (x @ K).

    With nodes ordered n = (k, j, i), i fastest:
      krs = [kron(I, D̂^T) | kron(I, D̂^T ⊗ I_N1)] on one chunk (w, 2w) —
            the r and s gradients of every plane in the chunk,
      kt  = kron(D̂^T, I_{N1^2}) on the whole row (N1^3, N1^3).
    """
    n1 = dhat.shape[0]
    w = chunk_width(n1)
    dt = dhat.T
    kr = np.kron(np.eye(w // n1), dt)
    ks = np.kron(np.eye(w // (n1 * n1)), np.kron(dt, np.eye(n1)))
    kt = np.kron(dt, np.eye(n1 * n1))
    return np.concatenate([kr, ks], axis=1), kt


def _jacobian_weights(xi: np.ndarray) -> np.ndarray:
    """The node weights of Algorithm 3 as 9 planes (9, N1^3), rounded to
    fp32 exactly as `geometry.trilinear_terms` forms them:
    [1-xi_j, 1+xi_j, 1-xi_i, 1+xi_i, xi_k, (1-xi_i)(1-xi_j),
     (1+xi_i)(1-xi_j), (1+xi_i)(1+xi_j), (1-xi_i)(1+xi_j)]."""
    n1 = xi.shape[0]
    x = np.asarray(xi, np.float32)
    lo, hi = np.float32(1) - x, np.float32(1) + x
    k, j, i = (a.ravel() for a in np.meshgrid(
        np.arange(n1), np.arange(n1), np.arange(n1), indexing="ij"))
    return np.stack([lo[j], hi[j], lo[i], hi[i], x[k], lo[i] * lo[j],
                     hi[i] * lo[j], hi[i] * hi[j], lo[i] * hi[j]])


def _parallelepiped_matrix(w3: np.ndarray):
    """gelem (E, 7) @ wp = the 7 factor planes w3 * gelem[a] (E, 7*N1^3)."""
    n3 = w3.size
    wp = np.zeros((7, 7 * n3))
    for a in range(7):
        wp[a, a * n3:(a + 1) * n3] = w3.ravel()
    return wp


def kernel_constants(variant: str, basis: SpectralBasis, dtype) -> List:
    """The broadcast operands of `variant`, in call order, at `dtype`."""
    krs, kt = _sumfact_matrices(np.asarray(basis.dhat))
    consts = [krs, kt]
    if variant in ("trilinear", "merged", "partial"):
        consts.append(_jacobian_weights(np.asarray(basis.points)))
        if variant == "trilinear":
            consts.append(np.asarray(basis.w3).reshape(1, -1))
    elif variant == "parallelepiped":
        consts.append(_parallelepiped_matrix(np.asarray(basis.w3)))
    return [jnp.asarray(c, dtype=dtype) for c in consts]


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _dot_t(a, b):
    """a @ b^T."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _lane_cat(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _grad(x, krs, kt):
    """(xr, xs, xt) of x (R, N1^3): r and s per lane chunk, t whole-row."""
    w = krs.shape[0]
    rs = [_dot(x[:, c:c + w], krs) for c in range(0, x.shape[1], w)]
    xr = _lane_cat([p[:, :w] for p in rs])
    xs = _lane_cat([p[:, w:] for p in rs])
    return xr, xs, _dot(x, kt)


def _grad_transpose(gxr, gxs, gxt, krs, kt):
    """y = D_r^T gxr + D_s^T gxs + D_t^T gxt (the transposed matmuls)."""
    w = krs.shape[0]
    y = _lane_cat([_dot_t(_lane_cat([gxr[:, c:c + w], gxs[:, c:c + w]]),
                          krs) for c in range(0, gxr.shape[1], w)])
    return y + _dot_t(gxt, kt)


def _jacobian_planes(verts, wj):
    """J~[a][b] as (EB, N1^3) planes from the (EB, 24) vertex block.

    `geometry.trilinear_terms` + `jacobian_trilinear_at` operation for
    operation (Algorithm 3), with each vertex coordinate a static (EB, 1)
    column and the node weights `wj` (`_jacobian_weights`) as lane planes,
    so the factors round exactly as the reference's do.
    """
    loj, hij, loi, hii, t, r0s0, r1s0, r1s1, r0s1 = (
        wj[c:c + 1] for c in range(9))
    planes = [[None] * 3 for _ in range(3)]
    for a in range(3):
        def v(n):
            return verts[:, 3 * n + a:3 * n + a + 1]

        # d/dr: vertex pairs differing in the r bit, weighted along s
        ar = loj * (v(1) - v(0)) + hij * (v(3) - v(2))
        br = loj * (v(5) - v(4)) + hij * (v(7) - v(6))
        planes[a][0] = (ar + br) + t * (br - ar)
        # d/ds: pairs differing in the s bit, weighted along r
        cs = loi * (v(2) - v(0)) + hii * (v(3) - v(1))
        ds = loi * (v(6) - v(4)) + hii * (v(7) - v(5))
        planes[a][1] = (cs + ds) + t * (ds - cs)
        # d/dt: pairs differing in the t bit, bilinear in (r, s)
        planes[a][2] = (r0s0 * (v(4) - v(0)) + r1s0 * (v(5) - v(1))
                        + r1s1 * (v(7) - v(3)) + r0s1 * (v(6) - v(2)))
    return planes


def _adjugate_planes(j):
    """adj(K) of K = j^T j as 6 planes [a00, a01, a02, a11, a12, a22] —
    geometry.adjugate6 on planes, division- and determinant-free."""
    cols = [[j[a][b] for a in range(3)] for b in range(3)]

    def dot3(p, q):
        return p[0] * q[0] + p[1] * q[1] + p[2] * q[2]

    c0, c1, c2 = cols
    k00, k01, k02 = dot3(c0, c0), dot3(c0, c1), dot3(c0, c2)
    k11, k12, k22 = dot3(c1, c1), dot3(c1, c2), dot3(c2, c2)
    return [k11 * k22 - k12 * k12, k02 * k12 - k01 * k22,
            k01 * k12 - k02 * k11, k00 * k22 - k02 * k02,
            k01 * k02 - k00 * k12, k00 * k11 - k01 * k01]


def _det_planes(j):
    """det(j) on planes, in geometry.factors_from_jacobian's order."""
    return (j[0][0] * (j[1][1] * j[2][2] - j[2][1] * j[1][2])
            - j[1][0] * (j[0][1] * j[2][2] - j[2][1] * j[0][2])
            + j[2][0] * (j[0][1] * j[1][2] - j[1][1] * j[0][2]))


def _kernel(*refs, variant: str, helmholtz: bool, has_lam0: bool,
            has_lam1: bool, cols: int, n3: int):
    """Unified kernel body; ref order matches build_axhelm_call's operands."""
    it = iter(refs[:-1])
    out_ref = refs[-1]

    def load():
        return next(it)[...].astype(_F32)

    krs, kt = load(), load()
    g = gwj = None
    if variant == "precomputed":
        geom = load()                                # (EB, (6|7)*N1^3)
        g = [geom[:, a * n3:(a + 1) * n3] for a in range(6)]
        if helmholtz:
            gwj = geom[:, 6 * n3:7 * n3]
    elif variant == "parallelepiped":
        wp = load()
        planes = _dot(load(), wp)                    # gelem (EB, 7) @ wp
        g = [planes[:, a * n3:(a + 1) * n3] for a in range(6)]
        gwj = planes[:, 6 * n3:7 * n3]
    else:                                            # in-kernel Algorithm 3
        wj = load()
        w3 = load() if variant == "trilinear" else None
        jt = _jacobian_planes(load(), wj)
        adj = _adjugate_planes(jt)
        if variant == "trilinear":
            det = _det_planes(jt)
            gscale = JT_SCALE * w3 / det
            g = [a * gscale for a in adj]
            gwj = w3 * (JT_SCALE ** 3) * det

    x = load()                                       # (EB, cols*N1^3)
    lam0 = load() if has_lam0 else None
    lam1 = load() if has_lam1 else None
    if variant == "merged":
        # §4.1.1: lam0 slot carries Lam2 = gScale*lambda0, lam1 slot carries
        # Lam3 = GwJ*lambda1 — both precomputed, so no det/div in this loop.
        g = [a * lam0 for a in adj]
        gwj, lam0, lam1 = lam1, None, None           # mass = Lam3 directly
    elif variant == "partial":
        # §4.1.2: lam0 slot carries gScale = w3/(8 det), re-read from memory.
        g = [a * lam0 for a in adj]
        lam0 = None

    eb = x.shape[0]
    # the columns stack on the matmul row axis: (cols*EB, N1^3)
    xc = jnp.concatenate([x[:, c * n3:(c + 1) * n3] for c in range(cols)],
                         axis=0) if cols > 1 else x
    xr, xs, xt = _grad(xc, krs, kt)

    def per_col(a):                                  # (cols, EB, N1^3)
        return a.reshape(cols, eb, n3)

    xr, xs, xt = per_col(xr), per_col(xs), per_col(xt)
    gxr = g[0] * xr + g[1] * xs + g[2] * xt
    gxs = g[1] * xr + g[3] * xs + g[4] * xt
    gxt = g[2] * xr + g[4] * xs + g[5] * xt
    if lam0 is not None:
        gxr, gxs, gxt = lam0 * gxr, lam0 * gxs, lam0 * gxt
    rows = (cols * eb, n3)
    y = _grad_transpose(gxr.reshape(rows), gxs.reshape(rows),
                        gxt.reshape(rows), krs, kt)
    if helmholtz:
        mass = gwj if lam1 is None else lam1 * gwj
        y = (per_col(y) + mass * per_col(xc)).reshape(rows)
    for c in range(cols):
        out_ref[:, c * n3:(c + 1) * n3] = \
            y[c * eb:(c + 1) * eb].astype(out_ref.dtype)


def _operands(variant: str, *, e_total: int, n1: int, cols: int,
              helmholtz: bool, has_lam0: bool, has_lam1: bool
              ) -> List[Operand]:
    n3 = n1 ** 3
    w = chunk_width(n1)
    ops = [Operand("krs", (w, 2 * w), False), Operand("kt", (n3, n3), False)]
    if variant == "precomputed":
        ops.append(Operand("geom", (e_total, 7 * n3), True))
    elif variant == "parallelepiped":
        ops += [Operand("wp", (7, 7 * n3), False),
                Operand("gelem", (e_total, 7), True)]
    elif variant in ("trilinear", "merged", "partial"):
        ops.append(Operand("wj", (9, n3), False))
        if variant == "trilinear":
            ops.append(Operand("w3", (1, n3), False))
        ops.append(Operand("verts", (e_total, 24), True))
    else:
        raise ValueError(variant)
    ops.append(Operand("x", (e_total, cols * n3), True))
    if has_lam0:
        ops.append(Operand("lam0", (e_total, n3), True))
    if has_lam1:
        ops.append(Operand("lam1", (e_total, n3), True))
    return ops


def build_axhelm_call(variant: str, *, e_total: int, n1: int, cols: int,
                      block_elems: int, helmholtz: bool, has_lam0: bool,
                      has_lam1: bool, out_dtype, interpret: bool):
    """Construct the pallas_call for a given static configuration.

    X is (e_total, cols*N1^3): the cols = nrhs*d columns of an element side
    by side on the lane axis, all sharing one geometry load/recomputation
    per element (the multi-RHS amortization of the paper's factor
    traffic).  Returns ``(call, operands)``: `operands` lists the expected
    `Operand`s in call order (constants from `kernel_constants` first).

    Compiled for the chip (``interpret=False``), `block_elems` must be a
    multiple of 8 — the fp32 sublane tile — unless one block covers every
    element.
    """
    if e_total % block_elems != 0:
        raise ValueError("e_total must be padded to a multiple of block_elems")
    if not interpret and block_elems % 8 and block_elems != e_total:
        raise ValueError(f"block_elems={block_elems} is not a multiple of "
                         f"the 8-row sublane tile")
    if variant == "merged" and not (helmholtz and has_lam0 and has_lam1):
        raise ValueError("merged requires helmholtz=True with Lam2 (lam0 "
                         "slot) and Lam3 (lam1 slot) operands")
    if variant == "partial" and (helmholtz or not has_lam0 or has_lam1):
        raise ValueError("partial is Poisson-only with a gScale operand in "
                         "the lam0 slot")
    n3 = n1 ** 3
    eb = block_elems
    operands = _operands(variant, e_total=e_total, n1=n1, cols=cols,
                         helmholtz=helmholtz, has_lam0=has_lam0,
                         has_lam1=has_lam1)

    def spec(op: Operand):
        if not op.per_elem:
            return pl.BlockSpec(op.shape, lambda i: (0, 0))
        width = op.shape[1]
        if op.name == "geom" and not helmholtz:
            width = 6 * n3                           # gwj plane never read
        return pl.BlockSpec((eb, width), lambda i: (i, 0))

    kern = functools.partial(_kernel, variant=variant, helmholtz=helmholtz,
                             has_lam0=has_lam0, has_lam1=has_lam1, cols=cols,
                             n3=n3)
    call = pl.pallas_call(
        kern,
        grid=(e_total // eb,),
        in_specs=[spec(op) for op in operands],
        out_specs=pl.BlockSpec((eb, cols * n3), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((e_total, cols * n3), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=f"axhelm_{variant}",
    )
    return call, operands
