"""Plain reference of the Nekbone Poisson operator, kept with the benchmark.

It imports nothing of the program and takes nothing the program made: it
builds its own GLL basis, its own warped box from the configuration's
numbers, its own geometric factors, Q, Q^T and Dirichlet mask, so that a
change to the program cannot move the yardstick that judges it.

Global fields live on the GLL lattice of an ``nx x ny x nz`` box of order
``N``: ``g = (nx N + 1, ny N + 1, nz N + 1)`` nodes, and node ``(ix, iy, iz)``
is dof ``(ix * gy + iy) * gz + iz`` -- the C order of a ``(gx, gy, gz)``
array, which is the order of a global field handed to the solver.  Element
``(ex, ey, ez)`` holds the lattice nodes ``ex N .. ex N + N`` along x (and
alike along y and z); its local array is indexed ``[i, j, k]`` along x, y, z.

The operator is  A x = M Q^T A_e Q M x + (1 - M) x  with M the zero-mask on
the boundary nodes and, per element,

    A_e u = sum_ab D_a^T G_ab D_b u,    G = w det(J) J^-1 J^-T,

J the Jacobian of the trilinear map of the element's eight vertices at the
GLL nodes and w the tensor GLL weights.  Contractions run at one of two
precisions: ``"highest"`` (float32, the judge) and ``"bf16_3x"`` (three
bfloat16 passes, written out so that it is the same arithmetic on every
platform): the control one precision step below the configuration's.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Box", "box_from_config", "gll", "vertex_grid", "scatter", "gather",
           "boundary", "build", "apply_in_slabs", "pcg"]

PRECISIONS = ("highest", "bf16_3x")


class Box(NamedTuple):
    """The mesh of a configuration, in the reference's own terms."""

    shape: tuple           # elements (nx, ny, nz)
    lengths: tuple         # domain [0, Lx] x [0, Ly] x [0, Lz]
    order: int             # polynomial order N
    amplitude: float       # sine warp of the vertex grid, per element size

    @property
    def lattice(self) -> tuple:
        return tuple(n * self.order + 1 for n in self.shape)

    @property
    def n_global(self) -> int:
        gx, gy, gz = self.lattice
        return gx * gy * gz

    @property
    def n_elements(self) -> int:
        nx, ny, nz = self.shape
        return nx * ny * nz


def box_from_config(cfg: dict) -> Box:
    return Box(tuple(cfg["elements"]), tuple(cfg["lengths"]), cfg["order"],
               cfg["warp_amplitude"])


@functools.lru_cache(maxsize=None)
def gll(order: int):
    """GLL nodes, weights and the derivative matrix D[i, l] = l_l'(x_i),
    in float64 on the host."""
    n = order
    legendre = np.polynomial.legendre.Legendre.basis(n)
    inner = np.sort(legendre.deriv().roots().real)
    x = np.concatenate([[-1.0], inner, [1.0]])
    p = legendre(x)
    w = 2.0 / (n * (n + 1) * p ** 2)
    d = np.zeros((n + 1, n + 1))
    for i in range(n + 1):
        for j in range(n + 1):
            if i != j:
                d[i, j] = p[i] / (p[j] * (x[i] - x[j]))
    d[0, 0] = -n * (n + 1) / 4.0
    d[n, n] = n * (n + 1) / 4.0
    return x, w, d


def vertex_grid(box: Box) -> np.ndarray:
    """The warped vertex grid, (nx+1, ny+1, nz+1, 3) float64.

    A sine bump vanishing on the boundary moves each interior vertex by up
    to ``amplitude`` of an element's size, so the elements are general
    trilinear hexahedra and the mesh stays conforming.
    """
    nx, ny, nz = box.shape
    axes = [np.linspace(0.0, length, n + 1)
            for length, n in zip(box.lengths, box.shape)]
    v = np.stack(np.meshgrid(*axes, indexing="ij"), axis=-1)
    span = np.asarray(box.lengths, np.float64)
    u = v / span
    h = box.amplitude * span / np.array([nx, ny, nz], np.float64)
    s = (np.sin(np.pi * u[..., 0]) * np.sin(np.pi * u[..., 1])
         * np.sin(np.pi * u[..., 2]))
    offset = np.stack([h[0] * s * (1.0 + 0.4 * u[..., 1]),
                       h[1] * s * (1.0 + 0.4 * u[..., 2]),
                       h[2] * s * (1.0 + 0.4 * u[..., 0])], axis=-1)
    return v + offset


def _split(x):
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def contract(eq: str, a, b, precision: str):
    """einsum of two float32 operands at the given precision."""
    if precision == "highest":
        return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "bf16_3x":
        a_hi, a_lo = _split(a)
        b_hi, b_lo = _split(b)
        f32 = jnp.float32
        return (jnp.einsum(eq, a_hi, b_lo, preferred_element_type=f32)
                + jnp.einsum(eq, a_lo, b_hi, preferred_element_type=f32)
                + jnp.einsum(eq, a_hi, b_hi, preferred_element_type=f32))
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


def dot(u, v, precision: str):
    return contract("i,i->", u.reshape(-1), v.reshape(-1), precision)


def _expand(x, axis: int, n: int, order: int):
    """Q along one lattice axis: length n*N+1 -> (n, N+1) element nodes."""
    x = jnp.moveaxis(x, axis, -1)
    head = x[..., :n * order].reshape(x.shape[:-1] + (n, order))
    tail = x[..., order::order][..., None]
    return jnp.moveaxis(jnp.concatenate([head, tail], axis=-1),
                        (-2, -1), (axis, axis + 1))


def _fold(y, axis: int, n: int, order: int):
    """Q^T along one lattice axis: (n, N+1) element nodes summed back into
    a lattice axis of length n*N+1 (the adjoint of `_expand`)."""
    y = jnp.moveaxis(y, (axis, axis + 1), (-2, -1))
    lead = y.shape[:-2]
    pad_end = [(0, 0)] * len(lead) + [(0, 1)]
    pad_start = [(0, 0)] * len(lead) + [(1, 0)]
    head = jnp.pad(y[..., :order].reshape(lead + (n * order,)), pad_end)
    tail = jnp.concatenate(
        [jnp.zeros(lead + (n, order - 1), y.dtype), y[..., order:]], axis=-1)
    tail = jnp.pad(tail.reshape(lead + (n * order,)), pad_start)
    return jnp.moveaxis(head + tail, -1, axis)


def scatter(x, box: Box):
    """Q: a global field (n_global,) -> element nodes (E, N1, N1, N1)."""
    nx, ny, nz = box.shape
    n = box.order
    u = x.reshape(box.lattice)
    u = _expand(u, 0, nx, n)                     # (nx, N1, gy, gz)
    u = _expand(u, 2, ny, n)                     # (nx, N1, ny, N1, gz)
    u = _expand(u, 4, nz, n)                     # (nx, N1, ny, N1, nz, N1)
    u = u.transpose(0, 2, 4, 1, 3, 5)
    return u.reshape((box.n_elements,) + (n + 1,) * 3)


def gather(y, box: Box):
    """Q^T: element nodes (E, N1, N1, N1) summed into a global field."""
    nx, ny, nz = box.shape
    n = box.order
    y = y.reshape((nx, ny, nz) + (n + 1,) * 3).transpose(0, 3, 1, 4, 2, 5)
    y = _fold(y, 4, nz, n)
    y = _fold(y, 2, ny, n)
    y = _fold(y, 0, nx, n)
    return y.reshape(-1)


def boundary(box: Box):
    """True on the boundary nodes of the lattice, (n_global,)."""
    gx, gy, gz = box.lattice
    ix = jnp.arange(gx)[:, None, None]
    iy = jnp.arange(gy)[None, :, None]
    iz = jnp.arange(gz)[None, None, :]
    b = ((ix == 0) | (ix == gx - 1) | (iy == 0) | (iy == gy - 1)
         | (iz == 0) | (iz == gz - 1))
    return b.reshape(-1)


def geometry(box: Box, precision: str):
    """G_ab = w det(J) (J^-1 J^-T)_ab at every element node, as the six
    fields (G00, G11, G22, G01, G02, G12), each (E, N1, N1, N1) float32."""
    return _geometry(jnp.asarray(vertex_grid(box), jnp.float32), box.order,
                     precision)


def _geometry(v, order: int, precision: str):
    """`geometry` of the elements of a vertex grid v (nx+1, ny+1, nz+1, 3)."""
    x, w, _ = gll(order)
    nx, ny, nz = (s - 1 for s in v.shape[:3])
    corners = jnp.stack([jnp.stack([jnp.stack(
        [v[a:a + nx, b:b + ny, c:c + nz] for c in (0, 1)], axis=-2)
        for b in (0, 1)], axis=-3) for a in (0, 1)], axis=-4)
    corners = corners.reshape((nx * ny * nz, 2, 2, 2, 3))
    phi = jnp.asarray(np.stack([(1.0 - x) / 2.0, (1.0 + x) / 2.0]),
                      jnp.float32)                          # (2, N1)
    dphi = jnp.asarray(np.stack([np.full_like(x, -0.5),
                                 np.full_like(x, 0.5)]), jnp.float32)

    def column(fr, fs, ft):
        # d x / d (one of r, s, t) at every node: (E, N1, N1, N1, 3)
        c = contract("Eabcx,ai->Eibcx", corners, fr, precision)
        c = contract("Eibcx,bj->Eijcx", c, fs, precision)
        return contract("Eijcx,ck->Eijkx", c, ft, precision)

    cols = (column(dphi, phi, phi), column(phi, dphi, phi),
            column(phi, phi, dphi))
    # rows of adj(J): the inverse's rows are these over det(J)
    adj = (jnp.cross(cols[1], cols[2]), jnp.cross(cols[2], cols[0]),
           jnp.cross(cols[0], cols[1]))
    det = jnp.sum(cols[0] * adj[0], axis=-1)
    w3 = jnp.asarray(np.einsum("i,j,k->ijk", w, w, w), jnp.float32)
    scale = w3 / det
    pairs = ((0, 0), (1, 1), (2, 2), (0, 1), (0, 2), (1, 2))
    return tuple(scale * jnp.sum(adj[a] * adj[b], axis=-1) for a, b in pairs)


def element_apply(u, g, d, precision: str):
    """A_e u on element nodes (E, N1, N1, N1)."""
    g00, g11, g22, g01, g02, g12 = g
    ur = contract("il,Eljk->Eijk", d, u, precision)
    us = contract("jl,Eilk->Eijk", d, u, precision)
    ut = contract("kl,Eijl->Eijk", d, u, precision)
    wr = g00 * ur + g01 * us + g02 * ut
    ws = g01 * ur + g11 * us + g12 * ut
    wt = g02 * ur + g12 * us + g22 * ut
    return (contract("li,Eljk->Eijk", d, wr, precision)
            + contract("lj,Eilk->Eijk", d, ws, precision)
            + contract("lk,Eijl->Eijk", d, wt, precision))


def element_diagonal(g, d, precision: str):
    """diag(A_e) on element nodes: the squared derivative weights of each
    node against the diagonal of G, plus the node's own cross terms."""
    g00, g11, g22, g01, g02, g12 = g
    d2 = d * d
    dd = jnp.diagonal(d)
    di, dj, dk = dd[:, None, None], dd[None, :, None], dd[None, None, :]
    return (contract("li,Eljk->Eijk", d2, g00, precision)
            + contract("lj,Eilk->Eijk", d2, g11, precision)
            + contract("lk,Eijl->Eijk", d2, g22, precision)
            + 2.0 * (di * dj * g01 + di * dk * g02 + dj * dk * g12))


class Operator(NamedTuple):
    """The assembled reference operator of one box at one precision.

    Its arrays enter the jitted functions below as arguments, never as
    captured constants."""

    box: Box
    precision: str
    data: tuple                # (geometry fields, D, boundary mask)

    def apply(self, x):
        """A x on a global field (n_global,)."""
        return _apply(self.box, self.precision, self.data, x)

    def diagonal(self):
        """diag(A) on the global dofs, 1 on the boundary."""
        return _diagonal(self.box, self.precision, self.data)


def _apply_traced(box, precision, data, x):
    geom, d, mask = data
    xm = jnp.where(mask, 0.0, x)
    y = gather(element_apply(scatter(xm, box), geom, d, precision), box)
    return jnp.where(mask, x, y)


_apply = jax.jit(_apply_traced, static_argnums=(0, 1))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _diagonal(box, precision, data):
    geom, d, mask = data
    dg = gather(element_diagonal(geom, d, precision), box)
    return jnp.where(mask, 1.0, dg)


def build(box: Box, precision: str = "highest") -> Operator:
    """The operator's device-resident data, made on the default device."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; one of "
                         f"{PRECISIONS}")
    _, _, d = gll(box.order)
    geom = jax.jit(geometry, static_argnums=(0, 1))(box, precision)
    return Operator(box, precision,
                    (geom, jnp.asarray(d, jnp.float32), boundary(box)))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _slab_apply(slab: Box, precision, v, d, x):
    geom = _geometry(v, slab.order, precision)
    return gather(element_apply(scatter(x, slab), geom, d, precision), slab)


def apply_in_slabs(box: Box, x, precision: str = "highest"):
    """A x on a global field (n_global,), one layer of elements along x at
    a time, each layer's geometry made afresh: what the reference holds on
    the device is one layer's, a 1/nx share of `build`'s."""
    nx, ny, nz = box.shape
    n = box.order
    gx, gy, gz = box.lattice
    v = jnp.asarray(vertex_grid(box), jnp.float32)
    d = jnp.asarray(gll(n)[2], jnp.float32)
    mask = boundary(box)
    xm = jnp.where(mask, 0.0, x).reshape(box.lattice)
    layer = box._replace(shape=(1, ny, nz))
    y = jnp.zeros(box.lattice, jnp.float32)
    for a in range(nx):
        rows = slice(a * n, a * n + n + 1)
        ya = _slab_apply(layer, precision, v[a:a + 2], d,
                         xm[rows].reshape(-1))
        y = y.at[rows].add(ya.reshape(n + 1, gy, gz))
    return jnp.where(mask, x, y.reshape(-1))


def pcg(op: Operator, b, tol, max_iter):
    """Plain Jacobi PCG on ``op`` at its precision, stopping when
    ||r|| <= tol (absolute) or after ``max_iter`` iterations.

    Returns ``(x, iterations, converged)``.  This is the control: the
    reference put in the program's place.
    """
    return _pcg(op.box, op.precision, op.data, b, tol, max_iter)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _pcg(box, prec, data, b, tol, max_iter):
    def a_op(v):
        return _apply_traced(box, prec, data, v)

    inv_diag = 1.0 / _diagonal(box, prec, data)
    x = jnp.zeros_like(b)
    r = b
    z = inv_diag * r
    rz = dot(r, z, prec)
    rr = dot(r, r, prec)
    tol2 = tol * tol

    def cond(state):
        _, _, _, _, rr, it = state
        return (it < max_iter) & (rr > tol2)

    def body(state):
        x, r, p, rz, _, it = state
        ap = a_op(p)
        alpha = rz / dot(p, ap, prec)
        x = x + alpha * p
        r = r - alpha * ap
        z = inv_diag * r
        rz_new = dot(r, z, prec)
        p = z + (rz_new / rz) * p
        return x, r, p, rz_new, dot(r, r, prec), it + 1

    x, _, _, _, rr, it = jax.lax.while_loop(
        cond, body, (x, r, z, rz, rr, jnp.asarray(0, jnp.int32)))
    return x, it, rr <= tol2
