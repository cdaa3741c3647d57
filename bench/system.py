"""The system under test: the program's Nekbone path, as a user calls it.

On one chip the solve runs through one jit, compiled ahead of the window; on
four it is called outside any enclosing jit, with the element-sharded
problem of ``make_solver_ctx(devices=4)``.  The tolerance and the iteration
limit enter as arguments, so a one-iteration call warms up the very program
the window runs.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

from repro.core import mesh_gen, nekbone  # noqa: E402
from repro.distributed.context import make_solver_ctx  # noqa: E402

__all__ = ["build"]


def build(cfg: dict, cell: dict):
    """Mesh, partition and ``setup_problem`` for one configuration, on the
    cell's chips with its exchange and shard grid.  Returns
    ``solve(b, tol, max_iter) -> PCGResult``, not yet waited on."""
    chips = cell["chips"]
    mesh = mesh_gen.deform_trilinear(
        mesh_gen.box_mesh(*cfg["elements"], cfg["order"],
                          lengths=tuple(cfg["lengths"])),
        amplitude=cfg["warp_amplitude"])
    ctx = None
    if chips > 1:
        ctx = make_solver_ctx(devices=chips, exchange=cell["exchange"],
                              grid=cell["grid"])
    prob = nekbone.setup_problem(
        mesh, variant=cfg["variant"], d=cfg["d"],
        helmholtz=cfg["equation"] == "helmholtz",
        dtype=jnp.dtype(cfg["precision"]), backend=cfg["backend"],
        shard_ctx=ctx)

    def solve(b, tol, max_iter):
        return nekbone.solve(prob, b, precond=cfg["preconditioner"], tol=tol,
                             max_iter=max_iter)

    if ctx is not None:
        # the sharded runners are jitted inside; an enclosing jit would
        # capture their per-shard arrays as constants
        return solve
    b0 = jax.ShapeDtypeStruct((mesh.n_global,), jnp.dtype(cfg["precision"]))
    compiled = jax.jit(solve).lower(b0, jnp.float32(0), jnp.int32(0)).compile()
    return lambda b, tol, max_iter: compiled(b, jnp.float32(tol),
                                             jnp.int32(max_iter))
