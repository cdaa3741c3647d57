"""Sharding context threaded through model code.

`ShardCtx` carries the mesh and the axis-name conventions; `None` means
single-device execution (tests).  Models receive it explicitly — no globals.

`SolverShardCtx` is the solver-side analogue: a 1-D device mesh over which
the Nekbone solve partitions *elements* (see `core.nekbone.setup_problem`
and DESIGN.md).  Same convention: `None` means the single-device path.
"""

from __future__ import annotations

import warnings
from typing import NamedTuple, Optional, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ShardCtx", "SolverShardCtx", "EXCHANGES", "HALO_COMPRESS",
           "make_ctx", "make_solver_ctx", "parse_grid_arg", "constraint"]


class ShardCtx(NamedTuple):
    mesh: Mesh
    data_axes: Tuple[str, ...]    # axes sharding the batch, e.g. ("pod","data")
    model_axis: str               # tensor/expert-parallel axis

    @property
    def ep_size(self) -> int:
        return self.mesh.shape[self.model_axis]

    @property
    def dp_size(self) -> int:
        size = 1
        for a in self.data_axes:
            size *= self.mesh.shape[a]
        return size

    @property
    def all_axes(self) -> Tuple[str, ...]:
        return tuple(self.mesh.axis_names)


class SolverShardCtx(NamedTuple):
    """1-D device mesh for the element-sharded Nekbone solve.

    `axis` is the mesh axis name the elements are partitioned over; PCG dot
    products (and the interface-dof exchange, in "psum" mode) collective
    over it.  `nrhs` is the declared RHS-batch width of the solves this
    context will run (the execution shape, like the mesh itself):
    `setup_problem` defaults to it, so block autotuning charges VMEM for
    the batch the solve will actually carry.  Any batch width still works
    at solve time — the operator is shape-polymorphic — this is a tuning
    declaration, not a constraint.

    `exchange` selects the interface-dof exchange implementation:
      "psum"      — one mesh-wide `lax.psum` over all interface dofs (the
                    default and the parity oracle);
      "neighbour" — per-neighbour `lax.ppermute` rounds, with the exchange
                    overlapped against interior-element compute (see
                    DESIGN.md).  Numerically equivalent up to summation
                    order.

    `grid` selects the element-partition shard-grid shape
    (`core.mesh_gen.normalize_grid`): None — 1-D slabs (the original
    partition); a (px[, py[, pz]]) tuple multiplying to the device count —
    a Cartesian box decomposition whose per-shard interface surface is
    O((E/S)^(2/3)) instead of the slab's full cross-section; or "auto" —
    the smallest-surface factorization for the mesh at setup time.  The
    device mesh itself stays 1-D: the shard grid is linearized into the
    single `axis`, and neighbour offsets become linearized grid shifts.

    `compress` selects an on-the-wire codec for the neighbour halo
    buffers (`HALO_COMPRESS`; None — full-width sends):
      "bf16" — cast the per-neighbour partials to bfloat16 for the
               ppermute, halving interface bytes (a ~2^-8 relative
               perturbation of the exchanged partials);
      "int8" — per-dof symmetric int8 quantization (the
               `distributed.compression` machinery), quartering interface
               bytes, with a tiny fp32 per-row scale riding along.
    Lossy on full-precision solves (the operator is perturbed at the
    codec's precision, which floors the attainable residual) — built for
    the bf16_x32 refined solve, whose inner sweeps are already
    reduced-precision and whose fp32 outer loop absorbs the codec error;
    requires exchange="neighbour" (the psum exchange has no per-buffer
    seam to compress at).
    """

    mesh: Mesh
    axis: str
    nrhs: int = 1
    exchange: str = "psum"
    grid: object = None
    compress: Optional[str] = None

    @property
    def n_shards(self) -> int:
        return self.mesh.shape[self.axis]


EXCHANGES = ("psum", "neighbour")
HALO_COMPRESS = ("bf16", "int8")


def parse_grid_arg(spec: str):
    """Parse a CLI shard-grid spec: 'slab' -> None (1-D slabs), 'auto'
    -> 'auto', 'PXxPYxPZ' (e.g. '2x2x1', '2x2') -> an explicit tuple.
    Shared by examples/nekbone_solve.py and benchmarks/bench_nekbone.py so
    the two drivers cannot diverge on the syntax."""
    spec = spec.strip().lower()
    if spec in ("", "slab", "none"):
        return None
    if spec == "auto":
        return "auto"
    try:
        return tuple(int(p) for p in spec.split("x"))
    except ValueError:
        raise ValueError(
            f"bad grid spec {spec!r}: expected 'slab', 'auto', or "
            f"per-axis shard counts like '2x2x1'") from None


def _validate_grid_spec(grid, devices: int) -> None:
    """Early shard-grid validation: `mesh_gen.normalize_grid` with
    shape=None runs exactly the mesh-independent rules (spec form,
    positivity, shard-count product) — ONE implementation; the extent
    checks re-run at partition time, when the mesh is known."""
    from repro.core.mesh_gen import normalize_grid

    normalize_grid(grid, None, devices)


def make_solver_ctx(devices: Optional[int] = None,
                    axis: str = "elem",
                    nrhs: int = 1,
                    exchange: str = "psum",
                    grid=None,
                    compress: Optional[str] = None
                    ) -> Optional[SolverShardCtx]:
    """Build a 1-D element mesh over the first `devices` local devices.

    devices=None uses every visible device; devices=1 (or a single visible
    device) returns None — callers fall through to the unsharded path, which
    keeps single-device execution bit-identical to today's solve.  Because
    that path has no exchange and no partition at all, a non-default
    `exchange` or `grid` cannot take effect there: rather than silently
    dropping them (which would let a bench row mislabel the exchange it
    actually ran), the collapse warns and normalizes.  `nrhs` declares the
    RHS-batch width of the planned solves, `exchange` the interface
    exchange implementation, `grid` the element-partition shard-grid
    shape, and `compress` the on-the-wire halo codec (neighbour mode
    only; see `SolverShardCtx`).
    """
    if nrhs < 1:
        raise ValueError(f"nrhs must be >= 1, got {nrhs}")
    if exchange not in EXCHANGES:
        raise ValueError(f"unknown exchange {exchange!r}; expected one of "
                         f"{EXCHANGES}")
    if compress is not None and compress not in HALO_COMPRESS:
        raise ValueError(f"unknown halo compress {compress!r}; expected "
                         f"None or one of {HALO_COMPRESS}")
    if compress is not None and exchange != "neighbour":
        raise ValueError(
            f"compress={compress!r} requires exchange='neighbour': the "
            f"psum exchange is one fused all-reduce with no per-buffer "
            f"seam to compress at (got exchange={exchange!r})")
    devs = jax.devices()
    if devices is not None:
        if devices > len(devs):
            raise ValueError(
                f"requested {devices} devices but only {len(devs)} are "
                f"visible (set XLA_FLAGS=--xla_force_host_platform_device_"
                f"count={devices} to simulate more on CPU)")
        devs = devs[:devices]
    if len(devs) <= 1:
        dropped = [f"{name}={val!r}" for name, val, default in
                   (("exchange", exchange, "psum"), ("grid", grid, None),
                    ("compress", compress, None))
                   if val != default]
        if dropped:
            warnings.warn(
                f"make_solver_ctx: single-device context runs the exact "
                f"unsharded solve — {', '.join(dropped)} cannot apply and "
                f"will be ignored (pass devices>1 to shard)",
                UserWarning, stacklevel=2)
        return None
    _validate_grid_spec(grid, len(devs))
    return SolverShardCtx(Mesh(np.asarray(devs), (axis,)), axis, nrhs,
                          exchange, grid, compress)


def make_ctx(mesh: Optional[Mesh]) -> Optional[ShardCtx]:
    if mesh is None:
        return None
    names = mesh.axis_names
    data_axes = tuple(a for a in ("pod", "data") if a in names)
    return ShardCtx(mesh, data_axes, "model" if "model" in names else names[-1])


def constraint(x, ctx: Optional[ShardCtx], spec: P):
    """with_sharding_constraint that no-ops off-mesh."""
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(ctx.mesh, spec))
