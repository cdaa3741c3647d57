"""Neighbour-wise (ppermute) interface exchange: parity + properties + gate.

The contract: `exchange="neighbour"` replaces the mesh-wide interface psum
with per-neighbour ppermute rounds overlapped against interior-element
compute, and must be indistinguishable from the psum path up to summation
order — same post-gather state (every valid slot holds the full global
sum), solve iteration counts within ±1, on both equations, both backends,
2/4/8 simulated devices, nrhs ∈ {1, 4}, and element counts that do NOT
divide evenly.  The compiled neighbour solve must contain
`collective-permute` and ZERO interface-sized all-reduces (the CI gate
mirroring PR 3's one-psum gate).

The index-set algebra (pair tables, interface-element classification,
exchange == psum in exact arithmetic) is property-tested WITHOUT a device
mesh by emulating the ppermute shifts in numpy; the real collective path
runs in subprocesses with forced host devices, like
tests/test_nekbone_sharded.py.
"""

import contextlib
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import gather_scatter as gs, mesh_gen

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

TOL = 1e-6


@contextlib.contextmanager
def _x64():
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", prev)


def _run(script: str, devices: int) -> list:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-4000:]
    return [json.loads(line) for line in out.stdout.strip().splitlines()
            if line.startswith("{")]


def _random_mesh(rng, nx, ny, nz, order):
    mesh = mesh_gen.box_mesh(nx, ny, nz, order)
    return mesh_gen.deform_trilinear(mesh, seed=int(rng.integers(100)))


def _grid_for(mesh, n_shards, gy, gz):
    """Map drawn (gy, gz) onto a feasible shard grid for this mesh and
    shard count: each is clamped to the mesh extent and to a divisor of
    the (remaining) shard count, the leftover factor goes to x.  Returns
    None — today's 1-D slab partition — when the draw degenerates to 1x1
    cross-sections or the x factor cannot chunk the x extent, so the
    property sweep covers slab, 2-D and 3-D box grids in one strategy."""
    nx, ny, nz = mesh.shape
    gy = max(g for g in range(1, min(gy, ny) + 1) if n_shards % g == 0)
    rem = n_shards // gy
    gz = max(g for g in range(1, min(gz, nz) + 1) if rem % g == 0)
    gx = rem // gz
    if (gy == 1 and gz == 1) or gx > nx:
        return None
    return (gx, gy, gz)


def _shard_rounds(part, t):
    """Shard t's NeighbourRound list, built by the REAL table-slicing path
    (`gs.neighbour_rounds` over the flattened operand layout the solver
    ships through shard_map)."""
    tables = []
    for j in range(len(part.nbr_offsets)):
        tables += [jnp.asarray(part.nbr_lo_idx[j][t]),
                   jnp.asarray(part.nbr_lo_mask[j][t]),
                   jnp.asarray(part.nbr_hi_idx[j][t]),
                   jnp.asarray(part.nbr_hi_mask[j][t])]
    return gs.neighbour_rounds(part.nbr_offsets, part.n_shards, tables)


def _emulated_exchange(part, y_dofs_all):
    """The REAL per-shard exchange algebra with only the transport faked.

    y_dofs_all: per-shard local-dof arrays, list of (L[, c]).  Sends use
    the same `gs.shared_contrib` masking `neighbour_start` uses and the
    accumulation IS `gs.neighbour_finish`; only `lax.ppermute` itself is
    played by a host-side `recv = send[source]` shift with zeros where no
    source exists (the collective transport is covered by the subprocess
    tests).  Returns the post-exchange per-shard arrays.
    """
    s = part.n_shards
    rounds = [_shard_rounds(part, t) for t in range(s)]
    recvs = [[] for _ in range(s)]
    for j, k in enumerate(part.nbr_offsets):
        send_lo = [gs.shared_contrib(jnp.asarray(y_dofs_all[t]),
                                     rounds[t][j].lo_idx,
                                     rounds[t][j].lo_mask)
                   for t in range(s)]
        send_hi = [gs.shared_contrib(jnp.asarray(y_dofs_all[t]),
                                     rounds[t][j].hi_idx,
                                     rounds[t][j].hi_mask)
                   for t in range(s)]
        for t in range(s):
            recvs[t].append((
                send_lo[t - k] if t >= k else jnp.zeros_like(send_lo[t]),
                send_hi[t + k] if t < s - k else jnp.zeros_like(send_hi[t]),
            ))
    return [np.asarray(gs.neighbour_finish(jnp.asarray(y_dofs_all[t]),
                                           rounds[t], recvs[t]))
            for t in range(s)]


# ------------------------------------------------------ property layer ----


def _check_pair_tables(mesh, part):
    """The pair tables enumerate exactly the pairwise-shared dofs, in the
    same order on both sides, and the interface-element classification is
    precisely 'touches a shared dof' — on ANY shard grid."""
    s = part.n_shards

    # per-shard global dof sets, from the partition's own map
    shard_gids = [set(part.local_to_global[t][part.valid_mask[t]].tolist())
                  for t in range(s)]
    offs = set(part.nbr_offsets)
    for k in range(1, s):
        for t in range(s - k):
            expect = sorted(shard_gids[t] & shard_gids[t + k])
            if not expect:
                continue
            assert k in offs, (k, expect)
            j = part.nbr_offsets.index(k)
            lo = part.nbr_lo_idx[j][t][part.nbr_lo_mask[j][t]]
            hi = part.nbr_hi_idx[j][t + k][part.nbr_hi_mask[j][t + k]]
            # both sides enumerate the SAME dofs in the SAME order
            np.testing.assert_array_equal(
                part.local_to_global[t][lo], expect)
            np.testing.assert_array_equal(
                part.local_to_global[t + k][hi], expect)
    # no phantom offsets
    for k in offs:
        j = part.nbr_offsets.index(k)
        assert part.nbr_lo_mask[j].any(), k

    # elem_perm: real slots are a permutation of the mesh's elements,
    # dead padding slots are -1
    real = part.elem_perm[part.elem_perm >= 0]
    np.testing.assert_array_equal(np.sort(real), np.arange(len(mesh.verts)))
    for t in range(s):
        assert (part.elem_perm[t, :part.elem_counts[t]] >= 0).all()
        assert (part.elem_perm[t, part.elem_counts[t]:] == -1).all()

    # interface-element classification: an element's slot is < iface_count
    # iff it touches a dof valid on >= 2 shards
    presence = np.zeros(mesh.n_global, np.int32)
    for g in shard_gids:
        presence[list(g)] += 1
    for t in range(s):
        lids = part.local_ids[t]
        gids = part.local_to_global[t]
        for e in range(part.elem_counts[t]):
            touches_shared = bool(
                (presence[gids[lids[e]]] >= 2).any())
            assert touches_shared == (e < part.iface_counts[t]), (t, e)
    assert part.e_iface == part.iface_counts.max()


@settings(max_examples=10, deadline=None)
@given(nx=st.integers(1, 4), ny=st.integers(1, 3), nz=st.integers(1, 2),
       order=st.integers(1, 3), n_shards=st.integers(2, 6),
       gy=st.integers(1, 3), gz=st.integers(1, 2),
       seed=st.integers(0, 2**31 - 1))
def test_neighbour_tables_cover_interfaces(nx, ny, nz, order, n_shards,
                                           gy, gz, seed):
    """Property: the pair-table contract holds verbatim on 1-D slab AND
    2-D/3-D box shard grids (drawn via `_grid_for`), including dofs shared
    by 4 shards at sub-box edges and 8 at corners."""
    rng = np.random.default_rng(seed)
    mesh = _random_mesh(rng, nx, ny, nz, order)
    n_shards = min(n_shards, len(mesh.verts))
    grid = _grid_for(mesh, n_shards, gy, gz)
    part = mesh_gen.partition_elements(mesh, n_shards, grid=grid)
    _check_pair_tables(mesh, part)


def _check_exchange_matches(mesh, part, rng, nrhs):
    """The pairwise neighbour exchange leaves every valid local slot
    holding the full global sum — equal (exact arithmetic) to both the
    psum-style exchange and the dense single-device gather."""
    e = len(mesh.verts)
    n_shards = part.n_shards
    n1 = mesh.order + 1
    bshape = (nrhs,) if nrhs > 1 else ()

    y = rng.standard_normal((e, n1, n1, n1) + bshape)
    with _x64():
        dense = np.asarray(gs.gather(jnp.asarray(y),
                                     jnp.asarray(mesh.global_ids),
                                     mesh.n_global))
        # reassemble each shard's padded element block in PARTITION order
        # (slabs are interface-first reordered; elem_perm maps slot ->
        # mesh element), dead padding filled with garbage
        y_dofs = []
        for t in range(n_shards):
            blk = rng.standard_normal((part.e_per_shard, n1, n1, n1)
                                      + bshape)
            ne = part.elem_counts[t]
            blk[:ne] = y[part.elem_perm[t, :ne]]
            y_dofs.append(np.asarray(gs.gather(jnp.asarray(blk),
                                               jnp.asarray(part.local_ids[t]),
                                               part.n_local)))
        # psum-style oracle
        total = sum(
            gs.shared_contrib(jnp.asarray(y_dofs[t]),
                              jnp.asarray(part.shared_idx[t]),
                              jnp.asarray(part.shared_present[t]))
            for t in range(n_shards))
        psum_out = [np.asarray(gs.apply_shared(
            jnp.asarray(y_dofs[t]), jnp.asarray(part.shared_idx[t]), total))
            for t in range(n_shards)]
        nbr_out = _emulated_exchange(part, y_dofs)
    for t in range(n_shards):
        valid = part.valid_mask[t]
        gids = part.local_to_global[t][valid]
        np.testing.assert_allclose(nbr_out[t][valid], psum_out[t][valid],
                                   rtol=1e-10, atol=1e-10)
        np.testing.assert_allclose(nbr_out[t][valid], dense[gids],
                                   rtol=1e-10, atol=1e-10)


@settings(max_examples=10, deadline=None)
@given(nx=st.integers(1, 4), ny=st.integers(1, 3), nz=st.integers(1, 2),
       order=st.integers(1, 3), n_shards=st.integers(2, 8),
       gy=st.integers(1, 3), gz=st.integers(1, 2),
       nrhs=st.integers(1, 3), seed=st.integers(0, 2**31 - 1))
def test_neighbour_exchange_matches_psum_and_dense(nx, ny, nz, order,
                                                   n_shards, gy, gz, nrhs,
                                                   seed):
    """Property: exchange == psum == dense on random meshes, shard counts,
    RHS-batch widths, and shard grids (slab and 2-D/3-D boxes)."""
    rng = np.random.default_rng(seed)
    mesh = _random_mesh(rng, nx, ny, nz, order)
    n_shards = min(n_shards, len(mesh.verts))
    grid = _grid_for(mesh, n_shards, gy, gz)
    part = mesh_gen.partition_elements(mesh, n_shards, grid=grid)
    _check_exchange_matches(mesh, part, rng, nrhs)


def _check_dssum_adjoint(mesh, part, rng):
    """With the neighbour-exchanged gather standing in for Q^T, adjointness
    <Q x, y> == <x, Q^T y> holds, and multiplicity-averaged dssum built on
    it is a projection — the same identities the psum exchange satisfies
    (test_gather_scatter), now on the pairwise path."""
    e = len(mesh.verts)
    n_shards = part.n_shards
    n1 = mesh.order + 1

    def gather_neighbour_global(y_blocks):
        """Q^T via per-shard local gathers + emulated neighbour exchange +
        owner-wins reassembly."""
        y_dofs = [np.asarray(gs.gather(jnp.asarray(y_blocks[t]),
                                       jnp.asarray(part.local_ids[t]),
                                       part.n_local))
                  for t in range(n_shards)]
        exch = _emulated_exchange(part, y_dofs)
        out = np.zeros(mesh.n_global)
        for t in range(n_shards):
            own = part.owned_mask[t]
            out[part.local_to_global[t][own]] = exch[t][own]
        return out

    def to_blocks(y_local):
        """(E, n1,n1,n1) mesh-ordered local field -> per-shard padded
        blocks in partition (interface-first, elem_perm) order."""
        blocks = []
        for t in range(n_shards):
            blk = np.zeros((part.e_per_shard, n1, n1, n1))
            ne = part.elem_counts[t]
            blk[:ne] = y_local[part.elem_perm[t, :ne]]
            blocks.append(blk)
        return blocks

    with _x64():
        x = rng.standard_normal(mesh.n_global)
        y = rng.standard_normal((e, n1, n1, n1))
        qx = np.asarray(gs.scatter(jnp.asarray(x),
                                   jnp.asarray(mesh.global_ids)))
        qty = gather_neighbour_global(to_blocks(y))
        np.testing.assert_allclose(float(np.vdot(qx, y)),
                                   float(np.vdot(x, qty)), rtol=1e-10)

        mult = np.asarray(gs.multiplicity(jnp.asarray(mesh.global_ids),
                                          mesh.n_global))

        def average(y_local):
            g = gather_neighbour_global(to_blocks(y_local)) / mult
            return np.asarray(gs.scatter(jnp.asarray(g),
                                         jnp.asarray(mesh.global_ids)))

        once = average(y)
        twice = average(once)
    np.testing.assert_allclose(twice, once, rtol=1e-10, atol=1e-10)


@settings(max_examples=10, deadline=None)
@given(nx=st.integers(2, 4), ny=st.integers(1, 3), nz=st.integers(1, 2),
       order=st.integers(1, 3), n_shards=st.integers(2, 6),
       gy=st.integers(1, 3), gz=st.integers(1, 2),
       seed=st.integers(0, 2**31 - 1))
def test_neighbour_dssum_projection_and_adjointness(nx, ny, nz, order,
                                                    n_shards, gy, gz, seed):
    """Property: adjointness + dssum projection hold through the neighbour
    path on slab AND box shard grids."""
    rng = np.random.default_rng(seed)
    mesh = _random_mesh(rng, nx, ny, nz, order)
    n_shards = min(n_shards, len(mesh.verts))
    grid = _grid_for(mesh, n_shards, gy, gz)
    part = mesh_gen.partition_elements(mesh, n_shards, grid=grid)
    _check_dssum_adjoint(mesh, part, rng)


def test_box_grid_properties_fixed_configs():
    """Deterministic box-grid coverage the random draws cannot guarantee:
    2-D and 3-D grids with dofs shared by exactly 4 shards (sub-box edges)
    and 8 shards (corners), plus non-divisible per-axis extents.  Runs the
    SAME check bodies as the hypothesis properties above."""
    rng = np.random.default_rng(7)
    configs = [
        ((4, 4, 2), 1, (2, 2, 1), 4),   # 2-D grid: 4-shard edge dofs
        ((2, 2, 2), 2, (2, 2, 2), 8),   # 3-D grid: 8-shard corner dof
        ((5, 3, 2), 1, (2, 3, 1), 4),   # non-divisible extents (5/2, 3/3)
        ((3, 4, 2), 2, (3, 2), 4),      # 2-axis spec, padded with 1
    ]
    for shape, order, grid, want_sharers in configs:
        mesh = _random_mesh(rng, *shape, order)
        n_shards = int(np.prod(grid))
        part = mesh_gen.partition_elements(mesh, n_shards, grid=grid)
        assert part.grid == tuple(grid) + (1,) * (3 - len(grid))
        # the advertised worst-case sharing multiplicity really occurs
        sharers = part.shared_present.sum(axis=0).max()
        assert sharers == want_sharers, (shape, grid, sharers)
        _check_pair_tables(mesh, part)
        _check_exchange_matches(mesh, part, rng, nrhs=2)
        _check_dssum_adjoint(mesh, part, rng)


# ----------------------------------------------------- collective layer ----


_PARITY_SCRIPT = """
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.core import mesh_gen, nekbone
from repro.distributed.context import make_solver_ctx

devices = %(devices)d
assert jax.device_count() == devices, jax.devices()
# E = 18: not divisible by 4 or 8; the (5,1,1) mesh adds a 2-indivisible case
meshes = [mesh_gen.deform_trilinear(mesh_gen.box_mesh(3, 3, 2, 3), seed=3)]
if devices == 2:
    meshes.append(mesh_gen.deform_trilinear(mesh_gen.box_mesh(5, 1, 1, 3),
                                            seed=4))


def kappa(mesh, helm):
    # 2-norm condition number of the assembled operator (dense, tiny)
    p = nekbone.setup_problem(mesh, variant="trilinear", helmholtz=helm,
                              dtype=jnp.float32)
    a = np.asarray(p.op(jnp.eye(mesh.n_global, dtype=jnp.float32)),
                   np.float64)
    ev = np.linalg.eigvalsh((a + a.T) / 2)
    return float(ev[-1] / ev[0])


rng = np.random.default_rng(0)
for mesh in meshes:
    for nrhs in (1, 4):
        shape = (mesh.n_global, nrhs) if nrhs > 1 else (mesh.n_global,)
        x_true = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        for helm in (False, True):
            for backend in ("reference", "pallas"):
                if backend == "pallas" and nrhs > 1:
                    continue  # covered at nrhs=1; keeps interpret-mode wall
                variant = ("merged" if helm else "partial") \\
                    if backend == "pallas" else "trilinear"
                kw = dict(variant=variant, helmholtz=helm,
                          dtype=jnp.float32, backend=backend)
                ctx_p = make_solver_ctx(devices=devices, nrhs=nrhs,
                                        exchange="psum")
                ctx_n = make_solver_ctx(devices=devices, nrhs=nrhs,
                                        exchange="neighbour")
                ref = nekbone.setup_problem(mesh, shard_ctx=ctx_p, **kw)
                b = nekbone.rhs_from_solution(ref, x_true)
                r0 = nekbone.solve(ref, b, tol=%(tol)g, max_iter=300)
                sh = nekbone.setup_problem(mesh, shard_ctx=ctx_n, **kw)
                r1 = nekbone.solve(sh, b, tol=%(tol)g, max_iter=300)
                it0 = np.atleast_1d(np.asarray(r0.iterations)).tolist()
                it1 = np.atleast_1d(np.asarray(r1.iterations)).tolist()
                def cols(a):
                    return jnp.linalg.norm(a.reshape(mesh.n_global, -1),
                                           axis=0)
                print(json.dumps({
                    "elements": len(mesh.verts), "helm": helm,
                    "backend": backend, "nrhs": nrhs,
                    "it_psum": it0, "it_nbr": it1,
                    "dx_rel": np.asarray(cols(r1.x - r0.x)
                                         / cols(r0.x)).tolist(),
                    "tol_rel": np.asarray(%(tol)g / cols(b)).tolist(),
                    "kappa": kappa(mesh, helm)}))
"""


@pytest.mark.parametrize("devices", [2, 4, 8])
def test_neighbour_solve_matches_psum(devices):
    """exchange="neighbour" solve == exchange="psum" solve within ±1 PCG
    iteration, both equations/backends, nrhs 1 and 4, non-divisible E.

    The solutions agree to what the tolerance allows: each solve stops at
    a relative residual of max(tol/||b||, eps) (eps: the fp32 floor, the
    two exchanges sum interface contributions in different orders), and
    a relative residual r bounds the relative error by kappa(A) * r — so
    the two answers differ by at most twice that."""
    rows = _run(_PARITY_SCRIPT % {"devices": devices, "tol": TOL}, devices)
    # per mesh: nrhs=1 x {poisson, helmholtz} x {ref, pallas} = 4 rows,
    # nrhs=4 x {poisson, helmholtz} x ref = 2 rows
    assert len(rows) == (12 if devices == 2 else 6)
    eps = float(np.finfo(np.float32).eps)
    for r in rows:
        for a, b in zip(r["it_psum"], r["it_nbr"]):
            assert abs(a - b) <= 1, r
        for dx, tol_rel in zip(r["dx_rel"], r["tol_rel"]):
            assert dx <= 2 * r["kappa"] * max(tol_rel, eps), r


def test_gather_sharded_neighbour_matches_psum_gather():
    """ISSUE acceptance line, on the REAL collectives: inside shard_map,
    `gather_sharded_neighbour` == `gather_sharded` (psum) on every valid
    local slot, scalar and batched fields, with garbage in the dead-element
    padding."""
    rows = _run(textwrap.dedent("""
        import functools, json
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import gather_scatter as gs, mesh_gen
        from repro.distributed.context import make_solver_ctx

        mesh = mesh_gen.deform_trilinear(mesh_gen.box_mesh(3, 3, 2, 3),
                                         seed=3)
        ctx = make_solver_ctx(devices=4, exchange="neighbour")
        part = mesh_gen.partition_elements(mesh, 4)
        s, ep, nl = part.n_shards, part.e_per_shard, part.n_local
        n1 = mesh.order + 1
        lid = jnp.asarray(part.local_ids.reshape(s * ep, n1, n1, n1))
        sidx = jnp.asarray(part.shared_idx.reshape(-1))
        spres = jnp.asarray(part.shared_present.reshape(-1))
        nbr = tuple(jnp.asarray(t.reshape(-1))
                    for j in range(len(part.nbr_offsets))
                    for t in (part.nbr_lo_idx[j], part.nbr_lo_mask[j],
                              part.nbr_hi_idx[j], part.nbr_hi_mask[j]))
        pe = P(ctx.axis)

        def body(y, lid, sidx, spres, *nbr):
            rounds = gs.neighbour_rounds(part.nbr_offsets, s, nbr)
            a = gs.gather_sharded(y, lid, nl, sidx, spres, ctx.axis)
            b = gs.gather_sharded_neighbour(y, lid, nl, rounds, ctx.axis)
            return a, b

        smap = jax.shard_map(
            body, mesh=ctx.mesh, check_vma=False,
            in_specs=(pe,) * (4 + len(nbr)), out_specs=(pe, pe))
        rng = np.random.default_rng(0)
        for shape in [(s * ep, n1, n1, n1), (s * ep, n1, n1, n1, 3)]:
            y = jnp.asarray(rng.standard_normal(shape), jnp.float32)
            a, b = jax.jit(smap)(y, lid, sidx, spres, *nbr)
            valid = part.valid_mask.reshape(-1)
            diff = float(jnp.max(jnp.abs((a - b).reshape(
                (s * nl,) + a.shape[1:])[valid])))
            scale = float(jnp.max(jnp.abs(a)))
            print(json.dumps({"ndim": len(shape), "rel": diff / scale}))
    """), devices=4)
    assert len(rows) == 2
    for r in rows:
        assert r["rel"] < 1e-6, r


def test_neighbour_op_matches_dense_operator():
    """The neighbour-exchange shard_map operator == the single-device
    operator, every variant, d=3 included."""
    rows = _run(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.core import mesh_gen, nekbone
        from repro.distributed.context import make_solver_ctx
        mesh = mesh_gen.deform_trilinear(mesh_gen.box_mesh(3, 3, 2, 3),
                                         seed=3)
        ctx = make_solver_ctx(devices=8, exchange="neighbour")
        rng = np.random.default_rng(1)
        for variant, d in [("precomputed", 1), ("trilinear", 1),
                           ("trilinear", 3), ("merged", 1), ("partial", 1)]:
            helm = variant == "merged"
            x = jnp.asarray(rng.standard_normal(
                (mesh.n_global,) if d == 1 else (mesh.n_global, d)),
                jnp.float32)
            ref = nekbone.setup_problem(mesh, variant=variant, d=d,
                                        helmholtz=helm, dtype=jnp.float32)
            sh = nekbone.setup_problem(mesh, variant=variant, d=d,
                                       helmholtz=helm, dtype=jnp.float32,
                                       shard_ctx=ctx)
            scale = float(jnp.max(jnp.abs(ref.op(x))))
            diff = float(jnp.max(jnp.abs(sh.op(x) - ref.op(x))))
            print(json.dumps({"variant": variant, "d": d,
                              "rel": diff / scale}))
    """), devices=8)
    assert len(rows) == 5
    for r in rows:
        assert r["rel"] < 1e-5, r


def test_neighbour_hlo_gate():
    """CI gate (mirrors PR 3's one-psum gate): the compiled
    exchange="neighbour" operator/solve contain `collective-permute` and
    ZERO interface-sized all-reduces — the whole interface exchange is
    point-to-point; only the scalar/batched dot psums remain in the solve."""
    rows = _run(textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        from repro.analysis import contracts
        from repro.core import mesh_gen, nekbone
        from repro.distributed.context import make_solver_ctx
        mesh = mesh_gen.deform_trilinear(mesh_gen.box_mesh(3, 3, 2, 3),
                                         seed=3)
        for nrhs in (1, 4):
            ctx = make_solver_ctx(devices=4, nrhs=nrhs,
                                  exchange="neighbour")
            sh = nekbone.setup_problem(mesh, variant="trilinear",
                                       dtype=jnp.float32, shard_ctx=ctx)
            ns = int(sh.partition.n_shared)
            shape = (mesh.n_global, nrhs) if nrhs > 1 else (mesh.n_global,)
            B = jnp.zeros(shape, jnp.float32)
            txt_op = jax.jit(sh.op).lower(B).compile().as_text()
            txt_solve = jax.jit(lambda b: sh.run_pcg(b, 1e-6, 300)).lower(
                B).compile().as_text()
            n_rounds = 2 * len(sh.partition.nbr_offsets)
            print(json.dumps({
                "nrhs": nrhs, "n_shared": ns, "rounds": n_rounds,
                # any all-reduce whose leading buffer dim is the
                # interface size (nrhs=None: leading-dim predicate)
                "op_iface_psums": contracts.interface_allreduce_count(
                    txt_op, ns),
                "op_cperms": contracts.collective_census(
                    txt_op)["collective-permute"],
                "solve_iface_psums": contracts.interface_allreduce_count(
                    txt_solve, ns),
                "solve_cperms": contracts.collective_census(
                    txt_solve)["collective-permute"]}))
    """), devices=4)
    assert len(rows) == 2
    for r in rows:
        assert r["op_iface_psums"] == 0, r
        assert r["solve_iface_psums"] == 0, r
        # one permute per neighbour round per apply; the solve pays the
        # initial-residual apply + ONE set in the while body = 2x
        assert r["op_cperms"] == r["rounds"], r
        assert r["solve_cperms"] == 2 * r["rounds"], r


def test_exchange_flag_validation():
    from repro.distributed.context import make_solver_ctx

    with pytest.raises(ValueError, match="exchange"):
        make_solver_ctx(devices=1, exchange="ring")
