"""The Pallas axhelm kernels and the solve compile for a TPU v5e.

Nothing here runs on a chip: the TPU compiler, which is installed with
jaxlib, compiles for a v5e:2x2 topology that is described, not attached.
That catches what interpret mode cannot — block shapes the Mosaic layout
pass refuses, and more VMEM than a kernel may use — at about a second per
kernel.  Every case compiles the block size the VMEM model picks, so the
model and the compiler are checked against each other too.

The topology is described inside a fixture (only one process at a time may
load the TPU library; a worker that imports this file must not), and the
persistent compilation cache is off around these compiles: an executable
for a described chip is written there but cannot be read back without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import mesh_gen, nekbone
from repro.kernels.axhelm import ops as kops
from repro.kernels.axhelm import tune
from repro.kernels.axhelm.kernel import build_axhelm_call

N1 = 8
VARIANTS = ("precomputed", "trilinear", "parallelepiped", "merged",
            "partial")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_flags(variant):
    helm = variant == "merged"
    return dict(helmholtz=helm, has_lam0=variant in ("merged", "partial"),
                has_lam1=helm)


@pytest.mark.parametrize("nrhs", [1, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("variant", VARIANTS)
def test_kernel_compiles_for_v5e(one_chip, variant, dtype, nrhs):
    flags = _kernel_flags(variant)
    eb = tune.model_block_elems(variant, N1, 1, dtype, flags["helmholtz"],
                                nrhs=nrhs)
    call, operands = build_axhelm_call(
        variant, e_total=4 * eb, n1=N1, cols=nrhs, block_elems=eb,
        out_dtype=dtype, interpret=False, **flags)
    args = [jax.ShapeDtypeStruct(op.shape, dtype, sharding=one_chip)
            for op in operands]
    compiled = jax.jit(call).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_solve_compiles_for_v5e(one_chip, monkeypatch):
    """One whole jitted single-chip `nekbone.solve` on the Pallas backend
    (trilinear, N = 7): the PCG while_loop holds the compiled kernel, not
    the interpreter and not the reference contractions."""
    # the solve's own code asks JAX for its backend, which is the CPU
    # here; the described chip stands in for the TPU it would find
    monkeypatch.setattr(kops, "_on_tpu", lambda: True)
    mesh = mesh_gen.deform_trilinear(mesh_gen.box_mesh(2, 2, 2, N1 - 1),
                                     seed=3)
    eb = tune.model_block_elems("trilinear", N1, 1, jnp.float32,
                                e_total=len(mesh.verts))
    prob = nekbone.setup_problem(mesh, variant="trilinear",
                                 dtype=jnp.float32, backend="pallas",
                                 block_elems=eb, interpret=False)
    b = jax.ShapeDtypeStruct((mesh.n_global,), jnp.float32,
                             sharding=one_chip)
    compiled = jax.jit(lambda bb: nekbone.solve(
        prob, bb, tol=1e-4, max_iter=50)).lower(b).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("e", [5, 20])
def test_small_block_launch_compiles_for_v5e(one_chip, monkeypatch, tmp_path,
                                             e):
    """A block resolved for a launch below the 8-row tile (a small shard,
    or the smaller sub-batch of the neighbour exchange's split) compiles
    on that launch and on a larger one; a hand-picked block off the tile
    is refused, not rounded."""
    from repro.core.spectral import basis

    monkeypatch.setattr(kops, "_on_tpu", lambda: True)
    monkeypatch.setenv(tune.CACHE_ENV, str(tmp_path / "tune.json"))
    eb = tune.get_block_elems("trilinear", N1, 1, jnp.float32, e_total=3,
                              interpret=False)
    assert eb == 8
    b = basis(N1 - 1)
    x = jax.ShapeDtypeStruct((e, N1, N1, N1), jnp.float32, sharding=one_chip)
    verts = jax.ShapeDtypeStruct((e, 8, 3), jnp.float32, sharding=one_chip)

    def lower(block):
        return jax.jit(lambda xx, vv: kops.axhelm(
            xx, b, "trilinear", vv, block_elems=block,
            interpret=False)).lower(x, verts)

    assert "tpu_custom_call" in lower(eb).compile().as_text()
    with pytest.raises(ValueError, match="sublane tile"):
        lower(3)
