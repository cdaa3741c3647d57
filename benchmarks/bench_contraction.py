"""Paper §4.2 analogue: tensor-contraction strategy comparison.

Compares the three ways this repo expresses the sum-factorization
contractions (the axhelm hot loop):

  einsum    — jnp.einsum per axis (the reference path, core/sumfact.py)
  matmul    — lane-dense Kronecker matmuls (the Pallas kernel's MXU shapes)
  fused     — one jitted function doing grad + factors + grad^T (what the
              kernel fuses in VMEM)

The paper's D_r/D_s Tensor-Core offload maps to the matmul form (DESIGN.md
§3); on CPU the ranking is indicative, on TPU the matmul form is MXU-shaped
by construction.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import geometry, mesh_gen, sumfact
from repro.core.spectral import basis
from repro.kernels.axhelm.kernel import _grad, kernel_constants


def _time(fn, *args, iters: int = 10) -> float:
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def rows(n: int = 7, e: int = 512):
    b = basis(n)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((e, b.n1, b.n1, b.n1)), jnp.float32)
    dhat = jnp.asarray(b.dhat, jnp.float32)
    mesh = mesh_gen.deform_trilinear(mesh_gen.box_mesh(8, 8, e // 64, n),
                                     seed=1)
    verts = jnp.asarray(mesh.verts, jnp.float32)
    factors = geometry.factors_trilinear(verts, b)

    einsum_fn = jax.jit(lambda xx: sumfact.grad_ref(xx, dhat))
    krs, kt = kernel_constants("precomputed", b, jnp.float32)[:2]
    matmul_fn = jax.jit(lambda xx: tuple(
        g.reshape(xx.shape) for g in _grad(xx.reshape(e, -1), krs, kt)))

    def fused(xx):
        xr, xs, xt = sumfact.grad_ref(xx, dhat)
        g = factors.g
        gxr = g[..., 0] * xr + g[..., 1] * xs + g[..., 2] * xt
        gxs = g[..., 1] * xr + g[..., 3] * xs + g[..., 4] * xt
        gxt = g[..., 2] * xr + g[..., 4] * xs + g[..., 5] * xt
        return sumfact.grad_ref_transpose(gxr, gxs, gxt, dhat)

    fused_fn = jax.jit(fused)

    flops_grad = 3 * 2 * e * b.n1**4
    flops_full = 12 * e * b.n1**4 + 15 * e * b.n1**3
    out = []
    for name, fn, fl in (("einsum_grad", einsum_fn, flops_grad),
                         ("matmul_grad", matmul_fn, flops_grad),
                         ("fused_axhelm", fused_fn, flops_full)):
        t = _time(fn, x)
        out.append({"name": name, "us_per_call": t * 1e6,
                    "gflops": fl / t / 1e9})
    # correctness cross-check einsum vs matmul forms
    r1 = einsum_fn(x)
    r2 = matmul_fn(x)
    for a, c in zip(r1, r2):
        np.testing.assert_allclose(a, c, rtol=2e-5, atol=1e-5)
    return out


def main():
    print("# bench_contraction: name,us_per_call,gflops")
    for r in rows():
        print(f"bench_contraction,{r['name']},{r['us_per_call']:.1f},"
              f"{r['gflops']:.2f}")


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
