"""The benchmark's plain reference and traffic generator against the program
and against themselves, on the CPU at small sizes."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import reference, traffic  # noqa: E402

BOXES = [reference.Box((3, 2, 2), (1.0, 1.0, 1.0), 3, 0.08),
         reference.Box((4, 2, 3), (2.0, 1.0, 1.5), 4, 0.08),
         reference.Box((2, 2, 2), (1.0, 1.0, 1.0), 7, 0.08)]
TRAFFIC = {"field_seed": 0}


def _program(box):
    from repro.core import mesh_gen, nekbone

    mesh = mesh_gen.deform_trilinear(
        mesh_gen.box_mesh(*box.shape, box.order, lengths=box.lengths),
        amplitude=box.amplitude)
    return mesh, nekbone.setup_problem(mesh, variant="trilinear",
                                       backend="reference")


@pytest.mark.parametrize("box", BOXES, ids=str)
def test_reference_operator_agrees_with_the_programs(box):
    mesh, prob = _program(box)
    assert mesh.n_global == box.n_global
    np.testing.assert_allclose(
        reference.vertex_grid(box)[:-1, :-1, :-1].reshape(-1, 3),
        mesh.verts[:, 0], rtol=0, atol=1e-12)
    op = reference.build(box)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(box.n_global),
                    jnp.float32)
    y_ref, y_prog = op.apply(x), prob.op(x)
    scale = float(jnp.max(jnp.abs(y_prog)))
    assert float(jnp.max(jnp.abs(y_ref - y_prog))) <= 1e-6 * scale
    d_ref, d_prog = op.diagonal(), prob.diag
    assert float(jnp.max(jnp.abs(d_ref - d_prog))) <= 1e-6 * float(
        jnp.max(d_prog))


@pytest.mark.parametrize("box", BOXES[:2], ids=str)
def test_gather_is_the_adjoint_of_scatter(box):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal(box.n_global), jnp.float32)
    y = jnp.asarray(rng.standard_normal((box.n_elements,)
                                        + (box.order + 1,) * 3), jnp.float32)
    lhs = float(jnp.vdot(reference.scatter(x, box), y))
    rhs = float(jnp.vdot(x, reference.gather(y, box)))
    assert lhs == pytest.approx(rhs, rel=1e-5)
    # every dof is counted once per element holding it
    mult = reference.gather(jnp.ones_like(y), box).reshape(box.lattice)
    assert float(mult.min()) == 1 and float(mult.max()) == 8


def test_diagonal_is_the_operators():
    box = BOXES[0]
    op = reference.build(box)
    diag = np.asarray(op.diagonal())
    for dof in (0, 57, box.n_global // 2, box.n_global - 40):
        e = jnp.zeros(box.n_global, jnp.float32).at[dof].set(1.0)
        assert float(op.apply(e)[dof]) == pytest.approx(diag[dof], rel=1e-5)


def test_gll_differentiates_polynomials_exactly():
    x, w, d = reference.gll(7)
    assert w.sum() == pytest.approx(2.0)
    for p in range(8):
        np.testing.assert_allclose(d @ x ** p,
                                   p * x ** max(p - 1, 0) * (p > 0),
                                   atol=1e-10)


def test_control_precision_departs_by_about_1e5():
    box = BOXES[0]
    x = jnp.asarray(np.random.default_rng(3).standard_normal(box.n_global),
                    jnp.float32)
    y = reference.build(box).apply(x)
    y3 = reference.build(box, "bf16_3x").apply(x)
    rel = float(jnp.max(jnp.abs(y3 - y)) / jnp.max(jnp.abs(y)))
    assert 1e-7 < rel < 1e-4


def test_reference_pcg_solves_its_operator():
    box = BOXES[0]
    op = reference.build(box)
    x_true = traffic.field(box, {"field_seed": 5})
    b = op.apply(x_true)
    x, it, ok = reference.pcg(op, b, 1e-5, 200)
    assert bool(ok) and 0 < int(it) < 200
    assert float(jnp.linalg.norm(x - x_true) / jnp.linalg.norm(x_true)) < 1e-5


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 7, 2 ** 40 + 3])
def test_solutions_repeat_for_a_seed_and_vanish_on_the_boundary(seed):
    box = BOXES[1]
    a = traffic.field(box, {"field_seed": seed})
    b = traffic.field(box, {"field_seed": seed})
    c = traffic.field(box, {"field_seed": seed + 1})
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.allclose(a, c)
    inner = np.asarray(a)[~np.asarray(reference.boundary(box))]
    assert abs(inner.std() - 1.0) < 0.1 and np.all(inner != 0)
    assert float(jnp.max(jnp.abs(jnp.where(reference.boundary(box), a, 0))
                         )) == 0.0
    # a run's seed draws the signs: the same for a seed, not for the next
    s1, s2, s3 = traffic.Signs(seed), traffic.Signs(seed), \
        traffic.Signs(seed + 1)
    d1 = [s1.next() for _ in range(64)]
    assert d1 == [s2.next() for _ in range(64)]
    assert d1 != [s3.next() for _ in range(64)]
    assert 8 < sum(d1) < 56


def test_every_seed_does_the_same_work():
    """Every seed's window solves the same field, +-: the same work."""
    box = BOXES[0]
    op = reference.build(box)
    w = traffic.field(box, TRAFFIC)
    b = op.apply(w)
    x_p, it_p, ok_p = reference.pcg(op, b, 1e-5, 200)
    x_m, it_m, ok_m = reference.pcg(op, -b, 1e-5, 200)
    assert bool(ok_p) and bool(ok_m) and int(it_p) == int(it_m)
    np.testing.assert_array_equal(np.asarray(x_m), -np.asarray(x_p))
    assert jax.devices()[0].platform == "cpu"


@pytest.mark.parametrize("box", BOXES, ids=str)
def test_layer_by_layer_apply_is_the_operators(box):
    x = jnp.asarray(np.random.default_rng(4).standard_normal(box.n_global),
                    jnp.float32)
    y = reference.build(box).apply(x)
    y_slabs = reference.apply_in_slabs(box, x)
    assert float(jnp.max(jnp.abs(y_slabs - y))) <= 1e-6 * float(
        jnp.max(jnp.abs(y)))
