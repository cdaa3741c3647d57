"""The benchmark's counts of operations and bytes, and its peak table."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import counts  # noqa: E402

# Tables 3-4 at N = 7 (N1 = 8), written out by hand: (variant, helmholtz)
# -> (F_ax, F_regeo, words moved per element)
TABLE_N7 = {
    ("precomputed", False): (56832, 0, 6 * 512 + 2 * 512 + 64),
    ("parallelepiped", False): (56832, 7 * 512, 6 + 2 * 512 + 64),
    ("trilinear", False): (56832, 576 + 3264 + 82 * 512, 24 + 2 * 512 + 64),
    ("partial", False): (56832, 576 + 3264 + 66 * 512,
                         24 + 512 + 2 * 512 + 64),
    ("precomputed", True): (59392, 0, 7 * 512 + 4 * 512 + 64),
    ("parallelepiped", True): (59392, 8 * 512, 7 + 4 * 512 + 64),
    ("trilinear", True): (59392, 576 + 3264 + 85 * 512, 24 + 4 * 512 + 64),
    ("merged", True): (59392, 576 + 3264 + 66 * 512, 24 + 4 * 512 + 64),
}


@pytest.mark.parametrize("variant, helmholtz", sorted(TABLE_N7))
@pytest.mark.parametrize("word", [2, 4, 8])
def test_axhelm_cost_reproduces_tables_3_and_4_at_n7(variant, helmholtz,
                                                     word):
    f_ax, f_regeo, words = TABLE_N7[(variant, helmholtz)]
    c = counts.axhelm_cost(7, 1, helmholtz, variant, word)
    assert (c.f_ax, c.f_regeo, c.m_bytes) == (f_ax, f_regeo, words * word)
    assert c.f_tot == f_ax + f_regeo


@pytest.mark.parametrize("variant, helmholtz", sorted(TABLE_N7))
def test_axhelm_cost_matches_the_programs_copy(variant, helmholtz):
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src"))
    from repro.core import paper_roofline

    for n, d in ((3, 1), (7, 1), (7, 3), (15, 1)):
        ours = counts.axhelm_cost(n, d, helmholtz, variant, 4)
        theirs = paper_roofline.axhelm_cost(n, d, helmholtz, variant,
                                            fp_size=4)
        assert (ours.f_ax, ours.f_regeo, ours.m_bytes) == (
            theirs.f_ax, theirs.f_regeo, theirs.m_bytes)


@pytest.mark.parametrize("variant, helmholtz", [("merged", False),
                                                ("partial", True),
                                                ("affine", False)])
def test_axhelm_cost_refuses_what_the_paper_does_not_define(variant,
                                                            helmholtz):
    with pytest.raises(ValueError):
        counts.axhelm_cost(7, 1, helmholtz, variant, 4)


def test_iteration_cost_matches_a_hand_count_on_a_tiny_mesh():
    # 2 x 1 x 1 elements at N = 2: 45 unique dofs, 54 element nodes, fp32
    c = counts.iteration_cost(2, 45, 2, 1, False, "trilinear", 4)
    assert c.axhelm_bytes == 2 * (24 + 2 * 27 + 9) * 4
    assert c.gs_bytes == 2 * (45 * 4 + 54 * 4 + 54 * 4)
    assert c.vector_bytes == 8 * 45 * 4
    assert c.bytes == 696 + 1224 + 1440
    f_elem = (12 * 81 + 15 * 27) + (72 * 3 + 51 * 9 + 82 * 27)
    assert c.flops == 2 * f_elem + 7 * 45


def test_nekbone_flops_matches_the_programs_count():
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src"))
    from repro.core import mesh_gen, nekbone

    mesh = mesh_gen.box_mesh(3, 2, 2, 4)
    assert counts.nekbone_flops(12, mesh.n_global, 4, 1, False) == \
        nekbone.flop_count(mesh, 1, False, 1)


def test_peaks_know_the_v5e_and_refuse_an_unknown_kind():
    row = counts.peaks("TPU v5 lite")
    assert row["flops_per_s"] == 1.97e14
    assert row["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


def test_least_time_names_its_bound():
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert counts.least_time(2e9, 1e6, peak) == (2e-3, "compute")
    assert counts.least_time(1e6, 3e6, peak) == (3e-3, "memory")


def test_the_p7_kernel_is_memory_bound_at_about_22_us():
    c = counts.axhelm_cost(7, 1, False, "trilinear", 4)
    t, bound = counts.least_time(4096 * c.f_tot, 4096 * c.m_bytes,
                                 counts.peaks("TPU v5 lite"))
    assert bound == "memory"
    assert t == pytest.approx(4096 * 1112 * 4 / 8.19e11)
