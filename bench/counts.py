"""Operations and bytes the algorithm needs, from shapes alone.

``axhelm_cost`` is the paper's Tables 3-4 per element (arXiv 2504.07042),
``nekbone_flops`` Nekbone's useful-FLOP count per CG iteration, and
``iteration_cost`` the benchmark's count of one Jacobi-PCG iteration of the
assembled solve.  They are the yardstick's own copies: no PR to the program
can move them.  Words are counted at the cell's word size, not a platform's.
"""

from __future__ import annotations

import json
import os
from typing import NamedTuple

__all__ = ["AxhelmCost", "axhelm_cost", "nekbone_flops", "IterationCost",
           "iteration_cost", "peaks", "least_time"]

INDEX_BYTES = 4          # int32 element-to-global map
PCG_VECTOR_PASSES = 8    # reads of x, r, p, A p, diag; writes of x, r, p


class AxhelmCost(NamedTuple):
    """FLOPs and bytes of one axhelm application on one element."""

    f_ax: float      # useful FLOPs (Table 3)
    f_regeo: float   # FLOPs recomputing the geometry (Table 4)
    m_bytes: float   # global-memory bytes: geometry, X, Y, lambdas, Dhat

    @property
    def f_tot(self) -> float:
        return self.f_ax + self.f_regeo


def axhelm_cost(n: int, d: int, helmholtz: bool, variant: str,
                word_bytes: int) -> AxhelmCost:
    """Tables 3 and 4 of the paper, per element, at order ``n``."""
    n1 = n + 1
    helm = 1 if helmholtz else 0
    f_ax = d * (12.0 * n1 ** 4 + (15.0 + 5.0 * helm) * n1 ** 3)
    m_xyl = (2.0 * helm + 2.0 * d) * n1 ** 3
    if variant == "precomputed":
        m_geo, f_regeo = (6.0 + helm) * n1 ** 3, 0.0
    elif variant == "parallelepiped":
        m_geo, f_regeo = 6.0 + helm, (7.0 + helm) * n1 ** 3
    elif variant == "trilinear":
        m_geo = 24.0
        f_regeo = 72.0 * n1 + 51.0 * n1 ** 2 + (82.0 + 3.0 * helm) * n1 ** 3
    elif variant in ("merged", "partial"):
        if (variant == "merged") != bool(helmholtz):
            raise ValueError(f"{variant} does not apply to "
                             f"{'Helmholtz' if helmholtz else 'Poisson'}")
        m_geo = 24.0 + (1 - helm) * n1 ** 3
        f_regeo = 72.0 * n1 + 51.0 * n1 ** 2 + 66.0 * n1 ** 3
    else:
        raise ValueError(f"unknown variant {variant!r}")
    return AxhelmCost(f_ax, f_regeo, (m_geo + m_xyl + n1 ** 2) * word_bytes)


def nekbone_flops(n_elements: int, n_global: int, n: int, d: int,
                  helmholtz: bool) -> float:
    """Nekbone's useful FLOPs of one CG iteration: one axhelm (Table 3) and
    about 7 FLOPs per dof of vector work (two dots, three updates)."""
    n1 = n + 1
    helm = 1 if helmholtz else 0
    f_ax = d * (12.0 * n1 ** 4 + (15.0 + 5.0 * helm) * n1 ** 3) * n_elements
    return f_ax + 7.0 * n_global * d


class IterationCost(NamedTuple):
    """FLOPs and bytes of one Jacobi-PCG iteration of the assembled solve."""

    flops: float
    axhelm_bytes: float      # the element kernel, Tables 3-4
    gs_bytes: float          # Q and Q^T: global and local fields, index maps
    vector_bytes: float      # the PCG vectors

    @property
    def bytes(self) -> float:
        return self.axhelm_bytes + self.gs_bytes + self.vector_bytes


def iteration_cost(n_elements: int, n_global: int, n: int, d: int,
                   helmholtz: bool, variant: str,
                   word_bytes: int) -> IterationCost:
    """One iteration: one operator application and the PCG vector work.

    Q reads the global field and the index map and writes the local field;
    Q^T reads the local field and the index map and writes the global
    field.  The vectors are each read or written once.
    """
    ax = axhelm_cost(n, d, helmholtz, variant, word_bytes)
    local = n_elements * (n + 1) ** 3
    field = n_global * d * word_bytes
    local_field = local * d * word_bytes
    gs = 2.0 * (field + local * INDEX_BYTES + local_field)
    flops = (nekbone_flops(n_elements, n_global, n, d, helmholtz)
             + n_elements * ax.f_regeo)
    return IterationCost(flops, n_elements * ax.m_bytes, gs,
                         PCG_VECTOR_PASSES * field)


def peaks(device_kind: str) -> dict:
    """The peak table's row for a device kind; an unknown kind is an error."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(table)}")
    return table[device_kind]


def least_time(flops: float, nbytes: float, peak: dict):
    """(seconds, bound): the larger of FLOPs over peak FLOP/s and bytes over
    peak bytes/s, and which of the two it is."""
    t_flop = flops / peak["flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")
