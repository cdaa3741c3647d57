"""The paper's own workload: Nekbone PCG on trilinear hexahedral meshes.

Default: N=7 (the paper's choice: NekRS default + Tensor-Core-friendly),
E selectable; Poisson/Helmholtz, d in {1, 3}, all axhelm variants.

`tol` is the ABSOLUTE 2-norm of the PCG residual at which the solve stops
(`core.pcg`).  For the manufactured problem b = A x_true with a standard
normal x_true, ||b|| grows from 94 (E = 8^3) to 116 (E = 12^3), so 1e-4
is about 1e-6 relative: the tightest stop whose true residual ||b - A x||
still follows the recursive one in fp32 Jacobi PCG.  Below it the true
residual sits at a 4e-5..8e-5 floor while the recursive residual keeps
falling (CPU reference solves at E = 8^3, 10^3, 12^3).  Those solves took
134, 151 and 176 iterations at 1e-4, about 10 more per element layer, so
E = 16^3 needs about 220: `max_iter` leaves room for that and for the
Pallas backend's roundoff.
"""

from dataclasses import dataclass


@dataclass(frozen=True)
class NekboneConfig:
    name: str = "nekbone"
    order: int = 7
    elements: tuple = (16, 16, 16)     # nx, ny, nz => E = 4096
    helmholtz: bool = False
    d: int = 1
    variant: str = "trilinear"         # paper Algorithm 3
    precision: str = "float32"
    preconditioner: str = "jacobi"
    max_iter: int = 400
    tol: float = 1e-4


CONFIG = NekboneConfig()
