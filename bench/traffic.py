"""The one traffic generator: right-hand sides for a closed loop of solves.

A traffic mix is a data file ``traffic/<name>.json`` of parameters:

    field_seed       the seed of the mix's one solution field
    checked_per_run  how many of the window's answers the check compares
                     (all of them when the window has no more)

The solution is one field of unit white noise on the interior nodes, zero on
the boundary, drawn from ``field_seed``: the same field for every run, as
Nekbone solves one fixed pseudo-random field on every run.  White-noise
draws differ in their weight on the lowest modes, and with it in the
iterations a solve takes, so a field drawn from each run's seed would
change the work from seed to seed.  The run's seed draws the sign of each
solve's right-hand side instead, ``b = +-A w``: the same work in another
order, and no answer is the one before it.  The right-hand side is made
through the benchmark's own reference operator.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

__all__ = ["base_key", "field", "Signs"]


def base_key(seed: int):
    """A threefry key from any whole number, however large."""
    state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32),
                                    impl="threefry2x32")


def field(box: reference.Box, traffic: dict):
    """The mix's solution field w: (n_global,) float32."""
    return _field(base_key(traffic["field_seed"]), reference.boundary(box))


@jax.jit
def _field(key, mask):
    noise = jax.random.normal(key, mask.shape, jnp.float32)
    return jnp.where(mask, 0.0, noise)


class Signs:
    """The sign of each solve's right-hand side, drawn from the run's seed:
    0 for ``+A w``, 1 for ``-A w``."""

    def __init__(self, seed: int):
        self._rng = np.random.default_rng(
            np.random.SeedSequence([int(seed), 2]))

    def next(self) -> int:
        return int(self._rng.integers(0, 2))
