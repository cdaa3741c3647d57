"""`correct` on the CPU at a small size: sound runs pass; the control and each
fault the cells can have fail.

Every test drives a whole run through ``harness.run_cell`` past the look for
a chip, with the system under test (or a part of the program beneath it)
replaced.  The limits of the small cells lie between what sound runs and
the control read at that size (``tinybench``); the real cells' limits are
set from chip runs and given with their readings in PERF.md.
"""

import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import control, harness, system, tinybench  # noqa: E402


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    return tinybench.make(str(tmp_path_factory.mktemp("tiny")))


def _run(spec, seed, build=None, cell="tiny.solve"):
    return harness.run_cell(spec, cell, seed, 0.3, False,
                            time.perf_counter(), build_system=build,
                            require_chip=False, log=lambda msg: None)


@pytest.mark.parametrize("seed", [3, 2 ** 33 + 17])
def test_sound_runs_are_correct(spec, seed):
    r = _run(spec, seed)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert list(r) == ["correct", "attempted", "failed", "metrics", "device",
                       "checks"]
    assert set(r["metrics"]) == {"solve_s", "setup_s"}
    assert r["checks"]["checked"]["value"] >= 1


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_the_control_is_not_correct(spec, seed):
    r = _run(spec, seed, control.build_control)
    assert not r["correct"]
    assert r["checks"]["residual_rel"]["value"] > \
        r["checks"]["residual_rel"]["limit"]


def _unchanged(cfg, cell):
    """A solve that hands back its starting state."""
    sound = system.build(cfg, cell)

    def solve(b, tol, max_iter):
        r = sound(b, tol, max_iter)
        return r._replace(x=jnp.zeros_like(r.x))

    return solve


def _altered(cfg, cell):
    """One entry of each answer changed where the solve produces it."""
    sound = system.build(cfg, cell)

    def solve(b, tol, max_iter):
        r = sound(b, tol, max_iter)
        i = r.x.shape[0] // 2
        return r._replace(x=r.x.at[i].add(1e-2 * jnp.max(jnp.abs(r.x))))

    return solve


def _half_batch(monkeypatch):
    """The element kernel leaves out the second half of its elements."""
    from repro.kernels.axhelm import ops as kops

    whole = kops.axhelm

    def half(x, *args, **kwargs):
        y = whole(x, *args, **kwargs)
        return y.at[y.shape[0] // 2:].set(0)

    monkeypatch.setattr(kops, "axhelm", half)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_a_planted_fault_is_not_correct(spec, monkeypatch, fault):
    build = {"state_unchanged": _unchanged,
             "answer_altered": _altered}.get(fault)
    if fault == "half_batch":
        _half_batch(monkeypatch)
    r = _run(spec, 21, build)
    assert not r["correct"] and r["failed"] >= 1


_FOUR = r"""
import json, sys, time
sys.path.insert(0, {root!r})
from bench import harness, system, tinybench  # puts the program on the path
from repro.core import gather_scatter as gs
spec = tinybench.make({tmp!r})
out = {{}}
def run(key):
    r = harness.run_cell(spec, "tiny_x4.solve", 31, 0.3, False,
                         time.perf_counter(), require_chip=False,
                         log=lambda m: None)
    out[key] = [r["correct"], r["device"]["count"],
                r["checks"]["residual_rel"]["value"]]
run("sound")
gs.exchange_shared = lambda y, *a, **k: y
run("no_exchange")
print(json.dumps(out))
"""


def test_four_devices_sound_and_without_the_exchange(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR.format(root=ROOT, tmp=str(tmp_path))],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"][:2] == [True, 4]
    assert out["no_exchange"][:2] == [False, 4]
