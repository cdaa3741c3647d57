"""chip_smoke.py off the chip.

The phases run here at a tiny size with the kernels in interpret mode, so
a change that breaks the smoke's control flow shows up before a chip run.
The run itself must refuse the CPU, and a copy of the script without the
rest of the checkout must fail too — neither may print a result line.
"""

import dataclasses
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro import compile_cache
from repro.configs.nekbone import CONFIG

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_ROOT, "chip_smoke.py")
_spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

# the config at N = 3 on a 2^3 mesh; everything else as configured
TINY = dataclasses.replace(CONFIG, elements=(2, 2, 2), order=3)


@pytest.fixture(scope="module")
def meshes():
    return smoke.config_meshes(TINY)


def _result_lines(stdout):
    return [line for line in stdout.splitlines() if '"ok"' in line]


def test_kernel_phase_interpreted(meshes, capsys):
    smoke.phase_kernels(TINY, *meshes, interpret=True)
    out = capsys.readouterr().out
    assert all(f"kernel {v}:" in out for v in smoke.VARIANTS)


def test_solve_phase_interpreted(meshes, capsys):
    smoke.phase_solve(TINY, meshes[0], interpret=True)
    out = capsys.readouterr().out
    assert "backend=pallas" in out and "backend=reference" in out
    assert out.count("status=CONVERGED") == 2


def test_service_phase_interpreted(meshes, capsys):
    smoke.phase_service(TINY, meshes[0], interpret=True)
    assert "post_warmup_traces=0" in capsys.readouterr().out


def test_kernel_phase_catches_a_wrong_kernel(meshes, monkeypatch):
    """The parity check has teeth: a kernel off by one part in 1e3 fails
    the phase."""
    from repro.kernels.axhelm import ops as kops

    real = kops.axhelm
    monkeypatch.setattr(kops, "axhelm",
                        lambda *a, **k: real(*a, **k) * (1 + 1e-3))
    with pytest.raises(smoke.SmokeFailure, match="kernel parity"):
        smoke.phase_kernels(TINY, *meshes, interpret=True)


_SHARDED = """
import dataclasses, sys
sys.path.insert(0, %(root)r)
import chip_smoke as smoke
from repro.configs.nekbone import CONFIG
cfg = dataclasses.replace(CONFIG, elements=(4, 2, 2), order=3)
smoke.phase_sharded(cfg, smoke.config_meshes(cfg)[0], True, 4)
"""


def test_sharded_phase_on_host_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(_ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", _SHARDED % {"root": _ROOT}],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    for exchange in ("psum", "neighbour"):
        assert f"exchange={exchange}" in out.stdout
    assert out.stdout.count("|d iters|=") == 2


def _run_script(cwd, script, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_the_cpu():
    out = _run_script(_ROOT, _SCRIPT)
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr, out.stderr[-2000:]
    assert not _result_lines(out.stdout)


def test_fails_without_the_checkout(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(_SCRIPT, lone)
    out = _run_script(str(tmp_path), str(lone))
    assert out.returncode != 0
    assert not _result_lines(out.stdout)


def test_result_line_names_the_device(monkeypatch, capsys):
    """With the phases stubbed, a passing run ends in exactly the result
    line the contract asks for, taken from what JAX reports."""

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(compile_cache, "enable", lambda: "unused")
    monkeypatch.setattr(smoke, "check_device", lambda chips: (Dev(), chips))
    monkeypatch.setattr(smoke, "config_meshes", lambda cfg: (None, None))
    for phase in ("phase_kernels", "phase_solve", "phase_service",
                  "phase_sharded"):
        monkeypatch.setattr(smoke, phase, lambda *a, **k: None)
    for chips in (1, 4):
        assert smoke.main(["--chips", str(chips)]) == 0
        last = capsys.readouterr().out.strip().splitlines()[-1]
        assert json.loads(last) == {"ok": True, "device": {
            "platform": "tpu", "kind": "TPU v5 lite", "count": chips}}
    monkeypatch.setattr(smoke, "phase_solve", lambda *a, **k: smoke._require(
        False, "stub failure"))
    assert smoke.main([]) == 1
    assert not _result_lines(capsys.readouterr().out)


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_in_the_checkout(monkeypatch, cache_dir_restored):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    assert compile_cache.enable() == str(compile_cache.CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(
        compile_cache.CACHE_DIR)
    assert compile_cache.CACHE_DIR.parent == type(compile_cache.CACHE_DIR)(
        _ROOT)
    with open(os.path.join(_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_env_wins(monkeypatch, cache_dir_restored):
    """Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and the
    code sets no directory."""
    monkeypatch.setenv(compile_cache.ENV, "/elsewhere")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before
