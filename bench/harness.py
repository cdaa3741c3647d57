"""One run of one cell: set-up, a closed-loop window of solves, the check.

Set-up first makes the traffic's right-hand sides ``b = +-A_ref w`` on the
device through the benchmark's reference, one element layer at a time, and
lets the reference go; ``setup_s`` leaves those seconds out, as the
reference's own.  Then it makes the program's problem and its compiled
solve, and warms that solve up with a one-iteration call.  The window
solves right-hand side after right-hand side, each sign drawn from the seed,
each to the configuration's tolerance and waited on, and launches no solve
once ``seconds`` have passed.  After the window the peak memory is read, the
program is let go, and the answers the window produced are held against the
reference: the residual ``||b - A_ref x|| / ||b||`` and the error
``||x - x_true|| / ||x_true||`` of each, against the cell's limits.
"""

from __future__ import annotations

import contextlib
import gc
import math
import os
import shutil
import sys
import tempfile
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, reference, specs as specs_mod, traffic as gen
from bench import tracing

__all__ = ["NoChip", "Measurements", "run_cell", "check_device",
           "enable_compile_cache"]

CONVERGED = 0      # the solver's status code for a converged solve


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout;
    every program is kept, so a second run compiles nothing."""
    path = os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: where the machine sets a size limit, the evicting
    # cache's bookkeeping (an access-time file per entry) failed every
    # write on the TPU hosts, and nothing was cached
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def check_device(chips: int):
    """The devices of the cell, or NoChip naming what JAX found."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {len(devs)} "
                     f"{devs[0].platform} device(s) ({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)} "
                     f"{devs[0].device_kind}")
    return devs[:chips]


class Measurements(NamedTuple):
    """What a per-layer metric reader may read (``metrics/<name>.py``)."""

    cfg: dict
    chips: int
    iterations: list          # PCG iterations of each solve in the window
    trace: object             # tracing.Reduction of the traced window
    peak: dict                # the device kind's row of peaks.json

    @property
    def total_iterations(self) -> int:
        return int(sum(self.iterations))

    @property
    def applies(self) -> int:
        """Operator applications: one per iteration, one per initial
        residual."""
        return self.total_iterations + len(self.iterations)

    def per_iteration_ms(self, cls: str):
        it = self.total_iterations
        if self.trace is None or it == 0:
            return None
        return self.trace.times.by_class[cls] / it / 1e6

    def elements_per_chip(self) -> float:
        nx, ny, nz = self.cfg["elements"]
        return nx * ny * nz / self.chips

    def dofs_per_chip(self) -> float:
        n = self.cfg["order"]
        nx, ny, nz = self.cfg["elements"]
        return (nx * n + 1) * (ny * n + 1) * (nz * n + 1) / self.chips


def _word_bytes(cfg) -> int:
    return jnp.dtype(cfg["precision"]).itemsize


def axhelm_least(m: Measurements):
    """(seconds, bound) of one axhelm apply on one chip's elements."""
    cfg = m.cfg
    cost = counts.axhelm_cost(cfg["order"], cfg["d"],
                              cfg["equation"] == "helmholtz",
                              cfg["variant"], _word_bytes(cfg))
    e = m.elements_per_chip()
    return counts.least_time(e * cost.f_tot, e * cost.m_bytes, m.peak)


def iteration_least(m: Measurements):
    """(seconds, bound) of one PCG iteration on one chip's share."""
    cfg = m.cfg
    cost = counts.iteration_cost(
        m.elements_per_chip(), m.dofs_per_chip(), cfg["order"], cfg["d"],
        cfg["equation"] == "helmholtz", cfg["variant"], _word_bytes(cfg))
    return counts.least_time(cost.flops, cost.bytes, m.peak)


def _reservoir(rng, n_seen: int, cap: int):
    """Slot for the n-th item of a seeded reservoir sample of ``cap``."""
    if n_seen < cap:
        return n_seen
    j = int(rng.integers(0, n_seen + 1))
    return j if j < cap else None


def _window(solve, pool, signs, tol, max_iter, seconds, cap, rng):
    """The measured closed loop: ([(iterations, status, seconds)] of each
    solve, window seconds, kept answers (index, sign, x))."""
    kept = [None] * cap
    solves = []
    annotate = jax.profiler.TraceAnnotation
    t0 = time.perf_counter()
    with annotate("window"):
        while not solves or time.perf_counter() - t0 < seconds:
            i = len(solves)
            sign = signs.next()
            t_call = time.perf_counter()
            with annotate("solve_call"):
                res = solve(pool[sign], tol, max_iter)
                jax.block_until_ready(res)
            with annotate("bookkeeping"):
                solves.append((res.iterations, res.status,
                               time.perf_counter() - t_call))
                slot = _reservoir(rng, i, cap)
                if slot is not None:
                    kept[slot] = (i, sign, res.x)
        t1 = time.perf_counter()
    return solves, t1 - t0, [k for k in kept if k is not None]


def _peak_bytes(devices) -> int:
    """The peak of the fullest device."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _check(kept, solves, pool, w, box, limits, first):
    """Each kept answer against the reference; returns (failed indices,
    worst residual, worst error, unconverged count)."""
    status = [int(st) for _, st, _ in solves]
    bad = {i for i, st in enumerate(status) if st != CONVERGED}
    worst_res = worst_err = 0.0
    for i, sign, x in kept:
        b = pool[sign]
        x = jax.device_put(x, first)
        x_true = -w if sign else w
        res = float(jnp.linalg.norm(b - reference.apply_in_slabs(box, x))
                    / jnp.linalg.norm(b))
        err = float(jnp.linalg.norm(x - x_true) / jnp.linalg.norm(x_true))
        if not (res <= limits["residual_rel"]
                and err <= limits["error_rel"]):
            bad.add(i)
        worst_res = max(worst_res, res) if math.isfinite(res) else math.inf
        worst_err = max(worst_err, err) if math.isfinite(err) else math.inf
    return bad, worst_res, worst_err, len(status) - status.count(CONVERGED)


def run_cell(spec: specs_mod.Specs, workload: str, seed: int,
             seconds: float, trace: bool, t_start: float,
             build_system=None, require_chip: bool = True,
             log=None) -> dict:
    """One run of ``workload``; returns the result object of the run.

    ``build_system(cfg, cell)`` makes the solve under test (by default
    ``bench.system.build``); a test may put a broken one, or the control,
    in its place.  ``require_chip=False`` skips the look for a TPU.
    """
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell = spec.workload(workload)
    devices = check_device(cell["chips"]) if require_chip \
        else jax.devices()[:cell["chips"]]
    cfg = spec.config(cell["config"])
    tr = spec.traffic(cell["traffic"])
    if build_system is None:
        from bench import system as system_mod
        build_system = system_mod.build
    annotate = jax.profiler.TraceAnnotation

    if int(cell["rhs_per_solve"]) != 1:
        raise ValueError("the harness solves one right-hand side per call")
    phases = [("start", time.perf_counter() - t_start)]

    def phase(name):
        phases.append((name, time.perf_counter() - t_start))

    with annotate("setup"):
        with annotate("rhs_staging"):
            box = reference.box_from_config(cfg)
            w = gen.field(box, tr)
            b = reference.apply_in_slabs(box, w)
            pool = (b, -b)
            jax.block_until_ready(pool)
        phase("rhs")
        rhs_peak = _peak_bytes(devices)
        solve = build_system(cfg, cell)
        phase("system")
        warm = solve(pool[0], cfg["tol"], 1)
        jax.block_until_ready(warm)
        int(warm.iterations), int(warm.status)
        del warm
        # what set-up left for the collector is collected here, and no
        # collection in the window walks it again
        gc.collect()
        gc.freeze()
        phase("warmup")
    rhs_s = phases[1][1] - phases[0][1]
    setup_s = time.perf_counter() - t_start - rhs_s
    log("setup phases (s since start): " + " ".join(
        f"{n}={t:.2f}" for n, t in phases))
    log(f"setup_s {setup_s:.3f} (leaves out rhs {rhs_s:.3f}); peak bytes "
        f"after the right-hand sides {rhs_peak}")

    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    cap = int(tr["checked_per_run"])
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        ctx = (jax.profiler.trace(trace_dir) if trace
               else contextlib.nullcontext())
        with ctx:
            solves, window_s, kept = _window(
                solve, pool, gen.Signs(seed), cfg["tol"], cfg["max_iter"],
                seconds, cap, rng)
        peak_bytes = _peak_bytes(devices)
        iterations = [int(it) for it, _, _ in solves]
        reduction = None
        if trace:
            devs, spans = tracing.read_xplane(tracing.find_xplane(trace_dir))
            reduction = tracing.reduce_trace(devs, spans,
                                             [d.id for d in devices])
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    del solve

    limits = cell["limits"]
    bad, worst_res, worst_err, unconverged = _check(
        kept, solves, pool, w, box, limits, devices[0])
    checks = {
        "residual_rel": {"value": worst_res, "limit": limits["residual_rel"]},
        "error_rel": {"value": worst_err, "limit": limits["error_rel"]},
        "unconverged": {"value": unconverged, "limit": 0},
        "checked": {"value": len(kept), "limit": 1},
    }
    failed = len(bad)
    correct = failed == 0 and len(kept) >= 1

    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": len(solves),
              "failed": failed}
    if not trace:
        e2e = {"solve_s": window_s / len(solves), "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in spec.metrics_for(workload, "end_to_end")}
    else:
        device["busy_s"] = reduction.busy_mean_ns / 1e9
        device["window_s"] = reduction.window_ns / 1e9
        meas = Measurements(cfg, cell["chips"], iterations, reduction,
                            counts.peaks(dev0.device_kind)
                            if require_chip else None)
        metrics = {}
        for m in spec.metrics_for(workload, "per_layer"):
            value = spec.reader(m["name"])(meas)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = {
            "device_ops": [[n, v / 1e9] for n, v in reduction.device_ops],
            "idle_gaps": [[n, v / 1e9] for n, v in reduction.gaps],
        }
        result["roofline_bounds"] = {
            "axhelm": axhelm_least(meas)[1] if meas.peak else None,
            "iteration": iteration_least(meas)[1] if meas.peak else None,
        }
    result["device"] = device
    result["checks"] = checks
    log(f"solves {len(solves)} window_s {window_s:.3f} iterations "
        f"{min(iterations)}..{max(iterations)}; each solve's seconds "
        + " ".join(f"{t:.4f}" for _, _, t in solves))
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    return result
