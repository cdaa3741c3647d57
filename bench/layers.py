"""One cell split by the program's own layers: scoped device times, set-up
spans and compile counts, beside the benchmark's own per-layer metrics.

    python bench/layers.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--out <file.json>]

From the root of a checkout, on a machine that holds the cell's chips.  The
run is the benchmark's (``bench/harness.py``): the traffic's right-hand
sides from the reference, the program's set-up, a one-iteration warm-up and
a closed loop of solves for ``--seconds``, with no check of the answers.  A
``repro.obs`` recorder is open from the program's set-up to the window's
end, and the window is the span ``window``; with ``--trace 1`` the profiler
records the window.  The last line printed is one JSON object: the metrics
of ``metrics/{q_ms,qt_ms,iface_ms,unscoped_pct,setup_problem_s,compile_s,
window_compiles}.py`` (the first four need ``--trace 1``), the benchmark's
per-layer metrics from the same trace, ``setup_spans`` and
``idle_gaps_program``.  ``--out`` also writes the busiest device's window
operations with their ``op_name``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SCOPED_METRICS = ("q_ms", "qt_ms", "iface_ms", "unscoped_pct",
                  "setup_problem_s", "compile_s", "window_compiles")


def run(spec, workload, seed, seconds, trace, out=None, require_chip=True):
    import jax

    from bench import counts, harness, reference, scopes, system, tracing
    from bench import traffic as gen
    from repro import obs

    cell = spec.workload(workload)
    devices = harness.check_device(cell["chips"]) if require_chip \
        else jax.devices()[:cell["chips"]]
    cfg, tr = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    box = reference.box_from_config(cfg)
    b = reference.apply_in_slabs(box, gen.field(box, tr))
    pool = (b, -b)
    jax.block_until_ready(pool)
    signs = gen.Signs(seed)
    trace_dir = tempfile.mkdtemp(prefix="bench_layers_") if trace else None
    iterations = []
    try:
        with obs.record(devices) as rec:
            solve = system.build(cfg, cell)
            warm = solve(pool[0], cfg["tol"], 1)
            jax.block_until_ready(warm)
            del warm
            gc.collect()
            gc.freeze()
            ctx = (jax.profiler.trace(trace_dir) if trace
                   else contextlib.nullcontext())
            with ctx, obs.span("window"):
                t0 = time.perf_counter()
                while not iterations or time.perf_counter() - t0 < seconds:
                    sign = signs.next()
                    with obs.span("solve_call"):
                        res = solve(pool[sign], cfg["tol"], cfg["max_iter"])
                        jax.block_until_ready(res)
                    with obs.span("bookkeeping"):
                        iterations.append(int(res.iterations))
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        used = [d.id for d in devices]
        old = scoped = None
        if trace:
            path = tracing.find_xplane(trace_dir)
            old = tracing.reduce_trace(*tracing.read_xplane(path), used)
            scoped_devices, spans = scopes.read_xplane(path)
            scoped = scopes.reduce_trace(scoped_devices, spans, used)
            if out:
                w = next(s for s in spans if s.name == "window")
                events = scoped_devices.get(scoped.busiest, [])
                with open(out, "w") as f:
                    json.dump({"window": [w.start, w.end], "events": [
                        [tracing.instruction(e.name), e.start, e.end,
                         e.op_name] for e in events]}, f)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    window = scopes.window_span(rec)
    dev0 = devices[0]
    peaks = counts.peaks(dev0.device_kind) if require_chip else None
    base = harness.Measurements(cfg, cell["chips"], iterations, old, peaks)
    meas = types.SimpleNamespace(
        cfg=cfg, chips=cell["chips"], iterations=iterations,
        total_iterations=base.total_iterations, scoped=scoped, recorder=rec)
    metrics = {}
    for name in SCOPED_METRICS:
        value = spec.reader(name)(meas)
        if value is not None:
            metrics[name] = value
    if trace:
        for m in spec.metrics_for(workload, "per_layer"):
            value = spec.reader(m["name"])(base)
            if value is not None:
                metrics[m["name"]] = value
    result = {
        "workload": workload, "seed": seed, "trace": int(trace),
        "device": {"kind": dev0.device_kind, "count": len(devices),
                   "memory_peak_bytes": int(peak)},
        "solves": len(iterations), "iterations": iterations,
        "window_s": window.seconds,
        "seconds_per_solve": window.seconds / len(iterations),
        "setup_program_s": (window.start_ns - rec.spans[0].start_ns) / 1e9,
        "metrics": metrics,
        "setup_spans": scopes.setup_spans(rec),
        "solve_spans": len([s for s in rec.spans
                            if s.name.startswith("solve.")]),
        "compiles": {e: list(v) for e, v in rec.compile_counts().items()},
    }
    if trace:
        result["scoped_ms"] = {c: v / 1e6
                               for c, v in scoped.times.by_class.items()}
        result["idle_gaps_program"] = [[n, v / 1e9] for n, v in scoped.gaps]
        result["idle_gaps"] = [[n, v / 1e9] for n, v in old.gaps]
        result["device_ops"] = [[n, v / 1e9] for n, v in old.device_ops]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from bench import harness, specs

    harness.enable_compile_cache(ROOT)
    try:
        result = run(specs.Specs(), args.workload, args.seed, args.seconds,
                     bool(args.trace), args.out)
    except harness.NoChip as exc:
        print(f"bench/layers.py: {exc}", file=sys.stderr, flush=True)
        return 3
    result["process_s"] = time.perf_counter() - T_START
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
