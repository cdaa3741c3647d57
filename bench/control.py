"""Readings that the correctness limits of a cell are set from.

    python bench/control.py --workload <cell> --seeds 11 12 13 \
        --seconds 20 [--who control program]

Runs the cell's set-up, window and check for each seed, all in one process:
with the control in the program's place -- the reference's own Jacobi PCG
at ``bf16_3x``, one precision step below the configuration's float32 --
and, where ``--who`` names it, with the program itself.  Each run prints
one JSON line: who ran, the seed, ``correct`` and the compared numbers.  A
limit lies above the largest number sound program runs read and below the
smallest the control reads.  The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROL_PRECISION = "bf16_3x"


class _Result:
    def __init__(self, x, iterations, status):
        self.x, self.iterations, self.status = x, iterations, status


def build_control(cfg, cell):
    """The reference solve, at the control precision, in the program's
    place; it runs on the first device."""
    import jax.numpy as jnp

    from bench import reference

    op = reference.build(reference.box_from_config(cfg), CONTROL_PRECISION)

    def solve(b, tol, max_iter):
        x, it, ok = reference.pcg(op, b, tol, max_iter)
        return _Result(x, it, jnp.where(ok, 0, 1))

    return solve


def readings(spec, workload, seeds, seconds, who=("control",),
             require_chip=True):
    """[(who, seed, result)] for the control and the program, as ``who``
    names them.

    The control needs one chip whatever the cell asks for; the program
    needs the cell's chips."""
    from bench import harness

    if require_chip:
        harness.check_device(1)
    out = []
    runs = [r for r in (("control", build_control, False),
                        ("program", None, require_chip)) if r[0] in who]
    for who, build, need_chips in runs:
        for seed in seeds:
            res = harness.run_cell(spec, workload, seed, seconds, False,
                                   time.perf_counter(), build_system=build,
                                   require_chip=need_chips,
                                   log=lambda msg: None)
            out.append((who, seed, res))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--who", nargs="+", choices=("control", "program"),
                    default=["control"])
    args = ap.parse_args(argv)

    from bench import harness, specs

    harness.enable_compile_cache(ROOT)
    try:
        out = readings(specs.Specs(), args.workload, args.seeds,
                       args.seconds, args.who)
    except harness.NoChip as exc:
        print(f"bench/control.py: {exc}", file=sys.stderr, flush=True)
        return 3
    for who, seed, res in out:
        print(json.dumps({
            "who": who, "seed": seed, "correct": res["correct"],
            "attempted": res["attempted"], "failed": res["failed"],
            "solve_s": res["metrics"]["solve_s"]["value"],
            "checks": {k: v["value"] for k, v in res["checks"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
