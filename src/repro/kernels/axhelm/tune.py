"""Per-configuration block-size autotuner for the Pallas axhelm kernels.

The paper tunes its CUDA kernels per polynomial order N (thread layout,
k-layer unrolling); the TPU translation has a single knob — ``block_elems``,
the number of elements resident in VMEM per grid step.  This module replaces
the static heuristic with measurement:

  1. enumerate VMEM-feasible ``block_elems`` candidates for a
     ``(variant, n1, d, dtype, helmholtz)`` configuration,
  2. time each candidate once on synthetic data,
  3. cache the winner in-process *and* in a JSON file keyed by backend
     (``tpu`` / ``cpu`` / ``...-interpret``), so later processes skip the
     sweep — see DESIGN.md for the cache format.

Autotuning is opt-in (``block_elems="auto"`` on the ops/axhelm entry points
or an explicit :func:`autotune` call); the default resolution order is
in-process cache -> JSON cache -> :func:`model_block_elems` (the VMEM model
capped by the :func:`default_block_elems` heuristic), so untuned call sites
never pay a timing sweep.
"""

from __future__ import annotations

import json
import os
import threading
import warnings
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "default_block_elems",
    "block_vmem_bytes",
    "feasible_block_elems",
    "get_block_elems",
    "model_block_elems",
    "autotune",
    "cache_path",
]

# Mosaic's default scoped-VMEM limit on a v5e TensorCore.  The model below
# over-predicts what that compiler allocates, so 2 MiB of headroom is for
# configurations the calibration did not cover (DESIGN.md §6).
VMEM_LIMIT_BYTES = 16 << 20
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES - (2 << 20)
# A compiled kernel's block is a multiple of the 8-row fp32 sublane tile
# (`kernel.build_axhelm_call` refuses anything else short of the whole
# launch); every candidate is one.
_TILE = 8
_CANDIDATES = (8, 16, 32, 64, 128, 256)
# Compiler scratch of the fp32-precision matmuls, independent of the block
# (2.9-3.6 MiB in the calibration, rounded up).
_FIXED_SCRATCH = 9 << 19

CACHE_ENV = "REPRO_AXHELM_TUNE_CACHE"
_DEFAULT_CACHE = os.path.join(os.path.expanduser("~"), ".cache", "repro",
                              "axhelm_tune.json")

_MEM_CACHE: Dict[Tuple[str, str], int] = {}
_LOCK = threading.Lock()


def default_block_elems(n1: int, d: int, nrhs: int = 1) -> int:
    """Static fallback: EB so the contraction matmuls see ~128 rows (one row
    per element column, nrhs*d columns per element) while the X block stays
    under ~1 MiB fp32."""
    rows_per_elem = d * nrhs
    eb = max(1, int(np.ceil(128 / rows_per_elem)))
    while eb > 1 and eb * d * nrhs * n1**3 * 4 > 1 << 20:
        eb //= 2
    return eb


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) buffer in the (8|16, 128) tiled layout."""
    sub = 32 // min(itemsize, 4)
    return -(-rows // sub) * sub * -(-cols // 128) * 128 * itemsize


def block_vmem_bytes(variant: str, n1: int, d: int, dtype, eb: int,
                     helmholtz: bool = False, nrhs: int = 1) -> int:
    """Estimated VMEM bytes for one grid step of the lane-dense kernel.

    * operand windows, double-buffered by the Pallas pipeline, at their
      storage dtype in the tiled layout: X and Y (eb, nrhs*d*N1^3), the
      per-element geometry window and the lambda planes;
    * the broadcast constants (Kronecker D̂ factors, Jacobian weights),
      double-buffered too;
    * the fp32 working set: eight (eb*nrhs*d, N1^3) planes (stacked X, the
      three gradients, the three factor products, Y — the Y accumulator is
      fp32 whatever the storage dtype) plus seven per-element factor
      planes shared by every column;
    * `_FIXED_SCRATCH`.

    X, Y and the working planes scale with the RHS batch `nrhs`; the
    geometry and lambda windows do NOT — they are per-element and shared by
    every RHS, which is the whole point of the batching.  Calibrated against
    the compiler for a described v5e (DESIGN.md §6): it over-predicts every
    measured configuration.
    """
    from repro.kernels.axhelm.kernel import chunk_width

    ws = jnp.dtype(dtype).itemsize
    fp32 = 4
    nodes = n1 ** 3
    cols = nrhs * d
    w = chunk_width(n1)
    if variant == "precomputed":
        geom = (6 + (1 if helmholtz else 0)) * nodes
        consts = []
    elif variant == "parallelepiped":
        geom, consts = 7, [(7, 7 * nodes)]
    elif variant in ("trilinear", "merged", "partial"):
        geom, consts = 24, [(9, nodes)]
        if variant == "trilinear":
            consts.append((1, nodes))
    else:
        raise ValueError(f"unknown axhelm variant {variant!r}")
    if variant == "partial":
        lams = 1                                  # gScale
    elif variant == "merged" or helmholtz:
        lams = 2                                  # Lam2/Lam3, or lam0/lam1
    else:
        lams = 0
    windows = (2 * _tile_bytes(eb, cols * nodes, ws)
               + _tile_bytes(eb, geom, ws) + lams * _tile_bytes(eb, nodes, ws))
    consts += [(w, 2 * w), (nodes, nodes)]
    total = 2 * windows + 2 * sum(_tile_bytes(r, c, ws) for r, c in consts)
    total += 8 * eb * cols * nodes * fp32
    total += 7 * eb * nodes * fp32
    return total + _FIXED_SCRATCH


def feasible_block_elems(variant: str, n1: int, d: int, dtype,
                         helmholtz: bool = False,
                         e_total: Optional[int] = None,
                         budget: int = VMEM_BUDGET_BYTES,
                         nrhs: int = 1) -> List[int]:
    """VMEM-feasible candidate block sizes (always contains at least 1)."""
    out = [eb for eb in _CANDIDATES
           if (e_total is None or eb <= max(int(e_total), 1))
           and block_vmem_bytes(variant, n1, d, dtype, eb, helmholtz,
                                nrhs=nrhs) <= budget]
    return out or [_CANDIDATES[0]]


def _interpreting(interpret: Optional[bool]) -> bool:
    """None -> interpreted off a TPU, compiled on one (as ops.axhelm)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _backend_tag(interpret: Optional[bool]) -> str:
    return jax.default_backend() + ("-interpret" if _interpreting(interpret)
                                    else "")


def _config_key(variant: str, n1: int, d: int, dtype,
                helmholtz: bool, nrhs: int = 1) -> str:
    # "v3/": the VMEM-model schema version.  v1 entries were tuned with a
    # model that charged the fp32 y accumulator at the storage width, v2
    # entries for the kernel layout before the lane-dense one (block sizes
    # that are no multiple of the sublane tile) — those entries must MISS,
    # not resolve.
    key = f"v3/{variant}/n1={n1}/d={d}/" \
          f"{jnp.dtype(dtype).name}/helm={int(helmholtz)}"
    # nrhs=1 keeps the pre-batching key so existing caches stay valid
    return key if nrhs == 1 else key + f"/nrhs={nrhs}"


def cache_path() -> str:
    return os.environ.get(CACHE_ENV, _DEFAULT_CACHE)


def _load_json() -> dict:
    """Read the JSON cache; a missing, truncated, or otherwise corrupt file
    (a process killed mid-write before atomic replace existed, a stray
    editor save) degrades to an EMPTY cache with a warning — the caller
    re-tunes and the next `_save_json` overwrites the wreck atomically.
    The cache is an accelerator, never a correctness input, so it must not
    be able to raise into a solve."""
    path = cache_path()
    try:
        with open(path) as f:
            data = json.load(f)
    except FileNotFoundError:
        return {}
    except (OSError, ValueError) as e:
        warnings.warn(
            f"autotune cache {path} is unreadable or corrupt ({e}); "
            f"ignoring it — the next tuning run rewrites it atomically",
            RuntimeWarning, stacklevel=2)
        return {}
    if not isinstance(data, dict):
        warnings.warn(
            f"autotune cache {path} holds {type(data).__name__}, not the "
            f"expected backend->config mapping; ignoring it",
            RuntimeWarning, stacklevel=2)
        return {}
    return data


def _cache_entry(backend: str, key: str):
    """Look up one cache entry, treating any malformed level of a corrupt-
    but-valid-JSON file (wrong nesting, missing/garbage block_elems) as a
    miss."""
    level = _load_json().get(backend)
    entry = level.get(key) if isinstance(level, dict) else None
    try:
        return int(entry["block_elems"]) if entry is not None else None
    except (TypeError, KeyError, ValueError):
        warnings.warn(
            f"autotune cache entry {backend}/{key} is malformed "
            f"({entry!r}); treating it as a miss", RuntimeWarning,
            stacklevel=2)
        return None


def _save_json(backend: str, key: str, entry: dict) -> None:
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        data = _load_json()
        data.setdefault(backend, {})[key] = entry
        # atomic publish: write a sibling tmp (pid-unique, so concurrent
        # tuners never interleave writes into one file) and os.replace it
        # over the cache — readers see the old file or the new one, never
        # a torn half-write
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # a read-only cache dir must never break the solve


def _clamp_to_elems(eb: int, e_total: Optional[int],
                    compiled: bool = False) -> int:
    """Clamp a tuned block size to the caller's element count.

    The cache is keyed per (variant, N, d, dtype) configuration, but the
    element-sharded solve calls the kernel on per-shard blocks that can be
    far smaller than the mesh the sweep ran on — a winning block of 64 on a
    9-element shard would spend 86% of the grid step on padding.  Under the
    overlapped neighbour exchange the caller passes the element count of
    `core.nekbone._neighbour_launch_plan` — the SMALLER sub-batch
    (min(e_iface, EP - e_iface)) in split mode, so neither launch pads up
    to the block (padding the interface launch would delay the ppermutes)
    and the larger one just takes more grid steps, or the full EP when the
    degenerate all-interface partition falls back to one unsplit launch.
    The cached winner stays unclamped; only this call's resolution
    shrinks.  Below the smallest candidate an interpreted launch takes its
    own element count (one block, no padding).  A `compiled` block stays a
    multiple of the sublane tile whatever the clamp — one padded tile
    there — because the block also runs launches larger than `e_total`
    (the neighbour exchange's other sub-batch)."""
    if compiled:
        eb = -(-eb // _TILE) * _TILE
    if e_total is None or eb <= e_total:
        return eb
    e = max(int(e_total), 1)
    under = [c for c in _CANDIDATES if c <= e]
    if under:
        return max(under)
    return _TILE if compiled else e


def get_block_elems(variant: str, n1: int, d: int, dtype,
                    helmholtz: bool = False,
                    e_total: Optional[int] = None,
                    autotune_now: bool = False,
                    interpret: Optional[bool] = None,
                    nrhs: int = 1) -> int:
    """Resolve the block size: mem cache -> JSON cache -> sweep/heuristic.

    `nrhs` keys the caches per RHS-batch width and shrinks the VMEM-feasible
    candidate set (the X window scales with nrhs; the geometry window does
    not), so a block tuned for the matvec cannot overflow VMEM when the
    batched solve drives the same configuration.
    """
    backend = _backend_tag(interpret)
    compiled = not _interpreting(interpret)
    key = _config_key(variant, n1, d, dtype, helmholtz, nrhs)
    with _LOCK:
        hit = _MEM_CACHE.get((backend, key))
    if hit is not None:
        return _clamp_to_elems(hit, e_total, compiled)
    eb = _cache_entry(backend, key)
    if eb is not None:
        with _LOCK:
            _MEM_CACHE[(backend, key)] = eb
        return _clamp_to_elems(eb, e_total, compiled)
    if autotune_now:
        eb, _ = autotune(variant, n1 - 1, d=d, dtype=dtype,
                         helmholtz=helmholtz, interpret=interpret, nrhs=nrhs)
        return _clamp_to_elems(eb, e_total, compiled)
    return model_block_elems(variant, n1, d, dtype, helmholtz, e_total,
                             nrhs=nrhs)


def model_block_elems(variant: str, n1: int, d: int, dtype,
                      helmholtz: bool = False,
                      e_total: Optional[int] = None, nrhs: int = 1) -> int:
    """The block size from the VMEM model alone — no cache, no timing: the
    largest feasible candidate at or below `default_block_elems`."""
    cand = feasible_block_elems(variant, n1, d, dtype, helmholtz, e_total,
                                nrhs=nrhs)
    heuristic = default_block_elems(n1, d, nrhs)
    under = [c for c in cand if c <= heuristic]
    return max(under) if under else min(cand)


def _synthetic_inputs(variant, n, d, dtype, helmholtz, e, nrhs=1):
    """Build (x, geom, lam0, lam1) for a timing run (lazy heavy imports)."""
    from repro.core import axhelm as core_ax
    from repro.core import geometry
    from repro.core.spectral import basis as make_basis
    from repro.kernels.axhelm import ref as kref

    b = make_basis(n)
    rng = np.random.default_rng(0)
    ref_cube = np.asarray(geometry.reference_cube())
    verts = jnp.asarray(
        ref_cube[None] + 0.15 * rng.standard_normal((e, 8, 3)), dtype)
    node = (e,) + (b.n1,) * 3
    if nrhs > 1:
        x_shape = (e, nrhs, d) + (b.n1,) * 3
    else:
        x_shape = node if d == 1 else (e, d) + (b.n1,) * 3
    x = jnp.asarray(rng.standard_normal(x_shape), dtype)
    lam0 = lam1 = None
    if variant == "precomputed":
        from repro.core import geometry
        f = geometry.factors_trilinear(verts, b)
        geom = jnp.concatenate([jnp.moveaxis(f.g, -1, 1), f.gwj[:, None]],
                               axis=1)
        if helmholtz:
            lam0 = jnp.ones(node, dtype)
            lam1 = jnp.full(node, 0.1, dtype)
    elif variant == "parallelepiped":
        geom = kref.gelem_from_verts(verts)
        if helmholtz:
            lam0 = jnp.ones(node, dtype)
            lam1 = jnp.full(node, 0.1, dtype)
    elif variant == "trilinear":
        geom = verts
        if helmholtz:
            lam0 = jnp.ones(node, dtype)
            lam1 = jnp.full(node, 0.1, dtype)
    elif variant == "merged":
        geom = verts
        lam0, lam1 = core_ax.setup_merged_lambdas(
            verts, b, jnp.ones(node, dtype), jnp.full(node, 0.1, dtype))
    elif variant == "partial":
        geom = verts
        lam0 = core_ax.setup_partial_gscale(verts, b)
    else:
        raise ValueError(variant)
    return b, x, geom, lam0, lam1


def autotune(variant: str, n: int, d: int = 1, dtype=jnp.float32,
             helmholtz: Optional[bool] = None, e: int = 64, iters: int = 3,
             candidates: Optional[Sequence[int]] = None,
             interpret: Optional[bool] = None,
             save: bool = True, nrhs: int = 1) -> Tuple[int, Dict[int, float]]:
    """Time every feasible block size once; cache and return the winner.

    Returns ``(best_block_elems, {block_elems: seconds})``.  The sweep runs
    on synthetic elements of order ``n`` — what wins there wins on any mesh
    of the same (variant, n1, d, dtype) shape, which is the whole point of
    the paper's per-N tuning.  Candidates are clamped to ``e`` so every
    timed run does the same amount of real work (a block larger than the
    synthetic mesh would be charged for its padding); raise ``e`` to
    explore bigger blocks.
    """
    from repro.kernels.axhelm import ops  # lazy: ops imports this module

    if helmholtz is None:
        helmholtz = variant == "merged"
    n1 = n + 1
    cand = list(candidates) if candidates else feasible_block_elems(
        variant, n1, d, dtype, helmholtz, e_total=e, nrhs=nrhs)
    b, x, geom, lam0, lam1 = _synthetic_inputs(variant, n, d, dtype,
                                               helmholtz, e, nrhs=nrhs)
    kw = {}
    if variant not in ("merged", "partial") and helmholtz:
        kw["helmholtz"] = True
    timings: Dict[int, float] = {}
    for eb in cand:
        def run():
            return ops.axhelm(x, b, variant, geom, lam0=lam0, lam1=lam1,
                              block_elems=eb, interpret=interpret, **kw)
        jax.block_until_ready(run())           # compile + warm
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(run())
            best = min(best, time.perf_counter() - t0)
        timings[eb] = best
    winner = min(timings, key=timings.get)
    backend = _backend_tag(interpret)
    key = _config_key(variant, n1, d, dtype, helmholtz, nrhs)
    with _LOCK:
        _MEM_CACHE[(backend, key)] = winner
    if save:
        _save_json(backend, key, {
            "block_elems": winner,
            "timings_s": {str(k): v for k, v in timings.items()},
            "e": e, "iters": iters,
        })
    return winner, timings
