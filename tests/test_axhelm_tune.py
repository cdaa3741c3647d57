"""kernels/axhelm/tune.py: VMEM feasibility model, sweep, and caches."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import axhelm as core_ax
from repro.core.spectral import basis
from repro.kernels.axhelm import ops as kops
from repro.kernels.axhelm import tune


@pytest.fixture()
def isolated_cache(tmp_path, monkeypatch):
    """Point the JSON cache at a tmp file and clear the in-process cache."""
    path = tmp_path / "axhelm_tune.json"
    monkeypatch.setenv(tune.CACHE_ENV, str(path))
    saved = dict(tune._MEM_CACHE)
    tune._MEM_CACHE.clear()
    yield path
    tune._MEM_CACHE.clear()
    tune._MEM_CACHE.update(saved)


@pytest.mark.parametrize("variant", core_ax.VARIANTS)
def test_feasible_candidates_respect_budget(variant):
    helm = variant == "merged"
    cand = tune.feasible_block_elems(variant, 8, 1, jnp.float32, helm)
    assert cand and cand == sorted(cand)
    for eb in cand:
        assert tune.block_vmem_bytes(variant, 8, 1, jnp.float32, eb,
                                     helm) <= tune.VMEM_BUDGET_BYTES
    # a huge block must be infeasible for a per-node-factor variant
    assert tune.block_vmem_bytes("precomputed", 8, 3, jnp.float32, 4096,
                                 True) > tune.VMEM_BUDGET_BYTES


def test_bf16_block_charges_fp32_accumulator():
    """REGRESSION (pre-fix: the y block was charged at the storage dtype).

    The kernels accumulate in fp32 regardless of input width
    (`preferred_element_type` on every contraction), so a bf16 block's y
    accumulator — like the rest of the fp32 working set — occupies fp32
    bytes of VMEM.  The pre-fix model halved it with the storage dtype and
    admitted bf16 block sizes whose real footprint overflows the budget."""
    eb, n1 = 16, 8
    nodes = n1 ** 3
    tile = tune._tile_bytes

    def storage(ws):
        # double-buffered x/y/vertex windows and broadcast constants, tiled
        windows = 2 * tile(eb, nodes, ws) + tile(eb, 24, ws)
        consts = (tile(9, nodes, ws) + tile(1, nodes, ws)
                  + tile(128, 256, ws)
                  + tile(nodes, nodes, ws))
        return 2 * windows + 2 * consts

    # stacked x, 3 gradients, 3 factor products, the y accumulator; plus 7
    # per-element factor planes — all fp32 whatever the storage dtype
    working = (8 + 7) * eb * nodes * 4 + tune._FIXED_SCRATCH
    got = tune.block_vmem_bytes("trilinear", n1, 1, jnp.bfloat16, eb)
    assert got == storage(2) + working, (got, storage(2) + working)
    # halving the storage dtype narrows the HBM-backed windows and
    # constants ONLY — pre-fix the difference also carried a (phantom)
    # narrowed y accumulator
    f32 = tune.block_vmem_bytes("trilinear", n1, 1, jnp.float32, eb)
    assert f32 - got == storage(4) - storage(2), (f32, got)


def test_v1_cache_entries_miss_under_v2_schema(isolated_cache):
    """Entries tuned under the v1 VMEM model (which undercounted bf16
    blocks) must MISS, not resolve: the key carries the model schema."""
    backend = tune._backend_tag(None)
    v1_key = "trilinear/n1=3/d=1/bfloat16/helm=0"
    isolated_cache.write_text(json.dumps(
        {backend: {v1_key: {"block_elems": 256}}}))
    assert tune._config_key(
        "trilinear", 3, 1, jnp.bfloat16, False).startswith("v3/")
    eb = tune.get_block_elems("trilinear", 3, 1, jnp.bfloat16)
    assert eb != 256
    assert eb in tune.feasible_block_elems("trilinear", 3, 1, jnp.bfloat16)


def test_get_block_elems_heuristic_fallback(isolated_cache):
    """With empty caches and no sweep, the static heuristic (clamped to a
    feasible candidate) is returned."""
    eb = tune.get_block_elems("trilinear", 4, 1, jnp.float32)
    assert eb in tune.feasible_block_elems("trilinear", 4, 1, jnp.float32)


def test_autotune_sweeps_caches_and_reuses(isolated_cache):
    winner, timings = tune.autotune("trilinear", 2, d=1, dtype=jnp.float32,
                                    e=8, iters=1, candidates=[1, 2, 4])
    assert winner in (1, 2, 4)
    assert set(timings) == {1, 2, 4}
    assert all(t > 0 for t in timings.values())

    # JSON cache written, keyed by backend tag
    data = json.loads(isolated_cache.read_text())
    backend = tune._backend_tag(None)
    key = tune._config_key("trilinear", 3, 1, jnp.float32, False)
    assert data[backend][key]["block_elems"] == winner

    # in-process cache hit
    assert tune.get_block_elems("trilinear", 3, 1, jnp.float32) == winner
    # cold process (mem cache cleared) falls back to the JSON entry
    tune._MEM_CACHE.clear()
    assert tune.get_block_elems("trilinear", 3, 1, jnp.float32) == winner


def test_cached_winner_clamped_to_shard_elems(isolated_cache):
    """A cached block size larger than the caller's element count is clamped
    to the next candidate at or below it — the element-sharded solve calls
    the kernel on per-shard blocks much smaller than the tuned mesh."""
    backend = tune._backend_tag(None)
    key = tune._config_key("trilinear", 3, 1, jnp.float32, False)
    tune._MEM_CACHE[(backend, key)] = 64
    assert tune.get_block_elems("trilinear", 3, 1, jnp.float32) == 64
    assert tune.get_block_elems("trilinear", 3, 1, jnp.float32,
                                e_total=9) == 8
    assert tune.get_block_elems("trilinear", 3, 1, jnp.float32,
                                e_total=64) == 64
    # the cached entry itself must stay unclamped
    assert tune._MEM_CACHE[(backend, key)] == 64


def test_compiled_resolution_keeps_the_sublane_tile(isolated_cache):
    """Resolved for the compiled kernel, a block is a multiple of the 8-row
    sublane tile however far it is clamped (it also runs launches larger
    than the one it was clamped to); interpreted, a launch below every
    candidate runs as one block of its own size."""
    backend = tune._backend_tag(False)
    key = tune._config_key("trilinear", 3, 1, jnp.float32, False)
    tune._MEM_CACHE[(backend, key)] = 64
    assert tune.get_block_elems("trilinear", 3, 1, jnp.float32, e_total=3,
                                interpret=False) == 8
    assert tune.get_block_elems("trilinear", 3, 1, jnp.float32, e_total=20,
                                interpret=False) == 16
    # a hand-picked winner off the tile rounds up to it
    tune._MEM_CACHE[(backend, key)] = 12
    assert tune.get_block_elems("trilinear", 3, 1, jnp.float32,
                                interpret=False) == 16
    ibackend = tune._backend_tag(True)
    tune._MEM_CACHE[(ibackend, key)] = 64
    assert tune.get_block_elems("trilinear", 3, 1, jnp.float32, e_total=3,
                                interpret=True) == 3


def test_block_elems_auto_entry_point(isolated_cache, rng):
    """block_elems='auto' on the public op autotunes then computes."""
    from repro.core import geometry
    b = basis(2)
    verts = jnp.broadcast_to(geometry.reference_cube(jnp.float32), (4, 8, 3))
    verts = verts + 0.1 * jnp.asarray(
        rng.standard_normal(verts.shape), jnp.float32)
    x = jnp.asarray(rng.standard_normal((4, b.n1, b.n1, b.n1)), jnp.float32)
    y = kops.axhelm(x, b, "trilinear", verts, block_elems="auto")
    y_ref = kops.reference(x, b, "trilinear", verts)
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=1e-4)
    backend = tune._backend_tag(None)
    key = tune._config_key("trilinear", 3, 1, jnp.float32, False)
    assert (backend, key) in tune._MEM_CACHE
    with pytest.raises(ValueError):
        kops.axhelm(x, b, "trilinear", verts, block_elems="fastest")


def test_corrupt_cache_file_warns_and_degrades_to_miss(isolated_cache):
    """A truncated cache (a process killed mid-write before the atomic
    publish existed) must warn + fall through to the heuristic — never
    raise into a solve."""
    isolated_cache.write_text('{"pallas": {"tri')
    with pytest.warns(RuntimeWarning, match="corrupt"):
        eb = tune.get_block_elems("trilinear", 4, 1, jnp.float32)
    assert eb in tune.feasible_block_elems("trilinear", 4, 1, jnp.float32)


def test_non_mapping_cache_warns_and_is_ignored(isolated_cache):
    isolated_cache.write_text("[1, 2, 3]")
    with pytest.warns(RuntimeWarning, match="mapping"):
        assert tune._load_json() == {}


def test_malformed_entry_is_a_miss_and_retune_heals(isolated_cache):
    """Valid JSON with a garbage entry: the lookup treats it as a miss and
    the next tuning run overwrites the wreck atomically (no tmp litter)."""
    backend = tune._backend_tag(None)
    key = tune._config_key("trilinear", 3, 1, jnp.float32, False)
    isolated_cache.write_text(json.dumps(
        {backend: {key: {"block_elems": "garbage"}}}))
    with pytest.warns(RuntimeWarning, match="malformed"):
        assert tune._cache_entry(backend, key) is None
    winner, _ = tune.autotune("trilinear", 2, d=1, dtype=jnp.float32,
                              e=8, iters=1, candidates=[1, 2])
    data = json.loads(isolated_cache.read_text())
    assert data[backend][key]["block_elems"] == winner
    assert not list(isolated_cache.parent.glob("*.tmp.*"))
