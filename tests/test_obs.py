"""The solver's named layers (`repro.obs`): device scopes on the compiled
solve, and the recorder of host spans and compile events.

Coverage: in the optimized HLO of every PCG loop body, each instruction that
computes names a layer of `obs.LAYERS` in its ``op_name``.  Parameters,
tuples, get-tuple-elements, constants (and what is made of constants alone),
bitcasts and copies compute nothing, and a nested loop is checked through
its own body.  An instruction with no
``op_name`` at all was made by the compiler, not traced from the program (a
sunk constant's broadcast, a reduction split into a reduce-window, a bf16
conversion on a CPU): it belongs to the one layer of the instructions that
read it, else to the one layer of those it reads.
"""

import json
import os
import re
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import mesh_gen, nekbone

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "..", "src")

EXEMPT = frozenset({"parameter", "tuple", "get-tuple-element", "constant",
                    "bitcast", "copy", "while"})


def _balanced(text: str, i: int) -> int:
    """Index just past the bracket group that opens at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] in "([{":
            depth += 1
        elif text[j] in ")]}":
            depth -= 1
            if depth == 0:
                return j + 1
    raise ValueError(f"unbalanced: {text[i:i + 80]}")


def _parse(line: str):
    """(name, opcode, operand names, op_name or None, the line) of one
    instruction."""
    line = line.strip()
    if line.startswith("ROOT "):
        line = line[5:]
    name, rest = line.split(" = ", 1)
    k = _balanced(rest, 0) if rest[0] == "(" else rest.index(" ")
    rest = rest[k:].lstrip()
    op = re.match(r"[\w\-]+", rest).group(0)
    args = rest[len(op):_balanced(rest, len(op))]
    meta = re.search(r'op_name="([^"]*)"', rest)
    return (name.lstrip("%"), op, re.findall(r"%([\w.\-]+)", args),
            meta.group(1) if meta else None, line)


def _computations(text: str):
    """(entry name, {computation: [instruction tuples]})."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        m = re.match(r"^(ENTRY )?%?([\w.\-]+) .*\{$", line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
        elif cur and line.startswith("  ") and " = " in line:
            comps[cur].append(_parse(line))
    return entry, comps


def layer_of(op_name):
    """The innermost layer scope in an ``op_name`` path, or None."""
    if op_name is None:
        return None
    for part in reversed(op_name.split("/")):
        if part in obs.LAYERS:
            return part
    return None


def loop_bodies(text: str, traced_only: bool = False):
    """The bodies of the while loops the entry runs, nested ones too, with
    each computing instruction's layer: {body: {instruction: layer}}.
    ``traced_only`` leaves out what the compiler made (no ``op_name``)."""
    entry, comps = _computations(text)
    out, todo = {}, [entry]
    while todo:
        for *_, line in comps[todo.pop()]:
            m = re.search(r"\swhile\(.*body=%?([\w.\-]+)", line)
            if m and m.group(1) not in out:
                out[m.group(1)] = _body_layers(comps[m.group(1)],
                                               traced_only)
                todo.append(m.group(1))
    return out


def _body_layers(instrs, traced_only):
    users = {}
    for name, _, args, _, _ in instrs:
        for a in args:
            users.setdefault(a, []).append(name)
    by_name = {i[0]: i for i in instrs}
    # what is made of constants alone (a sunk constant's broadcast) is one
    constant = set()
    for name, op, args, _, _ in instrs:
        if op == "constant" or (args and set(args) <= constant):
            constant.add(name)
    memo = {}

    def layer(name, seen=()):
        if name in memo:
            return memo[name]
        _, op, args, meta, _ = by_name[name]
        if meta is not None:
            got = layer_of(meta)
        else:
            # a compiler-made instruction: the layer of its readers, else
            # (a loop carry's last step) of its operands
            seen = seen + (name,)
            got = None
            for near in (users.get(name, []), args):
                found = {layer(u, seen) for u in near
                         if u in by_name and u not in seen
                         and by_name[u][1] not in EXEMPT} - {None}
                if len(found) == 1:
                    got = found.pop()
                    break
        if not seen:
            memo[name] = got
        return got

    return {name: layer(name) for name, op, _, meta, _ in instrs
            if op not in EXEMPT and name not in constant
            and (meta is not None or not traced_only)}


def _unscoped(text: str, traced_only: bool = False):
    bodies = loop_bodies(text, traced_only)
    assert bodies, "no while loop in the compiled solve"
    bad = {b: sorted(n for n, lay in layers.items() if lay is None)
           for b, layers in bodies.items()}
    found = set()
    for layers in bodies.values():
        found |= set(layers.values())
    return {b: n for b, n in bad.items() if n}, found - {None}


def test_layer_of_takes_the_innermost_scope():
    assert layer_of("jit(f)/while/body/vec.dot/exchange/psum") == "exchange"
    assert layer_of("jit(f)/while/body/gs.qt/scatter-add") == "gs.qt"
    assert layer_of("jit(f)/while/body/vec.update/jit(_where)/select_n") \
        == "vec.update"
    assert layer_of("jit(f)/while/body/gs.q.extra/gather") is None
    assert layer_of(None) is None


def test_scope_names_only_layers():
    with pytest.raises(ValueError, match="unknown layer"):
        obs.scope("gs")
    with obs.scope("gs.q"):
        pass


def _mesh():
    return mesh_gen.deform_trilinear(mesh_gen.box_mesh(4, 2, 2, 3))


@pytest.mark.parametrize("backend, nrhs, precision", [
    ("reference", 0, None), ("pallas", 0, None), ("reference", 2, None),
    ("reference", 0, "bf16_x32")])
def test_every_loop_op_names_a_layer_on_one_device(backend, nrhs, precision):
    mesh = _mesh()
    prob = nekbone.setup_problem(mesh, variant="trilinear",
                                 dtype=jnp.float32, backend=backend,
                                 precision=precision)
    b = jnp.ones((mesh.n_global,) + ((nrhs,) if nrhs else ()), jnp.float32)
    text = jax.jit(lambda bb: nekbone.solve(
        prob, bb, tol=1e-6, max_iter=50)).lower(b).compile().as_text()
    # a CPU has no bfloat16 arithmetic: its compiler's conversions fuse
    # ops of several layers and keep no op_name, so the refined solve is
    # held to its traced instructions
    bad, found = _unscoped(text, traced_only=precision is not None)
    assert not bad, bad
    assert {"axhelm", "gs.q", "gs.qt", "vec.dot", "vec.update"} <= found
    assert "vec.mask" in found or precision is not None, found


_SHARDED = """
import json, sys
sys.path.insert(0, %(here)r)
import jax, jax.numpy as jnp
from repro import obs
from repro.core import nekbone
from repro.distributed.context import make_solver_ctx
import test_obs

assert jax.device_count() == 2, jax.devices()
mesh = test_obs._mesh()
ctx = make_solver_ctx(devices=2, exchange=%(exchange)r)
with obs.record() as rec:
    prob = nekbone.setup_problem(mesh, variant="trilinear",
                                 dtype=jnp.float32, backend=%(backend)r,
                                 shard_ctx=ctx)
    b = jnp.ones((mesh.n_global,), jnp.float32)
    res = nekbone.solve(prob, b, tol=1e-6, max_iter=50)
    jax.block_until_ready(res)
text = prob.run_pcg.func.lower(prob.run_pcg.args[0], b, 1e-6,
                               50).compile().as_text()
bad, found = test_obs._unscoped(text)
print(json.dumps({"bad": bad, "found": sorted(found),
                  "spans": [[s.name, s.parent, s.attrs] for s in rec.spans]}))
"""


@pytest.mark.parametrize("exchange, backend", [
    ("psum", "reference"), ("neighbour", "reference"),
    ("neighbour", "pallas")])
def test_every_loop_op_names_a_layer_on_two_devices(exchange, backend):
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count"
               "=2", PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_SHARDED % dict(
            here=HERE, exchange=exchange, backend=backend))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert not got["bad"], got["bad"]
    assert {"axhelm", "gs.q", "gs.qt", "gs.iface", "exchange", "vec.dot",
            "vec.update", "vec.mask"} <= set(got["found"]), got["found"]
    # the sharded solve's host work, under the set-up and solve spans
    spans = got["spans"]
    names = [s[0] for s in spans]
    top = names.index("setup.problem")
    kids = [s[0] for s in spans if s[1] == top]
    assert {"setup.partition", "setup.block", "setup.geometry",
            "setup.diag", "setup.place"} <= set(kids), kids
    host = names.index("solve.host")
    assert spans[host][1] is None and "solve" in spans[host][2]
    assert [s[0] for s in spans if s[1] == host] == ["solve.place",
                                                     "solve.launch"]


def test_spans_nest_with_parents_and_self_time():
    with obs.record(devices=[]) as rec:
        with obs.span("a", k=1):
            time.sleep(0.02)
            with obs.span("b"):
                time.sleep(0.03)
            with obs.span("c"):
                pass
        with obs.span("d"):
            pass
    assert [s.name for s in rec.spans] == ["a", "b", "c", "d"]
    assert [s.parent for s in rec.spans] == [None, 0, 0, None]
    assert rec.spans[0].attrs == {"k": 1}
    assert rec.children(0) == [1, 2]
    a, b, c = rec.spans[:3]
    assert a.start_ns <= b.start_ns <= b.end_ns <= c.start_ns <= c.end_ns \
        <= a.end_ns <= rec.spans[3].start_ns
    ns = [s.end_ns - s.start_ns for s in rec.spans]
    assert rec.self_ns(0) == ns[0] - ns[1] - ns[2]
    assert rec.self_ns(0) >= 0.02e9 and b.seconds >= 0.03


def test_nothing_is_recorded_without_a_recorder():
    with obs.record(devices=[]) as rec:
        pass
    assert obs._active is None
    with obs.span("outside"):
        jax.jit(lambda x: x * 3.0 - 1.0)(jnp.ones(5))
    assert rec.spans == [] and rec.compiles == []


def test_setup_spans_of_the_one_device_path():
    with obs.record(devices=[]) as rec:
        mesh = mesh_gen.deform_trilinear(mesh_gen.box_mesh(2, 2, 2, 2))
        nekbone.setup_problem(mesh, variant="trilinear", dtype=jnp.float32)
    names = [s.name for s in rec.spans]
    assert names.count("setup.mesh") == 2
    top = names.index("setup.problem")
    assert rec.spans[top].parent is None
    assert [rec.spans[i].name for i in rec.children(top)] == [
        "setup.block", "setup.geometry", "setup.diag"]


def test_a_fresh_jit_bumps_the_compile_counter_and_a_second_call_does_not():
    def f(x):
        return jnp.sin(x) * 2.0 + 1.0

    g = jax.jit(f)
    x = jnp.ones(7)
    with obs.record(devices=[]) as rec:
        with obs.span("first"):
            g(x).block_until_ready()
        first = rec.compile_counts()
        n_first = len(rec.compiles)
        with obs.span("second"):
            g(x).block_until_ready()
    assert first[obs.TRACE][0] >= 1
    assert first[obs.COMPILE][0] + first[obs.CACHE_LOAD][0] >= 1
    assert all(c.span == 0 for c in rec.compiles)
    assert len(rec.compiles) == n_first
    assert rec.compile_counts(start_ns=rec.spans[1].start_ns) == {
        e: (0, 0.0) for e in obs.COMPILE_EVENTS}


def test_peak_bytes_is_the_fullest_devices():
    class Dev:
        def __init__(self, peak):
            self.peak = peak

        def memory_stats(self):
            return None if self.peak is None else {
                "peak_bytes_in_use": self.peak}

    with obs.record(devices=[Dev(5), Dev(None), Dev(11)]) as rec:
        with obs.span("x"):
            pass
    assert rec.spans[0].peak_bytes == 11
