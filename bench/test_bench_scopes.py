"""The reduction by the program's layer scopes, the recorder's readings, and
the metric readers that read them."""

import json
import os
import subprocess
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import harness, layers, scopes, specs  # noqa: E402
from bench import tinybench, tracing  # noqa: E402
from bench.scopes import ScopedEvent as Ev  # noqa: E402
from bench.tracing import Span  # noqa: E402
from repro import obs  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BODY = "jit(solve)/while/body/"
KERNEL = "%axhelm_trilinear.6 = f32[4096,512]{1,0} custom-call(f32[8] %a)"
GATHER = "%fusion.17 = f32[2097152]{0} fusion(f32[1442897]{0} %b)"
SCATTER = "%fusion.18 = f32[1442897]{0} fusion(s32[2097152]{0} %c)"
IFACE = "%fusion.40 = f32[100352]{0} fusion(f32[1442897]{0} %d)"
PSUM = "%all-reduce.3 = f32[100352]{0} all-reduce(f32[100352]{0} %e)"
UPDATE = "%add_select_fusion.3 = f32[1442897]{0} fusion(f32[1442897]{0} %f)"
COPY = "%copy-done.1 = f32[1442897]{0} copy-done((f32[1442897]{0}) %g)"
LOOP = "%while.1 = (f32[1442897]{0}, s32[]) while((f32[1442897]{0}) %t)"


def test_the_layers_are_the_programs():
    assert scopes.LAYERS == obs.LAYERS
    assert scopes.TRACE == obs.TRACE and scopes.COMPILE == obs.COMPILE
    assert scopes.CACHE_LOAD == obs.CACHE_LOAD


@pytest.mark.parametrize("op_name, cls", [
    (BODY + "gs.q/gather", "gs.q"), (BODY + "gs.qt/scatter-add", "gs.qt"),
    (BODY + "vec.update/vec.dot/exchange/psum", "exchange"),
    (BODY + "vec.update/vec.dot/dot_general", "vec"),
    (BODY + "vec.mask/jit(_where)/select_n", "vec"),
    (BODY + "axhelm/axhelm/jit(_axhelm_impl)/reshape", "axhelm"),
    ("jit(_run_pcg)/shard_map/while/body/gs.qt/gs.iface/scatter", "gs.iface"),
    ("jit(solve)/while", "unscoped"), (None, "unscoped"),
    (BODY + "gs.qt.x/add", "unscoped")])
def test_an_operation_takes_its_innermost_layer(op_name, cls):
    assert scopes.layer_class(op_name) == cls


def _sum(t):
    return sum(t.by_class.values()) + sum(e - s for s, e in t.gaps)


def test_layers_and_idle_add_up_to_the_window():
    ev = [Ev(KERNEL, 10, 30, BODY + "axhelm/pallas_call"),
          Ev(GATHER, 30, 50, BODY + "gs.q/gather"),
          Ev(SCATTER, 50, 70, BODY + "gs.qt/scatter-add"),
          Ev(UPDATE, 75, 80, BODY + "vec.update/add"),
          Ev(COPY, 80, 82, None), Ev(LOOP, 0, 100, "jit(solve)/while")]
    t = scopes.reduce_device(ev, 0, 100)
    assert t.by_class == {"axhelm": 20, "gs.qt": 20, "gs.q": 20,
                          "gs.iface": 0, "exchange": 0, "vec": 5,
                          "unscoped": 2}
    assert t.busy == 67 and _sum(t) == 100
    assert sorted(t.gaps) == [(0, 10), (70, 75), (82, 100)]
    assert t.counts["unscoped"] == 1 and t.counts["axhelm"] == 1


def test_overlap_goes_to_the_first_class_in_order():
    # a psum hidden under the interface gather, a Q gather under Q^T
    ev = [Ev(IFACE, 0, 40, BODY + "gs.iface/gather"),
          Ev(PSUM, 20, 60, BODY + "exchange/psum"),
          Ev(SCATTER, 70, 90, BODY + "gs.qt/scatter-add"),
          Ev(GATHER, 80, 95, BODY + "gs.q/gather")]
    t = scopes.reduce_device(ev, 0, 100)
    assert t.by_class["gs.iface"] == 40 and t.by_class["exchange"] == 20
    assert t.by_class["gs.qt"] == 20 and t.by_class["gs.q"] == 5
    assert _sum(t) == 100


def _recorded():
    with open(os.path.join(HERE, "testdata",
                           "p7_two_iterations_scoped.json")) as f:
        rec = json.load(f)
    return rec["window"], [Ev(*e) for e in rec["events"]]


def test_recorded_iterations_split_by_scope():
    (t0, t1), events = _recorded()
    t = scopes.reduce_device(events, t0, t1)
    assert _sum(t) == pytest.approx(t1 - t0)
    # two iterations and the initial residual: three kernel calls, Q twice
    # (Q of the zero start is folded away), Q^T three times
    assert sum(1 for e in events if tracing.instruction(e.name).startswith(
        "axhelm_trilinear") and t0 <= e.start < t1) == 3
    assert 14.5e6 < t.by_class["gs.q"] / 2 < 15.5e6
    assert 13.5e6 < t.by_class["gs.qt"] / 3 < 14.5e6
    assert 0.2e6 < t.by_class["axhelm"] / 3 < 0.3e6
    assert t.by_class["gs.iface"] == 0 and t.by_class["exchange"] == 0
    assert t.by_class["vec"] > 0
    assert t.by_class["unscoped"] / t.busy < 0.01


def test_recorded_scopes_agree_with_the_hlo_text_rule():
    """The old reduction on the same events: Q and Q^T are what it calls
    gs, and the busy time is the same."""
    (t0, t1), events = _recorded()
    new = scopes.reduce_device(events, t0, t1)
    old = tracing.reduce_device(
        [tracing.Event(e.name, e.start, e.end) for e in events], t0, t1)
    assert new.busy == pytest.approx(old.busy)
    assert new.by_class["gs.q"] + new.by_class["gs.qt"] == pytest.approx(
        old.by_class["gs"], rel=1e-3)


def _meas(scoped, iterations=(100, 100), chips=1, rec=None):
    return types.SimpleNamespace(scoped=scoped, recorder=rec, chips=chips,
                                 iterations=list(iterations),
                                 total_iterations=sum(iterations))


@pytest.fixture(scope="module")
def spec():
    return specs.Specs()


def test_readers_split_the_busiest_device(spec):
    spans = [Span("window", 0, 1000), Span("solve_call", 0, 1000)]
    ev = [Ev(GATHER, 0, 300, BODY + "gs.q/gather"),
          Ev(SCATTER, 300, 700, BODY + "gs.qt/scatter-add"),
          Ev(IFACE, 700, 800, BODY + "gs.iface/gather"),
          Ev(COPY, 800, 850, None)]
    r = scopes.reduce_trace({0: ev, 1: ev[:1]}, spans, used=[0, 1])
    assert r.busiest == 0 and r.window_ns == 1000
    m = _meas(r, iterations=(1,), chips=4)
    assert spec.reader("q_ms")(m) == pytest.approx(300 / 1e6)
    assert spec.reader("qt_ms")(m) == pytest.approx(400 / 1e6)
    assert spec.reader("iface_ms")(m) == pytest.approx(100 / 1e6)
    assert spec.reader("unscoped_pct")(m) == pytest.approx(100 * 50 / 850)
    assert spec.reader("iface_ms")(_meas(r, chips=1)) is None


@pytest.mark.parametrize("metric", layers.SCOPED_METRICS)
def test_readers_read_nothing_from_the_harness_alone(spec, metric):
    """Without the program's record (the harness as it stands, or a program
    with no scopes and spans) every reader returns None."""
    m = harness.Measurements({}, 1, [188], None, None)
    assert spec.reader(metric)(m) is None


def _recorder():
    rec = obs.Recorder([])
    ms = 1_000_000
    rec.spans = [obs.Span("setup.mesh", None, 0, 5 * ms, {}, 10),
                 obs.Span("setup.problem", None, 6 * ms, 30 * ms, {}, 40),
                 obs.Span("setup.diag", 1, 10 * ms, 12 * ms, {}, 40),
                 obs.Span("window", None, 50 * ms, 90 * ms, {}, 45),
                 obs.Span("solve.host", 3, 51 * ms, 52 * ms, {"solve": 0},
                          45)]
    rec.compiles = [obs.CompileEvent(obs.TRACE, 7 * ms, 0.5, 1),
                    obs.CompileEvent(obs.COMPILE, 40 * ms, 2.0, None),
                    obs.CompileEvent(obs.CACHE_LOAD, 40 * ms, 1.5, None),
                    obs.CompileEvent(obs.TRACE, 60 * ms, 0.25, 3)]
    return rec


def test_the_recorders_readings(spec):
    rec = _recorder()
    assert scopes.window_span(rec).name == "window"
    assert scopes.setup_spans(rec) == [["setup.mesh", 0.005, 10],
                                       ["setup.problem", 0.024, 40],
                                       ["setup.diag", 0.002, 40]]
    m = _meas(None, rec=rec)
    assert spec.reader("setup_problem_s")(m) == pytest.approx(0.024)
    # a cache load is inside its backend compile's seconds
    assert spec.reader("compile_s")(m) == pytest.approx(2.5)
    assert spec.reader("window_compiles")(m) == 1
    rec.spans = rec.spans[:3]
    assert spec.reader("compile_s")(m) is None
    assert spec.reader("window_compiles")(m) is None


_XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000 }
    events { metadata_id: 2 offset_ps: 30000 duration_ps: 40000 }
    events { metadata_id: 3 offset_ps: 75000 duration_ps: 5000 } }
  event_metadata { key: 1 value { id: 1 name: "%s"
    stats { metadata_id: 7 str_value: "%s" } } }
  event_metadata { key: 2 value { id: 2 name: "%s"
    stats { metadata_id: 8 int64_value: 3 }
    stats { metadata_id: 7 ref_value: 9 } } }
  event_metadata { key: 3 value { id: 3 name: "%s" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "flops" } }
  stat_metadata { key: 9 value { id: 9 name: "%s" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python" timestamp_ns: 990
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 5000 duration_ps: 90000 }
    events { metadata_id: 3 offset_ps: 2000 duration_ps: 20000 }
    events { metadata_id: 4 offset_ps: 3000 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "solve_call" } }
  event_metadata { key: 3 value { id: 3 name: "solve.host" } }
  event_metadata { key: 4 value { id: 4 name: "$python frame" } }
}
"""


def test_op_names_are_read_from_the_event_metadata(tmp_path):
    from jax.profiler import ProfileData

    def q(s):
        return s.replace('"', '\\"')

    text = _XSPACE % (q(KERNEL), BODY + "axhelm/pallas_call:", q(GATHER),
                      q(COPY), BODY + "gs.q/gather:")
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    assert scopes.op_names(str(path)) == {"/device:TPU:0": {
        KERNEL: "jit(solve)/while/body/axhelm/pallas_call",
        GATHER: "jit(solve)/while/body/gs.q/gather"}}
    devices, spans = scopes.read_xplane(str(path))
    assert [(scopes.layer_class(e.op_name), e.start, e.end)
            for e in devices[0]] == [("axhelm", 1000, 1020),
                                     ("gs.q", 1030, 1070),
                                     ("unscoped", 1075, 1080)]
    assert [s.name for s in spans] == ["window", "solve_call", "solve.host"]
    r = scopes.reduce_trace(devices, spans, used=[0])
    # the first gap (990-1000) lies in solve.host (992-1012), inside the
    # harness's solve_call (995-1085)
    assert ("solve.host", 10) in r.gaps
    assert r.times.busy == 65


def test_the_tiny_cell_reports_the_recorders_metrics(tmp_path):
    spec = tinybench.make(str(tmp_path))
    r = layers.run(spec, "tiny.solve", 5, 0.2, False, require_chip=False)
    m = r["metrics"]
    assert set(m) == {"setup_problem_s", "compile_s", "window_compiles"}
    assert m["window_compiles"] == 0
    assert 0 < m["setup_problem_s"] < r["setup_program_s"]
    assert m["compile_s"] > 0
    names = [s[0] for s in r["setup_spans"]]
    assert names[:2] == ["setup.mesh", "setup.mesh"]
    assert "setup.problem" in names and "setup.diag" in names
    # one chip: the solve is traced once, in set-up
    assert r["solve_spans"] == 1
    assert r["solves"] >= 1 and r["iterations"][0] > 0


_X4 = """
import json, sys, tempfile
sys.path.insert(0, %(root)r)
from bench import layers, tinybench
spec = tinybench.make(tempfile.mkdtemp())
r = layers.run(spec, "tiny_x4.solve", 7, 0.5, False, require_chip=False)
print(json.dumps(r))
"""


def test_the_sharded_tiny_cell_compiles_nothing_in_the_window():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    t = time.time()
    out = subprocess.run([sys.executable, "-c", _X4 % dict(root=ROOT)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["metrics"]["window_compiles"] == 0, (r, time.time() - t)
    names = [s[0] for s in r["setup_spans"]]
    assert {"setup.partition", "setup.place", "setup.geometry"} <= set(names)
    # every solve of the window, and the warm-up, runs the host spans
    assert r["solve_spans"] == 3 * (r["solves"] + 1)
