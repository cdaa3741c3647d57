"""The reduction from a profiler trace to per-layer device times."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import tracing  # noqa: E402
from bench.tracing import Event, Span  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))

# HLO heads as the TPU trace names them (nekbone_p7, TPU v5 lite)
GATHER = ("%fusion.17 = f32[2097152]{0:T(1024)S(1)} fusion(f32[1442897]{0:"
          "T(1024)S(1)} %broadcast_select_fusion.3, s32[2097152]{0:T(1024)"
          "S(1)} %broadcast_clamp_fusion.2), kind=kCustom")
SCATTER = ("%fusion.18 = f32[1442897]{0:T(1024)S(1)} fusion(s32[2097152]{0:"
           "T(1024)} %get-tuple-element.238, f32[2097152]{0:T(1024)S(1)} "
           "%bitcast.9, f32[]{:T(128)} %constant.57..sunk), kind=kCustom")
KERNEL = ("%axhelm_trilinear.6 = f32[4096,512]{1,0:T(8,128)S(1)} custom-call("
          "f32[128,256]{1,0:T(8,128)S(1)} %copy-done.8)")
UPDATE = ("%add_select_fusion.3 = (f32[1442897]{0:T(1024)S(1)}, f32[1442897]"
          "{0:T(1024)S(1)}) fusion(f32[1442897]{0:T(1024)S(1)} %custom-call"
          ".4, f32[]{:T(128)S(6)} %select_n.86, pred[]{:T(512)S(6)} %or.9)")
COPY = ("%copy-done.1 = f32[1442897]{0:T(1024)} copy-done((f32[1442897]{0:"
        "T(1024)}, f32[1442897]{0:T(1024)S(1)}, u32[]{:S(2)}) %copy-start.1)")
LOOP = ("%while.1 = (f32[1442897]{0:T(1024)}, s32[]) while((f32[1442897]"
        "{0:T(1024)}, s32[]) %tuple), condition=%cond, body=%body")
PSUM = ("%all-reduce.3 = f32[100352]{0:T(1024)} all-reduce(f32[100352]"
        "{0:T(1024)} %fusion.40), replica_groups={{0,1,2,3}}")
PSUM_DONE = ("%all-reduce-done.1 = f32[] all-reduce-done(f32[] "
             "%all-reduce-start.1)")
CLAMP = ("%broadcast_clamp_fusion.2 = s32[2097152]{0:T(1024)S(1)} fusion("
         "s32[2097152]{0:T(1024)} %get-tuple-element.238), kind=kLoop")


@pytest.mark.parametrize("name, cls", [
    (GATHER, "gs"), (SCATTER, "gs"), (CLAMP, "gs"), (KERNEL, "axhelm"),
    (UPDATE, "vec"), (COPY, "vec"), (LOOP, None), (PSUM, "exchange"),
    (PSUM_DONE, "exchange")])
def test_classify_real_hlo_heads(name, cls):
    assert tracing.classify(name) == cls


def _sum(t):
    return sum(t.by_class.values()) + sum(e - s for s, e in t.gaps)


def test_buckets_and_idle_add_up_to_the_window():
    ev = [Event(KERNEL, 10, 30), Event(GATHER, 30, 70), Event(UPDATE, 75, 80),
          Event(LOOP, 0, 100)]
    t = tracing.reduce_device(ev, 0, 100)
    assert t.by_class == {"axhelm": 20, "gs": 40, "vec": 5, "exchange": 0}
    assert t.busy == 65
    assert sorted(t.gaps) == [(0, 10), (70, 75), (80, 100)]
    assert _sum(t) == 100


def test_busy_is_the_union_when_events_overlap():
    # a collective hidden under the kernel, an update overlapping a gather
    ev = [Event(KERNEL, 0, 50), Event(PSUM, 20, 60), Event(GATHER, 70, 90),
          Event(UPDATE, 80, 95), Event(UPDATE, 85, 88)]
    t = tracing.reduce_device(ev, 0, 100)
    assert t.busy == 50 + 10 + 25
    assert t.by_class == {"axhelm": 50, "gs": 20, "vec": 5, "exchange": 10}
    assert _sum(t) == 100
    assert t.counts == {"axhelm": 1, "gs": 1, "vec": 2, "exchange": 1}


def test_events_are_clipped_to_the_window():
    t = tracing.reduce_device([Event(GATHER, -50, 20), Event(KERNEL, 90, 150)],
                              0, 100)
    assert t.by_class["gs"] == 20 and t.by_class["axhelm"] == 10
    assert _sum(t) == 100


def test_the_busiest_of_four_devices_is_reported():
    spans = [Span("window", 0, 1000), Span("solve_call", 0, 900),
             Span("bookkeeping", 900, 1000)]
    devices = {d: [Event(KERNEL, 0, 100 + 100 * d), Event(PSUM, 950, 960)]
               for d in range(4)}
    devices[2].append(Event(GATHER, 400, 800))
    r = tracing.reduce_trace(devices, spans, used=[0, 1, 2, 3])
    assert r.busiest == 2
    assert r.times.busy == 300 + 400 + 10
    assert r.busy_mean_ns == pytest.approx(
        (110 + 210 + 710 + 410) / 4)
    assert r.window_ns == 1000
    # the longest idle gap lies in a solve call, the last in bookkeeping
    assert r.gaps[0] == ("solve_call", 150)
    assert ("bookkeeping", 40) in r.gaps


def test_a_used_device_without_events_counts_as_idle():
    spans = [Span("window", 0, 100)]
    r = tracing.reduce_trace({0: [Event(KERNEL, 0, 50)]}, spans, used=[0, 1])
    assert r.busiest == 0 and r.busy_mean_ns == 25


def test_a_window_with_no_operation_is_refused():
    with pytest.raises(ValueError):
        tracing.reduce_trace({0: [Event(LOOP, 0, 100)]},
                             [Span("window", 0, 100)], used=[0])


def test_recorded_iterations_split_by_layer():
    with open(os.path.join(HERE, "testdata", "p7_two_iterations.json")) as f:
        rec = json.load(f)
    t0, t1 = rec["window"]
    events = [Event(*e) for e in rec["events"]]
    t = tracing.reduce_device(events, t0, t1)
    assert _sum(t) == pytest.approx(t1 - t0)
    assert t.counts["axhelm"] == 2
    assert t.by_class["exchange"] == 0
    # Q and Q^T take almost the whole iteration on one chip
    assert t.by_class["gs"] / (t1 - t0) > 0.95
    assert 0.2e6 < t.by_class["axhelm"] / 2 < 0.3e6
    assert t.by_class["vec"] > 0


_XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 0 duration_ps: 90000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000 }
    events { metadata_id: 2 offset_ps: 30000 duration_ps: 40000 } }
  event_metadata { key: 1 value { id: 1 name: "%s" } }
  event_metadata { key: 2 value { id: 2 name: "%s" } }
  event_metadata { key: 9 value { id: 9 name: "jit_fn(1)" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python" timestamp_ns: 990
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 5000 duration_ps: 90000 } }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "solve_call" } }
}
"""


def test_xplane_file_is_read_into_events_and_spans(tmp_path):
    from jax.profiler import ProfileData

    text = _XSPACE % (KERNEL.replace('"', '\\"'), GATHER.replace('"', '\\"'))
    raw = ProfileData.text_proto_to_serialized_xspace(text)
    path = tmp_path / "plugins" / "profile" / "t" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(raw)
    found = tracing.find_xplane(str(tmp_path))
    devices, spans = tracing.read_xplane(found)
    assert list(devices) == [0]
    assert [(tracing.classify(e.name), e.start, e.end)
            for e in devices[0]] == [("axhelm", 1000, 1020),
                                     ("gs", 1030, 1070)]
    assert {s.name for s in spans} == {"window", "solve_call"}
    r = tracing.reduce_trace(devices, spans, used=[0])
    assert r.window_ns == 100 and r.times.busy == 60
