"""The trace reduction on a recorded trace: busy union, idle share, top
ops and idle-gap labels (benchmark/tracing.py and the metric readers)."""

import os

import numpy as np
import pytest
from jax.profiler import ProfileData

import run
import tracing

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_v5e.textproto")
T0, T1 = 727.0e6, 734.1e6    # the recorded slice, in trace nanoseconds


def _reader(name):
    return run.load_module(f"benchmark/metrics/{name}.py")


@pytest.fixture(scope="module")
def recorded():
    with open(DATA) as f:
        return tracing.load(ProfileData.from_text_proto(f.read()))


def _timeline(ops, t0, t1):
    """Busy nanoseconds by brute force: one flag per nanosecond."""
    busy = np.zeros(int(t1 - t0), bool)
    for _, a, b in ops:
        busy[int(round(max(a, t0) - t0)):int(round(min(b, t1) - t0))] = True
    return busy


def test_loads_device_ops_and_host_spans(recorded):
    devices, spans = recorded
    assert list(devices) == ["/device:TPU:0"]
    assert len(devices["/device:TPU:0"]) == 275
    assert sorted(s[0] for s in spans) == ["block", "launch"]


def test_busy_union_and_idle_share_match_brute_force(recorded):
    devices, spans = recorded
    ops = devices["/device:TPU:0"]
    brute = _timeline(ops, T0, T1)
    busy = tracing.busy_ns(ops, T0, T1)
    assert abs(busy - brute.sum()) <= len(ops)
    red = tracing.reduce(devices, spans, T0, T1)
    idle = _reader("idle_share").read({"trace": red})
    assert idle == pytest.approx(100 * (1 - brute.sum() / (T1 - T0)),
                                 abs=0.01)
    gap_ns = sum(b - a for a, b in tracing.gaps(ops, T0, T1))
    assert gap_ns + busy == pytest.approx(T1 - T0)


def test_idle_gaps_are_labelled_by_the_host_span_over_them(recorded):
    devices, spans = recorded
    red = tracing.reduce(devices, spans, T0, T1)
    (label, longest), *rest = red["idle_gaps"]
    # The device ends one launch at 727.24 ms and starts the next at
    # 733.88 ms; `block` returns at 729.87 ms and `launch` covers the
    # rest, the larger part, of the gap.
    assert label == "launch"
    assert longest == pytest.approx(6.64e-3, rel=0.01)
    assert all(r[1] <= longest for r in rest)
    assert tracing.label((0.0, 1.0), spans) == "none"


def test_top_ops_count_self_time_once(recorded):
    devices, spans = recorded
    ops = tracing.clip(devices["/device:TPU:0"], T0, T1)
    selfs = tracing.self_times(ops)
    assert sum(ns for _, ns in selfs) == pytest.approx(
        tracing.busy_ns(ops, T0, T1), rel=1e-9)
    top = tracing.top_ops(devices, T0, T1)
    assert len(top) == 10
    assert [r[1] for r in top] == sorted((r[1] for r in top), reverse=True)
    assert all("%" not in r[0] and " " not in r[0] for r in top)
    # the window loop's `while` spans the body's ops: it may not lead
    assert not top[0][0].startswith("while")


SYNTH = """
planes { id: 1 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.3 = s32[8] fusion()" } }
  event_metadata { key: 2 value { id: 2 name: "%all-to-all.1 = s32[8] all-to-all()" } }
}
planes { id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.3 = s32[8] fusion()" } }
  event_metadata { key: 2 value { id: 2 name: "%all-gather.7 = s32[8] all-gather()" } }
}
planes { id: 3 name: "/device:TPU:0 SparseCore 0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "ignored" } }
}
"""


def test_a_slice_of_several_devices_averages_over_them():
    devices, spans = tracing.load(ProfileData.from_text_proto(SYNTH))
    assert sorted(devices) == ["/device:TPU:0", "/device:TPU:1"]
    red = tracing.reduce(devices, spans, 0.0, 10e3)
    # busy: 7 us on device 0, 6 us on device 1, of 10 us
    assert _reader("idle_share").read({"trace": red}) == pytest.approx(35.0)
    assert _reader("step_us").read({"trace": red, "steps_slice": 13}) == \
        pytest.approx(0.5)
    assert red["device_ops"][0] == ["fusion.3", pytest.approx(3.5e-6)]
    assert [g[0] for g in red["idle_gaps"]] == ["none"] * 4


def test_counter_and_span_readers():
    assert _reader("steps_per_sim_s").read(
        {"steps_window": 1200, "sim_s_window": 3.0}) == 400.0
    assert _reader("build_s").read({"spans": {"build": 1.5}}) == 1.5
    assert _reader("warm_s").read({"spans": {"warm": 6.25}}) == 6.25
    assert _reader("runtime_s").read({"spans": {"runtime": 11.5}}) == 11.5
