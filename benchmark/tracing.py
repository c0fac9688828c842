"""Reduction of a profiler trace to device busy time, idle gaps and ops.

A trace is read with `jax.profiler.ProfileData` (an `.xplane.pb` file, or
a text proto in the tests) into plain lists:

* device ops: for each device plane (`/device:TPU:<n>`), the events of its
  `XLA Ops` line as (name, start_ns, end_ns);
* host spans: the benchmark's own `TraceAnnotation` spans (build, warm,
  launch, block, restart, fetch) from the host planes.

Everything after loading works on those lists, so the arithmetic below is
the same for a recorded fixture and for a live trace.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_SPANS = ("build", "warm", "launch", "block", "restart", "fetch")


def load(pd, span_names=HOST_SPANS):
    """(devices, spans) from a ProfileData: devices maps a device plane's
    name to its op intervals, spans lists the named host spans."""
    devices, spans = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name in span_names)
    return devices, spans


def load_dir(logdir, span_names=HOST_SPANS):
    """Load the newest `.xplane.pb` under a `jax.profiler.trace` dir."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return load(ProfileData.from_file(max(files, key=os.path.getmtime)),
                span_names)


def device_index(plane_name):
    return int(DEVICE_PLANE.match(plane_name).group(1))


def clip(intervals, t0, t1):
    """Intervals cut to [t0, t1]; those outside it are dropped."""
    out = []
    for iv in intervals:
        a, b = max(iv[-2], t0), min(iv[-1], t1)
        if b > a:
            out.append((*iv[:-2], a, b))
    return out


def union(intervals):
    """Merged, sorted (start, end) pairs covering the given intervals."""
    merged = []
    for a, b in sorted((iv[-2], iv[-1]) for iv in intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(intervals, t0, t1):
    """Nanoseconds of [t0, t1] in which at least one interval runs."""
    return sum(b - a for a, b in union(clip(intervals, t0, t1)))


def gaps(intervals, t0, t1):
    """The (start, end) stretches of [t0, t1] that no interval covers."""
    out, cur = [], t0
    for a, b in union(clip(intervals, t0, t1)):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        out.append((cur, t1))
    return out


def label(gap, spans):
    """Name of the host span that covers most of a gap ('none' if no span
    overlaps it): what the host was doing while the device idled."""
    a, b = gap
    best, cover = "none", 0.0
    for name, s, e in spans:
        c = min(b, e) - max(a, s)
        if c > cover:
            best, cover = name, c
    return best


def op_name(event_name):
    """XLA's name of an op ("fusion.12") from the trace's event name, which
    is the op's whole HLO line ("%fusion.12 = s32[...] fusion(...)")."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def self_times(ops):
    """(name, self_ns) for each op: its duration less that of the ops
    nested in it.  Control flow nests on the device's op line (a `while`
    spans every op of its body), so self time is what sums to busy time
    without counting a nested op twice."""
    evs = sorted(ops, key=lambda o: (o[-2], -o[-1]))
    own = [b - a for _, a, b in evs]
    stack = []
    for i, (_, a, b) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= evs[stack[-1]][2]:
            own[stack[-1]] -= b - a
        stack.append(i)
    return [(evs[i][0], own[i]) for i in range(len(evs))]


def top_ops(devices, t0, t1, n=10):
    """[[name, seconds]] of the n ops with the most device self time in
    the slice, summed over every run of the op, averaged over devices."""
    tot = {}
    for ops in devices.values():
        for name, ns in self_times(clip(ops, t0, t1)):
            key = op_name(name)
            tot[key] = tot.get(key, 0.0) + ns
    nd = max(len(devices), 1)
    rows = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / nd / 1e9] for name, ns in rows]


def idle_gaps(devices, spans, t0, t1, n=10):
    """[[label, seconds]] of the n longest idle gaps, over all devices,
    each labelled by the host span that covered it."""
    rows = []
    for ops in devices.values():
        for g in gaps(ops, t0, t1):
            rows.append([label(g, spans), (g[1] - g[0]) / 1e9])
    rows.sort(key=lambda r: -r[1])
    return rows[:n]


def reduce(devices, spans, t0, t1):
    """Everything a metric reader needs from one traced slice."""
    window = t1 - t0
    busy = {d: busy_ns(ops, t0, t1) for d, ops in devices.items()}
    return {
        "window_ns": window,
        "busy_ns": busy,
        "device_ops": top_ops(devices, t0, t1),
        "idle_gaps": idle_gaps(devices, spans, t0, t1),
    }
