"""Runtime tracing & metrics: where wall-time goes inside a run.

`observe.py` watches the *simulated* world (heartbeats, pcap, drops);
this module watches the *simulator*.  A `Profiler` records host-side
phase spans (device launches, tracker/log drains, substrate syncs,
bridge RPCs), device->host transfer volume, and JIT compile events,
while a device-side `TraceCounters` block (core/state.py) accumulates
per-window aggregates -- packets exchanged, peak inbox-slab occupancy --
inside the compiled step so they cost one extra scalar fetch per drain,
not per window.

Three names reach a `jax.profiler` trace whether or not a Profiler is
installed:

* every span (`Profiler.span`, and the no-op profiler's) is also a
  `jax.profiler.TraceAnnotation`, so `sim.run`, `prepare`, `dispatch`,
  `device_step`, the drains and `progress` land on the trace's host
  plane, on the clock of the device ops;
* the window loop's phases are `jax.named_scope`s (`phase`, `PHASES`):
  every compiled op's `op_name` metadata carries the innermost phase
  that encloses it, so device time splits by phase
  (tools/phaseprof.py);
* JAX's compile-phase spans (trace, lowering, backend compile or cache
  load) are kept from import on in one bounded list,
  `compile_spans()`, which also feeds the Profiler's `compile` block.

Three artifacts per profiled run:

* ``trace.json`` -- Chrome trace-event format; open in chrome://tracing
  or https://ui.perfetto.dev.  Phase spans are duration events; device
  counter snapshots are counter tracks.
* ``metrics.json`` -- aggregates: per-phase count/total/p50/p95/max,
  transfer bytes, compile count, device counters.
* a one-screen summary table (``Profiler.summary_table()``).

The module-level `install()/current()` pair keeps hook sites cheap:
engine/observe/bridge call ``trace.current().span(...)``, which is a
no-op singleton unless a run installed a real Profiler.  Hot compiled
code never consults the profiler -- device-side counting is opted into
by putting a TraceCounters block on the state (``ensure_counters``),
the same present-or-None pattern as the capture and log rings.
"""

from __future__ import annotations

import collections
import functools
import json
import time

import jax
from jax._src import monitoring as _monitoring
from jax.profiler import TraceAnnotation

# ---------------------------------------------------------------------------
# Window-loop phases: named scopes on every compiled op
# ---------------------------------------------------------------------------

# The one set of phase names the engine scopes its ops under (engine.py).
# Window level: `exchange` (the boundary exchange, the mesh's all_to_all
# body included), `scan` (the next-event scans and outbox-pending mins
# that drive both loops), `bounds` (window start/end, netem advance, the
# hoisted window ctx), `close` (window records: flight, scope, sentinel,
# digest).  Micro-step (`_microstep_core`): `rx`, `tcp_timers`, `app`,
# `tcp_tx`, `stage`, `tx`, `cpu`, then `scan` again.  `mesh_min` is every
# cross-chip min or max reduction (engine._mesh_reduce), nested inside the
# phase that calls it.  An op belongs to the innermost phase in its
# `op_name`; renaming one renames what every trace reduction reads.
PHASES = ("exchange", "scan", "bounds", "close",
          "rx", "tcp_timers", "app", "tcp_tx", "stage", "tx", "cpu",
          "mesh_min")


def phase(name):
    """`jax.named_scope` for one of PHASES: metadata only, the compiled
    ops stay the same (tools/kernelcount.py counts are unchanged)."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}; PHASES are {PHASES}")
    return jax.named_scope(name)


# ---------------------------------------------------------------------------
# Compile record: JAX's compile-phase spans, kept from import on
# ---------------------------------------------------------------------------

# JAX announces each phase of a compile with the function's name
# (jax._src.dispatch): the Python trace (`fun_name` "run_until"), the
# lowering to MLIR and the backend compile, which is also a persistent
# cache load ("jit(run_until)" in jax 0.9).
_COMPILE_PREFIX = "/jax/core/compile/"
BACKEND_COMPILE = _COMPILE_PREFIX + "backend_compile_duration"
COMPILE_SPANS_MAX = 16384
_compile_spans = collections.deque(maxlen=COMPILE_SPANS_MAX)


def compile_spans():
    """Every JAX compile-phase span of this process since shadow1_tpu was
    imported (the newest COMPILE_SPANS_MAX), oldest first, verbatim:
    (event, fun_name, start_s, end_s) on the `time.time()` clock.  The
    counter an operator reads to see which function recompiled."""
    return list(_compile_spans)


def _on_time_span(event, start_s, end_s, **kw):
    """Keep a compile-phase span in the record and hand it to the active
    Profiler.  Runs on whichever thread compiles; both appends are single
    calls, and readers copy before they loop."""
    if event.startswith(_COMPILE_PREFIX):
        span = (event, kw.get("fun_name", ""), start_s, end_s)
        _compile_spans.append(span)
        p = _active
        if p.enabled:
            p.compile_spans.append(span)


# The one process-wide listener (JAX has no scoped one), registered when
# the package is imported.
_monitoring.register_event_time_span_listener(_on_time_span)


def _fun_key(fun_name):
    """A function's name as its trace event gives it: lowering and
    compile events name the module ("jit(run_until)" or "jit_run_until")."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name.removeprefix("jit_")


# ---------------------------------------------------------------------------
# Null profiler: the installed-by-default no-op
# ---------------------------------------------------------------------------


class NullProfiler:
    """Inactive profiler: every hook is a constant-time no-op, except that
    a span still opens a TraceAnnotation (one TraceMe check when no
    `jax.profiler` trace is running)."""

    enabled = False
    sync = False

    def span(self, name, **args):
        return TraceAnnotation(name)

    def add_span(self, name, t0_abs, t1_abs, **args):
        pass

    def transfer(self, nbytes, count=1):
        pass

    def counter_sample(self, values):
        pass


_NULL = NullProfiler()
_active = _NULL


def current():
    """The active profiler (a NullProfiler unless a run installed one)."""
    return _active


def install(prof):
    """Install `prof` as the process-wide active profiler (None/falsy
    restores the no-op).  Returns the now-active profiler."""
    global _active
    _active = prof if prof else _NULL
    return _active


def spanned(name):
    """Decorator: the call runs inside span `name` of the active
    profiler."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with _active.span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


# ---------------------------------------------------------------------------
# The real profiler
# ---------------------------------------------------------------------------


class _Span:
    __slots__ = ("prof", "name", "args", "t0", "annot")

    def __init__(self, prof, name, args):
        self.prof = prof
        self.name = name
        self.args = args
        self.t0 = 0.0
        self.annot = None

    def __enter__(self):
        self.annot = TraceAnnotation(self.name)
        self.annot.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        p = self.prof
        t0 = self.t0
        p.events.append((self.name, t0 - p.t0,
                         time.perf_counter() - t0, self.args))
        self.annot.__exit__(*exc)
        return False


class Profiler:
    """Host-side run profiler.

    sync=True makes the engine's chunk loop block_until_ready after each
    device launch so `device_step` spans measure execution rather than
    async dispatch (full --profile mode).  sync=False records spans
    without extra synchronization -- the cheap mode bench.py uses.

    counters=False keeps the state pytree untouched: the run loops skip
    `ensure_counters`, so the profiler records host-side spans and
    compile events only.  The run server's per-request accounting uses
    this mode -- a served run must stay byte-identical to an unobserved
    one (zero kernelcount delta); events/s still lands via
    `fetch_counters`, which reads the always-present n_events scalar.
    """

    enabled = True

    def __init__(self, sync: bool = True, counters: bool = True):
        self.sync = sync
        self.counters = counters
        self.t0 = time.perf_counter()
        self.t0_wall = time.time()
        self.events = []        # (name, t_rel_s, dur_s, args|None)
        self.transfer_bytes = 0
        self.transfer_count = 0
        # compile_spans() entries that ended while this was installed
        self.compile_spans = []
        self.counter_samples = []   # (t_rel_s, {name: value})
        self.kernelcount = None     # tools/kernelcount.py report|None
        self.extra_metrics = {}     # {name: number} via set_metric
        self.flight_rows = []       # drained FlightRecorder rows
        self.flight_summary = None  # aggregate `mesh` section|None
        self.scope_flow_rows = []   # drained FlowScope flow rows
        self.scope_link_rows = []   # drained FlowScope link rows
        self.scope_summary = None   # aggregate `net` section|None
        self.lineage_rows = []      # drained LineageDrain span rows
        self.lineage_summary = None  # aggregate `lineage` section|None
        self.digest_summary = None  # aggregate `digest` section|None

    # -- recording hooks ----------------------------------------------------

    def span(self, name, **args):
        """Context manager timing one phase occurrence."""
        return _Span(self, name, args or None)

    def add_span(self, name, t0_abs, t1_abs, **args):
        """Record one phase occurrence from absolute perf_counter()
        endpoints.  The window pipeline (sim.WindowPipeline) and the
        supervisor use this to record a `device_window` span from
        dispatch time to the block_until_ready at the drain point --
        the span is only known after the fact, so a context manager
        cannot time it."""
        self.events.append((name, t0_abs - self.t0,
                            max(0.0, t1_abs - t0_abs), args or None))

    def transfer(self, nbytes, count=1):
        """Account a device->host transfer of `nbytes` over `count`
        fetch round trips."""
        self.transfer_bytes += int(nbytes)
        self.transfer_count += int(count)

    @property
    def compiles(self):
        """(t_rel_s, dur_s) of each backend compile (or persistent-cache
        load) that ended while this profiler was installed."""
        return [(s - self.t0_wall, e - s)
                for ev, _f, s, e in list(self.compile_spans)
                if ev == BACKEND_COMPILE]

    def counter_sample(self, values: dict):
        """Record a snapshot of (already-fetched) device counters."""
        self.counter_samples.append((time.perf_counter() - self.t0,
                                     dict(values)))

    def set_kernelcount(self, report: dict | None):
        """Attach a tools/kernelcount.py report: compiled HLO op/fusion
        counts per engine phase.  Rides metrics()/metrics.json so every
        profiled artifact carries the compiled-graph size alongside the
        wall times (benchdiff gates on it with --kernels)."""
        self.kernelcount = report

    def set_flight(self, rows: list, summary: dict | None):
        """Attach drained flight-recorder rows (FlightDrain.rows) + their
        aggregate.  The aggregate becomes the `mesh` section of
        metrics(); the rows become a simulated-time track (pid 2) in
        trace_events(), so the Chrome trace shows wall time and sim time
        side by side."""
        self.flight_rows = list(rows)
        self.flight_summary = summary

    def set_scope(self, flow_rows: list, link_rows: list,
                  summary: dict | None):
        """Attach drained flowscope rows (ScopeDrain.flow_rows /
        .link_rows) + their aggregate.  The aggregate becomes the `net`
        section of metrics(); the rows become per-sample counter tracks
        on the simulated-time process (pid 2) in trace_events()."""
        self.scope_flow_rows = list(flow_rows)
        self.scope_link_rows = list(link_rows)
        self.scope_summary = summary

    def set_lineage(self, rows: list, summary: dict | None):
        """Attach drained packet-lineage spans (LineageDrain.rows) +
        their aggregate.  The aggregate becomes the `lineage` section of
        metrics(); the rows become a per-packet waterfall track (pid 3)
        in trace_events() -- each traced packet renders as one span from
        its first hop to its last, alongside wall time (pid 1) and sim
        time (pid 2)."""
        self.lineage_rows = list(rows)
        self.lineage_summary = summary

    def set_digest(self, summary: dict | None):
        """Attach the statescope digest aggregate (DigestDrain.summary):
        row/wrap counts and the cadence.  Becomes the `digest` section
        of metrics() -- machine-bound for benchdiff (reported, never
        gated)."""
        self.digest_summary = summary

    def set_metric(self, name: str, value):
        """Attach one named scalar metric (e.g. a measured phase cost
        like stage_emissions_ms) so it rides metrics()/metrics.json and
        tools/benchdiff.py can gate on it across rounds.  None values
        are dropped (a failed measurement must not poison the JSON)."""
        if value is not None:
            self.extra_metrics[name] = value

    # -- aggregation --------------------------------------------------------

    def metrics(self) -> dict:
        """Aggregate recorded data: per-phase percentiles + totals."""
        by_phase = {}
        for name, _t, dur, _a in self.events:
            by_phase.setdefault(name, []).append(dur)
        phases = {}
        for name, durs in sorted(by_phase.items()):
            durs = sorted(durs)
            phases[name] = {
                "count": len(durs),
                "total_s": round(sum(durs), 6),
                "p50_ms": round(_pct(durs, 50) * 1e3, 3),
                "p95_ms": round(_pct(durs, 95) * 1e3, 3),
                "max_ms": round(durs[-1] * 1e3, 3),
            }
        compiles = self.compiles
        by_fun = {}
        for ev, fun, s, e in list(self.compile_spans):
            row = by_fun.setdefault(_fun_key(fun), {})
            kind = ev[len(_COMPILE_PREFIX):].removesuffix("_duration")
            row[kind] = row.get(kind, 0) + 1
            row["total_s"] = round(row.get("total_s", 0.0) + e - s, 6)
        out = {
            "wall_s": round(time.perf_counter() - self.t0, 3),
            "phases": phases,
            "transfers": {"bytes": self.transfer_bytes,
                          "count": self.transfer_count},
            # `functions`: per function, how often each compile phase
            # ran (jaxpr_trace, jaxpr_to_mlir_module, backend_compile)
            # and their seconds -- a second trace of `run_until` is a
            # recompile of the window loop.
            "compile": {"count": len(compiles),
                        "total_s": round(sum(d for _t, d in compiles), 3),
                        "functions": by_fun},
            # Flat aliases for benchdiff gating (tools/benchdiff.py):
            # "compiles" is a graph property (0-tolerance -- a new
            # compile in a sweep means a shape bucket broke), while
            # "compile_ms" is machine-bound wall time.
            "compiles": len(compiles),
            "compile_ms": round(sum(d for _t, d in compiles) * 1e3, 1),
        }
        dev = [(t, t + d) for n, t, d, _a in self.events
               if n in ("device_step", "device_window")]
        if dev:
            # The async-window-pipeline judgment metric: how much of
            # the host-drain wall is hidden under device execution.
            # Sync-mode loops sit near 0% by construction (drains run
            # after block_until_ready, outside every device_step span);
            # the pipeline drives it toward 100% by draining window N
            # while window N+1 executes.  The denominator is the DRAIN
            # wall, not the device wall: a correct pipeline hides all
            # of the (small) drain work inside the (large) device work,
            # and the metric should read ~100% then, however cheap the
            # drains are relative to the launches.
            drains = [(t, t + d) for n, t, d, _a in self.events
                      if n in _HOST_DRAIN_PHASES]
            drain_total = sum(b - a for a, b in _union(drains))
            if drain_total > 0:
                out["host_drain_overlap_pct"] = round(
                    100.0 * _overlap(dev, drains) / drain_total, 2)
            else:
                out["host_drain_overlap_pct"] = 0.0
        if self.counter_samples:
            out["device_counters"] = self.counter_samples[-1][1]
        if self.kernelcount is not None:
            out["kernelcount"] = self.kernelcount
        if self.flight_summary is not None:
            out["mesh"] = self.flight_summary
        if self.scope_summary is not None:
            out["net"] = self.scope_summary
        if self.lineage_summary is not None:
            out["lineage"] = self.lineage_summary
        if self.digest_summary is not None:
            out["digest"] = self.digest_summary
        out.update(self.extra_metrics)
        return out

    # -- artifacts ----------------------------------------------------------

    def trace_events(self) -> list:
        """The run as Chrome trace-event dicts (ts/dur in microseconds)."""
        tids = {}

        def tid(name):
            return tids.setdefault(name, len(tids) + 1)

        evs = []
        for name, t, dur, args in sorted(self.events, key=lambda e: e[1]):
            e = {"name": name, "cat": "run", "ph": "X", "pid": 1,
                 "tid": tid(name), "ts": round(t * 1e6, 3),
                 "dur": round(dur * 1e6, 3)}
            if args:
                e["args"] = args
            evs.append(e)
        for t, dur in self.compiles:
            evs.append({"name": "jit_compile", "cat": "jit", "ph": "X",
                        "pid": 1, "tid": tid("jit_compile"),
                        "ts": round(t * 1e6, 3),
                        "dur": round(dur * 1e6, 3)})
        for t, values in self.counter_samples:
            for k, v in values.items():
                evs.append({"name": k, "cat": "counters", "ph": "C",
                            "pid": 1, "ts": round(t * 1e6, 3),
                            "args": {k: v}})
        meta = [{"name": "thread_name", "ph": "M", "pid": 1, "tid": i,
                 "args": {"name": n}} for n, i in tids.items()]
        if self.flight_rows or self.scope_flow_rows or self.scope_link_rows:
            # Simulated-time track: pid 2's clock is SIM nanoseconds
            # (rendered as trace microseconds), one span per window plus
            # events/routed counter tracks -- wall time (pid 1) and sim
            # time (pid 2) side by side in the same viewer.
            meta.append({"name": "process_name", "ph": "M", "pid": 2,
                         "args": {"name": "simulated time (windows)"}})
            meta.append({"name": "thread_name", "ph": "M", "pid": 2,
                         "tid": 1, "args": {"name": "window"}})
        if self.flight_rows:
            for r in self.flight_rows:
                ts = round(r["t_start"] / 1e3, 3)
                dur = round(max(r["t_end"] - r["t_start"], 1) / 1e3, 3)
                evs.append({"name": "window", "cat": "sim", "ph": "X",
                            "pid": 2, "tid": 1, "ts": ts, "dur": dur,
                            "args": {k: r[k] for k in
                                     ("window", "steps", "events",
                                      "routed", "delivered", "dropped",
                                      "killed")}})
                for k in ("events", "routed"):
                    evs.append({"name": k, "cat": "sim", "ph": "C",
                                "pid": 2, "ts": ts, "args": {k: r[k]}})
        if self.scope_flow_rows:
            # Flowscope counter tracks on the sim-time clock: per-sample
            # aggregate congestion window + worst smoothed RTT.
            agg = {}
            for r in self.scope_flow_rows:
                a = agg.setdefault(r["t"], [0, 0])
                a[0] += r["cwnd"]
                a[1] = max(a[1], r["srtt_ns"])
            for t in sorted(agg):
                ts = round(t / 1e3, 3)
                evs.append({"name": "cwnd_total", "cat": "net", "ph": "C",
                            "pid": 2, "ts": ts,
                            "args": {"cwnd_total": agg[t][0]}})
                evs.append({"name": "srtt_max_us", "cat": "net", "ph": "C",
                            "pid": 2, "ts": ts,
                            "args": {"srtt_max_us":
                                     round(agg[t][1] / 1e3, 1)}})
        if self.scope_link_rows:
            agg = {}
            for r in self.scope_link_rows:
                a = agg.setdefault(r["t"], [0, 0])
                a[0] += r["qdepth"]
                a[1] += r["drops"]
            for t in sorted(agg):
                ts = round(t / 1e3, 3)
                evs.append({"name": "link_qdepth", "cat": "net", "ph": "C",
                            "pid": 2, "ts": ts,
                            "args": {"link_qdepth": agg[t][0]}})
                evs.append({"name": "link_drops", "cat": "net", "ph": "C",
                            "pid": 2, "ts": ts,
                            "args": {"link_drops": agg[t][1]}})
        if self.lineage_rows:
            # Packet-lineage waterfall on the sim-time clock (pid 3):
            # one span per traced packet from its first hop to its last,
            # the hop chain + death reason in args.  Bounded to the
            # first _LINEAGE_TRACK_IDS packets by first-hop time so a
            # high-rate trace cannot bloat trace.json.
            meta.append({"name": "process_name", "ph": "M", "pid": 3,
                         "args": {"name": "packet lineage (spans)"}})
            by_id = {}
            for r in self.lineage_rows:
                by_id.setdefault(r["id"], []).append(r)
            order = sorted(by_id, key=lambda i: by_id[i][0]["t"])
            if len(order) > _LINEAGE_TRACK_IDS:
                order = order[:_LINEAGE_TRACK_IDS]
            for n, pid_ in enumerate(order):
                hops = by_id[pid_]
                t0, t1 = hops[0]["t"], hops[-1]["t"]
                reason = next((h["reason"] for h in hops
                               if h["reason"] != "none"), "none")
                row_tid = (n % 64) + 1
                evs.append({"name": f"pkt {pid_:08x}", "cat": "lineage",
                            "ph": "X", "pid": 3, "tid": row_tid,
                            "ts": round(t0 / 1e3, 3),
                            "dur": round(max(t1 - t0, 1) / 1e3, 3),
                            "args": {"id": pid_,
                                     "chain": "->".join(h["stage"]
                                                        for h in hops),
                                     "reason": reason}})
        return meta + evs

    def write_trace(self, path: str):
        with open(path, "w") as f:
            json.dump({"traceEvents": self.trace_events(),
                       "displayTimeUnit": "ms"}, f)

    def write_metrics(self, path: str, extra: dict | None = None):
        m = self.metrics()
        if extra:
            m.update(extra)
        with open(path, "w") as f:
            json.dump(m, f, indent=2)
        return m

    def summary_table(self) -> str:
        """One-screen end-of-run phase breakdown."""
        m = self.metrics()
        lines = [f"{'phase':<16s} {'count':>7s} {'total_s':>9s} "
                 f"{'p50_ms':>9s} {'p95_ms':>9s} {'max_ms':>9s}"]
        for name, p in m["phases"].items():
            lines.append(f"{name:<16s} {p['count']:>7d} "
                         f"{p['total_s']:>9.3f} {p['p50_ms']:>9.3f} "
                         f"{p['p95_ms']:>9.3f} {p['max_ms']:>9.3f}")
        t = m["transfers"]
        c = m["compile"]
        lines.append(f"transfers: {t['bytes']} bytes in {t['count']} "
                     f"fetches; jit compiles: {c['count']} "
                     f"({c['total_s']:.1f}s); wall: {m['wall_s']:.3f}s")
        dc = m.get("device_counters")
        if dc:
            lines.append("device: " + ", ".join(
                f"{k}={v}" for k, v in dc.items()))
        return "\n".join(lines)


def _pct(sorted_vals, q):
    """Nearest-rank percentile of an ascending list."""
    if not sorted_vals:
        return 0.0
    i = max(0, min(len(sorted_vals) - 1,
                   int(round(q / 100 * len(sorted_vals) + 0.5)) - 1))
    return sorted_vals[i]


# Span names that are host work competing with device launches.  Their
# wall overlap with `device_step` spans is the host_drain_overlap_pct
# metric (the async-window-pipeline yardstick in ROADMAP.md).
_HOST_DRAIN_PHASES = frozenset(
    ("heartbeat", "log_drain", "flight_drain", "scope_drain",
     "lineage_drain", "digest_drain", "progress"))

# Most traced packets rendered as pid-3 waterfall spans in trace.json
# (ordered by first hop); the full span set always lands in spans.jsonl.
_LINEAGE_TRACK_IDS = 256


def _union(intervals):
    """Merge (start, end) intervals into a disjoint ascending list."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _overlap(ivals_a, ivals_b) -> float:
    """Total length of the intersection of two interval sets."""
    ua, ub = _union(ivals_a), _union(ivals_b)
    tot, i, j = 0.0, 0, 0
    while i < len(ua) and j < len(ub):
        lo = max(ua[i][0], ub[j][0])
        hi = min(ua[i][1], ub[j][1])
        if hi > lo:
            tot += hi - lo
        if ua[i][1] <= ub[j][1]:
            i += 1
        else:
            j += 1
    return tot


# ---------------------------------------------------------------------------
# Device-counter helpers (the TraceCounters block on SimState)
# ---------------------------------------------------------------------------


def ensure_counters(state):
    """Return `state` with a TraceCounters block installed (idempotent).
    Changes the state pytree structure, so jitted engine calls recompile
    once for the counted variant."""
    if state.tr is None:
        from .core.state import make_trace_counters
        state = state.replace(tr=make_trace_counters())
    return state


def fetch_counters(state, profiler=None) -> dict:
    """ONE device->host fetch of the telemetry scalars + counter block,
    recorded as a counter sample (and a transfer) on `profiler` (default:
    the active one).  Safe to call whether or not counters are installed.
    """
    import jax

    vals = [state.n_steps, state.n_windows, state.n_events]
    names = ["microsteps", "windows", "events"]
    if state.tr is not None:
        vals += [state.tr.exchanges, state.tr.pkts_exchanged,
                 state.tr.occ_max]
        names += ["exchanges", "pkts_exchanged", "inbox_occ_max"]
    if getattr(state, "nm", None) is not None:
        import jax.numpy as _jnp
        vals += [state.nm.cursor, state.nm.killed,
                 _jnp.sum(state.nm.host_up == 0)]
        names += ["netem_events_applied", "netem_killed",
                  "netem_hosts_down"]
    fetched = jax.device_get(vals)
    out = {n: int(v) for n, v in zip(names, fetched)}
    if state.tr is not None:
        ki = state.inbox.capacity // state.hosts.num_hosts
        out["inbox_occ_frac"] = round(out["inbox_occ_max"] / max(ki, 1), 4)
    p = profiler if profiler is not None else _active
    p.transfer(sum(getattr(v, "nbytes", 8) for v in fetched), count=1)
    p.counter_sample(out)
    return out


# ---------------------------------------------------------------------------
# Flight recorder (the FlightRecorder ring on SimState; core/state.py)
# ---------------------------------------------------------------------------


def ensure_flight_recorder(state, capacity: int = 4096, shards: int = 1,
                           rows: int | None = None):
    """Return `state` with a per-window FlightRecorder ring installed
    (idempotent).  `shards` sizes the src->dst exchange matrices and
    must match the device count of a mesh run (1 for single-device);
    the host count and pool capacity must divide it so the logical
    shard of a host is well defined.  `rows` (the `--flight-rows` CLI
    surface) overrides `capacity`: long runs whose drain/checkpoint
    cadence exceeds 4096 windows size the ring up instead of losing
    per-window resolution to wrap (the FlightDrain caveat).

    The ring cursor (`fr.total`) seeds from `state.n_windows`, so the
    row index FlightDrain stamps into windows.jsonl is the GLOBAL
    monotonically increasing window counter of the simulation -- the
    same index `replay --window K` addresses -- even when the recorder
    is installed on a mid-run state."""
    if state.fr is not None:
        return state
    import jax.numpy as _jnp
    from .core.state import I64, make_flight_recorder
    if rows is not None:
        capacity = int(rows)
    if capacity < 1:
        raise ValueError(
            f"ensure_flight_recorder: ring capacity must be positive, "
            f"got {capacity}")
    h = int(state.hosts.num_hosts)
    if shards < 1 or h % shards or int(state.pool.capacity) % shards:
        raise ValueError(
            f"ensure_flight_recorder: shards={shards} must divide the "
            f"host count ({h}) and pool capacity "
            f"({int(state.pool.capacity)}); pad the world to the mesh "
            f"first (parallel.pad_world_to_mesh)")
    fr = make_flight_recorder(capacity, shards)
    fr = fr.replace(total=_jnp.asarray(state.n_windows, I64))
    return state.replace(fr=fr)


def _open_sink(path_or_file, mode: str = "w"):
    """(file, owned) from a drain's output target.

    A str path opens a file the drain OWNS (close() closes it).  An
    already-open file-like (anything with .write) is SHARED -- ensemble
    runs hand one windows.jsonl/flows.jsonl/... to W per-world drains,
    whose rows interleave with a "world" column telling them apart --
    and close() leaves it open for the owner (sim.run_ensemble)."""
    if path_or_file is None:
        return None, False
    if hasattr(path_or_file, "write"):
        return path_or_file, False
    return open(path_or_file, mode), True


class ReplayDivergence(RuntimeError):
    """A replayed trajectory produced a flight-recorder row that differs
    bitwise from the original run's windows.jsonl record.  Raised by
    FlightDrain when draining with `verify_against`; carries the first
    diverging global window index and the differing fields."""

    def __init__(self, window: int, got: dict, want: dict):
        self.window = int(window)
        self.got = got
        self.want = want
        fields = sorted(k for k in want
                        if k in got and got[k] != want[k])
        self.fields = fields
        super().__init__(
            f"replay diverged at window {window}: field(s) "
            f"{', '.join(fields) or '<missing row>'} differ from the "
            f"recorded windows.jsonl (triage: tools/parse.py replaydiff)")


class FlightDrain:
    """Host-side drain of the flight recorder: fetches new rows at chunk
    boundaries (one scalar probe + one bulk fetch only when rows are
    new -- riding the existing sync points, no extra per-window syncs),
    appends them to ``windows.jsonl`` when a path is given, and keeps
    them for Profiler.set_flight / aggregation.

    Every row is stamped with its GLOBAL window index (`"window"`: the
    simulation's monotonic window counter, which ensure_flight_recorder
    seeds the ring cursor from) -- the address `replay --window K`
    restores to.  Ring wrap between drains loses the oldest rows;
    lifetime totals are still exact because the recorder accumulates
    wrap-proof sums on the device (`ex_*_sum`) -- the drain reports
    `rows_lost` so a summary reader knows row-derived aggregates are
    partial.  CAVEAT: past `capacity` (default 4096) windows between
    drains the window INDEX stays exact but the per-window RESOLUTION
    is gone -- wrapped windows have no row, so a replay cross-check (and
    `replay --window K` targeting) can only address windows that
    survived into windows.jsonl; checkpoint cadences that drain at
    least every 4096 windows keep the record gap-free.

    `start` skips rows already drained in an earlier life of the ring:
    a replay restores a checkpoint whose ring carries the original
    run's rows below `fr.total`; starting the drain there emits only
    windows the replay itself produced, numbered exactly as the
    original run numbered them.

    `verify_against` is the replay-verify hook: a {window: row} mapping
    of the ORIGINAL run's windows.jsonl records.  Each drained row that
    has an original counterpart is compared bitwise (full dict
    equality, exchange matrices included); the first mismatch raises
    ReplayDivergence naming the window -- divergence is a loud,
    window-pinpointed error, never silent garbage.

    `mode="a"` appends to an existing windows.jsonl instead of
    truncating it: auto-resume (supervise.py) trims the file to rows
    below the resume checkpoint's window, then appends the re-recorded
    (bitwise-identical) rows from there, keeping one contiguous record
    across process lifetimes.

    `world` stamps every row with an ensemble world id (the drain-layer
    world-column convention, docs/ensemble.md); `path` may be an
    already-open shared file (see _open_sink)."""

    def __init__(self, path=None, start: int = 0,
                 verify_against: dict | None = None, mode: str = "w",
                 world: int | None = None):
        self.path = path
        self.rows = []
        self.rows_lost = 0
        self.shards = None      # learned from the ring at first drain
        self.capacity = None
        self._last = int(start)
        self.verify_against = verify_against
        self.verified = 0       # rows that matched an original record
        self.world = world
        self._f, self._own = _open_sink(path, mode)

    def drain(self, state, profiler=None) -> int:
        """Fetch rows appended since the last drain; returns how many."""
        fr = getattr(state, "fr", None)
        if fr is None:
            return 0
        import jax
        p = profiler if profiler is not None else _active
        with p.span("flight_drain"):
            total = int(jax.device_get(fr.total))
            p.transfer(8, count=1)
            new = total - self._last
            if new <= 0:
                return 0
            self.shards = fr.n_shards
            self.capacity = c = fr.capacity
            arrs = jax.device_get((fr.win_start, fr.win_end, fr.steps,
                                   fr.events, fr.routed, fr.delivered,
                                   fr.dropped, fr.killed, fr.ex_cnt,
                                   fr.ex_bytes))
            p.transfer(sum(a.nbytes for a in arrs), count=1)
            if new > c:
                # Ring wrap between drains: rows [self._last, total - c)
                # are gone.  When this drain is verifying a replay, a
                # wrapped-away verify target can never be checked --
                # fail loudly rather than silently skipping it; if every
                # verify target survived the wrap, verify the surviving
                # suffix but say so explicitly.
                if self.verify_against is not None:
                    gone = [w for w in self.verify_against
                            if self._last <= w < total - c]
                    if gone:
                        self._last = total
                        raise RuntimeError(
                            f"flight-recorder ring wrapped over "
                            f"{len(gone)} window(s) awaiting replay "
                            f"verification (first {min(gone)}, last "
                            f"{max(gone)}): the gap between drains "
                            f"exceeded the ring capacity ({c}); rerun "
                            f"with a larger recorder or a drain/"
                            f"checkpoint cadence under {c} windows")
                    import warnings
                    warnings.warn(
                        f"flight-recorder ring wrapped during a "
                        f"verified replay ({new - c} row(s) lost, none "
                        f"of them verify targets); only the surviving "
                        f"suffix of windows.jsonl is being verified",
                        RuntimeWarning, stacklevel=2)
                self.rows_lost += new - c
                start = total - c
            else:
                start = self._last
            ws, we, steps, ev, rt, dl, dp, kl, xc, xb = arrs
            for w in range(start, total):
                k = w % c
                row = {"window": w,
                       **({} if self.world is None
                          else {"world": self.world}),
                       "t_start": int(ws[k]), "t_end": int(we[k]),
                       "steps": int(steps[k]), "events": int(ev[k]),
                       "routed": int(rt[k]), "delivered": int(dl[k]),
                       "dropped": int(dp[k]), "killed": int(kl[k]),
                       "ex_cnt": xc[k].tolist(),
                       "ex_bytes": xb[k].tolist()}
                self.rows.append(row)
                if self.verify_against is not None and \
                        w in self.verify_against:
                    want = self.verify_against[w]
                    if row != want:
                        self._last = total
                        raise ReplayDivergence(w, row, want)
                    self.verified += 1
                if self._f is not None:
                    self._f.write(json.dumps(row) + "\n")
            if self._f is not None:
                self._f.flush()
            self._last = total
            return new

    def close(self):
        if self._f is not None:
            if self._own:
                self._f.close()
            self._f = None

    def summary(self, state=None, n_devices: int = 1) -> dict:
        """Aggregate the drained rows into the `mesh` metrics section.
        Pass the final state to include the device-side wrap-proof
        exchange totals (exact even when rows were lost to wrap)."""
        d = self.shards or 1
        agg = {k: sum(r[k] for r in self.rows)
               for k in ("steps", "events", "routed", "delivered",
                         "dropped", "killed")}
        mat_c = [[0] * d for _ in range(d)]
        mat_b = [[0] * d for _ in range(d)]
        for r in self.rows:
            for i in range(d):
                for j in range(d):
                    mat_c[i][j] += r["ex_cnt"][i][j]
                    mat_b[i][j] += r["ex_bytes"][i][j]
        if state is not None and getattr(state, "fr", None) is not None:
            import jax
            mat_c, mat_b = (a.tolist() for a in jax.device_get(
                (state.fr.ex_cnt_sum, state.fr.ex_bytes_sum)))
        out = {
            "n_devices": n_devices,
            "recorder": {"capacity": self.capacity, "shards": d},
            "windows": self._last,
            "rows_lost": self.rows_lost,
        }
        out.update(agg)
        out["exchange"] = {
            "movers": sum(map(sum, mat_c)),
            "bytes": sum(map(sum, mat_b)),
            "matrix_movers": mat_c,
            "matrix_bytes": mat_b,
        }
        if self.rows:
            sim_s = (self.rows[-1]["t_end"]
                     - self.rows[0]["t_start"]) / 1e9
            if sim_s > 0:
                out["windows_per_sim_s"] = round(len(self.rows) / sim_s, 3)
        return out


# ---------------------------------------------------------------------------
# Invariant sentinel (the SentinelBlock on SimState; core/state.py)
# ---------------------------------------------------------------------------


def ensure_sentinel(state):
    """Return `state` with the per-window invariant sentinel installed
    (idempotent).  The block is a handful of replicated scalars, so it
    needs no shard sizing -- the same install works single-device and
    on any mesh.  `last_we` seeds from the current sim time so a
    mid-run install never trips the monotonicity probe on its first
    window."""
    if state.sentinel is not None:
        return state
    import jax.numpy as _jnp
    from .core.state import I64, make_sentinel
    sn = make_sentinel()
    sn = sn.replace(last_we=_jnp.asarray(state.now, I64))
    return state.replace(sentinel=sn)


def sentinel_classes(bits: int) -> list:
    """The violation-class names set in a SENTINEL_* bitmask."""
    from .core.state import SENTINEL_CLASS_NAMES
    return [name for bit, name in sorted(SENTINEL_CLASS_NAMES.items())
            if int(bits) & bit]


class SentinelViolation(RuntimeError):
    """A device-side invariant probe fired: the simulation violated
    packet conservation, window-time monotonicity, a stage/queue/cursor
    bound, or finiteness of its float islands.  Raised by
    SentinelDrain.check(); carries the full sentinel row (the same dict
    the supervisor stamps into crash.json).  Ensemble rows name the
    offending world and point the replay hint at `--world K`."""

    def __init__(self, row: dict):
        self.row = row
        names = sentinel_classes(row.get("violations", 0))
        w = row.get("world")
        where = f" in world {w}" if w is not None else ""
        wflag = f" --world {w}" if w is not None else ""
        super().__init__(
            f"sentinel violation ({'+'.join(names) or 'unknown'}){where} "
            f"first at window {row.get('first_bad_window')} "
            f"(t={row.get('first_bad_t')} ns); replay it with "
            f"`shadow1-tpu replay{wflag} --window "
            f"{row.get('first_bad_window')}`"
        )


class SentinelDrain:
    """Host-side drain of the invariant sentinel: ONE bulk fetch of the
    block's scalars at chunk boundaries (riding the existing sync
    points, like FlightDrain).  `drain` returns the current row;
    `check` additionally raises SentinelViolation the moment any sticky
    violation bit is set, which is what the supervisor catches.

    Stacked states drain per world (the sentinel block vmaps like any
    other leaf, so the sticky bits/first_bad_window/first_bad_t are
    already per-world): the returned row aggregates -- checks summed,
    violation bits OR'd -- and carries the earliest-failing world's
    coordinates plus `world` / `bad_worlds` / `worlds` (one sub-row per
    offending world), which is what the supervisor's quarantine rung
    and crash.json consume."""

    _FIELDS = ("checks", "violations", "last_violation",
               "first_bad_window", "first_bad_t", "last_we",
               "resid_low", "resid_high", "nonfinite")

    def __init__(self):
        self.row = None

    @staticmethod
    def _row(checks, bits, last, fw, ft, lwe, rlo, rhi, nf):
        return {
            "checks": checks,
            "violations": bits,
            "classes": sentinel_classes(bits),
            "last_violation": last,
            "first_bad_window": fw,
            "first_bad_t": ft,
            "last_we": lwe,
            "resid_low": rlo,
            "resid_high": rhi,
            "nonfinite": nf,
        }

    def drain(self, state, profiler=None):
        sn = getattr(state, "sentinel", None)
        if sn is None:
            return None
        import jax
        p = profiler if profiler is not None else _active
        with p.span("sentinel_drain"):
            vals = jax.device_get((sn.checks, sn.violations,
                                   sn.last_violation, sn.first_bad_window,
                                   sn.first_bad_t, sn.last_we,
                                   sn.resid_low, sn.resid_high,
                                   sn.nonfinite))
            p.transfer(8 * len(vals), count=1)
        import numpy as np
        if np.ndim(vals[0]) == 0:
            self.row = self._row(*map(int, vals))
            return self.row
        arrs = [np.asarray(v).ravel() for v in vals]
        n = arrs[0].size
        per = [self._row(*(int(a[k]) for a in arrs)) for k in range(n)]
        bad = [k for k in range(n) if per[k]["violations"]]
        # The headline coordinates are the earliest failure's (smallest
        # first_bad_t, ties to the lowest world index).
        lead = min(bad, key=lambda k: (per[k]["first_bad_t"], k)) \
            if bad else None
        row = dict(per[lead if lead is not None else 0])
        bits = 0
        for r in per:
            bits |= r["violations"]
        row.update({
            "checks": sum(r["checks"] for r in per),
            "violations": bits,
            "classes": sentinel_classes(bits),
            "world": lead,
            "n_worlds": n,
            "bad_worlds": bad,
            "worlds": [dict(per[k], world=k) for k in bad],
        })
        self.row = row
        return self.row

    def check(self, state, profiler=None):
        """Drain; raise SentinelViolation if any probe has ever fired."""
        row = self.drain(state, profiler)
        if row is not None and row["violations"]:
            raise SentinelViolation(row)
        return row


# ---------------------------------------------------------------------------
# Statescope digests (the DigestBlock on SimState; core/state.py)
# ---------------------------------------------------------------------------


def ensure_digests(state, every: int = 1, capacity: int = 4096,
                   shards: int = 1):
    """Return `state` with a per-window DigestBlock installed
    (idempotent).  `every` is the cadence in windows (digest every Nth
    window close); `shards` sizes the per-logical-shard checksum
    columns and must match the device count of a mesh run (1 for
    single-device); the host count, pool capacity, and inbox capacity
    must divide it so element ownership is well defined.

    Rows stamp the GLOBAL window index (taken from `state.n_windows` at
    record time), so a mid-run install digests under the same indices
    an always-on block would use -- diff aligns streams by that index."""
    if state.dg is not None:
        return state
    from .core.state import make_digest
    every = int(every)
    if every < 1:
        raise ValueError(
            f"ensure_digests: cadence must be a positive window count, "
            f"got {every}")
    if capacity < 1:
        raise ValueError(
            f"ensure_digests: ring capacity must be positive, "
            f"got {capacity}")
    h = int(state.hosts.num_hosts)
    if shards < 1 or h % shards or int(state.pool.capacity) % shards \
            or int(state.inbox.capacity) % shards:
        raise ValueError(
            f"ensure_digests: shards={shards} must divide the host "
            f"count ({h}), pool capacity ({int(state.pool.capacity)}), "
            f"and inbox capacity ({int(state.inbox.capacity)}); pad the "
            f"world to the mesh first (parallel.pad_world_to_mesh)")
    return state.replace(dg=make_digest(capacity, shards, every))


class DigestDrain:
    """Host-side drain of the digest ring: one cursor probe per drain, a
    bulk fetch only when new rows exist (the FlightDrain recipe), each
    row appended to ``digests.jsonl``:

        {"window": 41, "t_end": 120000000,
         "sums": {"pool": [..D ints..], ..per DIGEST_GROUPS..}}

    Ring wrap between drains loses the oldest rows (`rows_lost`); size
    the ring or the cadence so the gap between drains stays under
    capacity when a complete record matters (the FlightDrain caveat).

    `world` stamps every row with an ensemble world id; `path` may be
    an already-open shared file (see _open_sink)."""

    def __init__(self, path=None, start: int = 0,
                 mode: str = "w", world: int | None = None):
        self.path = path
        self.rows = []
        self.rows_lost = 0
        self.shards = None
        self.capacity = None
        self.every = None
        self.world = world
        self._last = int(start)
        self._f, self._own = _open_sink(path, mode)

    def drain(self, state, profiler=None) -> int:
        """Fetch rows appended since the last drain; returns how many."""
        dg = getattr(state, "dg", None)
        if dg is None:
            return 0
        import jax
        from .core.state import DIGEST_GROUPS
        p = profiler if profiler is not None else _active
        with p.span("digest_drain"):
            total = int(jax.device_get(dg.total))
            p.transfer(8, count=1)
            new = total - self._last
            if new <= 0:
                return 0
            self.shards = dg.n_shards
            self.capacity = c = dg.capacity
            win, t_end, sums, every = jax.device_get(
                (dg.win, dg.t_end, dg.sums, dg.every))
            self.every = int(every)
            p.transfer(win.nbytes + t_end.nbytes + sums.nbytes, count=1)
            if new > c:
                self.rows_lost += new - c
                start = total - c
            else:
                start = self._last
            for r in range(start, total):
                k = r % c
                row = {"window": int(win[k]),
                       **({} if self.world is None
                          else {"world": self.world}),
                       "t_end": int(t_end[k]),
                       "sums": {g: sums[k, gi].tolist()
                                for gi, g in enumerate(DIGEST_GROUPS)}}
                self.rows.append(row)
                if self._f is not None:
                    self._f.write(json.dumps(row) + "\n")
            if self._f is not None:
                self._f.flush()
            self._last = total
            return new

    def close(self):
        if self._f is not None:
            if self._own:
                self._f.close()
            self._f = None

    def summary(self) -> dict:
        """Aggregate for the `digest` metrics section."""
        out = {
            "rows": len(self.rows),
            "rows_lost": self.rows_lost,
            "every": self.every,
            "shards": self.shards or 1,
        }
        if self.rows:
            out["first_window"] = self.rows[0]["window"]
            out["last_window"] = self.rows[-1]["window"]
        return out


# ---------------------------------------------------------------------------
# Flowscope (the FlowScope sampling block on SimState; core/state.py)
# ---------------------------------------------------------------------------


_SCOPE_UNITS = {"ns": 1, "us": 1_000, "ms": 1_000_000, "s": 1_000_000_000}


def parse_scope_spec(spec: str) -> dict:
    """Parse a ``--scope`` spec: ``flows[,links][:interval]``.

    The ring list picks what to sample (`flows`, `links`, or both,
    comma-separated, any order); the optional `:interval` suffix sets
    the sim-time cadence (`50ms`, `2s`, `500us`, or a bare nanosecond
    count; default 100ms).  Returns ensure_flowscope kwargs."""
    rings, _, ivl = spec.partition(":")
    names = [r.strip() for r in rings.split(",") if r.strip()]
    bad = [n for n in names if n not in ("flows", "links")]
    if bad or not names:
        raise ValueError(
            f"--scope: unknown ring(s) {bad or ['<empty>']} in {spec!r} "
            f"(expected flows[,links][:interval])")
    out = {"flows": "flows" in names, "links": "links" in names}
    if ivl:
        ivl = ivl.strip()
        unit = 1
        for suffix, mult in sorted(_SCOPE_UNITS.items(),
                                   key=lambda kv: -len(kv[0])):
            if ivl.endswith(suffix):
                unit, ivl = mult, ivl[:-len(suffix)]
                break
        try:
            ns = int(float(ivl) * unit)
        except ValueError:
            raise ValueError(
                f"--scope: bad interval {spec.partition(':')[2]!r} "
                f"(expected e.g. 100ms, 2s, 500us, or nanoseconds)")
        if ns < 1:
            raise ValueError(f"--scope: interval must be positive, got "
                             f"{spec.partition(':')[2]!r}")
        out["interval_ns"] = ns
    return out


def ensure_flowscope(state, flow_capacity: int = 1 << 16,
                     link_capacity: int = 1 << 14,
                     interval_ns: int = 100_000_000, shards: int = 1,
                     flows: bool = True, links: bool = True):
    """Return `state` with a FlowScope sampling block installed
    (idempotent).  `shards` must match the device count of a mesh run
    (1 for single-device) and divide the host count; install AFTER mesh
    padding, like the flight recorder."""
    if state.scope is not None:
        return state
    from .core.state import make_flowscope
    h = int(state.hosts.num_hosts)
    if shards < 1 or h % shards:
        raise ValueError(
            f"ensure_flowscope: shards={shards} must divide the host "
            f"count ({h}); pad the world to the mesh first "
            f"(parallel.pad_world_to_mesh)")
    return state.replace(scope=make_flowscope(
        flow_capacity=flow_capacity, link_capacity=link_capacity,
        interval_ns=interval_ns, shards=shards, flows=flows, links=links))


_FLOW_FIELDS = ("time", "host", "slot", "peer", "cwnd", "ssthresh",
                "srtt", "inflight", "retx", "acked", "sent", "recv")
_LINK_FIELDS = ("time", "host", "tx", "rx", "qdepth", "cap", "drops")


class ScopeDrain:
    """Host-side drain of the flowscope rings: fetches new rows at chunk
    boundaries (one cursor probe, bulk fetch only when rows are new --
    the FlightDrain pattern), merges per-shard ring segments into global
    sim-time order (the LogDrain pattern), and appends them to
    ``flows.jsonl``/``links.jsonl`` when paths are given.

    Row counters (acked/sent/recv/retx, tx/rx/drops) are CUMULATIVE
    lifetime values sampled from the socket/host tables, so a ring wrap
    between drains loses time resolution, never totals: the newest
    surviving row per flow/host still carries the exact lifetime sums.
    The drain derives per-row delivered-rate (`rate_Bps`) host-side from
    consecutive samples of the same flow.

    `real_hosts` drops link rows of padded hosts (global id >= the
    count; padding appends hosts at the end) so a mesh/bucket-padded
    run reports the same links as the exact-size world -- the same
    contract Tracker heartbeats keep by only writing named hosts.
    Padded hosts never open sockets, so flow rows need no filter.

    `world` stamps every row with an ensemble world id; the paths may
    be already-open shared files (see _open_sink)."""

    def __init__(self, flows_path=None,
                 links_path=None,
                 real_hosts: int | None = None,
                 world: int | None = None):
        self.real_hosts = real_hosts
        self.world = world
        self.flow_rows = []
        self.link_rows = []
        self.flow_rows_lost = 0
        self.link_rows_lost = 0
        self.interval_ns = None     # learned from the block at first drain
        self.samples = 0
        self.shards = None
        self._last = {}             # ring prefix -> [shards] cursors
        self._wrap_lost = {}        # ring prefix -> rows lost to wrap
        self._prev = {}             # flow key -> (t, acked) for rate_Bps
        self._ff, self._own_ff = _open_sink(flows_path)
        self._lf, self._own_lf = _open_sink(links_path)

    def drain(self, state, profiler=None) -> int:
        """Fetch rows appended since the last drain; returns how many."""
        scope = getattr(state, "scope", None)
        if scope is None:
            return 0
        import jax
        import numpy as np
        p = profiler if profiler is not None else _active
        with p.span("scope_drain"):
            probe = jax.device_get((scope.interval, scope.samples,
                                    scope.f_total, scope.f_lost,
                                    scope.l_total, scope.l_lost))
            p.transfer(sum(getattr(a, "nbytes", 8) for a in probe),
                       count=1)
            self.interval_ns = int(probe[0])
            self.samples = int(probe[1])
            ft, fl, lt, ll = (np.atleast_1d(np.asarray(a, np.int64))
                              for a in probe[2:])
            self.shards = ft.shape[0]
            n = 0
            if scope.sample_flows:
                n += self._drain_ring(scope, "f", _FLOW_FIELDS, ft, p,
                                      self._flow_row, self.flow_rows,
                                      self._ff)
                self.flow_rows_lost = int(fl.sum()) \
                    + self._wrap_lost.get("f", 0)
            if scope.sample_links:
                n += self._drain_ring(scope, "l", _LINK_FIELDS, lt, p,
                                      self._link_row, self.link_rows,
                                      self._lf)
                self.link_rows_lost = int(ll.sum()) \
                    + self._wrap_lost.get("l", 0)
            return n

    def _drain_ring(self, scope, prefix, fields, tot_a, p, mk_row,
                    rows, f) -> int:
        import jax
        import numpy as np
        shards = tot_a.shape[0]
        last = self._last.setdefault(prefix, np.zeros(shards, np.int64))
        total = int(tot_a.sum())
        if total == int(last.sum()):
            return 0
        arrs = jax.device_get(tuple(
            getattr(scope, f"{prefix}_{name}") for name in fields))
        p.transfer(sum(a.nbytes for a in arrs), count=1)
        per = arrs[0].shape[0] // shards
        parts = []
        for s in range(shards):
            total_s = int(tot_a[s])
            ns = total_s - int(last[s])
            if ns <= 0:
                continue
            if ns > per:
                self._wrap_lost[prefix] = \
                    self._wrap_lost.get(prefix, 0) + ns - per
                start = total_s - per
            else:
                start = int(last[s])
            parts.append(s * per + (np.arange(start, total_s) % per))
            last[s] = total_s
        if not parts:
            return 0
        idx = np.concatenate(parts)
        order = np.argsort(arrs[0][idx], kind="stable")
        n = 0
        for k in idx[order]:
            row = mk_row(fields, [a[k] for a in arrs])
            if prefix == "l" and self.real_hosts is not None \
                    and row["host"] >= self.real_hosts:
                continue
            if self.world is not None:
                row = {"world": self.world, **row}
            rows.append(row)
            if f is not None:
                f.write(json.dumps(row) + "\n")
            n += 1
        if f is not None:
            f.flush()
        return n

    def _flow_row(self, fields, vals) -> dict:
        v = dict(zip(fields, (int(x) for x in vals)))
        row = {"t": v["time"], "host": v["host"], "slot": v["slot"],
               "peer": v["peer"], "cwnd": v["cwnd"],
               "ssthresh": v["ssthresh"], "srtt_ns": v["srtt"],
               "inflight": v["inflight"], "retx": v["retx"],
               "acked": v["acked"], "sent": v["sent"], "recv": v["recv"]}
        key = (v["host"], v["slot"], v["peer"])
        prev = self._prev.get(key)
        rate = 0.0
        if prev is not None:
            dt, da = row["t"] - prev[0], row["acked"] - prev[1]
            if dt > 0 and da > 0:
                rate = da / dt * 1e9
        self._prev[key] = (row["t"], row["acked"])
        row["rate_Bps"] = round(rate, 1)
        return row

    def _link_row(self, fields, vals) -> dict:
        v = dict(zip(fields, (int(x) for x in vals)))
        return {"t": v["time"], "host": v["host"], "tx": v["tx"],
                "rx": v["rx"], "qdepth": v["qdepth"],
                "cap_Bps": v["cap"], "drops": v["drops"]}

    def close(self):
        for f, own in ((self._ff, self._own_ff), (self._lf, self._own_lf)):
            if f is not None and own:
                f.close()
        self._ff = self._lf = None

    def summary(self) -> dict:
        """Aggregate the drained rows into the `net` metrics section.
        Totals come from the newest row per flow/host (the counters are
        cumulative), so they survive ring wraps between drains."""
        out = {"interval_ns": self.interval_ns, "samples": self.samples,
               "shards": self.shards or 1}
        fin_f = {}
        for r in self.flow_rows:
            fin_f[(r["host"], r["slot"], r["peer"])] = r
        if self.flow_rows or self._ff is not None:
            out["flows"] = {
                "rows": len(self.flow_rows),
                "rows_lost": self.flow_rows_lost,
                "flows_seen": len(fin_f),
                "bytes_acked": sum(r["acked"] for r in fin_f.values()),
                "bytes_sent": sum(r["sent"] for r in fin_f.values()),
                "retransmit_segs": sum(r["retx"] for r in fin_f.values()),
            }
        fin_l = {}
        for r in self.link_rows:
            fin_l[r["host"]] = r
        if self.link_rows or self._lf is not None:
            out["links"] = {
                "rows": len(self.link_rows),
                "rows_lost": self.link_rows_lost,
                "hosts_seen": len(fin_l),
                "bytes_forwarded": sum(r["tx"] for r in fin_l.values()),
                "drops": sum(r["drops"] for r in fin_l.values()),
            }
        return out


# ---------------------------------------------------------------------------
# Packet lineage (sampled per-packet span tracing; docs/observability.md)
# ---------------------------------------------------------------------------


def parse_lineage_rate(spec) -> float:
    """Parse a ``--trace-packets`` / ``run(lineage=...)`` rate spec.

    Accepts a float string (``"0.01"``), a percentage (``"1%"``), the
    word ``"all"`` (rate 1.0), or a plain number.  The rate is a
    sampling PROBABILITY in (0, 1]; rates above 1 are an error rather
    than a silent clamp so a fat-fingered ``--trace-packets 10``
    (meant as a percent) fails loudly."""
    if isinstance(spec, (int, float)) and not isinstance(spec, bool):
        rate = float(spec)
    else:
        s = str(spec).strip().lower()
        if s == "all":
            return 1.0
        try:
            if s.endswith("%"):
                rate = float(s[:-1]) / 100.0
            else:
                rate = float(s)
        except ValueError:
            raise ValueError(
                f"--trace-packets: bad rate {spec!r} (expected a "
                f"probability like 0.01, a percentage like 1%, or 'all')")
    if not (0.0 < rate <= 1.0):
        raise ValueError(
            f"--trace-packets: rate must be in (0, 1], got {rate!r} "
            f"(use e.g. 0.01 for one packet in a hundred)")
    return rate


def ensure_lineage(state, rate: float = 0.01, capacity: int = 1 << 16,
                   shards: int = 1):
    """Return `state` with the packet-lineage tracer installed
    (idempotent).  `rate` is the sampling probability (a seeded,
    deterministic function of (src host, emission counter), so every
    device count -- and a replay -- samples the SAME packets);
    `capacity` sizes the span ring (rounded up to a multiple of
    `shards`).  `shards` must match the device count of a mesh run and
    divide the host count, pool capacity, and inbox capacity; install
    AFTER mesh padding, like the flight recorder and flowscope."""
    if state.lineage is not None:
        return state
    from .core.state import make_lineage
    h = int(state.hosts.num_hosts)
    pc, ic = int(state.pool.capacity), int(state.inbox.capacity)
    if shards < 1 or h % shards or pc % shards or ic % shards:
        raise ValueError(
            f"ensure_lineage: shards={shards} must divide the host count "
            f"({h}), pool capacity ({pc}) and inbox capacity ({ic}); pad "
            f"the world to the mesh first (parallel.pad_world_to_mesh)")
    return state.replace(lineage=make_lineage(
        pc, ic, rate=rate, capacity=capacity, shards=shards))


_SPAN_FIELDS = ("s_time", "s_id", "s_host", "s_stage", "s_reason")


class LineageDrain:
    """Host-side drain of the lineage span ring: fetches new rows at
    chunk boundaries (one scalar probe, bulk fetch only when rows are
    new -- the FlightDrain pattern), merges per-shard ring segments
    into global sim-time order (the ScopeDrain pattern), and appends
    them to ``spans.jsonl`` when a path is given.

    Each row is one hop of one traced packet's life story:
    ``{"t", "id", "host", "stage", "reason"}`` with `stage` one of
    emit/stage/tx/link/exchange/deliver and `reason` naming why a
    packet died at that hop (qdisc_overflow, loss, link_down,
    partition, host_down, ack_shed, pool_overflow; "none" for hops
    that succeeded).  Ring wrap between drains loses the OLDEST
    pending rows (append-side policy: the ring keeps the first
    `capacity` rows per drain interval and counts the rest into
    `lineage.lost`); `spans_lost` in the summary makes the gap
    visible, and lifetime counters (`n_assigned`, the drop totals the
    drained rows carry) stay exact.

    `world` stamps every row with an ensemble world id; `spans_path`
    may be an already-open shared file (see _open_sink)."""

    def __init__(self, spans_path=None, world: int | None = None):
        self.rows = []
        self.rows_lost = 0
        self.n_assigned = 0
        self.rate = None            # learned from the block at first drain
        self.shards = None
        self.world = world
        self._last = None           # [shards] drained-cursor array
        self._wrap_lost = 0
        self._f, self._own = _open_sink(spans_path)

    def drain(self, state, profiler=None) -> int:
        """Fetch span rows appended since the last drain; returns how
        many.  Rides existing sync points -- call at chunk boundaries."""
        ln = getattr(state, "lineage", None)
        if ln is None:
            return 0
        import jax
        import numpy as np
        from .core.state import LREASON_NAMES, SPAN_STAGE_NAMES
        p = profiler if profiler is not None else _active
        with p.span("lineage_drain"):
            probe = jax.device_get((ln.rate_x1p32, ln.n_assigned,
                                    ln.total, ln.lost))
            p.transfer(sum(getattr(a, "nbytes", 8) for a in probe),
                       count=1)
            self.rate = (int(probe[0]) + 1) / 4294967296.0
            self.n_assigned = int(probe[1])
            tot = np.atleast_1d(np.asarray(probe[2], np.int64))
            lost = np.atleast_1d(np.asarray(probe[3], np.int64))
            self.shards = tot.shape[0]
            self.rows_lost = int(lost.sum()) + self._wrap_lost
            if self._last is None:
                self._last = np.zeros(self.shards, np.int64)
            if int(tot.sum()) == int(self._last.sum()):
                return 0
            arrs = jax.device_get(tuple(
                getattr(ln, name) for name in _SPAN_FIELDS))
            p.transfer(sum(a.nbytes for a in arrs), count=1)
            per = arrs[0].shape[0] // self.shards
            parts = []
            for s in range(self.shards):
                total_s = int(tot[s])
                ns = total_s - int(self._last[s])
                if ns <= 0:
                    continue
                if ns > per:
                    self._wrap_lost += ns - per
                    self.rows_lost += ns - per
                    start = total_s - per
                else:
                    start = int(self._last[s])
                parts.append(s * per + (np.arange(start, total_s) % per))
                self._last[s] = total_s
            if not parts:
                return 0
            idx = np.concatenate(parts)
            order = np.argsort(arrs[0][idx], kind="stable")
            n = 0
            for k in idx[order]:
                row = {**({} if self.world is None
                          else {"world": self.world}),
                       "t": int(arrs[0][k]), "id": int(arrs[1][k]),
                       "host": int(arrs[2][k]),
                       "stage": SPAN_STAGE_NAMES.get(
                           int(arrs[3][k]), str(int(arrs[3][k]))),
                       "reason": LREASON_NAMES.get(
                           int(arrs[4][k]), str(int(arrs[4][k])))}
                self.rows.append(row)
                if self._f is not None:
                    self._f.write(json.dumps(row) + "\n")
                n += 1
            if self._f is not None:
                self._f.flush()
            return n

    def close(self):
        if self._f is not None and self._own:
            self._f.close()
        self._f = None

    def summary(self) -> dict:
        """Aggregate the drained spans into the `lineage` metrics
        section: span/ID counts, the drop-reason leaderboard, and how
        many traced packets reached delivery."""
        ids = set()
        delivered = set()
        drops = {}
        for r in self.rows:
            ids.add(r["id"])
            if r["reason"] != "none":
                drops[r["reason"]] = drops.get(r["reason"], 0) + 1
            elif r["stage"] == "deliver":
                delivered.add(r["id"])
        out = {"rate": self.rate, "n_assigned": self.n_assigned,
               "spans": len(self.rows), "spans_lost": self.rows_lost,
               "ids_seen": len(ids), "ids_delivered": len(delivered),
               "shards": self.shards or 1}
        if drops:
            out["drops"] = dict(sorted(drops.items(),
                                       key=lambda kv: -kv[1]))
        return out
