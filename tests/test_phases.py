"""The simulator's phases inside the program (trace.py).

* every window-loop phase in `trace.PHASES` reaches the compiled ops'
  `op_name` metadata (the names a device trace splits busy time by), on
  a UDP world, a lossy TCP world and the mesh body;
* `sim.run`'s spans (`sim.run` > `prepare`, `dispatch`) land on the host
  plane of a `jax.profiler` trace, on the clock of the device's ops;
* `trace.compile_spans()` records each compile phase of `run_until` once
  per shape, and shows the extra trace that a host-array leaf keys.
"""

import glob
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow1_tpu import parallel, sim, trace
from shadow1_tpu.core import engine, simtime
from shadow1_tpu.parallel import mesh as mesh_mod

MS = simtime.SIMTIME_ONE_MILLISECOND
SEC = simtime.SIMTIME_ONE_SECOND
TCP_ONLY = {"tcp_timers", "tcp_tx"}
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def _phases_in(lowered):
    """The PHASES named in a lowering's op locations."""
    txt = lowered.as_text(debug_info=True)
    found = set()
    for m in re.finditer(r'loc\("([^"]*)"', txt):
        found.update(c for c in m.group(1).split("/") if c in trace.PHASES)
    return found


def _phold(num_hosts, **kw):
    return sim.build_phold(num_hosts=num_hosts, msgs_per_host=2,
                           mean_delay_ns=10 * MS, stop_time=SEC,
                           pool_capacity=num_hosts * 8, **kw)


def _lossy_bulk():
    return sim.build_bulk(num_hosts=4, server=0, bytes_per_client=20_000,
                          latency_ns=5 * MS, reliability=0.95,
                          stop_time=5 * SEC, seed=6)


@pytest.mark.parametrize("world, absent", [
    ("phold", TCP_ONLY | {"mesh_min"}),
    ("lossy_bulk_tcp", {"mesh_min"}),
])
def test_run_until_ops_carry_every_phase(world, absent):
    state, params, app = _phold(8) if world == "phold" else _lossy_bulk()
    lowered = engine.run_until.lower(state, params, app, SEC)
    assert _phases_in(lowered) == set(trace.PHASES) - absent


def test_mesh_body_ops_carry_every_phase_and_its_name():
    state, params, app = _phold(8)
    mesh = parallel.make_mesh(jax.devices()[:4])
    sspecs = mesh_mod._state_specs(state)
    pspecs = mesh_mod._param_specs(params)
    fn = mesh_mod._build(app, mesh, sspecs, pspecs)
    state, params = mesh_mod._place(mesh, (state, params), (sspecs, pspecs))
    t0 = time.time()
    with mesh:
        lowered = fn.lower(state, params, jnp.asarray(SEC, jnp.int64))
    assert _phases_in(lowered) == set(trace.PHASES) - TCP_ONLY
    names = {(ev, fun) for ev, fun, s, _e in trace.compile_spans()
             if s >= t0}
    assert (TRACE, "mesh_run_until") in names
    assert (LOWER, "jit(mesh_run_until)") in names


def test_sim_run_spans_share_the_device_clock(tmp_path):
    state, params, app = _phold(8, seed=3)
    state, params = jax.device_put((state, params), jax.devices()[0])
    state = jax.block_until_ready(sim.run(state, params, app,
                                          until=50 * MS))   # compile
    with jax.profiler.trace(str(tmp_path)):
        for k in (2, 3):
            with jax.profiler.TraceAnnotation("launch"):
                state = jax.block_until_ready(
                    sim.run(state, params, app, until=k * 50 * MS))
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    host, ops = {}, []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("launch", "sim.run", "prepare", "dispatch"):
                    host.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns))
                elif dict(e.stats).get("hlo_module") == "jit_run_until":
                    ops.append((e.start_ns, e.end_ns))
    assert {k: len(v) for k, v in host.items()} == {
        "launch": 2, "sim.run": 2, "prepare": 2, "dispatch": 2}
    for (l0, l1), (r0, r1), (p0, p1), (d0, d1) in zip(
            *(sorted(host[k]) for k in ("launch", "sim.run", "prepare",
                                        "dispatch"))):
        assert l0 <= r0 <= p0 <= p1 <= d0 <= d1 <= r1 <= l1
        # The launch's device ops run after its dispatch began and before
        # the block that ends the launch: one clock for both planes.
        assert any(d0 <= a and b <= l1 for a, b in ops)


def _loop_events(t0):
    return [(ev.rsplit("/", 1)[-1], fun) for ev, fun, s, _e
            in trace.compile_spans() if s >= t0 and "run_until" in fun]


def test_compile_spans_one_trace_lower_compile_per_shape():
    state, params, app = _phold(24)
    state, params = jax.device_put((state, params), jax.devices()[0])
    t0 = time.time()
    out = jax.block_until_ready(sim.run(state, params, app, until=50 * MS))
    assert _loop_events(t0) == [
        ("jaxpr_trace_duration", "run_until"),
        ("jaxpr_to_mlir_module_duration", "jit(run_until)"),
        ("backend_compile_duration", "jit(run_until)")]
    t1 = time.time()
    jax.block_until_ready(sim.run(out, params, app, until=100 * MS))
    assert _loop_events(t1) == []


def test_compile_spans_show_the_trace_a_host_leaf_keys():
    """A world with a leaf written on the host (a numpy array, not
    committed to a device as a launch's output is) traces `run_until` a
    second time at its second launch.  This documents that repeat; the
    benchmark commits its worlds first (benchmark/world.py)."""
    state, params, app = _phold(40)
    hosts = state.hosts
    state = state.replace(hosts=hosts.replace(
        cpu_avail=np.array(jax.device_get(hosts.cpu_avail))))
    t0 = time.time()
    out = jax.block_until_ready(sim.run(state, params, app, until=50 * MS))
    jax.block_until_ready(sim.run(out, params, app, until=100 * MS))
    traces = [e for e in _loop_events(t0) if e[0] == "jaxpr_trace_duration"]
    assert len(traces) == 2


def test_profiler_compile_block_reads_the_record():
    state, params, app = _phold(56)
    prof = trace.Profiler()
    sim.run(state, params, app, until=50 * MS, profiler=prof)
    m = prof.metrics()
    row = m["compile"]["functions"]["run_until"]
    assert row["jaxpr_trace"] == 1 and row["backend_compile"] == 1
    assert m["compiles"] == len(prof.compiles) >= 1
    assert trace.current() is not prof


def test_profiler_metrics_while_another_thread_compiles():
    """The listener appends from whichever thread compiles (a run
    server's workers and its warm thread); reading the record and the
    Profiler's compile block meanwhile must neither raise nor lose a
    span."""
    prof = trace.install(trace.Profiler(sync=False, counters=False))
    errors, done = [], threading.Event()

    @jax.jit
    def twice_plus_one(x):
        return x * 2 + 1

    def compile_many():
        try:
            for n in range(1, 25):      # a new shape: one compile each
                twice_plus_one(np.zeros((n,))).block_until_ready()
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)
        finally:
            done.set()

    try:
        worker = threading.Thread(target=compile_many)
        worker.start()
        reads = 0
        while not done.is_set() or reads == 0:
            prof.metrics()
            trace.compile_spans()
            reads += 1
        worker.join()
    finally:
        trace.install(None)
    assert not errors
    row = prof.metrics()["compile"]["functions"]["twice_plus_one"]
    assert row["jaxpr_trace"] == row["backend_compile"] == 24
    assert len(prof.compiles) >= 24


def test_phase_rejects_unknown_names():
    with pytest.raises(ValueError, match="unknown phase"):
        trace.phase("rx_phase")
