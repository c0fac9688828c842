"""Benchmark: phold event rate on the current default JAX backend.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

PHOLD is the reference's own scheduler stress test / performance probe
(/root/reference/src/test/phold/test_phold.c; SURVEY.md §4).  The metric is
delivered messages per wall-clock second (each delivered message = one
routed packet + one application event, the engine hot path).

`vs_baseline`: the reference publishes no numbers (BASELINE.md), so the
denominator is MEASURED on this machine: baseline/refdes.c, a lean
reference-architecture pthread DES (per-host locked heaps, conservative
windows, malloc'd packets, latency-matrix lookups) running the same
phold shape.  It omits the reference's heavier per-event machinery
(userspace TCP, GLib, task closures), so it is a floor for reference
cost and the ratio is conservative.  The measurement is cached in
baseline/measured.json (tools/refbase.py regenerates); if absent, a
quick single-rep measurement runs inline.  The judge's recorded
BENCH_r{N}.json values are comparable across rounds via the raw value.
"""

from __future__ import annotations

import json
import os
import sys
import time

import shadow1_tpu  # noqa: F401  (x64)
import jax

from shadow1_tpu import sim, trace
from shadow1_tpu.core import engine, megakernel as mk, simtime

# The pre-measurement placeholder denominator: rounds recorded before
# baseline/measured.json existed (r4 and earlier) divided by this, so
# their vs_baseline is NOT comparable with measured rounds -- the r05
# switch to the ~5.68M measured rate silently re-scaled the ratio by
# ~5.7x.  The provenance fields below make that shift explicit in every
# JSON from now on.
NOMINAL_BASELINE = 1.0e6


def _baseline_events_per_sec() -> tuple[float, str, str, str]:
    """Comparator rate (events/sec) + provenance:
    (rate, kind, source, note)."""
    import pathlib
    import subprocess
    root = pathlib.Path(__file__).resolve().parent
    cached = root / "baseline" / "measured.json"
    try:
        if not cached.exists():
            subprocess.run(
                [sys.executable, str(root / "tools" / "refbase.py"),
                 "--quick"], check=True, capture_output=True, timeout=600)
        data = json.loads(cached.read_text())
        rate = float(data["phold"]["events_per_sec"])
        note = ("vs_baseline divides by the pthread DES measured on this "
                "machine (tools/refbase.py); rounds recorded before the "
                "measured file existed used the 1e6 nominal placeholder, "
                "so their vs_baseline is on a different scale")
        return rate, "measured", str(cached), note
    except Exception:  # noqa: BLE001  (toolchain missing: nominal fallback)
        note = ("baseline toolchain unavailable: vs_baseline divides by "
                "the 1e6 nominal placeholder, NOT comparable with rounds "
                "whose baseline_kind is 'measured'")
        return NOMINAL_BASELINE, "nominal", "nominal:1e6", note


def _stage_emissions_ms(state, params, app) -> float:
    """Staging-merge cost on the live backend (ms/merge), slope-timed
    by tools/phaseprof.measure_staging_ms over the warmed bench state.
    Runs AFTER the timed passes (one extra small compile).  A failure
    here fails the run: a record with a hole in it is not a record."""
    import pathlib
    root = pathlib.Path(__file__).resolve().parent
    sys.path.insert(0, str(root / "tools"))
    import phaseprof
    return round(phaseprof.measure_staging_ms(state, params, app), 4)


def _kernel_counts(rx_batch: int) -> dict | None:
    """Compiled HLO op/fusion counts per engine phase, measured in a
    fresh CPU-pinned interpreter (tools/kernelcount.py --json).

    A subprocess for the same reason dryrun_multichip uses one: the
    count is a property of the compiled graph, not the accelerator, and
    the child stays on the CPU (JAX_PLATFORMS=cpu) while this process
    holds the chip.  It counts the reference graph, the one bench runs.
    Returns None when counting fails -- the benchmark result must never
    be lost to its own metadata."""
    import os
    import pathlib
    import subprocess
    root = pathlib.Path(__file__).resolve().parent
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    env.pop("XLA_FLAGS", None)
    try:
        r = subprocess.run(
            [sys.executable, str(root / "tools" / "kernelcount.py"),
             "--json", "--no-megakernel", "--rx-batch", str(rx_batch)],
            env=env, cwd=str(root), capture_output=True, text=True,
            timeout=600)
        if r.returncode != 0:
            return None
        return json.loads(r.stdout.strip().splitlines()[-1])
    except Exception:  # noqa: BLE001
        return None

# Throughput scales with the host count (each micro-step advances every
# host; the per-step reductions grow sublinearly), so the benchmark runs
# the largest world that comfortably fits one chip.
NUM_HOSTS = 16384
MSGS_PER_HOST = 4
MEAN_DELAY_NS = 10 * simtime.SIMTIME_ONE_MILLISECOND
SIM_SECONDS = 2


def main(churn: float | None = None, churn_downtime_s: float = 5.0,
         gate_against: str | None = None):
    # The benchmark opts into arrival batching explicitly (rx_batch=2,
    # the measured sweet spot); the app default is serial rx_batch=1.
    # The batching config rides the JSON so recorded rounds are
    # interpretable when defaults move.
    state, params, app = sim.build_phold(
        num_hosts=NUM_HOSTS,
        msgs_per_host=MSGS_PER_HOST,
        mean_delay_ns=MEAN_DELAY_NS,
        stop_time=(SIM_SECONDS + 1) * simtime.SIMTIME_ONE_SECOND,
        pool_capacity=NUM_HOSTS * 8,
        rx_batch=2,
    )

    # Optional fault injection (--churn): measures the engine under host
    # flapping.  The netem settings ride the config block so benchdiff
    # refuses to compare a churned run against a clean one.
    netem_cfg = None
    if churn:
        state, params = sim.add_churn(state, params, churn,
                                      mean_down_s=churn_downtime_s)
        netem_cfg = {"churn_rate": churn,
                     "churn_downtime_s": churn_downtime_s}

    # Always-on cheap counters (trace.py): the device-side block adds
    # per-window aggregates to every recorded BENCH JSON, and the async
    # (sync=False) profiler attributes wall time to launches/compiles
    # without adding sync points to the measured loop.
    profiler = trace.install(trace.Profiler(sync=False))
    state = trace.ensure_counters(state)

    # Warmup: compile the whole windowed run (first TPU compile ~20-40s).
    with profiler.span("warmup_compile"):
        warm = engine.run_until(state, params, app,
                                10 * simtime.SIMTIME_ONE_MILLISECOND)
        jax.block_until_ready(warm)

    # Two measurement passes, best taken: the simulation is
    # deterministic, so max-of-N measures the engine rather than host
    # noise.
    best = None
    for _attempt in range(2):
        t0 = time.perf_counter()
        with profiler.span("measure_pass"):
            out = engine.run_chunked(warm, params, app,
                                     SIM_SECONDS * simtime.SIMTIME_ONE_SECOND)
            jax.block_until_ready(out)
            n_steps = int(out.n_steps)
        wall = time.perf_counter() - t0
        if best is None or wall < best[0]:
            best = (wall, out, n_steps)
    wall, out, n_steps = best

    events = int(out.app.recv.sum() - warm.app.recv.sum()) \
        + int(out.app.sent.sum() - warm.app.sent.sum())
    rate = events / wall
    steps = max(n_steps - int(warm.n_steps), 1)
    base_rate, base_kind, base_source, base_note = \
        _baseline_events_per_sec()
    counters = trace.fetch_counters(out, profiler)
    # Compiled-graph size (measured after the timed passes so the CPU
    # subprocess never competes with the benchmark for the machine).
    profiler.set_kernelcount(_kernel_counts(app.rx_batch))
    # Staging-phase cost on the live backend: the packed-pool block
    # write this round halved, tracked so benchdiff flags a regression.
    stage_ms = _stage_emissions_ms(warm, params, app)
    profiler.set_metric("stage_emissions_ms", stage_ms)
    metrics = profiler.metrics()
    trace.install(None)
    result = {
        "metric": "phold_events_per_sec",
        "value": round(rate, 2),
        "unit": "events/sec",
        "vs_baseline": round(rate / base_rate, 4),
        "baseline_events_per_sec": base_rate,
        "baseline_kind": base_kind,
        "baseline_source": base_source,
        "baseline_note": base_note,
        "events_per_microstep": round(events / steps, 2),
        "microsteps": steps,
        "windows": int(out.n_windows) - int(warm.n_windows),
        "wall_sec": round(wall, 2),
        "config": {
            "num_hosts": NUM_HOSTS,
            "msgs_per_host": MSGS_PER_HOST,
            "sim_seconds": SIM_SECONDS,
            "rx_batch": app.rx_batch,
            "app_tx_lanes": int(getattr(app, "app_tx_lanes", 1)),
            # Execution-path stamps, read from the gates the engine
            # traced with (what compiled, not what was asked for):
            # fused vs reference and the persistent window kernel are
            # ShapeKey statics, so benchdiff refuses a both-stamped
            # mismatch; legacy unstamped rounds compare against
            # anything.
            "megakernel": mk.enabled(out, params, app),
            "persistent": mk.persistent_enabled(out, params, app),
            "netem": netem_cfg,
            # Flowscope stamp: benchdiff refuses a sampled-vs-unsampled
            # compare (the ring writes change the traced graph), like
            # the netem/flight refusals.  bench.py never samples.
            "scope": None,
            # Lineage stamp: a packet-lineage tracer adds span-ring
            # writes to the traced graph, so benchdiff refuses a
            # traced-vs-untraced compare too.  bench.py never traces.
            "lineage": None,
            # Statescope stamp: per-window digests add checksum
            # reductions to the traced graph, so digested-vs-bare (or
            # different cadences) measure different programs -- the
            # lineage rule.  bench.py never digests.
            "digest": None,
            # Checkpoint stamp: cadenced saves add launch boundaries and
            # host-side npz wall time, so benchdiff refuses a cadence
            # mismatch; bench.py never checkpoints.
            "checkpoint_every": None,
            # Pipeline stamp: the async window pipeline overlaps host
            # drains with device windows on the checkpointed path, so
            # pipelined and sequential wall-clocks measure different
            # launch loops -- benchdiff refuses a both-stamped
            # mismatch.  bench.py never checkpoints, so no pipeline.
            "pipeline": None,
            # Batching stamp: continuous batching packs concurrent
            # server requests onto one vmapped train, so a batched
            # round's walls are not comparable to solo ones.  The solo
            # probe never batches.
            "batched": False,
            # Sentinel/supervise stamps: the sentinel block adds in-loop
            # invariant counters to the traced graph, and supervision
            # adds host-side checks per launch, so benchdiff refuses a
            # both-stamped mismatch on either.  bench.py runs bare.
            "sentinel": False,
            "supervise": False,
            # Serve stamp: a run executed inside the resident run
            # server (shadow1_tpu/server.py) shares its process with
            # other tenants and its compile cache with prior requests,
            # so its wall-clock is not comparable to a solo run's.
            # bench.py always runs solo.
            "serve": False,
        },
        # Wall-clock numbers are only comparable between runs on the
        # same backend and core count; benchdiff downgrades machine-
        # bound metrics to informational when these don't match (or
        # when the baseline predates the field).
        "env": {
            "backend": jax.default_backend(),
            "cpu_count": os.cpu_count(),
            # Throughput buckets by mesh size: benchdiff refuses to
            # compare across device counts (rc 2), like cross-netem.
            "n_devices": 1,
        },
        "profile": {
            "phases": metrics["phases"],
            "compile": metrics["compile"],
            # Flat compile metrics for benchdiff: the count gates at 0%
            # (a graph property -- a new compile means a shape or static
            # changed), the wall time is machine-bound/informational.
            "compiles": metrics["compiles"],
            "compile_ms": metrics["compile_ms"],
            "transfers": metrics["transfers"],
            "device_counters": counters,
            "kernelcount": metrics.get("kernelcount"),
            "stage_emissions_ms": stage_ms,
        },
    }
    print(json.dumps(result))
    if gate_against:
        return _gate(gate_against, result)
    return 0


# ENSEMBLE rung (--worlds N): the world-axis batching record
# (docs/ensemble.md).  N phold worlds run as ONE vmapped batch through
# ensemble.run_until -- one compiled graph serves every world -- and
# the record carries ensembles_per_sec (whole worlds retired per wall
# second) plus a per-world events/s breakdown.  A smaller world than
# the solo probe: the rung measures world-axis batching efficiency,
# not single-world engine throughput.
ENSEMBLE_HOSTS = 2048
ENSEMBLE_SIM_SECONDS = 1


def main_ensemble(n_worlds: int, gate_against: str | None = None) -> int:
    from shadow1_tpu import ensemble

    worlds = ensemble.replicate(
        sim.build_phold, n_worlds, seed=1,
        num_hosts=ENSEMBLE_HOSTS,
        msgs_per_host=MSGS_PER_HOST,
        mean_delay_ns=MEAN_DELAY_NS,
        stop_time=(ENSEMBLE_SIM_SECONDS + 1)
        * simtime.SIMTIME_ONE_SECOND,
        pool_capacity=ENSEMBLE_HOSTS * 8,
        rx_batch=2,
    )
    estate, eparams, app = ensemble.stack(worlds)

    profiler = trace.install(trace.Profiler(sync=False))
    with profiler.span("warmup_compile"):
        warm = ensemble.run_until(estate, eparams, app,
                                  10 * simtime.SIMTIME_ONE_MILLISECOND)
        jax.block_until_ready(warm)
    graphs_after_warm = ensemble.cache_size()

    best = None
    for _attempt in range(2):
        t0 = time.perf_counter()
        with profiler.span("measure_pass"):
            out = ensemble.run_until(
                warm, eparams, app,
                ENSEMBLE_SIM_SECONDS * simtime.SIMTIME_ONE_SECOND)
            n_steps = int(out.n_steps.sum())
        wall = time.perf_counter() - t0
        if best is None or wall < best[0]:
            best = (wall, out, n_steps)
    wall, out, n_steps = best

    # Per-world event deltas over the measured pass (axis 0 = world).
    ev_w = [(int(out.app.recv[k].sum() - warm.app.recv[k].sum())
             + int(out.app.sent[k].sum() - warm.app.sent[k].sum()))
            for k in range(n_worlds)]
    events = sum(ev_w)
    rate = events / wall
    metrics = profiler.metrics()
    trace.install(None)
    result = {
        "metric": "phold_ensemble_events_per_sec",
        "value": round(rate, 2),
        "unit": "events/sec",
        "wall_sec": round(wall, 2),
        "ensemble": {
            # Whole worlds retired per wall second on this fixed
            # workload: the headline world-axis batching number (an
            # N-world ensemble at the solo wall time scores N x the
            # solo run's 1/wall).
            "ensembles_per_sec": round(n_worlds / wall, 4),
            "per_world_events_per_sec": [round(e / wall, 2)
                                         for e in ev_w],
            # One-compiled-graph check: the measured passes must reuse
            # the warmup's graph (ladder rung 10 asserts growth <= 1).
            "run_until_graphs": ensemble.cache_size(),
            "run_until_graphs_after_warmup": graphs_after_warm,
        },
        "config": {
            "num_hosts": ENSEMBLE_HOSTS,
            "msgs_per_host": MSGS_PER_HOST,
            "sim_seconds": ENSEMBLE_SIM_SECONDS,
            "rx_batch": app.rx_batch,
            # stack() pins megakernel off (no vmap batching rule for
            # the Pallas kernel; docs/ensemble.md).
            "megakernel": bool(eparams.megakernel),
            # With megakernel pinned off, the persistent window kernel
            # never engages on the ensemble axis.
            "persistent": False,
            "netem": None,
            "scope": None,
            "lineage": None,
            "digest": None,
            "checkpoint_every": None,
            "pipeline": None,
            "batched": False,
            "sentinel": False,
            "supervise": False,
            "serve": False,
        },
        "env": {
            "backend": jax.default_backend(),
            "cpu_count": os.cpu_count(),
            "n_devices": 1,
            # World-count bucket: benchdiff refuses to compare records
            # across ensemble sizes (rc 2), like cross-device-count.
            "n_worlds": n_worlds,
        },
        "profile": {
            "phases": metrics["phases"],
            "compile": metrics["compile"],
            "compiles": metrics["compiles"],
            "compile_ms": metrics["compile_ms"],
            "transfers": metrics["transfers"],
        },
    }
    print(json.dumps(result))
    if gate_against:
        return _gate(gate_against, result)
    return 0


# SERVED rung (--serve K): the Servescope observability probe.  K
# identical phold builder requests go through a live resident run
# server (one worker, so requests queue); with max_lanes > 1 the
# compatible requests co-batch onto one vmapped lane train
# (shadow1_tpu/batch.py), so the rung measures the packed schedule:
# aggregate queue-wait, affinity hit rate, batched picks, per-request
# walls, and host-drain overlap land in a "server" block built from
# each run's request_metrics.json.  A much smaller world than the solo
# probe -- the rung measures the scheduler, not the engine.
SERVE_HOSTS = 1024
SERVE_SIM_SECONDS = 1


def main_served(k: int, queue_limit: int,
                gate_against: str | None = None,
                max_lanes: int = 4) -> int:
    import tempfile
    import threading

    from shadow1_tpu import protocol, server

    kw = dict(num_hosts=SERVE_HOSTS, msgs_per_host=MSGS_PER_HOST,
              seed=11,
              stop_time=(SERVE_SIM_SECONDS + 1)
              * simtime.SIMTIME_ONE_SECOND)
    spec = {"name": "phold", "kwargs": kw, "checkpoint_every": 2.0}
    results = [None] * k

    def _submit(i):
        rid, rc = None, None
        for ev in protocol.stream(
                protocol.default_socket(data_dir),
                {"op": "submit", "kind": "builder", "spec": spec,
                 "wait": True, "progress": False}):
            if rid is None and ev.get("id"):
                rid = ev["id"]
            if not ev.get("ok", True):
                rc = ev.get("rc")
                break
            if ev.get("event") == "done":
                rc = ev.get("rc")
                break
        results[i] = (rid, rc)

    with tempfile.TemporaryDirectory(prefix="shadow1-serve-bench-") \
            as data_dir:
        srv = server.Server(data_dir, workers=1,
                            queue_limit=max(queue_limit, k),
                            max_lanes=max_lanes, quiet=True).start()
        try:
            t0 = time.perf_counter()
            threads = [threading.Thread(target=_submit, args=(i,))
                       for i in range(k)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            span = time.perf_counter() - t0
        finally:
            srv.shutdown()
        if any(r is None or r[0] is None or r[1] != 0 for r in results):
            print(f"bench --serve: not all {k} requests finished rc 0: "
                  f"{results}", file=sys.stderr)
            return 1
        per_req = []
        for rid, _rc in results:
            with open(os.path.join(data_dir, "runs", rid,
                                   "request_metrics.json")) as f:
                per_req.append(json.load(f))

    waits = [m["queue_wait_s"] for m in per_req]
    hits = sum(1 for m in per_req if m.get("affinity_hit"))
    events = sum(m["events"] for m in per_req
                 if m.get("events") is not None)
    walls = [m.get("wall_s") for m in per_req
             if m.get("wall_s") is not None]
    overlaps = [m.get("host_drain_overlap_pct") for m in per_req
                if m.get("host_drain_overlap_pct") is not None]
    result = {
        "metric": "phold_events_per_sec",
        "value": round(events / span, 2),
        "unit": "events/sec",
        "wall_sec": round(span, 2),
        "config": {
            "num_hosts": SERVE_HOSTS,
            "msgs_per_host": MSGS_PER_HOST,
            "sim_seconds": SERVE_SIM_SECONDS,
            # Served builder worlds keep the default reference graph
            # (lane trains force it too).
            "megakernel": False,
            "persistent": False,
            "netem": None,
            "scope": None,
            "lineage": None,
            "digest": None,
            # Served runs checkpoint on the server's cadence (the
            # crash-safety contract), unlike the solo probe.
            "checkpoint_every": 2.0,
            # Served runs go through sim.run's checkpointed path, whose
            # async window pipeline is on by default; benchdiff refuses
            # to compare against a --no-pipeline round.
            "pipeline": True,
            # Continuous batching: with max_lanes > 1 the K concurrent
            # same-shape requests share one vmapped train, so the
            # per-request walls below measure the packed schedule --
            # not comparable to a solo (max_lanes=1) round.
            "batched": max_lanes > 1,
            "max_lanes": max_lanes,
            "sentinel": False,
            "supervise": True,
            "serve": True,
            # Queue waits scale with the admission bound, so benchdiff
            # buckets served rounds by it (the n_devices rule).
            "queue_limit": max(queue_limit, k),
            "requests": k,
        },
        "env": {
            "backend": jax.default_backend(),
            "cpu_count": os.cpu_count(),
            "n_devices": 1,
        },
        # server.* is machine-bound in benchdiff (scheduler wall times):
        # informational across environments, gated within one.
        "server": {
            "requests": k,
            "workers": 1,
            "requests_per_sec": round(k / span, 4),
            "queue_wait_total_s": round(sum(waits), 4),
            "queue_wait_mean_s": round(sum(waits) / k, 4),
            "queue_wait_max_s": round(max(waits), 4),
            "affinity_hits": hits,
            "affinity_hit_rate": round(hits / k, 4),
            # Continuous batching evidence: how many requests were
            # packed onto a live train, each request's own wall, and
            # the per-request host-drain overlap (the pipeline's
            # hide-the-drain-wall metric).  A batched round's
            # request_wall_max_s sits far below K x the solo wall.
            "batched_picks": sum(1 for m in per_req
                                 if m.get("pick_reason") == "batched"),
            "request_wall_s": [round(w, 4) for w in walls],
            "request_wall_mean_s": round(sum(walls) / len(walls), 4)
            if walls else None,
            "request_wall_max_s": round(max(walls), 4) if walls
            else None,
            "host_drain_overlap_pct_mean": round(
                sum(overlaps) / len(overlaps), 2) if overlaps else None,
            "compiles_total": sum(m.get("compiles") or 0
                                  for m in per_req),
            "events": events,
        },
    }
    print(json.dumps(result))
    if gate_against:
        return _gate(gate_against, result)
    return 0


# MULTICHIP scaling rung (--devices N): a smaller fixed world than the
# single-chip probe, because every rung of the ladder (1, 2, 4, .., N
# devices) runs it to completion and the 1-device rung bounds the wall
# time.  Same shape across rungs so ev/s is comparable within the record.
MESH_HOSTS = 2048
MESH_SIM_SECONDS = 1


def _mesh_rung(n_devices: int) -> dict:
    """One rung of --devices: phold ev/s through the explicit shard_map
    engine (parallel.mesh_run_until) on this process's first `n_devices`
    devices."""
    from shadow1_tpu import parallel

    mesh = parallel.make_mesh(jax.devices()[:n_devices])
    state, params, app = sim.build_phold(
        num_hosts=MESH_HOSTS,
        msgs_per_host=MSGS_PER_HOST,
        mean_delay_ns=MEAN_DELAY_NS,
        stop_time=(MESH_SIM_SECONDS + 1) * simtime.SIMTIME_ONE_SECOND,
        pool_capacity=MESH_HOSTS * 8,
        rx_batch=2,
    )
    # Flight recorder: per-window exchange matrices ride the rung so the
    # scaling record shows how much traffic actually crossed shards at
    # each device count (the recorder is replicated; its cost is the
    # same at every rung, so ev/s stays comparable within the record).
    state = trace.ensure_flight_recorder(state, shards=n_devices)
    warm = parallel.mesh_run_until(
        state, params, app, 10 * simtime.SIMTIME_ONE_MILLISECOND,
        mesh=mesh)
    jax.block_until_ready(warm)
    best = None
    for _attempt in range(2):
        t0 = time.perf_counter()
        out = parallel.mesh_run_chunked(
            warm, params, app,
            MESH_SIM_SECONDS * simtime.SIMTIME_ONE_SECOND, mesh=mesh)
        jax.block_until_ready(out)
        wall = time.perf_counter() - t0
        if best is None or wall < best[0]:
            best = (wall, out)
    wall, out = best
    events = int(out.app.recv.sum() - warm.app.recv.sum()) \
        + int(out.app.sent.sum() - warm.app.sent.sum())
    # Exchange totals for the measured pass (the sim is deterministic,
    # so both passes move the same packets) plus the all-to-all share of
    # wall time: exchange_probe_ms times one exchange in isolation (the
    # send buffer is fixed-size, so an idle probe is representative) and
    # the share scales it by the measured window count.
    wins = int(out.n_windows) - int(warm.n_windows)
    movers = int(out.fr.ex_cnt_sum.sum()) - int(warm.fr.ex_cnt_sum.sum())
    xbytes = int(out.fr.ex_bytes_sum.sum()) \
        - int(warm.fr.ex_bytes_sum.sum())
    probe_ms = parallel.exchange_probe_ms(out, params, mesh)
    share = round(min(1.0, probe_ms / 1000.0 * wins / wall), 4) \
        if wall > 0 else None
    return {
        "devices": n_devices,
        "events_per_sec": round(events / wall, 2),
        "events": events,
        "wall_sec": round(wall, 3),
        "err": int(out.err),
        "megakernel": bool(params.megakernel),
        "flight": {"capacity": int(out.fr.capacity),
                   "shards": int(out.fr.n_shards)},
        "exchange": {
            "movers": movers,
            "bytes": xbytes,
            "windows": wins,
            "alltoall_ms": round(probe_ms, 4),
            "alltoall_share": share,
        },
    }


def main_multichip(n_devices: int, gate_against: str | None = None) -> int:
    """--devices N: the MULTICHIP scaling record.  Runs the fixed
    MESH_HOSTS phold world through parallel.mesh_run_until at every
    power-of-two device count up to N (1, 2, 4, .., N), all in this one
    process over jax.devices()[:d] -- a chip belongs to one process, so
    no rung runs in a child.  Fails when the backend has fewer than N
    devices (on the CPU, provide them with
    XLA_FLAGS=--xla_force_host_platform_device_count=N).  Prints ONE
    JSON line whose value is the ev/s at N devices and whose
    multichip.scaling block holds the whole rung."""
    have = len(jax.devices())
    if have < n_devices:
        print(f"bench --devices {n_devices}: the {jax.default_backend()} "
              f"backend has {have} devices", file=sys.stderr)
        return 1
    counts = [d for d in (1, 2, 4, 8, 16, 32, 64) if d < n_devices]
    counts.append(n_devices)
    rungs = [_mesh_rung(d) for d in counts]
    top = rungs[-1]
    result = {
        "metric": "phold_events_per_sec",
        "value": top["events_per_sec"],
        "unit": "events/sec",
        "wall_sec": top["wall_sec"],
        "config": {
            "num_hosts": MESH_HOSTS,
            "msgs_per_host": MSGS_PER_HOST,
            "sim_seconds": MESH_SIM_SECONDS,
            "rx_batch": 2,
            "engine": "mesh_run_until",
            "megakernel": top["megakernel"],
            # Mesh worlds carry halo offsets (hoff), so the persistent
            # window kernel never engages there.
            "persistent": False,
            "netem": None,
            # Recorder shape: benchdiff refuses to compare a run whose
            # flight config differs (recorder on/off changes the traced
            # graph), mirroring the netem refusal.
            "flight": top.get("flight"),
            "scope": None,
            "lineage": None,
            "digest": None,
            "checkpoint_every": None,
            "pipeline": None,
            "batched": False,
            "sentinel": False,
            "supervise": False,
            "serve": False,
        },
        "env": {
            "backend": jax.default_backend(),
            "cpu_count": os.cpu_count(),
            "n_devices": n_devices,
        },
        # profile.flight.* is machine-bound in benchdiff (probe times
        # depend on the backend); the per-rung blocks live in
        # multichip.scaling[].exchange.
        "profile": {"flight": top.get("exchange")},
        "multichip": {"scaling": rungs},
    }
    print(json.dumps(result))
    if gate_against:
        return _gate(gate_against, result)
    return 0


def _gate(old_path: str, result: dict) -> int:
    """Diff this run against a recorded round with tools/benchdiff.py
    --kernels: fail (nonzero) when throughput OR compiled kernel count
    regressed.  The bench-flow wiring for CI / future rounds:

        python bench.py --gate-against BENCH_r07.json
    """
    import pathlib
    import tempfile
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent
                           / "tools"))
    import benchdiff
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(result, f)
        new_path = f.name
    rc = benchdiff.main([old_path, new_path, "--kernels"])
    if rc:
        print(f"bench gate FAILED against {old_path} (rc={rc})",
              file=sys.stderr)
    return rc


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--churn", type=float, default=None, metavar="RATE",
                    help="run under netem chaos: mean host flaps per "
                         "second (recorded in the JSON config block)")
    ap.add_argument("--churn-downtime", type=float, default=5.0,
                    metavar="SECONDS", help="mean down-time per flap")
    ap.add_argument("--gate-against", default=None, metavar="OLD_JSON",
                    help="after printing the result, diff it against a "
                         "recorded BENCH_r{N}.json / bench line with "
                         "tools/benchdiff.py --kernels and exit nonzero "
                         "on a throughput or kernel-count regression")
    ap.add_argument("--devices", type=int, default=None, metavar="N",
                    help="MULTICHIP scaling record: run the fixed mesh "
                         "world through parallel.mesh_run_until at 1, 2, "
                         "4, .., N devices in this process (fails when "
                         "the backend has fewer than N) and print one "
                         "JSON line with the scaling block")
    ap.add_argument("--serve", type=int, default=None, metavar="K",
                    help="SERVED rung: submit K identical phold "
                         "requests through a live resident run server "
                         "(one worker) and record aggregate queue-wait, "
                         "affinity hit rate, and requests/s in a "
                         "'server' block (Servescope, "
                         "docs/observability.md)")
    ap.add_argument("--queue-limit", type=int, default=8, metavar="N",
                    help="admission-queue bound for --serve (raised to "
                         "K when smaller; stamped in the config block "
                         "so benchdiff buckets served rounds by it)")
    ap.add_argument("--max-lanes", type=int, default=4, metavar="N",
                    help="continuous-batching width for --serve: up to "
                         "N compatible requests share one vmapped lane "
                         "train (1 disables batching; stamped in the "
                         "config block so benchdiff refuses a batched "
                         "vs solo compare)")
    ap.add_argument("--worlds", type=int, default=None, metavar="N",
                    help="ENSEMBLE rung: run N phold worlds as one "
                         "vmapped batch (shadow1_tpu/ensemble, one "
                         "compiled graph for every world) and record "
                         "ensembles_per_sec plus a per-world events/s "
                         "breakdown; n_worlds is stamped in env so "
                         "benchdiff buckets ensemble rounds by size")
    ns = ap.parse_args()
    if ns.worlds:
        sys.exit(main_ensemble(ns.worlds, ns.gate_against))
    if ns.serve:
        sys.exit(main_served(ns.serve, ns.queue_limit, ns.gate_against,
                             max_lanes=ns.max_lanes))
    if ns.devices:
        sys.exit(main_multichip(ns.devices, ns.gate_against))
    sys.exit(main(ns.churn, ns.churn_downtime, ns.gate_against))
