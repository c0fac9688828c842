"""Benchmark ladder: measure simulated-sec / wall-sec on the real chip.

The BASELINE.json bring-up ladder, measured end to end (build + compile
excluded; steady-state wall time per simulated second reported):

  rung 1: 2-host tgen file transfer      (examples/tgen-2host)
  rung 2: 100-host tgen                  (examples/tgen-100host)
  rung 3: 1k-host Tor-like onion circuits (sim.build_onion(200))
  rung 4: phold event-rate probe          (bench.py metric)
  rung 5: 10k-host onion circuits         (sim.build_onion(2000))
  rung 6: 500-node Bitcoin gossip flood   (sim.build_gossip(500))
  rung 7: phold under netem chaos churn   (sim.add_churn, docs/netem.md)
  rung 8: phold on an 8-device mesh       (parallel.mesh_run_until on 8
          virtual CPU devices; FAILS on any bitwise trajectory
          divergence from single-device -- docs/parallel.md)
  rung 9: shape-bucket compile sharing    (three differently-sized phold
          worlds through shapes.pad_world_to_bucket; FAILS if run_until
          compiles more than one graph for the sweep -- docs/shapes.md)
  rung 10: ensemble world-axis batching   (8 phold worlds vmapped over a
          leading world axis through ensemble.run_until; FAILS if the
          ensemble compiles more than one graph or its wall time is not
          well under 8 sequential solo runs -- docs/ensemble.md)
  rung 11: persistent window kernel       (phold through K_WINDOW,
          params.persistent; FAILS on any bitwise divergence from the
          reference trajectory, on more than one compiled run_until
          graph for the measured span, or if the per-window launch
          surface (tools/kernelcount.py `launches`) has not collapsed
          >= 5x vs the per-phase fused graph -- docs/megakernel.md)

    python tools/ladder.py [rung ...]     # default: 1 2 3 5 6
"""

from __future__ import annotations

import json
import sys
import time

import shadow1_tpu  # noqa: F401
import jax
import jax.numpy as jnp

from shadow1_tpu import sim
from shadow1_tpu.core import engine, simtime

SEC = simtime.SIMTIME_ONE_SECOND


def _measure(state, params, app, warm_s: int, span_s: int):
    state = engine.run_until(state, params, app, warm_s * SEC)
    s0 = int(state.n_steps)  # sync
    t0 = time.perf_counter()
    state = engine.run_until(state, params, app, (warm_s + span_s) * SEC)
    steps = int(state.n_steps) - s0  # sync
    wall = time.perf_counter() - t0
    return {
        "sim_seconds": span_s,
        "wall_seconds": round(wall, 3),
        "sim_per_wall": round(span_s / wall, 3),
        "microsteps": steps,
        "err": int(state.err),
    }, state


def rung_tgen(path: str, warm_s: int = 1):
    from shadow1_tpu.config import assemble
    asm = assemble.load(path)
    # Measure the ACTIVE phase (tgen streams run in the first seconds;
    # once traffic ends, windows skip and sim-per-wall becomes idle
    # speed, which is not the number that matters).  warm_s should sit
    # at the latest <process starttime> so the span is all-busy.
    return _measure(asm.state, asm.params, asm.app, warm_s, 15)[0]


def rung_phold():
    s, p, a = sim.build_phold(num_hosts=16384, msgs_per_host=4,
                              stop_time=10 * SEC,
                              pool_capacity=16384 * 8,
                              rx_batch=2)  # measured ladder config
    res, out = _measure(s, p, a, 1, 2)
    res["events"] = int(out.app.sent.sum() + out.app.recv.sum())
    return res


def rung_onion(circuits: int, pool_slab: int = 64):
    # Completion-time metric (round 4: TX back-pressure made fixed spans
    # finish inside the warmup): run until EVERY circuit completes and
    # report simulated/wall time over exactly that busy phase.
    # 1 MiB streams.
    def build():
        return sim.build_onion(num_circuits=circuits,
                               bytes_per_circuit=1 << 20,
                               pool_slab=pool_slab,
                               stop_time=120 * SEC)

    s, p, a = build()
    # Warm the executable over the REAL busy phase (compile + first-run
    # costs land here), then measure fresh worlds; best-of-2 (bench.py
    # does the same).
    jax.block_until_ready(engine.run_until(s, p, a, 5 * SEC))
    best = None
    for _attempt in range(2):
        s, p, a = build()
        jax.block_until_ready(s)
        t0 = time.perf_counter()
        t_sim = 0
        while t_sim < 120:
            t_sim += 5
            s = engine.run_until(s, p, a, t_sim * SEC)
            done = int((s.app.done_t != simtime.SIMTIME_INVALID).sum())
            if done == circuits:
                break
        wall = time.perf_counter() - t0
        if best is None or wall < best[0]:
            best = (wall, t_sim, done, s)
    wall, t_sim, done, s = best
    INVT = simtime.SIMTIME_INVALID
    done_t = int(jnp.max(jnp.where(s.app.done_t != INVT, s.app.done_t, 0)))
    sim_s = done_t / SEC
    return {
        "circuits_done": done,
        # None on timeout: a partial run has no completion time.
        "sim_seconds_to_complete": round(sim_s, 3) if done == circuits
        else None,
        "wall_seconds": round(wall, 3),
        "sim_per_wall": round(t_sim / wall, 3),  # sim-s actually executed
        "microsteps": int(s.n_steps),
        "err": int(s.err),
        "hosts": int(s.hosts.num_hosts),
    }


def rung_phold_churn(rate_per_s: float = 0.5, mean_down_s: float = 1.0):
    # The phold probe with the netem overlay LIVE: seeded chaos flaps
    # every host (exponential up/down churn), so this rung prices the
    # overlay math + event cursor against rung 4's clean number and
    # shows the fault path exercised at scale.
    s, p, a = sim.build_phold(num_hosts=16384, msgs_per_host=4,
                              stop_time=10 * SEC,
                              pool_capacity=16384 * 8,
                              rx_batch=2)
    s, p = sim.add_churn(s, p, rate_per_s, mean_down_s=mean_down_s)
    res, out = _measure(s, p, a, 1, 2)
    res["events"] = int(out.app.sent.sum() + out.app.recv.sum())
    res["netem"] = {
        "churn_rate": rate_per_s,
        "churn_downtime_s": mean_down_s,
        "events_applied": int(out.nm.cursor),
        "packets_killed": int(out.nm.killed),
        "hosts_down_at_stop": int((out.nm.host_up == 0).sum()),
    }
    return res


def rung_gossip():
    # BASELINE config 4's workload class: 500 nodes, 12 peers each,
    # inv/getdata/item floods every 200 ms.
    s, p, a = sim.build_gossip(num_hosts=500, degree=12, num_items=64,
                               stop_time=30 * SEC)
    res, out = _measure(s, p, a, 1, 10)
    from shadow1_tpu.apps import gossip as _g
    res["items_fully_flooded"] = int(
        (out.app.phase == _g.PH_HAVE).all(axis=0).sum())
    res["msgs"] = int(out.app.msgs_sent.sum())
    return res


def rung_multichip(n_devices: int = 8):
    # The sharded-execution rung: real mesh_run_until on a virtual CPU
    # mesh (self-provisioned child interpreter; __graft_entry__), which
    # ASSERTS bitwise equality with single-device execution at two
    # horizons before reporting its rate -- a divergence fails the rung.
    import pathlib
    import sys as _sys
    root = pathlib.Path(__file__).resolve().parent.parent
    if str(root) not in _sys.path:
        _sys.path.insert(0, str(root))
    import __graft_entry__ as graft
    return graft.dryrun_multichip(n_devices)


def rung_buckets(sizes=(40, 48, 56), slab: int = 8, span_s: int = 2):
    """Three differently-sized phold worlds padded into one shape bucket
    (shapes.pad_world_to_bucket) and run back to back.  Asserts the
    whole sweep costs at most ONE run_until compile -- the property the
    shapes subsystem exists to provide (docs/shapes.md).  Also reports
    the profiler's compile count/wall for the sweep."""
    from shadow1_tpu import shapes, trace

    worlds = []
    for h in sizes:
        s, p, a = sim.build_phold(num_hosts=h, pool_capacity=h * slab,
                                  stop_time=span_s * SEC)
        worlds.append(shapes.pad_world_to_bucket(s, p) + (a,))
    buckets = {int(s.hosts.num_hosts) for s, _p, _a in worlds}
    # Profile ONLY the run loop: world building compiles a pile of tiny
    # host-side ops that would drown the number under test (how many
    # graphs the sweep itself costs).  Scalar pulls happen after.
    profiler = trace.install(trace.Profiler())
    jit_before = engine.run_until._cache_size()
    t0 = time.perf_counter()
    outs = [engine.run_until(s, p, a, span_s * SEC) for s, p, a in worlds]
    jax.block_until_ready(outs)
    wall = time.perf_counter() - t0
    graphs = engine.run_until._cache_size() - jit_before
    m = profiler.metrics()
    trace.install(None)
    sent = [int(out.hosts.pkts_sent.sum()) for out in outs]
    for out in outs:
        assert int(out.err) == 0, f"err flags {int(out.err)}"
    assert graphs <= len(buckets), (
        f"bucket sweep compiled {graphs} run_until graphs for "
        f"{len(buckets)} bucket(s): shape bucketing is broken")
    return {
        "world_sizes": list(sizes),
        "buckets": sorted(buckets),
        "run_until_graphs": graphs,
        "compiles": m["compiles"],
        "compile_ms": m["compile_ms"],
        "wall_seconds": round(wall, 3),
        "pkts_sent": sent,
    }


def rung_ensemble(n_worlds: int = 8, num_hosts: int = 1024,
                  span_s: int = 1):
    """N phold worlds as ONE vmapped batch (shadow1_tpu/ensemble) vs
    the same N worlds run solo back to back.  Asserts (a) the whole
    ensemble costs at most ONE ensemble.run_until graph beyond warmup
    and (b) the batched wall time beats N sequential solo runs -- the
    two properties the world axis exists to provide (docs/ensemble.md).
    The wall gate applies on accelerator backends only: a TPU/GPU fills
    its idle lanes with the world axis, but XLA CPU executes the batch
    as wider serial vector work, so ensemble-vs-sequential wall there
    measures vectorization overhead, not batching (the same reason
    rung 8 asserts bitwise equality on CPU and leaves its rate
    informational).  The graph-count gate applies everywhere."""
    from shadow1_tpu import ensemble

    # Slab 16: per-world seeds explore different burst shapes, and the
    # deepest of 8 trajectories must still fit the shared pool (world 2
    # of the default seed overflows a x8 slab).
    kw = dict(num_hosts=num_hosts, pool_capacity=num_hosts * 16,
              msgs_per_host=4, rx_batch=2,
              stop_time=(span_s + 1) * SEC)
    worlds = ensemble.replicate(sim.build_phold, n_worlds, seed=1, **kw)
    estate, eparams, app = ensemble.stack(worlds)

    # Warm both paths (compile excluded from the measured spans).
    warm_e = ensemble.run_until(estate, eparams, app, SEC // 100)
    s0, p0, a0 = worlds[0]
    # stack() pins megakernel off; the solo comparator must run the
    # same graph flavor or the wall ratio measures the kernel, not the
    # world axis.
    p0 = p0.replace(megakernel=False)
    warm_s = engine.run_until(s0, p0, a0, SEC // 100)
    jax.block_until_ready((warm_e, warm_s))

    graphs0 = ensemble.cache_size()
    t0 = time.perf_counter()
    out_e = ensemble.run_until(warm_e, eparams, app, span_s * SEC)
    jax.block_until_ready(out_e)
    wall_ens = time.perf_counter() - t0
    graphs = ensemble.cache_size() - graphs0
    assert graphs <= 1, (
        f"ensemble sweep compiled {graphs} extra run_until graph(s): "
        f"one graph must serve every world")

    t0 = time.perf_counter()
    outs = []
    for s, p, a in worlds:
        outs.append(engine.run_until(
            s, p.replace(megakernel=False), a, span_s * SEC))
    jax.block_until_ready(outs)
    wall_solo = time.perf_counter() - t0

    for k in range(n_worlds):
        assert int(out_e.err[k]) == 0, \
            f"world {k} err flags {int(out_e.err[k])}"
    if jax.default_backend() != "cpu":
        assert wall_ens < wall_solo, (
            f"{n_worlds}-world ensemble took {wall_ens:.2f}s vs "
            f"{wall_solo:.2f}s for {n_worlds} sequential solo runs: "
            f"the world axis is not batching")
    return {
        "backend": jax.default_backend(),
        "wall_gated": jax.default_backend() != "cpu",
        "n_worlds": n_worlds,
        "num_hosts": num_hosts,
        "run_until_graphs": graphs,
        "wall_ensemble_s": round(wall_ens, 3),
        "wall_solo_sequential_s": round(wall_solo, 3),
        "speedup_vs_sequential": round(wall_solo / wall_ens, 2),
        "events": [int(out_e.n_events[k]) for k in range(n_worlds)],
    }


def rung_persistent(num_hosts: int = 1024, span_s: int = 2):
    """Phold through the persistent window kernel (K_WINDOW): the
    measured span must reuse the warmup's single compiled run_until
    graph (zero new compiles), the trajectory must be bitwise
    leaf-for-leaf equal to the reference oracle (megakernel off), and
    the per-window launch surface -- tools/kernelcount.py `launches`,
    the top-level op count of the run_until while-body -- must be
    collapsed >= 5x vs the per-phase fused graph (docs/megakernel.md,
    PERF.md round 10)."""
    import importlib.util
    import pathlib

    import numpy as np

    from shadow1_tpu.core import megakernel as mk

    s, p, a = sim.build_phold(num_hosts=num_hosts, msgs_per_host=4,
                              stop_time=(span_s + 1) * SEC,
                              pool_capacity=num_hosts * 8, rx_batch=2)
    assert p.persistent and mk.persistent_enabled(s, p, a), \
        "persistent window kernel did not engage on the ladder world"

    warm = engine.run_until(s, p, a, SEC // 100)
    jax.block_until_ready(warm)
    jit_before = engine.run_until._cache_size()
    t0 = time.perf_counter()
    out = engine.run_until(warm, p, a, span_s * SEC)
    jax.block_until_ready(out)
    wall = time.perf_counter() - t0
    graphs = engine.run_until._cache_size() - jit_before
    assert graphs == 0, (
        f"measured span compiled {graphs} extra run_until graph(s): "
        f"the persistent path must reuse the warmup's one graph")
    assert int(out.err) == 0, f"err flags {int(out.err)}"

    # Same warm-then-span schedule: stopping at the warm horizon clamps
    # a window there, so a straight run would chunk windows differently
    # (legitimately different bookkeeping, not a divergence).
    pref = p.replace(megakernel=False)
    ref = engine.run_until(s, pref, a, SEC // 100)
    ref = engine.run_until(ref, pref, a, span_s * SEC)
    la, _ta = jax.tree_util.tree_flatten(out)
    lb, _tb = jax.tree_util.tree_flatten(ref)
    assert len(la) == len(lb), "persistent/reference leaf count diverged"
    for i, (x, y) in enumerate(zip(la, lb)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (
            f"persistent trajectory diverged from reference at leaf {i}")

    spec = importlib.util.spec_from_file_location(
        "kernelcount",
        pathlib.Path(__file__).resolve().parent / "kernelcount.py")
    kc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kc)
    per = kc.phase_counts(megakernel=True, persistent=True)["run_until"]
    fused = kc.phase_counts(megakernel=True,
                            persistent=False)["run_until"]
    assert per["n_pallas"] == 1, per
    assert per["launches"] * 5 <= fused["launches"], (
        f"launch surface not collapsed >= 5x: persistent "
        f"{per['launches']} vs fused {fused['launches']}")
    return {
        "num_hosts": num_hosts,
        "sim_seconds": span_s,
        "wall_seconds": round(wall, 3),
        "sim_per_wall": round(span_s / wall, 3),
        "microsteps": int(out.n_steps),
        "run_until_graphs_measured_span": graphs,
        "bitwise_vs_reference": True,
        "launches_persistent": per["launches"],
        "launches_fused": fused["launches"],
        "launch_reduction_x": round(fused["launches"]
                                    / max(1, per["launches"]), 1),
    }


def main(rungs):
    unknown = set(rungs) - {"1", "2", "3", "4", "5", "6", "7", "8", "9",
                            "10", "11"}
    if unknown:
        raise SystemExit(f"unknown ladder rungs: {sorted(unknown)}")
    results = {"backend": jax.default_backend()}

    def record(name, fn):
        results[name] = fn()
        print(json.dumps({name: results[name]}), flush=True)

    if "1" in rungs:
        # warm to 2s: the 2-host example's client starts at t=2.
        record("tgen_2host",
               lambda: rung_tgen("examples/tgen-2host/shadow.config.xml",
                                 warm_s=2))
    if "2" in rungs:
        # warm to 5s: the 100-host example's web clients start at t=5.
        record("tgen_100host",
               lambda: rung_tgen("examples/tgen-100host/shadow.config.xml",
                                 warm_s=5))
    if "3" in rungs:
        record("onion_1k", lambda: rung_onion(200))
    if "4" in rungs:
        record("phold_16k", rung_phold)
    if "5" in rungs:
        # slab 64 halves pool-overflow drops vs 32 (fewer retransmits ->
        # the SACK fast path stays on): 0.537x vs 0.451x measured r4.
        record("onion_10k", lambda: rung_onion(2000, pool_slab=64))
    if "6" in rungs:
        record("gossip_500", rung_gossip)
    if "7" in rungs:
        record("phold_16k_churn", rung_phold_churn)
    if "8" in rungs:
        record("phold_multichip", rung_multichip)
    if "9" in rungs:
        record("phold_buckets", rung_buckets)
    if "10" in rungs:
        record("phold_ensemble", rung_ensemble)
    if "11" in rungs:
        record("phold_persistent", rung_persistent)
    print(json.dumps(results))


if __name__ == "__main__":
    main(sys.argv[1:] or ["1", "2", "3", "5", "6"])
