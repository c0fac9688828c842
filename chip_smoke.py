"""Bring-up smoke test: the simulator's main path, once, on a TPU chip.

    python chip_smoke.py                # one chip: phases (a)-(e)
    python chip_smoke.py --four-chips   # four chips: the sharded phase only

Everything runs in this one process (a chip belongs to one process), on
whatever `jax.devices()` gives, through the entry points a user calls:

  (a) phold at bench.py's size through `sim.run`;
  (b) the CLI's `run` on examples/tgen-2host, in-process (`cli.main`);
  (c) the 10k-host onion world (ladder rung 5) for 3 simulated seconds;
  (d) the resident run server (`server.Server` threads) answering three
      same-shape phold requests from the socket client (`client.py`);
  (e) determinism: a 256-host phold run twice on the chip must be
      bitwise equal; its agreement with the same run on the host CPU
      backend is printed, not gated.

With --four-chips only the sharded path runs: phold at 65,536 hosts
through `sim.run(devices=4)`, bitwise against the same world on one
chip, with the bytes each device holds.

The graphs of (a), (c) and (e) compile in threads while (b) and (d)
run: each is warmed by a `sim.run` call for one simulated nanosecond
(its compile_s), and the phase's own call then finds it compiled.  Each
phase prints one JSON line with its checks; any failed phase exits
nonzero.  Without a TPU the script exits nonzero before any work.  The
last line of a passing run is exactly
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
These are bring-up numbers, not benchmark cells.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import tempfile
import threading
import time

# Phase (e) compares against the host CPU backend and world assembly
# builds on it (shadow1_tpu.build_on_host), so a platform list that
# names the TPU alone gets the CPU added.
_plats = os.environ.get("JAX_PLATFORMS", "")
if _plats and "cpu" not in _plats.split(","):
    os.environ["JAX_PLATFORMS"] = _plats + ",cpu"

import jax  # noqa: E402

REPO = os.path.dirname(os.path.abspath(__file__))

# References from CPU runs of the same worlds (PERF.md, PR 21:
# JAX_PLATFORMS=cpu, the builders' default seed 1).  Phase (a)'s phold
# drops 16 of 65,536 messages at inbox overflow on the CPU and 17 on
# the chip (the backends' trajectories differ, phase (e)); the bound is
# four times the CPU's count.  Phase (c)'s onion world is integer TCP
# and matches the CPU exactly.
PHOLD_MAX_DROPPED = 64
ONION_CPU = {"bytes_delivered": 8_388_608_000, "drops_pool": 84_000}


def _emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def _leaf_diffs(a, b) -> list[str]:
    import numpy as np
    fa, ta = jax.tree_util.tree_flatten_with_path(a)
    fb, tb = jax.tree_util.tree_flatten_with_path(b)
    if ta != tb:
        return ["<pytree structure>"]
    return [jax.tree_util.keystr(p) for (p, x), (_q, y) in zip(fa, fb)
            if not np.array_equal(np.asarray(x), np.asarray(y))]


def _phold_world(hosts, sim_seconds, msgs=4):
    """bench.py's phold shape: 10 ms mean delay, pool H x 8, rx_batch 2."""
    from shadow1_tpu import sim
    from shadow1_tpu.core import simtime
    MS = simtime.SIMTIME_ONE_MILLISECOND
    return sim.build_phold(
        num_hosts=hosts, msgs_per_host=msgs, mean_delay_ns=10 * MS,
        stop_time=int(sim_seconds * simtime.SIMTIME_ONE_SECOND),
        pool_capacity=hosts * 8, rx_batch=2)


def _warm(world, **kw) -> float:
    """Compile the graph `sim.run(*world, **kw)` runs, by running it for
    one simulated nanosecond; returns the seconds that took."""
    from shadow1_tpu import sim
    t0 = time.perf_counter()
    jax.block_until_ready(sim.run(*world, until=1, **kw))
    return time.perf_counter() - t0


def _phold_counts(out, hosts: int, msgs: int) -> dict:
    a = out.app
    inflight = int((out.pool.stage != 0).sum()) + \
        int((out.inbox.stage != 0).sum())
    queued = int(out.socks.udp_count.sum())
    return {"sent": int(a.sent.sum()), "recv": int(a.recv.sum()),
            "population": int(a.pending.sum()) + inflight + queued,
            "expected_population": hosts * msgs,
            "dropped": int(out.hosts.pkts_dropped_inet.sum())
            + int(out.hosts.pkts_dropped_pool.sum()),
            "err": int(out.err)}


def _overflow_only(err: int) -> bool:
    """err is 0 or the pool-overflow bit alone (a counted drop)."""
    from shadow1_tpu.core.state import ERR_POOL_OVERFLOW
    return err in (0, ERR_POOL_OVERFLOW)


def phase_phold(world, compile_s, sim_seconds=2, msgs=4) -> dict:
    """(a) bench.py's world through sim.run for 2 simulated seconds.
    A few destination inbox slabs (8 slots a host) overflow at this
    size, on the CPU too: such a message is a counted drop and raises
    ERR_POOL_OVERFLOW.  So err may hold that bit and no other, the drops
    stay within PHOLD_MAX_DROPPED, and population + drops == hosts x
    msgs."""
    from shadow1_tpu import sim
    from shadow1_tpu.core import simtime
    SEC = simtime.SIMTIME_ONE_SECOND
    state, params, app = world
    hosts = int(state.hosts.num_hosts)
    t0 = time.perf_counter()
    out = jax.block_until_ready(
        sim.run(state, params, app, until=sim_seconds * SEC))
    wall = time.perf_counter() - t0
    c = _phold_counts(out, hosts, msgs)
    events = c["sent"] + c["recv"]
    ok = (_overflow_only(c["err"]) and events > 0
          and c["dropped"] <= PHOLD_MAX_DROPPED
          and c["population"] + c["dropped"] == c["expected_population"]
          and int(out.now) == sim_seconds * SEC)
    return {"phase": "a_phold", "ok": ok, "hosts": hosts,
            "sim_seconds": sim_seconds, "events": events,
            "compile_s": compile_s, "wall_s": wall,
            "events_per_wall_s": events / wall,
            "max_dropped": PHOLD_MAX_DROPPED, **c}


def phase_cli(config="examples/tgen-2host/shadow.config.xml",
              stop_time="5") -> dict:
    """(b) `shadow1-tpu run` in-process; the CPU's known summary at
    --stop-time 5 is 557 packets and 2 completed streams."""
    from shadow1_tpu import cli
    buf = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(buf):
        rc = cli.main(["run", os.path.join(REPO, config),
                       "--stop-time", stop_time])
    wall = time.perf_counter() - t0
    lines = [ln for ln in buf.getvalue().splitlines() if ln.startswith("{")]
    summ = json.loads(lines[-1]) if lines else {}
    ok = (rc == 0 and summ.get("packets_sent") == 557
          and summ.get("streams_completed") == 2
          and summ.get("err_flags") == 0)
    return {"phase": "b_cli_tgen_2host", "ok": ok, "rc": rc,
            "wall_s": wall, "summary": summ}


def _onion_world(circuits=2000, pool_slab=64):
    from shadow1_tpu import sim
    return sim.build_onion(circuits, pool_slab=pool_slab)


def phase_onion(world, compile_s, sim_seconds=3) -> dict:
    """(c) The 10k-host onion world (TCP relay chains).  Its relays
    overflow their pool slabs by design: the overflow is counted as
    drops and raises ERR_POOL_OVERFLOW, so err may hold that bit and no
    other, and bytes delivered and drops must match the CPU's
    (ONION_CPU)."""
    from shadow1_tpu import sim
    from shadow1_tpu.core import simtime
    SEC = simtime.SIMTIME_ONE_SECOND
    state, params, app = world
    t0 = time.perf_counter()
    out = jax.block_until_ready(
        sim.run(state, params, app, until=sim_seconds * SEC))
    wall = time.perf_counter() - t0
    got = {"bytes_delivered": int(out.socks.bytes_recv.sum()),
           "drops_pool": int(out.hosts.pkts_dropped_pool.sum())}
    err = int(out.err)
    return {"phase": "c_onion", "ok": _overflow_only(err) and got == ONION_CPU,
            "hosts": int(state.hosts.num_hosts),
            "pool_slab": state.pool.capacity // state.hosts.num_hosts,
            "sim_seconds": sim_seconds, "compile_s": compile_s,
            "wall_s": wall, **got, "cpu_reference": ONION_CPU, "err": err}


def phase_server(hosts=1024, requests=3) -> dict:
    """(d) The resident server in this process, driven by the socket
    client: `requests` same-shape phold submissions, each rc 0."""
    from shadow1_tpu import cli, server
    from shadow1_tpu.core import simtime
    kw = {"num_hosts": hosts, "msgs_per_host": 4, "seed": 11,
          "stop_time": simtime.SIMTIME_ONE_SECOND}
    rcs = [None] * requests
    buf = io.StringIO()
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as d:
        srv = server.Server(d, workers=1, quiet=True).start()
        try:
            def submit(i):
                rcs[i] = cli.main(["submit", "--server", d, "--world",
                                   "phold", "--world-kwargs",
                                   json.dumps(kw), "--quiet"])
            t0 = time.perf_counter()
            with contextlib.redirect_stdout(buf):
                threads = [threading.Thread(target=submit, args=(i,))
                           for i in range(requests)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=900)
            wall = time.perf_counter() - t0
        finally:
            srv.shutdown()
    return {"phase": "d_server", "ok": rcs == [0] * requests,
            "requests": requests, "hosts": hosts, "rcs": rcs,
            "wall_s": wall}


def phase_determinism(world, compile_s) -> dict:
    """(e) The same world twice on the chip (gated: bitwise equal), and
    once on the host CPU backend from the same initial state (printed,
    not gated: agreement; where they differ, the first divergent window
    and field group of the digest streams, then both backends re-run to
    that window's end and element-compared, as `shadow1-tpu diff`
    localizes)."""
    from shadow1_tpu import diff as diff_mod, sim, trace
    state, params, app = world
    outs = [sim.run(state, params, app, digest=1) for _ in range(2)]
    cpu = jax.devices("cpu")[0]
    on_cpu = jax.device_put((state, params), cpu)
    out_cpu = sim.run(on_cpu[0], on_cpu[1], app, digest=1)
    chip_diffs = _leaf_diffs(outs[0], outs[1])
    cpu_diffs = _leaf_diffs(outs[0], out_cpu)
    rec = {"phase": "e_determinism", "ok": not chip_diffs,
           "hosts": int(state.hosts.num_hosts), "compile_s": compile_s,
           "chip_runs_bitwise_equal": not chip_diffs,
           "chip_diff_leaves": chip_diffs[:8],
           "cpu_bitwise_equal": not cpu_diffs,
           "cpu_diff_leaves": cpu_diffs[:8],
           "cpu_diff_leaf_count": len(cpu_diffs)}
    if cpu_diffs:
        with tempfile.TemporaryDirectory(prefix="chip-smoke-dg-") as d:
            dirs = []
            for name, out in (("chip", outs[0]), ("cpu", out_cpu)):
                dirs.append(os.path.join(d, name))
                os.makedirs(dirs[-1])
                dd = trace.DigestDrain(os.path.join(dirs[-1],
                                                    "digests.jsonl"))
                dd.drain(out)
                dd.close()
            div = diff_mod.diff_runs(dirs[0], dirs[1],
                                     localize=False)["divergence"]
        first = None
        if div is not None:
            t_end = min(int(div["t_end"]["a"]), int(div["t_end"]["b"]))
            at = [sim.run(s, p, app, until=t_end, digest=1)
                  for s, p in ((state, params), on_cpu)]
            loc = diff_mod.compare_states(*at, div["group"],
                                          max_elements=2)
            first = {"window": div["window"], "group": div["group"],
                     "t_end_ns": t_end,
                     "groups_differing": loc["groups_differing"],
                     "fields_differing": [f["field"]
                                          for f in loc["fields"]],
                     "first_field": (loc["fields"] or [None])[0]}
        rec["cpu_first_divergence"] = first
    return rec


def _bytes_per_device(tree) -> dict:
    per: dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for sh in getattr(leaf, "addressable_shards", []):
            k = str(sh.device.id)
            per[k] = per.get(k, 0) + int(sh.data.nbytes)
    return per


def phase_mesh(hosts=65536, devices=4, sim_seconds=0.5) -> dict:
    """--four-chips: the sharded world (sim.run(devices=4)) against the
    same world on one chip, leaf for leaf (docs/parallel.md).  The two
    graphs compile in two threads; the runs then go one after the
    other."""
    from concurrent.futures import ThreadPoolExecutor

    from shadow1_tpu import sim
    world = _phold_world(hosts, sim_seconds)
    with ThreadPoolExecutor(2) as pool:
        c_one = pool.submit(_warm, world)
        c_mesh = pool.submit(_warm, world, devices=devices)
        compile_one, compile_mesh = c_one.result(), c_mesh.result()

    def timed(**kw):
        t0 = time.perf_counter()
        out = jax.block_until_ready(sim.run(*world, **kw))
        return out, time.perf_counter() - t0

    one, wall_one = timed()
    sharded, wall_mesh = timed(devices=devices)
    resident = _bytes_per_device(sharded)
    diffs = _leaf_diffs(one, sharded)
    events = int(one.app.sent.sum()) + int(one.app.recv.sum())
    ok = (not diffs and events > 0 and len(resident) == devices)
    return {"phase": "mesh", "ok": ok, "hosts": hosts, "devices": devices,
            "sim_seconds": sim_seconds, "events": events,
            "err": int(one.err), "bitwise_equal": not diffs,
            "diff_leaves": diffs[:8], "bytes_per_device": resident,
            "compile_s_one_chip": compile_one,
            "compile_s_mesh": compile_mesh,
            "wall_s_one_chip": wall_one, "wall_s_mesh": wall_mesh}


def _run_phases(phases) -> bool:
    ok = True
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            rec = phase()
        except Exception as e:  # noqa: BLE001 - report, then fail the run
            import traceback
            traceback.print_exc()
            rec = {"phase": name, "ok": False,
                   "error": f"{type(e).__name__}: {e}"[:2000]}
        rec["phase_wall_s"] = time.perf_counter() - t0
        _emit(rec)
        ok &= bool(rec["ok"])
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded phase, on four chips")
    args = ap.parse_args(argv)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU here (JAX found {devs[0].platform}); "
              f"refusing to run", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke: {need} TPU devices needed, {len(devs)} "
              f"found", file=sys.stderr)
        return 1
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    _emit({"device": device, "jax": jax.__version__,
           "jax_platforms": os.environ.get("JAX_PLATFORMS")})

    import shadow1_tpu  # noqa: F401  (x64; nothing runs before this)

    if args.four_chips:
        ok = _run_phases([("mesh", phase_mesh)])
    else:
        from concurrent.futures import ThreadPoolExecutor

        w_a = _phold_world(16384, 2)
        w_c = _onion_world()
        w_e = _phold_world(256, 1)
        with ThreadPoolExecutor(3) as pool:
            c_a = pool.submit(_warm, w_a)
            c_c = pool.submit(_warm, w_c)
            c_e = pool.submit(_warm, w_e, digest=1)
            # (b) and (d) compile their own graphs meanwhile.
            ok = _run_phases([
                ("b_cli_tgen_2host", phase_cli),
                ("d_server", phase_server),
                ("a_phold", lambda: phase_phold(w_a, c_a.result())),
                ("c_onion", lambda: phase_onion(w_c, c_c.result())),
                ("e_determinism",
                 lambda: phase_determinism(w_e, c_e.result())),
            ])
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
