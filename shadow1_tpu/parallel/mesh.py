"""Explicit sharded execution: the window loop under `shard_map`.

`sharded_run_until` (sharding.py) lets GSPMD infer collectives from
input shardings -- fine for correctness, but the compiler re-derives the
communication pattern of the boundary exchange from a scatter into a
fully-sharded inbox, and the loop-carried reductions get re-partitioned
per iteration.  `mesh_run_until` instead runs the engine's window loop
INSIDE `jax.shard_map` on a 1-D `hosts` mesh with
hand-placed collectives, mirroring the reference's explicit scheduler
protocol (/root/reference/src/main/core/scheduler/scheduler.c:359-414):

* hosts partition contiguously: shard k owns global hosts
  [k*h, (k+1)*h).  Every host/pool/inbox-leading leaf shards that axis;
  the engine body sees an ordinary (smaller) world plus `state.hoff`,
  the shard's global row offset.
* the window advance `jnp.min(t_h)` gets a cross-shard `pmin` (the
  reference's master window-advance reduction, master.c:450-480);
* the boundary exchange becomes a dst-bucketed `all_to_all` over
  superblock ranks followed by the unchanged local splice
  (engine._exchange_body_mesh);
* per-host params ride in PRE-SLICED via in_specs (so the engine's
  token-bucket/CPU/autotune code is untouched); `host_vertex` and
  `route_blk` stay replicated because packets carry GLOBAL ids end to
  end -- only slab addressing is local.

Determinism contract: docs/parallel.md.  Every cross-shard decision
(slot assignment, overflow choice, ACK-shed regime, window trip counts)
is reduced to a canonical global order or a uniform predicate before
use, so a world that divides the mesh runs leaf-for-leaf bitwise
identical on 1, 2, 4, or 8 shards, for any chunking.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import trace
from ..core import engine
from .sharding import (HOST_AXIS, PARAM_SPECS, _leaf_name, make_mesh,
                       pad_world_to_mesh)

I32 = jnp.int32
I64 = jnp.int64

# Per-host param leaves that enter the shard_map body pre-sliced to the
# shard's rows.  host_vertex and route_blk are deliberately NOT here:
# emission stamps global vertex ids and the routing gather is keyed by
# (src_vertex, dst_vertex) of arbitrary remote hosts, so both stay
# replicated under the explicit mesh (unlike the GSPMD path, which may
# shard route_blk rows and let the compiler insert the gather
# collective).
_PARAM_LOCAL = frozenset(
    name for name, spec in PARAM_SPECS.items() if spec == P(HOST_AXIS)
) - {"route_blk", "host_vertex"}


def _state_specs(state):
    """Partition specs for a SimState: shard every leaf whose leading
    axis is the host axis (host tables, both packet pools, [H]-leading
    app leaves); replicate scalars, telemetry, and the whole netem block
    (route_overlay gathers by GLOBAL src/dst, and the event schedule
    must advance identically on every shard)."""
    h = state.hosts.num_hosts
    host_rows = {h, state.pool.capacity, state.inbox.capacity}

    def spec(path, leaf):
        name = getattr(path[0], "name", "")
        if name in ("nm", "fr", "sentinel", "dg"):
            # Replicated blocks: netem gathers by global ids; the flight
            # recorder, the invariant sentinel, and the digest ring
            # compute identical values on every shard from psum/pmin/
            # all_gather-reduced inputs (engine._fr_record /
            # engine._sentinel_check / engine._digest_record).
            return P()
        if name in ("log", "cap", "scope", "lineage"):
            # Sharded observability rings (make_log_ring/make_capture_ring
            # /make_flowscope/make_lineage with shards=D): slot arrays
            # partition into per-shard segments and the [D] cursors into
            # per-shard scalars, so each shard appends independently;
            # observe.LogDrain / write_pcap / trace.ScopeDrain /
            # trace.LineageDrain merge the segments in sim-time order.
            # The cadence/config scalars (flowscope interval/next_due/
            # samples, lineage rate_x1p32/n_assigned) are 0-d and
            # replicate, keeping every cond collective-safe.  The
            # lineage pool_id/inbox_id side arrays are [P0]/[P1]-leading
            # and shard with their pools via the host_rows rule below --
            # this branch's ndim>=1 test covers them identically.
            if hasattr(leaf, "ndim") and leaf.ndim >= 1:
                return P(HOST_AXIS)
            return P()
        if hasattr(leaf, "ndim") and leaf.ndim >= 1 \
                and leaf.shape[0] in host_rows:
            return P(HOST_AXIS)
        return P()

    return jax.tree_util.tree_map_with_path(spec, state)


def _param_specs(params):
    def spec(path, leaf):
        return P(HOST_AXIS) if _leaf_name(path) in _PARAM_LOCAL else P()

    return jax.tree_util.tree_map_with_path(spec, params)


# (app, mesh, treedefs, specs) -> jitted shard_map entry.  jit's own
# signature cache handles shape changes within a key.
_MESH_CACHE: dict = {}


def _build(app, mesh, sspecs, pspecs):
    n_shards = mesh.devices.size

    def body(state, params, t_target):
        h = state.hosts.num_hosts  # shard-local rows
        hoff = (jax.lax.axis_index(HOST_AXIS) * h).astype(I32)
        st = state.replace(hoff=hoff)
        n_ev0 = st.n_events
        tr0 = st.tr
        killed0 = None if st.nm is None else st.nm.killed
        ln0 = None if st.lineage is None else st.lineage.n_assigned

        st = engine.run_until_impl(st, params, app, t_target)

        # Finalize cross-shard aggregates so every shard returns the
        # IDENTICAL value for every replicated leaf (out_specs P() with
        # check_vma=False trusts, but does not create, replication):
        # counters entered replicated, so global = start + psum(delta);
        # err is a bitmask -> all_gather + OR (psum would double-count
        # bits, pmax would drop them).  now/n_steps/n_windows/exchanges
        # are uniform for free: every loop predicate is pmin/pmax'd, so
        # all shards run identical trip counts.
        with trace.phase("close"):
            errs = jax.lax.all_gather(st.err, HOST_AXIS)
            err = errs[0]
            for i in range(1, n_shards):
                err = err | errs[i]
            st = st.replace(
                err=err,
                n_events=n_ev0 + jax.lax.psum(st.n_events - n_ev0,
                                              HOST_AXIS))
            if killed0 is not None:
                st = st.replace(nm=st.nm.replace(
                    killed=killed0
                    + jax.lax.psum(st.nm.killed - killed0, HOST_AXIS)))
            if tr0 is not None:
                st = st.replace(tr=st.tr.replace(
                    pkts_exchanged=tr0.pkts_exchanged + jax.lax.psum(
                        st.tr.pkts_exchanged - tr0.pkts_exchanged,
                        HOST_AXIS),
                    occ_max=engine.mesh_max(st.tr.occ_max)))
            if ln0 is not None:
                st = st.replace(lineage=st.lineage.replace(
                    n_assigned=ln0 + jax.lax.psum(
                        st.lineage.n_assigned - ln0, HOST_AXIS)))
        return st.replace(hoff=None)

    # The compile record (trace.compile_spans) names this function's
    # trace, lowering and compile after it.
    body.__name__ = body.__qualname__ = "mesh_run_until"
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(sspecs, pspecs, P()),
        out_specs=sspecs, check_vma=False))


def _place(mesh, tree, specs):
    """Lay `tree` out as a shard_map body with these in_specs expects.
    A world assembled on an accelerator arrives committed to
    jax.devices()[0] (shadow1_tpu.build_on_host), which jit refuses to
    mix with a multi-device mesh; leaves already laid out (the outputs
    of an earlier launch) do not move."""
    return jax.device_put(tree, jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), specs,
        is_leaf=lambda x: isinstance(x, P)))


def mesh_run_until(state, params, app, t_target, mesh=None):
    """Run the engine to t_target with hosts sharded over `mesh`.

    The world must DIVIDE the mesh (host count a multiple of the device
    count; state and params agreeing on it) -- pad first with
    parallel.pad_world_to_mesh(state, params, n_devices) if it doesn't.
    Capture/log rings must be built in the sharded layout
    (make_capture_ring/make_log_ring with shards=n_devices: per-shard
    segments + cursors); a flight recorder must be installed with
    matching shards (trace.ensure_flight_recorder).

    Returns the state fully finalized (global counters, hoff stripped),
    so chunked runs are just repeated calls."""
    if mesh is None:
        mesh = make_mesh()
    d = mesh.devices.size
    if state.hoff is not None:
        raise ValueError("mesh_run_until: state.hoff is set -- already "
                         "inside a mesh shard?")
    for ring, label, maker in ((state.cap, "capture", "make_capture_ring"),
                               (state.log, "log", "make_log_ring")):
        if ring is None:
            continue
        shards = ring.total.shape[0] if ring.total.ndim == 1 else 1
        if shards != d or ring.capacity % d != 0:
            raise ValueError(
                f"mesh_run_until: the {label} ring was built for "
                f"{shards} shard(s) but the mesh has {d} devices; build "
                f"it with core.state.{maker}(capacity, shards={d}) so "
                f"every shard gets its own segment and cursor")
    if state.fr is not None and state.fr.n_shards != d:
        raise ValueError(
            f"mesh_run_until: flight recorder built for "
            f"{state.fr.n_shards} shard(s) but the mesh has {d} devices; "
            f"install it with trace.ensure_flight_recorder(state, "
            f"shards={d})")
    if state.scope is not None and state.scope.n_shards != d:
        raise ValueError(
            f"mesh_run_until: flowscope built for "
            f"{state.scope.n_shards} shard(s) but the mesh has {d} "
            f"devices; install it with trace.ensure_flowscope(state, "
            f"shards={d}) so every shard gets its own ring segment")
    if state.lineage is not None and state.lineage.n_shards != d:
        raise ValueError(
            f"mesh_run_until: lineage tracer built for "
            f"{state.lineage.n_shards} shard(s) but the mesh has {d} "
            f"devices; install it with trace.ensure_lineage(state, "
            f"shards={d}) so every shard gets its own span-ring segment")
    if state.dg is not None and state.dg.n_shards != d:
        raise ValueError(
            f"mesh_run_until: digest block built for "
            f"{state.dg.n_shards} shard(s) but the mesh has {d} devices; "
            f"install it with trace.ensure_digests(state, shards={d}) so "
            f"the per-shard checksum columns match the mesh")
    h = state.hosts.num_hosts
    hp = params.host_vertex.shape[0]
    if hp != h:
        raise ValueError(
            f"mesh_run_until: params built for {hp} hosts but state has "
            f"{h}; pad them together with "
            f"parallel.pad_world_to_mesh(state, params, {d})")
    if h % d != 0:
        raise ValueError(
            f"mesh_run_until: {h} hosts do not divide {d} devices; pad "
            f"the world first with "
            f"parallel.pad_world_to_mesh(state, params, {d})")

    sspecs = _state_specs(state)
    pspecs = _param_specs(params)
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    key = (app, mesh,
           jax.tree_util.tree_structure((state, params)),
           tuple(map(str, jax.tree_util.tree_leaves(sspecs,
                                                    is_leaf=is_spec))),
           tuple(map(str, jax.tree_util.tree_leaves(pspecs,
                                                    is_leaf=is_spec))))
    fn = _MESH_CACHE.get(key)
    if fn is None:
        fn = _build(app, mesh, sspecs, pspecs)
        _MESH_CACHE[key] = fn
    state, params = _place(mesh, (state, params), (sspecs, pspecs))
    with mesh:
        return fn(state, params, jnp.asarray(t_target, I64))


def mesh_run_chunked(state, params, app, t_target: int, mesh=None,
                     chunk_ns: int = engine.CHUNK_NS):
    """Host-side loop of bounded mesh launches (engine.run_chunked's mesh
    twin); chunking is trajectory-invariant -- see docs/parallel.md.

    When a profiler is active (trace.install), each launch records a
    `device_step` span exactly like the single-device launcher, so
    metrics.json phase tables are comparable across device counts."""
    if mesh is None:
        mesh = make_mesh()
    t = int(state.now)
    t_target = int(t_target)
    prof = trace.current()
    while t < t_target:
        t = min(t + chunk_ns, t_target)
        with prof.span("device_step", t_ns=t):
            state = mesh_run_until(state, params, app, t, mesh=mesh)
            if prof.sync:
                jax.block_until_ready(state)
    return state


def exchange_probe_ms(state, params, mesh, reps: int = 5) -> float:
    """Median wall-clock milliseconds of ONE boundary-exchange pass
    (shard rank + tiled all_to_all + local splice) on `mesh`.

    The send buffer is fixed-size (every shard always ships d blocks of
    its full local pool capacity), so the collective's cost is mover-
    count independent -- probing an idle state is representative of any
    window.  bench.py uses this to attribute what share of window time
    the all-to-all costs at each device count."""
    import time as _time

    sspecs = _state_specs(state)
    pspecs = _param_specs(params)

    def body(st, pr):
        h = st.hosts.num_hosts
        hoff = (jax.lax.axis_index(HOST_AXIS) * h).astype(I32)
        st = engine._exchange_body_mesh(st.replace(hoff=hoff), pr)
        return st.replace(hoff=None)

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(sspecs, pspecs),
                               out_specs=sspecs, check_vma=False))
    state, params = _place(mesh, (state, params), (sspecs, pspecs))
    with mesh:
        jax.block_until_ready(fn(state, params))   # compile + warm
        times = []
        for _ in range(reps):
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(state, params))
            times.append(_time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e3
