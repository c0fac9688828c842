"""High-level simulation assembly.

The reference assembles a run from shadow.config.xml + a GraphML topology
(master.c:161-238, slave_addNewVirtualHost).  This module is the
programmatic equivalent: build params + state + app, then `run`.
The XML/GraphML front end (config/) lowers onto these calls.
"""

from __future__ import annotations

import jax.numpy as jnp

import shadow1_tpu as _pkg

from . import trace
from .apps import bulk as bulk_app
from .apps import phold as phold_app
from .core import engine, simtime
from .core.params import make_net_params
from .core.state import make_sim_state
from .routing.synthetic import uniform_full_mesh
from .transport import udp


def build_phold(num_hosts: int,
                latency_ns: int = 10 * simtime.SIMTIME_ONE_MILLISECOND,
                reliability: float = 1.0,
                msgs_per_host: int = 1,
                mean_delay_ns: int = 10 * simtime.SIMTIME_ONE_MILLISECOND,
                stop_time: int = simtime.SIMTIME_ONE_SECOND,
                seed: int = 1,
                sock_slots: int = 4,
                pool_capacity: int = 1 << 14,
                bw_up_Bps: int = 1 << 30,
                bw_down_Bps: int = 1 << 30,
                bootstrap_end: int = 0,
                rx_batch: int = 1):
    """A phold benchmark world on a uniform full-mesh topology.

    The topology is capped at 256 vertices with hosts striped across them
    (all pair latencies are identical anyway), so the [V,V] routing
    matrices stay small however many hosts the benchmark scales to.

    rx_batch > 1 enables arrival batching (faster, but the trajectory is
    not bitwise-equal to serial stepping; see apps/phold.py).  The
    default is the apples-to-apples serial semantics; benchmark entry
    points opt into batching explicitly."""
    if num_hosts < 2:
        raise ValueError("phold needs at least 2 hosts (every message is "
                         "forwarded to a different host)")
    v = min(num_hosts, 256)

    def _build_params():
        lat, rel = uniform_full_mesh(v, latency_ns, reliability)
        return make_net_params(
            latency_ns=lat,
            reliability=rel,
            host_vertex=jnp.arange(num_hosts) % v,
            bw_up_Bps=jnp.full(num_hosts, bw_up_Bps),
            bw_down_Bps=jnp.full(num_hosts, bw_down_Bps),
            seed=seed,
            stop_time=stop_time,
            bootstrap_end=bootstrap_end,
        )

    params = _pkg.build_on_host(_build_params)
    def _build_state():
        state = make_sim_state(num_hosts, sock_slots=sock_slots,
                               pool_capacity=pool_capacity,
                               uses_tcp=False)
        return state.replace(
            socks=udp.open_bind_all(state.socks, slot=0,
                                    port=phold_app.PHOLD_PORT),
            # rng_ctr starts at 1: counter value 0 is reserved for the
            # initial send-time draws in phold_app.init_state.
            hosts=state.hosts.replace(rng_ctr=state.hosts.rng_ctr + 1),
        )

    state = _pkg.build_on_host(_build_state)
    # App init keys off params.seed_key (already on the default backend),
    # so it runs there -- it is only a handful of ops.
    state = state.replace(app=phold_app.init_state(
        num_hosts, params, msgs_per_host, mean_delay_ns))
    app = phold_app.Phold(mean_delay_ns=mean_delay_ns, sock_slot=0,
                          rx_batch=rx_batch)
    return state, params, app


def build_bulk(num_hosts: int,
               server: int = 0,
               bytes_per_client: int = 1 << 20,
               latency_ns: int = 10 * simtime.SIMTIME_ONE_MILLISECOND,
               reliability: float = 1.0,
               start_time: int = simtime.SIMTIME_ONE_MILLISECOND,
               stop_time: int = 60 * simtime.SIMTIME_ONE_SECOND,
               seed: int = 1,
               sock_slots: int = 16,
               pool_capacity: int = 1 << 14,
               bw_up_Bps: int = 1 << 30,
               bw_down_Bps: int = 1 << 30,
               bootstrap_end: int = 0):
    """Bulk TCP transfers: every host but `server` sends
    `bytes_per_client` to the server (the reference's tgen file-transfer
    bring-up config, resource/examples/shadow.config.xml)."""
    def _build_all():
        lat, rel = uniform_full_mesh(num_hosts, latency_ns, reliability)
        params = make_net_params(
            latency_ns=lat,
            reliability=rel,
            host_vertex=jnp.arange(num_hosts),
            bw_up_Bps=jnp.full(num_hosts, bw_up_Bps),
            bw_down_Bps=jnp.full(num_hosts, bw_down_Bps),
            seed=seed,
            stop_time=stop_time,
            bootstrap_end=bootstrap_end,
        )
        state = make_sim_state(num_hosts, sock_slots=sock_slots,
                               pool_capacity=pool_capacity)
        ids = jnp.arange(num_hosts)
        is_server = ids == server
        state = state.replace(socks=bulk_app.setup_servers(state.socks,
                                                           is_server))
        state = state.replace(app=bulk_app.init_state(
            num_hosts,
            is_client=~is_server,
            dst=jnp.full(num_hosts, server),
            total_bytes=jnp.where(is_server, 0, bytes_per_client),
            start_t=jnp.full(num_hosts, start_time),
        ))
        return state, params

    state, params = _pkg.build_on_host(_build_all)
    app = bulk_app.Bulk()
    return state, params, app


_TGEN_SERVER_XML = """
<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
  <key attr.name="serverport" attr.type="string" for="node" id="k0"/>
  <graph edgedefault="directed">
    <node id="start"><data key="k0">{port}</data></node>
  </graph>
</graphml>"""

_TGEN_CLIENT_XML = """
<graphml xmlns="http://graphml.graphdrawing.org/xmlns">
  <key attr.name="peers" attr.type="string" for="node" id="k0"/>
  <key attr.name="sendsize" attr.type="string" for="node" id="k1"/>
  <key attr.name="recvsize" attr.type="string" for="node" id="k2"/>
  <key attr.name="count" attr.type="string" for="node" id="k3"/>
  <key attr.name="time" attr.type="string" for="node" id="k4"/>
  <graph edgedefault="directed">
    <node id="start"><data key="k0">server:{port}</data></node>
    <node id="stream"><data key="k1">{sendsize}</data>
      <data key="k2">{recvsize}</data></node>
    <node id="end"><data key="k3">{streams}</data></node>
    <node id="pause"><data key="k4">1,2</data></node>
    <edge source="start" target="stream"/>
    <edge source="stream" target="end"/>
    <edge source="end" target="pause"/>
    <edge source="pause" target="start"/>
  </graph>
</graphml>"""


def build_tgen(num_hosts: int,
               server: int = 0,
               sendsize: int = 50 * 1024,
               recvsize: int = 200 * 1024,
               streams: int = 3,
               latency_ns: int = 20 * simtime.SIMTIME_ONE_MILLISECOND,
               reliability: float = 1.0,
               stop_time: int = 120 * simtime.SIMTIME_ONE_SECOND,
               seed: int = 1,
               sock_slots: int = 16,
               pool_slab: int = 32,
               bw_Bps: int = 1 << 27):
    """Programmatic tgen world: one file server + (num_hosts-1) clients
    driving the modeled action-graph interpreter (apps/tgen.py) with the
    examples/tgen-100host graph shape -- each client streams `sendsize`
    up / `recvsize` down `streams` times with 1-2s pauses.  The same
    worlds the XML front end assembles, without the config files: this
    is the canonical flavor `shadow1-tpu warm` compiles for the tgen
    buckets."""
    from .apps import tgen as tgen_app
    from .transport import tcp as tcp_mod
    import numpy as np

    if num_hosts < 2:
        raise ValueError("tgen needs at least 2 hosts (one server plus "
                         "clients)")
    v = min(num_hosts, 256)
    port = 8888
    srv = tgen_app.parse_tgen(_TGEN_SERVER_XML.format(port=port))
    cli = tgen_app.parse_tgen(_TGEN_CLIENT_XML.format(
        port=port, sendsize=int(sendsize), recvsize=int(recvsize),
        streams=int(streams)))
    host_graph = np.full(num_hosts, 1, np.int64)
    host_graph[server] = 0
    start_t = np.full(num_hosts, 5 * simtime.SIMTIME_ONE_SECOND, np.int64)
    start_t[server] = simtime.SIMTIME_ONE_SECOND

    def _build():
        lat, rel = uniform_full_mesh(v, latency_ns, reliability)
        params = make_net_params(
            latency_ns=lat, reliability=rel,
            host_vertex=jnp.arange(num_hosts) % v,
            bw_up_Bps=jnp.full(num_hosts, bw_Bps),
            bw_down_Bps=jnp.full(num_hosts, bw_Bps),
            seed=seed, stop_time=stop_time)
        state = make_sim_state(num_hosts, sock_slots=sock_slots,
                               pool_capacity=num_hosts * pool_slab)
        mask = jnp.arange(num_hosts) == server
        state = state.replace(socks=tcp_mod.listen_v(
            state.socks, mask, 0, port, backlog=num_hosts))
        state = state.replace(app=tgen_app.build_state(
            num_hosts, [srv, cli], host_graph, start_t,
            resolve_peer=lambda s: (server, int(s.rsplit(":", 1)[1]))))
        return state, params

    state, params = _pkg.build_on_host(_build)
    return state, params, tgen_app.Tgen()


def build_gossip(num_hosts: int = 500,
                 degree: int = 12,
                 num_items: int = 32,
                 item_interval_ns: int = 200 * simtime.SIMTIME_ONE_MILLISECOND,
                 latency_ns: int = 40 * simtime.SIMTIME_ONE_MILLISECOND,
                 reliability: float = 1.0,
                 stop_time: int = 30 * simtime.SIMTIME_ONE_SECOND,
                 seed: int = 1,
                 pool_slab: int = 64,
                 bw_Bps: int = 1 << 27):
    """Bitcoin-style gossip world (apps/gossip.py): `num_hosts` nodes on a
    `degree`-peer overlay flooding `num_items` inv/getdata/item exchanges.
    The 500-node rung of the measured ladder (BASELINE config 4)."""
    from .apps import gossip as gossip_app

    v = min(num_hosts, 256)

    def _build():
        lat, rel = uniform_full_mesh(v, latency_ns, reliability)
        params = make_net_params(
            latency_ns=lat, reliability=rel,
            host_vertex=jnp.arange(num_hosts) % v,
            bw_up_Bps=jnp.full(num_hosts, bw_Bps),
            bw_down_Bps=jnp.full(num_hosts, bw_Bps),
            seed=seed, stop_time=stop_time)
        state = make_sim_state(num_hosts, sock_slots=2,
                               pool_capacity=num_hosts * pool_slab,
                               uses_tcp=False)
        state = state.replace(
            socks=udp.open_bind_all(state.socks, slot=0,
                                    port=gossip_app.GOSSIP_PORT))
        state = state.replace(app=gossip_app.init_state(
            num_hosts, degree, num_items, item_interval_ns, seed))
        return state, params

    state, params = _pkg.build_on_host(_build)
    return state, params, gossip_app.Gossip()


def add_churn(state, params, rate_per_s: float,
              mean_down_s: float = 5.0, hosts=None,
              t_start: int = 0, t_end: int | None = None,
              n_events: int | None = None):
    """Install seeded chaos churn on a built world: every selected host
    alternates exponential up-times (mean 1/rate_per_s s) and down-times
    (mean mean_down_s s), drawn from params.seed_key -- bitwise
    reproducible for a given seed (netem/timeline.py chaos).  Returns
    (state, params); params' conservative lookahead is untouched (churn
    never shortens latencies).  `n_events` pads the schedule to a fixed
    bucket so per-seed churn worlds (whose draw counts differ) stack on
    an ensemble world axis -- see ensemble.stack."""
    from . import netem
    num_hosts = int(state.hosts.num_hosts)
    tl = netem.timeline().chaos(
        params.seed_key, num_hosts, rate_per_s,
        mean_down_s=mean_down_s, hosts=hosts, t_start=t_start,
        t_end=int(params.stop_time) if t_end is None else int(t_end))
    return netem.install(state, params, tl, n_events=n_events)


class Drains:
    """The per-launch-boundary host-side drain set, behind one call.

    The run loops (sim._run_checkpointed, cli.run_config) all do the
    same thing after every bounded device launch: heartbeat if due,
    drain the event log, fetch the device counters, then drain the
    flight-recorder / flowscope / lineage / digest rings.  One object
    holds whichever of those the run installed so a new ring (the
    statescope digests were the sixth) slots into every loop by being
    constructed here, not by a new `if x is not None: x.drain(...)`
    copied into each loop.  Order is load-bearing only for the
    heartbeat (cheapest first) and counters (the ring drains attribute
    their transfer bytes to the already-installed profiler phases).
    """

    def __init__(self, *, tracker=None, log=None, flight=None, scope=None,
                 spans=None, digests=None, profiler=None):
        self.tracker = tracker
        self.log = log
        self.flight = flight
        self.scope = scope
        self.spans = spans
        self.digests = digests
        self.profiler = profiler
        self._hb_next = 0

    def drain_all(self, state, t=None) -> None:
        """Run every installed drain against `state`; `t` (sim ns)
        gates the heartbeat on its sample interval."""
        if self.tracker is not None and t is not None \
                and t >= self._hb_next:
            self.tracker.heartbeat(state, t)
            self._hb_next = t + self.tracker.sample_interval_ns
        if self.log is not None:
            self.log.drain(state)
        if self.profiler is not None:
            from . import trace
            trace.fetch_counters(state, self.profiler)
        for ring in (self.flight, self.scope, self.spans, self.digests):
            if ring is not None:
                ring.drain(state, self.profiler)


class WindowPipeline:
    """Double-buffered launch-boundary state: the async window pipeline
    (docs/observability.md "Async window pipeline").

    The sequential loops do launch -> block -> drain at every boundary,
    so every host drain serializes with the device and
    host_drain_overlap_pct sits at ~0.  Pipelined, the loop dispatches
    window N+1 BEFORE draining window N: JAX's asynchronous dispatch
    returns as soon as the launch is enqueued, the host then drains
    window N's rings (reading window N's retained device buffers, which
    are final -- the N+1 launch wrote fresh ones) while the device
    executes window N+1, and the block_until_ready moves one boundary
    later, to the drain point (`settle`).  Every drain still sees
    exactly the state it saw synchronously, at the same sim time, so
    heartbeat/windows/scope/lineage/digest rows and checkpoint files
    are byte-identical; only the wall-clock interleaving changes.

    `push(state, boundary, t0)` hands over a freshly dispatched
    window: its un-awaited output and the zero-argument callable that
    runs its boundary work (drains + checkpoint + progress).  `settle`
    is the drain point -- block on the pending window, record its
    dispatch->ready `device_window` span (when `t0` was given), run its
    boundary work -- and is idempotent, so control actions (park /
    cancel), supervisor retries, failures, and the end of the run can
    all call it (or `flush`, its alias) first and lose nothing."""

    def __init__(self, profiler=None):
        self.profiler = profiler
        self._pending = None

    def push(self, state, boundary, t0_wall=None):
        assert self._pending is None, "push() without settle()"
        self._pending = (state, boundary, t0_wall)

    def settle(self):
        if self._pending is None:
            return
        state, boundary, t0 = self._pending
        self._pending = None
        import time as _time

        import jax
        jax.block_until_ready(state)
        if self.profiler is not None and t0 is not None:
            self.profiler.add_span("device_window", t0,
                                   _time.perf_counter())
        boundary()

    def flush(self):
        self.settle()


@trace.spanned("sim.run")
def run(state, params, app, until=None, profiler=None, devices=None,
        bucket=False, scope=None, lineage=None, digest=None,
        checkpoint_every=None, checkpoint_dir=None, checkpoint_world=None,
        supervise=None, control=None, emit=None, resume=False,
        pipeline=True):
    """Run to `until` (default: params.stop_time).

    The call is span `sim.run`; inside it, on the paths below that
    launch directly, span `prepare` covers padding and the installs of
    the scope, lineage and digest blocks, and span `dispatch` the jitted
    launches (trace.py: both reach a `jax.profiler` trace).

    With `profiler` (a trace.Profiler), the run is profiled: the
    profiler is installed, device counters ride the state, and the run
    executes through the chunked launcher so device spans are recorded.

    With `bucket=True` the world is first padded up to its shape bucket
    (shapes.pad_world_to_bucket, docs/shapes.md): real-host rows stay
    bitwise-identical to the exact-size run, and every world sharing
    the bucket reuses one compiled graph.

    With `devices=N` (N > 1) the run shards across the first N visible
    devices (parallel.mesh_run_until, docs/parallel.md): the world is
    padded to a multiple of N hosts if needed, and the trajectory is
    bitwise-identical to a single-device run of the (padded) world.
    `bucket` composes with `devices` -- bucket first, then mesh-pad the
    bucketed size (ladder rungs divide every power-of-two device count
    up to 64, so the mesh pass is normally an identity).  `profiler`
    composes with `devices`: the mesh launcher records the same
    `device_step` spans, and the counter deltas finalize across shards
    (docs/observability.md), so telemetry rows match the single-device
    run bitwise.

    With `scope` (a ``flows[,links][:interval]`` spec string, same
    syntax as the CLI --scope flag) a FlowScope sampling block rides the
    state: cwnd/srtt/retransmit rows per TCP socket and per-host link
    rows at the given sim-time cadence (docs/observability.md).  The
    sampled trajectory is bitwise-identical to an unsampled one; read
    the rings back with trace.ScopeDrain.  Installed after all padding,
    sharded to match `devices`.

    With `lineage` (a sampling-rate spec: ``"0.01"``, ``"1%"``, a
    float, or ``"all"``; same syntax as the CLI --trace-packets flag)
    a packet-lineage tracer rides the state: a seeded, deterministic
    sample of packets gets i32 trace IDs at emission and appends one
    span row per hop (emit/stage/tx/link/exchange/deliver, with a
    drop-reason code where the packet died) into a device-side ring
    (docs/observability.md "Packet lineage").  The traced trajectory
    is bitwise-identical to an untraced one; read the spans back with
    trace.LineageDrain.  Installed after all padding, sharded to
    match `devices`.  Under checkpointing the spans drain to
    `checkpoint_dir`/spans.jsonl automatically.

    With `digest` (True, or an integer window cadence N) a statescope
    digest block rides the state: at the close of every N-th window the
    device folds each state field-group (pool, inbox, socks, hosts,
    rng, netem, app) into a 64-bit checksum per host-shard
    (docs/observability.md "Statescope").  Digests are bitwise
    trajectory-neutral and deterministic: two runs of the same world
    produce identical digest streams, and a mesh run's per-shard
    columns equal the single-device run's.  Read the rows back with
    trace.DigestDrain; under checkpointing they drain to
    `checkpoint_dir`/digests.jsonl automatically, and `shadow1-tpu
    diff` localizes the first divergence between two digest-recorded
    runs.  Installed after all padding, sharded to match `devices`.

    With `checkpoint_every` (a sim-time cadence in ns) the run becomes
    replayable (replay.py, docs/observability.md "Time-travel replay"):
    snapshots land in `checkpoint_dir`/ckpt/win_<K>.npz at existing
    chunk-boundary syncs, a flight recorder rides the state and drains
    to `checkpoint_dir`/windows.jsonl, and ckpt/run.json records the
    launch grid.  Checkpointing is host-side only -- the compiled
    graphs and the trajectory are bitwise identical to an
    uncheckpointed run over the same launch grid (the grid itself adds
    sync points; replay.next_sync).  `checkpoint_world` names the
    recipe `shadow1-tpu replay` rebuilds the world template from:
    ("phold", {"num_hosts": 64, ...}) re-calls sim.build_phold with
    those kwargs at replay time.  Without it the checkpoints still
    save/load programmatically, but the CLI cannot rebuild the
    template on its own.

    With `supervise` (True, or a dict of supervise.Supervisor kwargs:
    watchdog_s, quiet, resume_cmd) the run self-heals
    (docs/robustness.md): the invariant sentinel rides the state, every
    launch runs under supervise.Supervisor, and failures walk the
    checkpoint-anchored degradation ladder; an unrecovered failure
    raises supervise.UnrecoveredFailure after writing
    `checkpoint_dir`/crash.json.  Requires `checkpoint_every` --
    recovery is checkpoint-anchored.  The supervised trajectory is
    bitwise identical to an unsupervised one (the sentinel and every
    ladder rung are bitwise-neutral).

    `control` / `emit` / `resume` are the run server's hooks
    (server.py), valid only on the checkpointed path.  `control` (a
    server.RunControl-shaped object) is polled at every launch
    boundary: "park" checkpoints and returns early
    (control.outcome="parked"), "cancel"/"timeout" return early with
    the outcome recorded -- the returned state is wherever the run
    stopped.  `emit` receives {"event": ...} progress records.
    `resume=True` restores the newest readable checkpoint under
    `checkpoint_dir` (if any) before running, trimming windows.jsonl
    to the resume window and appending from there -- the same bitwise
    trim-and-append contract as the CLI's --auto-resume.

    `pipeline` (default True) enables the async window pipeline on the
    checkpointed path: window N+1 is dispatched before window N's
    drains run, so the host drain wall hides under device execution
    (WindowPipeline; docs/observability.md).  Artifacts are
    byte-identical either way -- `pipeline=False` (the CLI's
    --no-pipeline) restores the sequential launch->block->drain order
    without changing any compiled graph.
    """
    h_real = int(state.hosts.num_hosts)
    if bucket:
        from . import shapes
        with trace.current().span("prepare"):
            state, params = shapes.pad_world_to_bucket(state, params)
    # A Python int either way: a weakly typed scalar and the i64
    # params.stop_time array would key two compiles of run_until.
    t = int(params.stop_time if until is None else until)
    if checkpoint_every:
        if not checkpoint_dir:
            raise ValueError(
                "sim.run: checkpoint_every requires checkpoint_dir "
                "(where ckpt/ and windows.jsonl land)")
        return _run_checkpointed(
            state, params, app, t, profiler=profiler,
            devices=devices, bucket=bucket, scope=scope, lineage=lineage,
            digest=digest, every_ns=int(checkpoint_every),
            ckdir=checkpoint_dir, world=checkpoint_world,
            hosts_real=h_real, supervise=supervise, control=control,
            emit=emit, resume=resume, pipeline=pipeline)
    if supervise:
        raise ValueError(
            "sim.run: supervise requires checkpoint_every and "
            "checkpoint_dir (recovery is checkpoint-anchored)")
    if control is not None or resume:
        raise ValueError(
            "sim.run: control/resume require checkpoint_every and "
            "checkpoint_dir (parking and resuming are "
            "checkpoint-anchored)")

    def _install_blocks(st, shards):
        if scope is not None and st.scope is None:
            st = trace.ensure_flowscope(st, shards=shards,
                                        **trace.parse_scope_spec(scope))
        if lineage is not None and st.lineage is None:
            st = trace.ensure_lineage(
                st, rate=trace.parse_lineage_rate(lineage), shards=shards)
        if digest is not None and digest is not False and st.dg is None:
            st = trace.ensure_digests(
                st, every=1 if digest is True else int(digest),
                shards=shards)
        return st

    if devices is not None and int(devices) > 1:
        import jax as _jax

        from . import parallel
        n = int(devices)
        devs = _jax.devices()
        if len(devs) < n:
            raise ValueError(f"sim.run: devices={n} but only {len(devs)} "
                             f"{_jax.default_backend()} device(s) visible")
        with trace.current().span("prepare"):
            mesh = parallel.make_mesh(devs[:n])
            state, params = parallel.pad_world_to_mesh(state, params, n)
            state = _install_blocks(state, n)

        def launch(st):
            return parallel.mesh_run_chunked(st, params, app, t, mesh=mesh)
    else:
        with trace.current().span("prepare"):
            state = _install_blocks(state, 1)

        def launch(st):
            if profiler is None:
                return engine.run_until(st, params, app, t)
            return engine.run_chunked(st, params, app, t)
    if profiler is None:
        with trace.current().span("dispatch"):
            return launch(state)
    trace.install(profiler)
    try:
        if getattr(profiler, "counters", True):
            state = trace.ensure_counters(state)
        with profiler.span("dispatch"):
            state = launch(state)
        trace.fetch_counters(state, profiler)
        return state
    finally:
        trace.install(None)


def _run_checkpointed(state, params, app, t, *, profiler, devices, bucket,
                      scope, every_ns, ckdir, world, hosts_real,
                      lineage=None, digest=None, supervise=None,
                      control=None, emit=None, resume=False,
                      pipeline=True):
    """run()'s checkpointing path: same block installs as the plain
    paths (mesh pad, then scope/counters -- replay._rebuild_builder
    mirrors this order exactly), plus a flight recorder, a windows.jsonl
    drain, and Checkpointer saves on the memoryless launch grid
    (replay.next_sync with hb_ns=None).  `resume` restores the newest
    readable checkpoint first (fully-built template, then load, then
    trim-and-append); `control`/`emit` are the run server's park/
    cancel/timeout and progress-relay hooks (see run's docstring);
    `pipeline` double-buffers windows (WindowPipeline)."""
    import json
    import os
    import time as _time

    from . import replay as replay_mod
    from . import trace

    n = int(devices) if devices else 1
    mesh = None
    if n > 1:
        import jax as _jax

        from . import parallel
        devs = _jax.devices()
        if len(devs) < n:
            raise ValueError(f"sim.run: devices={n} but only {len(devs)} "
                             f"{_jax.default_backend()} device(s) visible")
        mesh = parallel.make_mesh(devs[:n])
        state, params = parallel.pad_world_to_mesh(state, params, n)
    if scope is not None and state.scope is None:
        state = trace.ensure_flowscope(state, shards=n,
                                       **trace.parse_scope_spec(scope))
    if lineage is not None and state.lineage is None:
        state = trace.ensure_lineage(
            state, rate=trace.parse_lineage_rate(lineage), shards=n)
    if digest is not None and digest is not False and state.dg is None:
        state = trace.ensure_digests(
            state, every=1 if digest is True else int(digest), shards=n)
    if profiler is not None:
        trace.install(profiler)
        # counters=False profilers (the run server's per-request
        # accounting) keep the pytree untouched: a served run must stay
        # byte-identical to an unobserved one.
        if getattr(profiler, "counters", True):
            state = trace.ensure_counters(state)
    state = trace.ensure_flight_recorder(state, shards=n)
    if supervise:
        state = trace.ensure_sentinel(state)

    os.makedirs(ckdir, exist_ok=True)

    # Auto-resume (the run server's crash-safety contract, same as the
    # CLI's --auto-resume): with the template fully built above, restore
    # the newest readable checkpoint, trim windows.jsonl to the resume
    # window, and append the re-recorded (bitwise-identical) rows.
    resumed = None
    if resume:
        import glob as _glob
        if _glob.glob(os.path.join(ckdir, "ckpt", "win_*.npz")):
            try:
                path, man = replay_mod.find_checkpoint(ckdir, None)
            except FileNotFoundError:
                path = None  # all torn: start the run over
            if path is not None:
                from . import checkpoint as _ckpt
                from . import supervise as _sup_mod
                state, params = _ckpt.load(path, state, params)
                resumed = {"file": os.path.basename(path),
                           "window": int(man["window"]),
                           "t_ns": int(man["t_ns"])}
                _sup_mod.trim_windows(
                    os.path.join(ckdir, "windows.jsonl"),
                    resumed["window"])
                if emit is not None:
                    emit({"event": "resumed", **resumed})

    flight = trace.FlightDrain(
        os.path.join(ckdir, "windows.jsonl"),
        start=resumed["window"] if resumed else 0,
        mode="a" if resumed else "w")
    spans = None
    if state.lineage is not None:
        spans = trace.LineageDrain(os.path.join(ckdir, "spans.jsonl"))
    digests = None
    if state.dg is not None:
        digests = trace.DigestDrain(os.path.join(ckdir, "digests.jsonl"))
    ck = replay_mod.Checkpointer(ckdir, every_ns, devices=n,
                                 bucket=bucket, hosts_real=hosts_real)
    if world is not None and not isinstance(world, dict):
        name, kwargs = world
        world = {"name": name, "kwargs": dict(kwargs or {})}
    write_recipe = resumed is None
    if resumed is not None:
        # Torn-file hardening parity (docs/robustness.md): a damaged
        # run.json must not strand a resumable run -- the recipe is a
        # pure function of the current arguments, so rewrite it.
        try:
            replay_mod.load_run(ckdir)
            write_recipe = False
        except (FileNotFoundError, ValueError, json.JSONDecodeError):
            write_recipe = True
    if write_recipe:
        replay_mod.write_run_json(ckdir, {
            "world": ({"kind": "builder", **world}
                      if world is not None else None),
            "hb_ns": None, "every_ns": int(every_ns), "stop_ns": int(t),
            "chunk_ns": engine.CHUNK_NS, "devices": n,
            "bucket": bool(bucket), "hosts_real": int(hosts_real),
            # "profile" means "the TraceCounters block is on the state"
            # (the replay template must match the checkpoint pytree): a
            # counters=False profiler (the run server's per-request
            # accounting) leaves the state bare, so record False.
            "scope": scope,
            "profile": (profiler is not None
                        and getattr(profiler, "counters", True)),
            "flight_rows": int(state.fr.steps.shape[0]),
            "lineage": (str(lineage) if lineage is not None else None),
            "digest": (int(state.dg.every)
                       if state.dg is not None else None),
            "digest_rows": (int(state.dg.capacity)
                            if state.dg is not None else None),
            "sentinel": bool(supervise), "supervise": bool(supervise)})
    sup = None
    if supervise:
        from . import supervise as sup_mod
        opts = dict(supervise) if isinstance(supervise, dict) else {}
        sup = sup_mod.Supervisor(
            ckdir, app, mesh=mesh, chunk_ns=engine.CHUNK_NS,
            on_violation=lambda st: flight.drain(st, profiler),
            emit=emit, **opts)
    drains = Drains(flight=flight, spans=spans, digests=digests,
                    profiler=profiler)
    pipe = WindowPipeline(profiler) if pipeline else None
    prev_sync = None
    if pipe is not None and profiler is not None and profiler.sync:
        # --profile runs sync per chunk inside the engine loop, which
        # would serialize the pipeline; the pipeline records its own
        # dispatch->ready device_window spans instead, so per-chunk
        # blocking is turned off for the duration of this run.
        prev_sync = True
        profiler.sync = False
    try:
        if resumed is None:
            ck.save(state, params)      # win_0: a replay anchor always exists
        tt = int(state.now)
        while tt < int(t):
            act = control.poll() if control is not None else None
            if act is not None:
                # The run server asked this run to stop at a launch
                # boundary (server.RunControl): park checkpoints here
                # and resumes on the next --auto-resume life; cancel
                # and timeout just stop (the worker maps the outcome
                # to its rc).
                if pipe is not None:
                    pipe.flush()  # the last window's drains land first
                if act == "park":
                    ck.save(state, params)
                    control.outcome = "parked"
                    if emit is not None:
                        emit({"event": "parked", "t_ns": int(tt),
                              "window": int(state.n_windows)})
                else:
                    control.outcome = ("cancelled" if act == "cancel"
                                       else "timed_out")
                return state
            tt = replay_mod.next_sync(tt, int(t), every_ns=every_ns)
            t0 = _time.perf_counter()
            if sup is not None:
                state = sup.launch(
                    state, params, tt,
                    overlap=pipe.settle if pipe is not None else None)
            elif mesh is not None:
                from . import parallel
                state = parallel.mesh_run_chunked(state, params, app, tt,
                                                  mesh=mesh)
            else:
                state = engine.run_chunked(state, params, app, tt)
            if pipe is None:
                drains.drain_all(state)
                ck.maybe(state, params, tt)
                if emit is not None:
                    emit({"event": "progress", "t_ns": int(tt),
                          "stop_ns": int(t),
                          "line": f"[shadow1-tpu] "
                                  f"{tt / simtime.SIMTIME_ONE_SECOND:g}"
                                  f"/{int(t) / simtime.SIMTIME_ONE_SECOND:g}"
                                  f"s\n"})
                continue
            if sup is None:
                # Drain window N while window N+1 executes (supervised
                # launches ran this via the overlap hook, between their
                # dispatch and their watchdog-bounded block).
                pipe.settle()

            def _boundary(st=state, ts=tt):
                drains.drain_all(st)
                ck.maybe(st, params, ts)
                if emit is not None:
                    emit({"event": "progress", "t_ns": int(ts),
                          "stop_ns": int(t),
                          "line": f"[shadow1-tpu] "
                                  f"{ts / simtime.SIMTIME_ONE_SECOND:g}"
                                  f"/{int(t) / simtime.SIMTIME_ONE_SECOND:g}"
                                  f"s\n"})
            # Supervised launches block (and span) internally, so the
            # pipeline must not re-record their window; t0=None skips it.
            pipe.push(state, _boundary, t0 if sup is None else None)
        if pipe is not None:
            pipe.flush()  # the drain point of the final window
        return state
    finally:
        if pipe is not None:
            try:
                # Already settled on every non-exception path (flush is
                # idempotent); after a launch failure this lands the
                # last good window's rows before the files close, and
                # best-effort is right -- a drain error must not mask
                # the failure being handled.
                pipe.flush()
            except Exception:
                pass
        if prev_sync and profiler is not None:
            profiler.sync = True
        flight.close()
        if spans is not None:
            spans.close()
            if profiler is not None:
                profiler.set_lineage(spans.rows, spans.summary())
        if digests is not None:
            digests.close()
            if profiler is not None:
                profiler.set_digest(digests.summary())
        if profiler is not None:
            trace.install(None)


def run_ensemble(worlds, until=None, *, data_dir=None, scope=None,
                 lineage=None, digest=None, heartbeat_s: int = 0,
                 log: bool = False, devices=None, chunk_ns=None,
                 hostnames=None, sweep=None, quiet: bool = True,
                 checkpoint_every=None, supervise=None, resume=False,
                 control=None, emit=None, run_extra=None,
                 world_cmds=None, pipeline=True):
    """Run N worlds as one vmapped ensemble (docs/ensemble.md).

    `worlds` is a sequence of built (state, params, app) triples -- one
    shape bucket, equal apps (ensemble.stack validates and refuses by
    name).  Each world is bitwise identical to the same world run solo
    through engine.run_chunked on the same launch grid (the tier-0 pin
    in tests/test_ensemble.py).

    Instrumentation (`scope`/`lineage`/`digest`, same specs as run())
    installs per world BEFORE stacking, so the blocks stack like any
    other state.  With `data_dir` the drains share one artifact file
    per kind -- heartbeat.csv, shadow.log, flows.jsonl/links.jsonl,
    spans.jsonl, digests.jsonl -- every row carrying a world column
    (the drain-layer convention); run.json records `n_worlds` and the
    `sweep` spec for replay bookkeeping, and summary.json holds one
    summary per world.

    `devices=N` places worlds world-major across the first N devices
    (ensemble.shard_worlds; n_worlds must divide).

    Crash safety mirrors sim.run's checkpointed path
    (docs/robustness.md "Ensemble resilience"): `checkpoint_every` (ns,
    requires `data_dir`) saves STACKED anchors -- ckpt/win_<K>.npz with
    a format-2 manifest carrying per-world windows/clocks -- on the
    memoryless next_sync grid; `supervise` (True or Supervisor kwargs)
    runs every launch under supervise.Supervisor with the per-world
    quarantine rung ahead of the ladder; `resume=True` restores the
    newest readable stacked anchor, trims windows.jsonl per world, and
    re-records bitwise.  `control`/`emit` are the run server's hooks,
    exactly as in sim.run.  `run_extra` merges extra keys into
    ckpt/run.json (the CLI records its world recipe and netem bucket
    there so `replay --world K` can rebuild one member); `world_cmds`
    is forwarded to the Supervisor for crash.json member commands.

    `pipeline` (default True) double-buffers windows exactly as in
    sim.run: window N's per-world drains run while window N+1 executes
    on the device (WindowPipeline), with byte-identical artifacts.

    Returns (estate, eparams, app, summaries): the final stacked state
    and one summary dict per world (with `quarantined` flags under
    supervision)."""
    import os
    import time as _time

    import jax

    from . import ensemble, trace
    from . import replay as replay_mod

    worlds = list(worlds)
    nw = len(worlds)
    if checkpoint_every and not data_dir:
        raise ValueError(
            "run_ensemble: checkpoint_every requires data_dir (where "
            "ckpt/ and windows.jsonl land)")
    if supervise and not checkpoint_every:
        raise ValueError(
            "run_ensemble: supervise requires checkpoint_every "
            "(recovery is checkpoint-anchored)")
    if (resume or control is not None) and not checkpoint_every:
        raise ValueError(
            "run_ensemble: control/resume require checkpoint_every "
            "(parking and resuming are checkpoint-anchored)")

    def _install(st, p, a):
        if scope is not None and st.scope is None:
            st = trace.ensure_flowscope(st, shards=1,
                                        **trace.parse_scope_spec(scope))
        if lineage is not None and st.lineage is None:
            st = trace.ensure_lineage(
                st, rate=trace.parse_lineage_rate(lineage), shards=1)
        if digest is not None and digest is not False and st.dg is None:
            st = trace.ensure_digests(
                st, every=1 if digest is True else int(digest), shards=1)
        if log and st.log is None:
            from .core.state import make_log_ring
            h = int(st.hosts.num_hosts)
            # Level 1 everywhere (drops + netem kills; the CLI's
            # "message" tier) -- ensemble runs log per-world incidents,
            # not per-packet debug floods.
            st = st.replace(log=make_log_ring(),
                            log_level=jnp.ones((h,), jnp.int32))
        if checkpoint_every and st.fr is None:
            st = trace.ensure_flight_recorder(st, shards=1)
        if supervise and st.sentinel is None:
            st = trace.ensure_sentinel(st)
        return st, p, a

    worlds = [_install(*w) for w in worlds]
    estate, eparams, app = ensemble.stack(worlds)
    if until is None:
        until = int(jnp.max(eparams.stop_time))
    until = int(until)
    if chunk_ns is None:
        chunk_ns = engine.CHUNK_NS

    # Auto-resume BEFORE world-major sharding: checkpoint.load wants
    # the unsharded template, and shard_worlds re-places the loaded
    # leaves afterwards.  A quarantined world rides the anchor frozen
    # (now >= ensemble.FROZEN_NOW), so the quarantine set re-derives
    # statelessly from the loaded state.
    resumed = None
    world_starts = None
    if resume and data_dir is not None:
        import glob as _glob
        if _glob.glob(os.path.join(data_dir, "ckpt", "win_*.npz")):
            try:
                path, man = replay_mod.find_checkpoint(data_dir, None)
            except FileNotFoundError:
                path = None  # all torn: start the run over
            if path is not None:
                from . import checkpoint as _ckpt
                from . import supervise as _sup_mod
                estate, eparams = _ckpt.load(path, estate, eparams)
                wins = [int(x) for x in
                        (man.get("windows") or [man["window"]] * nw)]
                frozen = {int(k) for k in man.get("frozen") or ()}
                resumed = {"file": os.path.basename(path),
                           "window": int(man["window"]),
                           "t_ns": int(man["t_ns"])}
                world_starts = dict(enumerate(wins))
                # Per-world trim: each surviving world re-records from
                # its OWN anchor window; a quarantined world's trail is
                # crash evidence a resume never re-records -- keep it.
                _sup_mod.trim_windows(
                    os.path.join(data_dir, "windows.jsonl"), None,
                    world_windows={k: w for k, w in world_starts.items()
                                   if k not in frozen})
                if emit is not None:
                    emit({"event": "resumed", **resumed,
                          "n_worlds": nw, "windows": wins,
                          "quarantined": sorted(frozen)})

    if devices is not None and int(devices) > 1:
        import jax as _jax

        from . import parallel
        n = int(devices)
        devs = _jax.devices()
        if len(devs) < n:
            raise ValueError(
                f"run_ensemble: devices={n} but only {len(devs)} "
                f"{_jax.default_backend()} device(s) visible")
        estate, eparams = ensemble.shard_worlds(
            estate, eparams, parallel.make_mesh(devs[:n]))

    # Per-world drain sets over shared artifact files (world columns
    # tell the rows apart; trace._open_sink ownership keeps the shared
    # file open until the run closes it).
    shared = []
    drains = []
    if data_dir is not None:
        os.makedirs(data_dir, exist_ok=True)
        names = (list(hostnames) if hostnames is not None else
                 [f"host{i}" for i in
                  range(int(worlds[0][0].hosts.num_hosts))])

        def share(fname, want, mode="w"):
            if not want:
                return None
            f = open(os.path.join(data_dir, fname), mode)
            shared.append(f)
            return f

        log_f = share("shadow.log", worlds[0][0].log is not None)
        ff = share("flows.jsonl", worlds[0][0].scope is not None
                   and bool(worlds[0][0].scope.sample_flows))
        lf = share("links.jsonl", worlds[0][0].scope is not None
                   and bool(worlds[0][0].scope.sample_links))
        sp = share("spans.jsonl", worlds[0][0].lineage is not None)
        dg = share("digests.jsonl", worlds[0][0].dg is not None)
        # A resumed run appends to the per-world-trimmed record; each
        # world's FlightDrain cursor starts at its own anchor window.
        wn = share("windows.jsonl", worlds[0][0].fr is not None,
                   mode="a" if resumed else "w")
        for k in range(nw):
            from .observe import LogDrain, Tracker
            tracker = None
            if heartbeat_s and heartbeat_s > 0:
                tracker = Tracker(data_dir, names,
                                  interval_s=int(heartbeat_s),
                                  world=k, write_header=(k == 0))
            drains.append(Drains(
                tracker=tracker,
                log=(LogDrain(log_f, names, world=k)
                     if log_f is not None else None),
                flight=(trace.FlightDrain(
                    wn, world=k,
                    start=(world_starts or {}).get(k, 0))
                        if wn is not None else None),
                scope=(trace.ScopeDrain(ff, lf, real_hosts=len(names),
                                        world=k)
                       if (ff is not None or lf is not None) else None),
                spans=(trace.LineageDrain(sp, world=k)
                       if sp is not None else None),
                digests=(trace.DigestDrain(dg, world=k)
                         if dg is not None else None),
            ))
        info = {
            "n_worlds": nw,
            "sweep": sweep,
            "stop_ns": until,
            "chunk_ns": int(chunk_ns),
            "digest": (1 if digest is True else int(digest))
            if digest else None,
            "devices": int(devices) if devices else 1,
        }
        if checkpoint_every:
            fr0 = worlds[0][0].fr
            info.update({
                "hb_ns": None,
                "every_ns": int(checkpoint_every),
                "flight_rows": int(fr0.steps.shape[0]),
                "hosts_real": len(names),
                "sentinel": bool(supervise),
                "supervise": bool(supervise),
            })
        if run_extra:
            info.update(run_extra)
        write_recipe = resumed is None
        if resumed is not None:
            # Torn-file hardening parity (docs/robustness.md): a
            # damaged run.json must not strand a resumable run.
            import json as _json
            try:
                replay_mod.load_run(data_dir)
                write_recipe = False
            except (FileNotFoundError, ValueError,
                    _json.JSONDecodeError):
                write_recipe = True
        if write_recipe:
            replay_mod.write_run_json(data_dir, info)

    def drain_all(st, t):
        for k, dr in enumerate(drains):
            ws = jax.tree_util.tree_map(lambda x: x[k], st)
            dr.drain_all(ws, t)

    ck = None
    sup = None
    if checkpoint_every:
        ck = replay_mod.Checkpointer(
            data_dir, int(checkpoint_every),
            devices=int(devices) if devices else 1,
            hosts_real=int(worlds[0][0].hosts.num_hosts))
    if supervise:
        from . import supervise as sup_mod
        opts = dict(supervise) if isinstance(supervise, dict) else {}
        if world_cmds is not None:
            opts.setdefault("world_cmds", world_cmds)

        def _flush_flights(st):
            # Evidence flush before a sentinel failure is handled:
            # every world's flight rows reach windows.jsonl, so the
            # crash report's replay command has its bad window row.
            for k, dr in enumerate(drains):
                if dr.flight is not None:
                    dr.flight.drain(
                        jax.tree_util.tree_map(lambda x: x[k], st))

        sup = sup_mod.Supervisor(
            data_dir, app, mesh=None, chunk_ns=int(chunk_ns),
            on_violation=_flush_flights, emit=emit, **opts)
        sup.quarantined = set(ensemble.frozen_worlds(estate))

    import numpy as _np

    def _world_max_window():
        return int(_np.asarray(estate.n_windows).max())

    wall0 = _time.monotonic()
    outcome = None
    pipe = WindowPipeline() if pipeline else None
    try:
        if ck is not None and resumed is None:
            ck.save(estate, eparams)  # win_0: an anchor always exists
        t = int(jnp.min(estate.now))
        while t < until:
            act = control.poll() if control is not None else None
            if act is not None:
                if pipe is not None:
                    pipe.flush()  # the last window's drains land first
                if act == "park":
                    ck.save(estate, eparams)
                    control.outcome = "parked"
                    if emit is not None:
                        emit({"event": "parked", "t_ns": int(t),
                              "window": _world_max_window()})
                else:
                    control.outcome = ("cancelled" if act == "cancel"
                                       else "timed_out")
                outcome = control.outcome
                break
            if ck is not None:
                t = replay_mod.next_sync(
                    t, until, every_ns=int(checkpoint_every))
            else:
                t = min(t + int(chunk_ns), until)
            if sup is not None:
                estate = sup.launch(
                    estate, eparams, t,
                    overlap=pipe.settle if pipe is not None else None)
            elif ck is not None:
                estate = ensemble.run_chunked(estate, eparams, app, t,
                                              chunk_ns=int(chunk_ns))
            else:
                estate = ensemble.run_until(estate, eparams, app, t)
            if pipe is None:
                drain_all(estate, t)
                if ck is not None:
                    ck.maybe(estate, eparams, t)
                if emit is not None:
                    emit({"event": "progress", "t_ns": int(t),
                          "stop_ns": until,
                          "line": f"[shadow1-tpu] "
                                  f"{t / simtime.SIMTIME_ONE_SECOND:g}"
                                  f"/{until / simtime.SIMTIME_ONE_SECOND:g}"
                                  f"s\n"})
                continue
            if sup is None:
                # Drain window N while window N+1 executes (supervised
                # launches ran this via their overlap hook already).
                pipe.settle()

            def _boundary(st=estate, ts=t):
                drain_all(st, ts)
                if ck is not None:
                    ck.maybe(st, eparams, ts)
                if emit is not None:
                    emit({"event": "progress", "t_ns": int(ts),
                          "stop_ns": until,
                          "line": f"[shadow1-tpu] "
                                  f"{ts / simtime.SIMTIME_ONE_SECOND:g}"
                                  f"/{until / simtime.SIMTIME_ONE_SECOND:g}"
                                  f"s\n"})
            pipe.push(estate, _boundary)
        if pipe is not None:
            pipe.flush()
        jax.block_until_ready(estate)
    finally:
        if pipe is not None:
            try:
                # Already settled on every non-exception path (flush is
                # idempotent); after a launch failure this lands the
                # last good window's rows before the files close, and
                # best-effort is right -- a drain error must not mask
                # the failure being handled.
                pipe.flush()
            except Exception:
                pass
        wall = _time.monotonic() - wall0
        for dr in drains:
            for ring in (dr.log, dr.flight, dr.scope, dr.spans,
                         dr.digests):
                if ring is not None:
                    ring.close()
        for f in shared:
            f.close()

    quarantined = sorted(sup.quarantined) if sup is not None \
        else sorted(ensemble.frozen_worlds(estate))
    summaries = []
    ev = jnp.asarray(estate.n_events)
    err = jnp.asarray(estate.err)
    sent = jnp.sum(jnp.asarray(estate.hosts.pkts_sent), axis=1)
    drop = (jnp.sum(jnp.asarray(estate.hosts.pkts_dropped_inet), axis=1)
            + jnp.sum(jnp.asarray(estate.hosts.pkts_dropped_router),
                      axis=1))
    for k in range(nw):
        summaries.append({
            "world": k,
            "events": int(ev[k]),
            "packets_sent": int(sent[k]),
            "drops": int(drop[k]),
            "err_flags": int(err[k]),
            "windows": int(jnp.asarray(estate.n_windows)[k]),
            **({"quarantined": k in quarantined}
               if sup is not None else {}),
        })
    if data_dir is not None:
        import json as _json
        top = {"n_worlds": nw, "wall_seconds": round(wall, 3),
               "simulated_seconds":
               until / simtime.SIMTIME_ONE_SECOND,
               "sweep": sweep, "worlds": summaries}
        if sup is not None:
            top["supervise"] = {
                "recoveries": int(sup.recoveries),
                "quarantined": quarantined,
                "ladder": sup.ladder,
            }
        if outcome is not None:
            top["outcome"] = outcome
        with open(os.path.join(data_dir, "summary.json"), "w") as f:
            _json.dump(top, f, indent=2)
    if not quiet:
        print(f"[shadow1-tpu] ensemble: {nw} worlds, "
              f"{until / simtime.SIMTIME_ONE_SECOND:.3f}s simulated in "
              f"{wall:.2f}s wall")
    return estate, eparams, app, summaries


def build_onion(num_circuits: int,
                hops: int = 3,
                bytes_per_circuit: int = 1 << 20,
                latency_ns: int = 20 * simtime.SIMTIME_ONE_MILLISECOND,
                stop_time: int = 120 * simtime.SIMTIME_ONE_SECOND,
                seed: int = 1,
                sock_slots: int = 8,
                pool_slab: int = 64,
                inbox_slab: int | None = None,
                bw_Bps: int = 1 << 27):
    """Tor-like onion-circuit world (apps/onion.py): `num_circuits` chains
    of client -> hops relays -> server, each circuit streaming
    `bytes_per_circuit` through every hop.  The 1k-host ladder rung is
    build_onion(200) = 200 circuits x 5 hosts."""
    from .apps import onion as onion_app
    from .transport import tcp as tcp_mod
    import numpy as np

    role, nxt = onion_app.build_circuits(num_circuits, hops, seed)
    num_hosts = len(role)
    v = min(num_hosts, 256)

    def _build():
        lat, rel = uniform_full_mesh(v, latency_ns)
        params = make_net_params(
            latency_ns=lat, reliability=rel,
            host_vertex=jnp.arange(num_hosts) % v,
            bw_up_Bps=jnp.full(num_hosts, bw_Bps),
            bw_down_Bps=jnp.full(num_hosts, bw_Bps),
            seed=seed, stop_time=stop_time)
        state = make_sim_state(
            num_hosts, sock_slots=sock_slots,
            pool_capacity=num_hosts * pool_slab,
            inbox_capacity=(num_hosts * inbox_slab) if inbox_slab else None)
        # Relays and servers listen; circuit legs arrive as children.
        listeners = jnp.asarray((role == 1) | (role == 2))
        state = state.replace(socks=tcp_mod.listen_v(
            state.socks, listeners, 1, onion_app.ONION_PORT, backlog=4))
        total = np.zeros(num_hosts, np.int64)
        total[role == 0] = bytes_per_circuit
        total[role == 2] = bytes_per_circuit   # server-side expectation
        start = np.zeros(num_hosts, np.int64)
        # Relays dial their next hop first (staggered microseconds), then
        # clients start milliseconds later -- guarantees CLIENT_SLOT is
        # occupied on every relay before any inbound SYN can spawn a
        # child there.
        start[role == 1] = simtime.SIMTIME_ONE_MICROSECOND * (
            1 + (np.arange((role == 1).sum()) % 499))
        start[role == 0] = simtime.SIMTIME_ONE_MILLISECOND * (
            50 + (np.arange((role == 0).sum()) % 997))
        state = state.replace(app=onion_app.init_state(role, nxt, total,
                                                       start))
        return state, params

    state, params = _pkg.build_on_host(_build)
    return state, params, onion_app.Onion()
