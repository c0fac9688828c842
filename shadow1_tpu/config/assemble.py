"""Simulation assembly: shadow.config.xml + GraphML -> runnable sim.

The reference path is master_new -> _master_loadConfiguration /
_master_loadTopology -> slave_addNewVirtualHost (dns_register,
topology_attach, interfaces, router) -> slave_addNewVirtualProcess
(/root/reference/src/main/core/master.c:161-238,271-398,
slave.c:296-336).  This module is that pipeline for the TPU engine:
expand <host quantity=N>, register DNS names/IPs, attach every host to a
topology vertex through the hint ladder, pull per-vertex bandwidths into
NetParams, precompute APSP routing matrices on device, and lower
<process> elements onto modeled applications (tgen action graphs).
"""

from __future__ import annotations

import dataclasses
import os

import jax.numpy as jnp
import numpy as np

import shadow1_tpu as _pkg

from ..apps import tgen as tgen_app
from ..core import simtime
from ..core.params import (NetParams, QDISC_FIFO, QDISC_RR,
                           make_net_params)
from ..core.state import make_sim_state
from ..routing import apsp, graphml
from ..routing.dns import DNS
from ..transport import cong as _cong
from ..transport import tcp

SEC = simtime.SIMTIME_ONE_SECOND

# Reference bandwidth attributes are KiB/s (docs/3.2-Network-Config.md).
_KIB = 1024
# Fallback when neither the host element nor its vertex specifies one.
_DEFAULT_BW_KIBPS = 102400  # 100 MiB/s
# Virtual CPU model base: a 3 GHz machine spends ~1us of CPU per
# simulation event; a host configured with cpufrequency F KHz pays
# 1us * (3e6 / F) per event (reference cpu.c frequencyRatio).
_BASE_CPU_KHZ = 3_000_000
_BASE_EVENT_NS = 1_000


@dataclasses.dataclass
class Assembled:
    """Everything the CLI / driver needs to run and report."""

    state: object            # SimState
    params: NetParams
    app: object
    hostnames: list          # [H]
    dns: DNS
    topology: graphml.Topology
    config: object           # ShadowConfig
    stop_time: int           # ns
    pcap_mask: object = None        # [H] bool: <host logpcap="true">
    pcap_dirs: dict = None          # host index -> pcapdir
    heartbeat_freq_s: object = None  # [H] i64, 0 = default
    loglevels: list = None          # per-host loglevel strings
    real_procs: list = None   # [(host_index, argv, start_ns, stop_ns|None)]
    netem: object = None      # netem.Timeline installed on state, or None


def _expand_hosts(cfg):
    """<host quantity=N> -> N hosts named id, or id1..idN when N > 1
    (reference master.c:309-320)."""
    names, specs = [], []
    for hs in cfg.hosts:
        q = max(1, hs.quantity)
        for i in range(q):
            names.append(hs.id if q == 1 else f"{hs.id}{i + 1}")
            specs.append(hs)
    return names, specs


def _plugin_path(cfg, plugin_id: str) -> str | None:
    """Resolve a plugin's path (one resolver for classification AND
    spawning, so they can never disagree)."""
    spec = cfg.plugins.get(plugin_id)
    if not (spec and spec.path):
        return None
    path = os.path.expanduser(spec.path)
    if not os.path.isabs(path):
        path = os.path.join(cfg.base_dir, path)
    return path


def _plugin_kind(cfg, plugin_id: str) -> str:
    """Classify a plugin: an executable PROGRAM runs as a REAL process
    under the substrate (here fork/exec of the binary itself); a shared
    object (.so, the reference's plugin format) or a known name maps to
    its modeled equivalent (tgen).  Shared objects routinely carry the
    exec bit, so the .so check must come first -- otherwise the same
    config flips between modeled and fork/exec depending on whether the
    plugin file happens to exist on disk."""
    path = _plugin_path(cfg, plugin_id)
    spec = cfg.plugins.get(plugin_id)
    hay = f"{plugin_id} {spec.path if spec else ''}".lower()
    is_shared_obj = bool(path) and (
        path.endswith(".so") or ".so." in os.path.basename(path))
    if is_shared_obj or not (
            path and os.path.isfile(path) and os.access(path, os.X_OK)):
        if "tgen" in hay:
            return "tgen"
        if is_shared_obj:
            raise ValueError(
                f"plugin {plugin_id!r} is a shared object ({path}); "
                f"fork/exec cannot run it and no modeled equivalent is "
                f"known -- point the plugin at an executable program")
        raise ValueError(
            f"plugin {plugin_id!r} is neither an existing executable "
            f"(real-process plugin) nor a known modeled equivalent (tgen)")
    return "real"


def build(cfg, seed: int = 1, sock_slots: int | None = None,
          pool_slab: int = 128, qdisc: str = "fifo",
          cpu_threshold_us: int = -1,
          cpu_precision_us: int = 200, cong: str = "reno",
          bucket: bool = False) -> Assembled:
    """Assemble a parsed ShadowConfig into (state, params, app).

    With `bucket=True` the assembled world is padded up to its shape
    bucket (shapes.pad_world_to_bucket, docs/shapes.md): real-host rows
    stay bitwise identical to the exact-size run, and configs sharing a
    bucket reuse one compiled graph.  Host-side tables (hostnames, DNS,
    pcap masks) keep the real host count.
    """
    names, specs = _expand_hosts(cfg)
    h = len(names)
    if h == 0:
        raise ValueError("config defines no hosts")

    # --- topology + attachment -------------------------------------------
    topo = graphml.load(cfg.topology_source())
    dns = DNS()
    for i, name in enumerate(names):
        dns.register(i, name, requested_ip=specs[i].iphint)
    host_vertex = graphml.attach_all(topo, [s.hints() for s in specs], seed)

    # --- bandwidths (host override, else vertex, else default) -----------
    bw_up = np.empty(h, np.int64)
    bw_dn = np.empty(h, np.int64)
    cpu_ns = np.zeros(h, np.int64)
    snd_buf = np.zeros(h, np.int64)      # 0 = default + autotune
    rcv_buf = np.zeros(h, np.int64)
    iface_pkts = np.zeros(h, np.int32)   # 0 = unbounded
    hb_freq = np.zeros(h, np.int64)      # 0 = tracker default
    pcap_mask = np.zeros(h, bool)
    pcap_dirs: dict = {}
    loglevels: list = [None] * h
    for i, s in enumerate(specs):
        v = host_vertex[i]
        up = s.bandwidthup_KiBps or int(topo.bw_up_KiBps[v]) or _DEFAULT_BW_KIBPS
        dn = s.bandwidthdown_KiBps or int(topo.bw_down_KiBps[v]) or _DEFAULT_BW_KIBPS
        bw_up[i], bw_dn[i] = up * _KIB, dn * _KIB
        if s.cpufrequency:
            cpu_ns[i] = max(1, (_BASE_EVENT_NS * _BASE_CPU_KHZ)
                            // max(1, s.cpufrequency))
        if s.socketsendbuffer:
            snd_buf[i] = s.socketsendbuffer
        if s.socketrecvbuffer:
            rcv_buf[i] = s.socketrecvbuffer
        if s.interfacebuffer:
            # Reference interfacebuffer is bytes; the router backlog is
            # packet-counted, so round up in MTUs.
            from ..core.state import MTU
            iface_pkts[i] = max(1, -(-s.interfacebuffer // MTU))
        if s.heartbeatfrequency_s:
            hb_freq[i] = s.heartbeatfrequency_s
        pcap_mask[i] = s.logpcap
        if s.logpcap and s.pcapdir:
            pcap_dirs[i] = s.pcapdir
        loglevels[i] = s.loglevel

    # --- routing matrices -------------------------------------------------
    # Small graphs resolve APSP + parameter packing on the local CPU
    # backend in one shot (eager ops on an accelerator each cost a
    # dispatch); big graphs run the Floyd-Warshall on the device, where the
    # O(V^3) relaxation belongs.
    def _routing_and_params():
        lat_ns, rel, jit_ns = apsp.build_matrices(
            jnp.asarray(topo.lat_ms), jnp.asarray(topo.edge_rel),
            self_lat_ms=jnp.asarray(topo.self_lat_ms),
            self_rel=jnp.asarray(topo.self_rel),
            edge_jitter_ms=jnp.asarray(topo.jitter_ms),
            self_jitter_ms=jnp.asarray(topo.self_jitter_ms))
        return make_net_params(
            latency_ns=lat_ns, reliability=rel,
            host_vertex=host_vertex,
            bw_up_Bps=bw_up, bw_down_Bps=bw_dn,
            seed=seed,
            stop_time=cfg.stoptime_s * SEC,
            bootstrap_end=cfg.bootstrap_end_s * SEC,
            jitter_ns=jit_ns,
            cpu_ns_per_event=cpu_ns,
            cpu_threshold_ns=(cpu_threshold_us * 1000
                              if cpu_threshold_us >= 0 else -1),
            cpu_precision_ns=max(1, cpu_precision_us) * 1000,
            qdisc={"fifo": QDISC_FIFO, "rr": QDISC_RR}[qdisc],
            autotune_snd=(snd_buf == 0),
            autotune_rcv=(rcv_buf == 0),
            iface_buf_pkts=iface_pkts,
            pcap_mask=pcap_mask if pcap_mask.any() else None,
            cong=_cong.validate(cong),
        )

    if topo.num_vertices <= 1024:
        params = _pkg.build_on_host(_routing_and_params)
    else:
        params = _routing_and_params()

    # --- connectivity validation (reference topology.c:371-560: a
    # disconnected graph fails at load, not as silent INF latencies at
    # send time).  Only vertices hosts actually attach to must be
    # mutually routable.
    used = np.unique(np.asarray(host_vertex))
    routable = np.array(  # writable copy: the diagonal is cleared below
        apsp.is_routable(params.latency_ns)[jnp.asarray(used)][:, jnp.asarray(used)])
    # Diagonal excluded: same-host loopback never consults the latency
    # matrix, so an isolated single-attached vertex is fine.
    np.fill_diagonal(routable, True)
    if not routable.all():
        # Normalize to unordered pairs (a one-directional hole on a
        # directed topology must still report, not IndexError).
        pairs = sorted({(min(i, j), max(i, j))
                        for i, j in np.argwhere(~routable)})
        vi, vj = used[pairs[0][0]], used[pairs[0][1]]
        raise ValueError(
            f"topology is not connected: no route between attached "
            f"vertices {topo.names[vi]!r} and {topo.names[vj]!r} "
            f"({len(pairs)} unroutable attached-vertex pairs); every "
            f"pair of vertices that hosts attach to must be connected")

    # --- processes -> modeled apps ---------------------------------------
    # Each distinct tgen arguments file is one parsed action graph; a
    # host's process points it at that graph.
    graph_of_args: dict = {}
    graphs: list = []
    host_graph = np.full(h, -1, np.int64)
    start_t = np.zeros(h, np.int64)
    stop_t = np.full(h, simtime.SIMTIME_INVALID, np.int64)
    real_procs: list = []    # (host_index, argv, start_ns, stop_ns|None)
    for i, s in enumerate(specs):
        if not s.processes:
            continue
        for p in s.processes:
            if _plugin_kind(cfg, p.plugin) == "real":
                argv = [_plugin_path(cfg, p.plugin)] + p.arguments.split()
                real_procs.append(
                    (i, argv, p.starttime_s * SEC,
                     p.stoptime_s * SEC if p.stoptime_s else None))
                continue
            if host_graph[i] >= 0:
                raise ValueError(f"host {names[i]!r}: multiple MODELED "
                                 f"processes per host not yet supported "
                                 f"(real-process plugins compose freely)")
            arg = (p.arguments.strip().split()[0]
                   if p.arguments.strip() else "")
            path = arg if os.path.isabs(arg) \
                else os.path.join(cfg.base_dir, arg)
            if path not in graph_of_args:
                graph_of_args[path] = len(graphs)
                graphs.append(tgen_app.parse_tgen(path))
            host_graph[i] = graph_of_args[path]
            start_t[i] = p.starttime_s * SEC
            if p.stoptime_s:
                stop_t[i] = p.stoptime_s * SEC

    # --- sizing -----------------------------------------------------------
    # Server fan-in bounds the needed socket slots: count clients whose
    # peers list names each server.
    def resolve_peer(spec: str):
        name, _, port = spec.rpartition(":")
        return dns.resolve_name(name).host_index, int(port)

    fan_in = np.zeros(h, np.int64)
    for i in range(h):
        g = host_graph[i]
        if g < 0:
            continue
        for node_peers in graphs[int(g)].peers:
            for ps in node_peers:
                fan_in[resolve_peer(ps)[0]] += 1
    if sock_slots is None:
        sock_slots = int(max(4, min(512, 2 * fan_in.max() + 4)))
        if real_procs:
            # Real processes allocate slots dynamically (sockets, child
            # connections); give them headroom the graph analysis above
            # cannot see.
            sock_slots = max(sock_slots, 16)

    # Packets occupy the *source* host's pool slab until consumed, so a
    # high-fan-in server needs slab room proportional to its concurrent
    # client count; exhaustion degrades to counted drops + the
    # ERR_POOL_OVERFLOW escape hatch rather than corruption.
    slab = int(max(pool_slab, min(4096, 32 * (1 + fan_in.max()))))

    # State construction is hundreds of small array ops; build it on the
    # local CPU backend and ship the finished pytree to the device once
    # (shadow1_tpu.build_on_host) -- on an accelerator each tiny op is
    # its own dispatch.
    def _build_state():
        state = make_sim_state(h, sock_slots=sock_slots,
                               pool_capacity=h * slab)
        socks = state.socks
        # Per-host socket-buffer defaults (reference <host
        # socketsendbuffer/socketrecvbuffer> -> host.c:162-220); every
        # socket the host creates starts from these.
        if (snd_buf > 0).any():
            socks = socks.replace(def_snd_buf=jnp.where(
                jnp.asarray(snd_buf > 0), jnp.asarray(snd_buf, jnp.int32),
                socks.def_snd_buf))
        if (rcv_buf > 0).any():
            socks = socks.replace(def_rcv_buf=jnp.where(
                jnp.asarray(rcv_buf > 0), jnp.asarray(rcv_buf, jnp.int32),
                socks.def_rcv_buf))
        for gi, g in enumerate(graphs):
            if g.serverport > 0:
                mask = jnp.asarray(host_graph == gi)
                socks = tcp.listen_v(socks, mask, 0, g.serverport,
                                     backlog=int(fan_in.max()) + 1)
        state = state.replace(socks=socks)
        if real_procs and not graphs:
            # Pure real-process world: the substrate datagram ring is
            # the only on-device app (the tgen interpreter cannot run on
            # zero graphs).
            from ..substrate import devapp
            return state.replace(app=devapp.init_state(h))
        tg_state = tgen_app.build_state(
            h, graphs, host_graph, start_t, stop_t,
            resolve_peer=resolve_peer)
        if real_procs:
            # Real processes need the device-side datagram ring; compose
            # it with the modeled tgen interpreter (apps/compose.py).
            from ..substrate import devapp
            return state.replace(app=(devapp.init_state(h), tg_state))
        return state.replace(app=tg_state)

    state = _pkg.build_on_host(_build_state)

    # --- netem (<netem> section): fault/dynamics schedule -----------------
    netem_tl = None
    if cfg.netem is not None:
        from .. import netem as _netem
        spec = cfg.netem
        netem_tl = _netem.load_json(
            {"events": spec.events, "groups": spec.groups},
            resolve=lambda n: dns.resolve_name(n).host_index)
        if spec.churn_rate:
            end_s = (spec.churn_end_s if spec.churn_end_s is not None
                     else cfg.stoptime_s)
            netem_tl.chaos(params.seed_key, h, spec.churn_rate,
                           mean_down_s=spec.churn_downtime_s,
                           t_start=int(spec.churn_start_s * SEC),
                           t_end=int(end_s * SEC))
        state, params = _netem.install(state, params, netem_tl)

    if real_procs:
        from ..apps.compose import Stacked
        from ..substrate import devapp
        if graphs:
            app = Stacked(devapp.SubstrateTx(), tgen_app.Tgen())
        else:
            app = devapp.SubstrateTx()
    else:
        app = tgen_app.Tgen()

    if bucket:
        from .. import shapes
        state, params = shapes.pad_world_to_bucket(state, params)

    return Assembled(state=state, params=params, app=app, hostnames=names,
                     dns=dns, topology=topo, config=cfg,
                     stop_time=cfg.stoptime_s * SEC,
                     pcap_mask=pcap_mask, pcap_dirs=pcap_dirs,
                     heartbeat_freq_s=hb_freq, loglevels=loglevels,
                     real_procs=real_procs, netem=netem_tl)


def load(path: str, **kw) -> Assembled:
    from . import shadowxml
    return build(shadowxml.parse(path), **kw)
