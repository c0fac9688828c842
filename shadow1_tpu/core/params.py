"""Static (per-run constant) simulation parameters.

The reference resolves latency/reliability lazily per source via Dijkstra
with a path cache (/root/reference/src/main/routing/topology.c:1678-1875).
Here the whole all-pairs answer is precomputed once at startup into dense
matrices indexed by topology vertex (see routing/apsp.py), and per-packet
"routing" is a 2-D gather -- the TPU-shaped replacement for the path cache.
"""

from __future__ import annotations

from flax import struct
import jax
import jax.numpy as jnp

from . import simtime
from .state import I32, I64, F32

QDISC_FIFO = 0
QDISC_RR = 1


# Columns of the packed routing block (all i32; i64 split lo/hi, f32
# bitcast).  One [V*V, 5] block means per-packet routing is ONE row
# gather instead of three separate [V,V] gathers -- gathers are among the
# few ops with real per-index cost inside a compiled loop
# (tools/opbench*.py), and the hot path issues them at [H, E] volume.
# Column ORDER is load-bearing: the always-needed fields (latency,
# reliability) come first so jitter-free worlds (the common case) gather
# only the leading RCOLS_NARROW columns per packet.
(RCOL_LAT_LO, RCOL_LAT_HI, RCOL_REL, RCOL_JIT_LO, RCOL_JIT_HI) = range(5)
RCOLS = 5
RCOLS_NARROW = 3            # lat lo/hi + reliability


@struct.dataclass
class NetParams:
    """Constant under jit for a whole run (still a pytree of arrays so it
    can be donated/sharded)."""

    route_blk: jnp.ndarray      # [V*V, RCOLS] i32 packed per-pair routing:
                                # one-way latency ns (i64 as lo/hi),
                                # jitter amplitude ns (i64 as lo/hi;
                                # per-packet latency perturbed uniformly in
                                # +/- this, reference edge attr
                                # topology.c:81-105), delivery probability
                                # (f32 bitcast)
    host_vertex: jnp.ndarray    # [H] i32 topology vertex each host attached to
    bw_up_Bps: jnp.ndarray      # [H] i64 upstream bytes/sec
    bw_down_Bps: jnp.ndarray    # [H] i64 downstream bytes/sec
    min_latency_ns: jnp.ndarray  # i64 scalar: conservative lookahead (min jump)
    seed_key: jax.Array         # PRNG root key
    stop_time: jnp.ndarray      # i64 scalar
    bootstrap_end: jnp.ndarray  # i64 scalar: before this, bandwidth unlimited
                                # (reference master.c:261-268, worker.c:445-453)
    # Virtual CPU model (reference cpu.c:15-108 + event deferral
    # event.c:71-84): every delivered packet / staged emission costs
    # cpu_ns_per_event of virtual CPU time; when the accumulated backlog
    # exceeds the threshold the host stops executing events until the
    # backlog drains.  0 = no CPU model for that host.
    cpu_ns_per_event: jnp.ndarray  # [H] i64
    cpu_threshold_ns: jnp.ndarray  # i64 scalar (reference --cpu-threshold)
    cpu_precision_ns: jnp.ndarray  # i64 scalar (reference --cpu-precision)
    # Interface qdisc (reference --interface-qdisc,
    # network_interface.c:466-540): QDISC_FIFO serves the lowest eligible
    # socket slot (creation order); QDISC_RR round-robins across them.
    qdisc: jnp.ndarray             # i32 scalar QDISC_*
    # Per-host TCP buffer autotuning switches: explicitly configured
    # socket buffers disable the corresponding autotune, mirroring the
    # reference (tcp.c autotune only when not user-set).
    autotune_snd: jnp.ndarray      # [H] bool
    autotune_rcv: jnp.ndarray      # [H] bool
    # Interface receive buffer in packets (reference <host
    # interfacebuffer> bytes / MTU; network_interface.c receive-side
    # bound): arrivals beyond this router backlog are tail-dropped
    # before CoDel even sees them.  0 = unbounded.
    iface_buf_pkts: jnp.ndarray    # [H] i32
    # Per-host capture gate (reference <host logpcap>): a packet is
    # recorded when its source OR destination host is marked.  Only
    # consulted when a CaptureRing is installed.
    pcap_mask: jnp.ndarray         # [H] bool
    # Traced REAL host count (present-or-None, the SimState.hoff
    # pattern): installed by shapes.pad_world_to_bucket when a world is
    # padded up to a shape bucket, so app-level global draws (phold's
    # dst pick) see the real count while every [H] array carries padded
    # rows.  None is a trace-time static -- un-bucketed worlds compile
    # byte-identical graphs to before this field existed.  When present
    # it is a runtime input, so every world padded into the same bucket
    # shares ONE compiled graph (docs/shapes.md).
    hosts_real: any = struct.field(pytree_node=True, default=None)  # i32 scalar | None
    # Congestion-control algorithm (reference --tcp-congestion-control,
    # tcp_cong.h hook table): STATIC -- part of the compiled step's
    # identity, so the untaken algorithm traces away.
    cong: str = struct.field(pytree_node=False, default="reno")
    # STATIC: any host has a bounded interface buffer.  The tail-drop
    # ranking costs an [H, slab, slab] comparison cube per micro-step, so
    # it must trace away entirely for the (default) unbounded case.
    has_iface_buf: bool = struct.field(pytree_node=False, default=False)
    # STATIC: maintain the per-packet PDS_* delivery-status trail
    # (reference packet.h:18-41).  Pure observability -- nothing consumes
    # it programmatically -- and it costs a packed scatter per window plus
    # masked updates in every micro-step, so it traces away by default.
    pds_trail: bool = struct.field(pytree_node=False, default=False)
    # STATIC: any pair has reliability < 1.0.  When False (and no fault
    # overlay is installed) the per-emission drop draw is provably never
    # taken, so the whole keyed-uniform hash chain traces away.  The
    # default True is the conservative always-correct setting; builders
    # going through make_net_params get the computed value.
    has_loss: bool = struct.field(pytree_node=False, default=True)
    # STATIC: any pair has jitter > 0.  When False the per-packet jitter
    # draw traces away AND routing gathers only the narrow (lat, rel)
    # leading columns of route_blk.
    has_jitter: bool = struct.field(pytree_node=False, default=True)
    # STATIC master switch for the dynamic micro-step gates (lax.cond
    # around _tx_drain / TCP timers / arrivals / transmit): the gated
    # graph is bitwise-identical to the ungated one -- this switch exists
    # so tests can run both variants and assert exactly that
    # (tests/test_kernel_diet.py).
    kernel_diet: bool = struct.field(pytree_node=False, default=True)
    # STATIC: compile the micro-step phase graph (drain -> route ->
    # deliver -> transport) into the hand-fused Pallas kernels in
    # core/megakernel.py instead of the reference XLA op-graph.  Default
    # off: the TPU's Mosaic compiler refuses the kernels today, so
    # asking for them on a TPU raises (megakernel.FusedPathUnavailable);
    # on other backends they run in Pallas interpret mode, which is how
    # the CPU tests pin them bitwise against the reference graph
    # (docs/megakernel.md).  The reference path (megakernel=False) is
    # the one that runs on every backend and the correctness oracle.
    megakernel: bool = struct.field(pytree_node=False, default=False)
    # STATIC: compile the WHOLE conservative window -- the boundary
    # exchange, the per-window scan, the netem advance, and the
    # micro-step while loop with its gmin loop predicate -- into one
    # persistent Pallas region (core/megakernel.py window_fused), so a
    # window costs O(1) kernel launches instead of O(steps x phases).
    # Only consulted when the megakernel path is admissible at all
    # (megakernel.persistent_enabled); off-mesh only -- the mesh's
    # loop-driving collectives cannot live inside a kernel, so sharded
    # runs keep the per-phase fused kernels.  persistent=False lowers
    # byte-identical HLO to pre-persistent builds.  Default off, with
    # megakernel.
    persistent: bool = struct.field(pytree_node=False, default=False)

    def global_hosts(self):
        """Global host count for app-level draws ("pick a random host"):
        the traced `hosts_real` scalar when installed (bucket-padded
        world, where the static row count would see the PADDED size and
        change every draw), else the static row count (a Python int, so
        the graph is byte-identical to pre-bucketing code).  Row counts
        stay exact in f32 up to 2**24, far above the 1M-host ladder cap,
        so the draw arithmetic is bitwise the same either way."""
        if self.hosts_real is not None:
            return self.hosts_real
        return self.host_vertex.shape[0]

    @property
    def n_vertices(self) -> int:
        v = int(round(self.route_blk.shape[0] ** 0.5))
        assert v * v == self.route_blk.shape[0]
        return v

    def route(self, vs, vd):
        """Packed routing lookup: one row gather.  Returns
        (latency_ns i64, jitter_ns i64, reliability f32) for any
        broadcastable integer index shapes."""
        from .state import dec_i64
        rows = self.route_blk[vs * self.n_vertices + vd]
        lat = dec_i64(rows[..., RCOL_LAT_LO], rows[..., RCOL_LAT_HI])
        jit = dec_i64(rows[..., RCOL_JIT_LO], rows[..., RCOL_JIT_HI])
        rel = jax.lax.bitcast_convert_type(rows[..., RCOL_REL], F32)
        return lat, jit, rel

    def route_narrow(self, vs, vd):
        """Jitter-free routing lookup: gather only the leading
        (lat lo/hi, rel) columns per packet.  The static column slice is
        loop-invariant, so XLA hoists it out of the micro-step while
        body and the per-packet gather moves 3/5 the bytes.  Returns
        (latency_ns i64, reliability f32)."""
        from .state import dec_i64
        narrow = self.route_blk[:, :RCOLS_NARROW]
        rows = narrow[vs * self.n_vertices + vd]
        lat = dec_i64(rows[..., RCOL_LAT_LO], rows[..., RCOL_LAT_HI])
        rel = jax.lax.bitcast_convert_type(rows[..., RCOL_REL], F32)
        return lat, rel

    @property
    def latency_ns(self):
        """[V,V] i64 latency matrix (decoded view, for host-side use)."""
        v = self.n_vertices
        from .state import dec_i64
        return dec_i64(self.route_blk[:, RCOL_LAT_LO],
                       self.route_blk[:, RCOL_LAT_HI]).reshape(v, v)

    @property
    def jitter_ns(self):
        v = self.n_vertices
        from .state import dec_i64
        return dec_i64(self.route_blk[:, RCOL_JIT_LO],
                       self.route_blk[:, RCOL_JIT_HI]).reshape(v, v)

    @property
    def reliability(self):
        v = self.n_vertices
        return jax.lax.bitcast_convert_type(
            self.route_blk[:, RCOL_REL], F32).reshape(v, v)

    def pair_latency(self, src_host, dst_host):
        """One-way latency between two hosts (ns)."""
        vs = self.host_vertex[src_host]
        vd = self.host_vertex[dst_host]
        return self.route(vs, vd)[0]

    def pair_reliability(self, src_host, dst_host):
        vs = self.host_vertex[src_host]
        vd = self.host_vertex[dst_host]
        return self.route(vs, vd)[2]


def make_net_params(
    latency_ns,
    reliability,
    host_vertex,
    bw_up_Bps,
    bw_down_Bps,
    seed: int = 1,
    stop_time: int = simtime.SIMTIME_ONE_SECOND,
    bootstrap_end: int = 0,
    min_latency_ns=None,
    jitter_ns=None,
    cpu_ns_per_event=None,
    cpu_threshold_ns: int = -1,  # reference --cpu-threshold default:
                                 # negative = CPU never blocks
    cpu_precision_ns: int = 200 * simtime.SIMTIME_ONE_MICROSECOND,
    qdisc: int = QDISC_FIFO,
    autotune_snd=None,
    autotune_rcv=None,
    iface_buf_pkts=None,
    pcap_mask=None,
    cong: str = "reno",
    megakernel: bool = False,
    persistent: bool = False,
) -> NetParams:
    from . import rng

    latency_ns = jnp.asarray(latency_ns, I64)
    if jitter_ns is None:
        jitter_ns = jnp.zeros_like(latency_ns)
    jitter_ns = jnp.asarray(jitter_ns, I64)
    if min_latency_ns is None:
        # Minimum latency over every pair that can carry CROSS-HOST
        # traffic bounds the lookahead window, like the reference's min
        # time jump with a 10ms default when the topology gives nothing
        # (master.c:133-159).  Jitter can shorten a path, so the
        # conservative bound subtracts it.  A vertex's self-path counts
        # whenever two or more hosts share that vertex (same-host
        # loopback bypasses the matrix and never constrains the window).
        v = latency_ns.shape[0]
        hv = jnp.asarray(host_vertex, I32)
        occupants = jnp.zeros((v,), I32).at[hv].add(1)
        shared_self = occupants >= 2
        eye = jnp.eye(v, dtype=bool)
        eligible = (~eye) | (eye & shared_self[None, :])
        eff = jnp.maximum(latency_ns - jitter_ns, 1)
        inv = jnp.asarray(simtime.SIMTIME_INVALID, I64)
        cand = jnp.where(eligible & (latency_ns > 0), eff, inv)
        m = jnp.min(cand)
        min_latency_ns = jnp.where(
            m == simtime.SIMTIME_INVALID,
            jnp.asarray(10 * simtime.SIMTIME_ONE_MILLISECOND, I64),
            m,
        )
    h = jnp.asarray(host_vertex).shape[0]
    if cpu_ns_per_event is None:
        cpu_ns_per_event = jnp.zeros((h,), I64)
    if autotune_snd is None:
        autotune_snd = jnp.ones((h,), bool)
    if autotune_rcv is None:
        autotune_rcv = jnp.ones((h,), bool)
    if iface_buf_pkts is None:
        iface_buf_pkts = jnp.zeros((h,), I32)
    if pcap_mask is None:
        pcap_mask = jnp.ones((h,), bool)
    from .state import enc_lo, enc_hi
    rel_m = jnp.asarray(reliability, F32)
    route_blk = jnp.stack([
        enc_lo(latency_ns.reshape(-1)),
        enc_hi(latency_ns.reshape(-1)),
        jax.lax.bitcast_convert_type(rel_m.reshape(-1), I32),
        enc_lo(jitter_ns.reshape(-1)),
        enc_hi(jitter_ns.reshape(-1)),
    ], axis=1)
    return NetParams(
        route_blk=route_blk,
        host_vertex=jnp.asarray(host_vertex, I32),
        bw_up_Bps=jnp.asarray(bw_up_Bps, I64),
        bw_down_Bps=jnp.asarray(bw_down_Bps, I64),
        min_latency_ns=jnp.asarray(min_latency_ns, I64),
        # `seed` is an int (the common case) or an already-derived PRNG
        # key -- ensemble.replicate builds world k from
        # rng.world_key(root_key(seed), k) and hands the key through.
        seed_key=(seed if isinstance(seed, jnp.ndarray)
                  else rng.root_key(seed)),
        stop_time=jnp.asarray(stop_time, I64),
        bootstrap_end=jnp.asarray(bootstrap_end, I64),
        cpu_ns_per_event=jnp.asarray(cpu_ns_per_event, I64),
        cpu_threshold_ns=jnp.asarray(cpu_threshold_ns, I64),
        cpu_precision_ns=jnp.asarray(cpu_precision_ns, I64),
        qdisc=jnp.asarray(qdisc, I32),
        autotune_snd=jnp.asarray(autotune_snd, bool),
        autotune_rcv=jnp.asarray(autotune_rcv, bool),
        iface_buf_pkts=jnp.asarray(iface_buf_pkts, I32),
        pcap_mask=jnp.asarray(pcap_mask, bool),
        cong=cong,
        has_iface_buf=bool(jnp.any(jnp.asarray(iface_buf_pkts, I32) > 0)),
        has_loss=bool(jnp.any(rel_m < 1.0)),
        has_jitter=bool(jnp.any(jitter_ns > 0)),
        megakernel=bool(megakernel),
        persistent=bool(persistent),
    )
