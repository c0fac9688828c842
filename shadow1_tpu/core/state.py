"""Simulator state as dense structure-of-arrays pytrees.

The reference keeps one heap-allocated object graph per host (Host owns
NetworkInterfaces, Routers, Descriptors, TCP structs; reference
src/main/host/host.c:57-105) and a locked priority queue of event objects
per host (scheduler_policy_host_single.c).  Here the same information lives
in fixed-capacity dense arrays with a leading `hosts` axis, so one compiled
device step advances every host at once and the host axis can be sharded
over a TPU mesh.

Three big tables:

* `PacketPool` -- every packet in the simulated world, in any lifecycle
  stage (reference: Packet objects + per-queue linked lists,
  src/main/routing/packet.c:40-63).  A packet's position in the network is
  a `stage` tag, not a container: FREE -> TX_QUEUED (socket/qdisc/token
  bucket at source, reference network_interface.c:466-540) -> IN_FLIGHT
  (latency line, reference worker.c:243-304) -> RX_QUEUED (destination
  upstream-router CoDel queue, reference router_queue_codel.c) -> consumed.
  Stage transitions are vectorized masked updates; "queues" are recovered
  by sorting on (time, id) keys, which reproduces the reference's
  deterministic event total order (src/main/core/work/event.c:110-153).

* `SocketTable` -- `[H, S]` per-host socket slots holding the entire
  transport state machine as int fields (reference TCP struct,
  src/main/host/descriptor/tcp.c:125-230).

* `HostTable` -- `[H]` per-host NIC token buckets, RNG counters, and
  tracker counters (reference network_interface.c:32-40, tracker.c).

Payload *bytes* never live on device: packets carry a `length` and an
optional host-side arena id (`payload_id`), mirroring how the reference
shares one refcounted Payload across hosts (src/main/routing/payload.c) --
the device only ever needs metadata.
"""

from __future__ import annotations

from flax import struct
import jax
import jax.numpy as jnp

from . import simtime

# ---------------------------------------------------------------------------
# Enums / constants
# ---------------------------------------------------------------------------

# Packet lifecycle stages.
STAGE_FREE = 0
STAGE_TX_QUEUED = 1   # waiting for source NIC tokens / qdisc
STAGE_IN_FLIGHT = 2   # traversing the latency line
STAGE_RX_QUEUED = 3   # in destination upstream-router (CoDel) queue

# IP protocols (only these two exist in the simulated net, like the
# reference's PTCP/PUDP/PLOCAL protocol tags, packet.h).
PROTO_NONE = 0
PROTO_TCP = 6
PROTO_UDP = 17

# TCP header flags.
TCP_FLAG_FIN = 1
TCP_FLAG_SYN = 2
TCP_FLAG_RST = 4
TCP_FLAG_PSH = 8   # used as the zero-window probe marker (forces an ACK)
TCP_FLAG_ACK = 16

# Socket slot types.
SOCK_FREE = 0
SOCK_UDP = 1
SOCK_TCP = 2

# TCP states (reference tcp.c:41-55).
TCPS_CLOSED = 0
TCPS_LISTEN = 1
TCPS_SYNSENT = 2
TCPS_SYNRECEIVED = 3
TCPS_ESTABLISHED = 4
TCPS_FINWAIT1 = 5
TCPS_FINWAIT2 = 6
TCPS_CLOSING = 7
TCPS_TIMEWAIT = 8
TCPS_CLOSEWAIT = 9
TCPS_LASTACK = 10

# Packet delivery-status trail bits, the observability analog of the
# reference's PDS_* flags (src/main/routing/packet.h:18-41).
PDS_SND_CREATED = 1 << 0
PDS_SND_TCP_ENQUEUE_THROTTLED = 1 << 1
PDS_SND_INTERFACE_SENT = 1 << 2
PDS_INET_SENT = 1 << 3
PDS_INET_DROPPED = 1 << 4
PDS_ROUTER_ENQUEUED = 1 << 5
PDS_ROUTER_DROPPED = 1 << 6
PDS_RCV_INTERFACE_RECEIVED = 1 << 7
PDS_RCV_SOCKET_PROCESSED = 1 << 8
PDS_DESTROYED = 1 << 9

# Error flag bits (raised to the host between windows; the escape hatch for
# fixed-capacity overflow).
ERR_POOL_OVERFLOW = 1 << 0
ERR_SOCKET_OVERFLOW = 1 << 1
ERR_UDPQ_OVERFLOW = 1 << 2

I32 = jnp.int32
I64 = jnp.int64
U32 = jnp.uint32
F32 = jnp.float32

MTU = 1500          # reference CONFIG_MTU, definitions.h:188
TCP_HEADER_SIZE = 40   # reference CONFIG_HEADER_SIZE_TCPIPETH ballpark
UDP_HEADER_SIZE = 28
TCP_MSS = MTU - TCP_HEADER_SIZE


def _full(shape, dtype, value):
    return jnp.full(shape, value, dtype=dtype)


def _zeros(shape, dtype):
    return jnp.zeros(shape, dtype=dtype)


# ---------------------------------------------------------------------------
# Packet pool
# ---------------------------------------------------------------------------


@struct.dataclass
class PacketPool:
    """All packets in the world (the OUTBOX half); fixed capacity P.

    Layout (round 5, narrowed round 7): every per-packet field that is
    written ONCE at staging lives in a packed [P, C] i32 block whose
    prefix columns are byte-identical to the world's inbox layout
    (C = OCOLS for TCP worlds, NCOLS_UDP + OEXT_COLS for TCP-free ones;
    see pool_cols/ext_base) -- emission staging writes the block with
    ONE one-hot merge (instead of ~21 per-field merges, the largest
    phase of the round-4 step), and the boundary exchange forwards rows
    into the inbox with a 2-column time splice instead of a 24-field
    re-pack.  Only the hot-mutated lifecycle
    fields stay as separate arrays: `stage` (every phase), `time`
    (authoritative; _tx_drain restamps departures), `status` (PDS trail).

    The deterministic total-order tiebreaker pkt_id = (src << 40) | ctr
    lives in the block's CTR columns, mirroring the reference's
    (srcHostID, srcHostEventID) order component (event.c:110-153); drop
    draws are keyed by it so loss is identical across meshes and window
    batchings.
    """

    blk: jnp.ndarray          # [P, C] i32 packed (immutable per stay;
                              # TIME cols stale after _tx_drain -- `time`
                              # below is authoritative).  C = OCOLS, or
                              # NCOLS_UDP + OEXT_COLS for TCP-free worlds
                              # (pool_cols); extension columns sit at the
                              # END of the block (ext_base + OEXT_*).
    stage: jnp.ndarray        # [P] i32 STAGE_*
    time: jnp.ndarray         # [P] i64 stage-dependent: ready/deliver/arrive
    status: jnp.ndarray       # [P] i32 PDS_* trail

    @property
    def capacity(self) -> int:
        return self.stage.shape[0]

    # Decoded views (observability / tests; column slices are cheap).
    @property
    def src(self):
        return self.blk[:, ICOL_SRC]

    @property
    def dst(self):
        return self.blk[:, ext_base(self.blk.shape[1]) + OEXT_DST]

    @property
    def proto(self):
        return self.blk[:, ICOL_PROTO]

    @property
    def length(self):
        return self.blk[:, ICOL_LEN]

    @property
    def lat_ns(self):
        b = ext_base(self.blk.shape[1])
        return dec_i64(self.blk[:, b + OEXT_LAT_LO],
                       self.blk[:, b + OEXT_LAT_HI])

    @property
    def priority(self):
        b = ext_base(self.blk.shape[1])
        return jax.lax.bitcast_convert_type(self.blk[:, b + OEXT_PRIO], F32)

    @property
    def pkt_id(self):
        src = self.blk[:, ICOL_SRC].astype(I64)
        ctr = dec_i64(self.blk[:, ICOL_CTR_LO], self.blk[:, ICOL_CTR_HI])
        return (src << 40) | ctr


def make_packet_pool(capacity: int, cols: int = None) -> PacketPool:
    return PacketPool(
        blk=_zeros((capacity, OCOLS if cols is None else cols), I32),
        stage=_zeros((capacity,), I32),
        time=_full((capacity,), I64, simtime.SIMTIME_INVALID),
        status=_zeros((capacity,), I32),
    )


# ---------------------------------------------------------------------------
# Inbox: per-DESTINATION slabs of arrived/arriving packets
# ---------------------------------------------------------------------------

# Column indices of the packed inbox block.  Everything is i32: packed
# row scatters of i32 are ~10x cheaper than i64 on this backend
# (tools/opbench.py), so i64 fields are split into (lo31, hi) pairs and
# u32 fields are bitcast.  All values are non-negative, so the 31-bit
# split round-trips exactly.
(ICOL_SRC, ICOL_SPORT, ICOL_DPORT, ICOL_PROTO, ICOL_FLAGS, ICOL_SEQ,
 ICOL_ACK, ICOL_WND, ICOL_LEN, ICOL_PAYLOAD,
 ICOL_TIME_LO, ICOL_TIME_HI, ICOL_CTR_LO, ICOL_CTR_HI,
 ICOL_TS_LO, ICOL_TS_HI, ICOL_TSE_LO, ICOL_TSE_HI,
 ICOL_SACK0_LO, ICOL_SACK0_HI, ICOL_SACK1_LO, ICOL_SACK1_HI,
 ICOL_SACK2_LO, ICOL_SACK2_HI) = range(24)
ICOLS = 24

# Narrow inbox width for worlds whose app never opens TCP sockets: the
# TS/TSE/SACK columns (14..23) only feed the TCP machine, and the
# window-boundary exchange moves whole packed rows into the inbox (a row
# gather on one device, a row scatter on the mesh) -- moving 14 columns
# instead of 24 cut the scatter ~40% for pure-UDP worlds (phold,
# tools/exchprof.py).
NCOLS_UDP = ICOL_CTR_HI + 1

# Outbox/emission extension columns: the packed OUTBOX block (and the
# emission staging block) shares the inbox's first ICOLS columns exactly,
# then appends the send-side-only fields.  One layout end to end means
# emit.put writes rows in their final wire format, staging merges ONE
# block, and the boundary exchange forwards rows verbatim (time spliced).
OCOL_DST = ICOLS + 0       # destination host
OCOL_LAT_LO = ICOLS + 1    # path latency incl. the packet's jitter draw,
OCOL_LAT_HI = ICOLS + 2    # fixed at staging (parked departures skip routing)
OCOL_PRIO = ICOLS + 3      # qdisc priority (f32 bitcast)
OCOLS = ICOLS + 4

# Width-relative extension addressing (round 7): the outbox block (and
# the emission staging block) is the inbox prefix -- ICOLS columns, or
# NCOLS_UDP for TCP-free worlds, matching the world's inbox width --
# followed by the four send-side extension columns ABOVE.  Extension
# columns are addressed from the END of the block (ext_base(C) + OEXT_*)
# so the same code compiles for both widths; the OCOL_* constants are the
# full-width (C == OCOLS) spellings and keep working for TCP worlds.
# Narrowing the outbox drops the TS/TSE/SACK columns that only feed the
# TCP machine from emit.put's row stack AND the staging merge's
# [H, E, Ko] one-hot -- the largest micro-step phase (PERF.md round 7).
(OEXT_DST, OEXT_LAT_LO, OEXT_LAT_HI, OEXT_PRIO) = range(4)
OEXT_COLS = 4


def ext_base(cols: int) -> int:
    """First extension column of a width-`cols` packed outbox block."""
    return cols - OEXT_COLS


def pool_cols(uses_tcp: bool) -> int:
    """Packed outbox/emission block width for a world: the world's inbox
    width plus the send-side extension columns."""
    return (ICOLS if uses_tcp else NCOLS_UDP) + OEXT_COLS


# Staging-scratch columns appended to the merge (split off into the
# separate stage/status arrays after the one big one-hot merge).  These
# are spelled relative to the block width at the staging site -- the
# full-width constants below exist for the C == OCOLS case.
MCOL_STAGE = OCOLS + 0
MCOL_STATUS = OCOLS + 1
MCOLS = OCOLS + 2

# SACK blocks carried per segment (reference packet TCP header
# selectiveACKs list, packet.c; RFC 2018 allows 3-4 -- 3 fit the
# timestamped header).
SACK_BLOCKS = 3

_LO_MASK = (1 << 31) - 1


def enc_lo(x):
    """Low 31 bits of a non-negative i64 as i32."""
    return (x & _LO_MASK).astype(I32)


def enc_hi(x):
    """High bits (>> 31) of a non-negative i64 as i32."""
    return (x >> 31).astype(I32)


def dec_i64(lo, hi):
    return (hi.astype(I64) << 31) | lo.astype(I64)


def onehot_slot(slots: int, slot):
    """[H,S] one-hot for a per-host slot index (clipped).  Indexed [H,S]
    gather/scatter costs real milliseconds inside a compiled loop; one-hot
    masked selects fuse for free (tools/opbench2.py)."""
    safe = jnp.clip(slot, 0, slots - 1)
    return safe[..., None] == jnp.arange(slots, dtype=I32)


def onehot_gather(tab, oh):
    """Gather [H] from [H,S] (or [H,S,R] with an [H,S,R] one-hot) under a
    one-hot mask; bool tables reduce with any()."""
    axes = tuple(range(1, tab.ndim)) if oh.ndim == tab.ndim else (1,)
    if tab.dtype == jnp.bool_:
        return jnp.any(oh & tab, axis=axes)
    return jnp.sum(jnp.where(oh, tab, 0), axis=axes, dtype=tab.dtype)


@struct.dataclass
class Inbox:
    """Packets at (or heading to) their destination, in per-destination
    slabs: slot `d * slab + k` belongs to destination host `d`.

    This is the receive half of the packet world (the reference's
    in-flight event queue + per-host upstream-router queue,
    src/main/core/worker.c:243-304 + router_queue_codel.c) laid out so
    every per-micro-step question -- "when is each host's next arrival",
    "which packet does the NIC drain next", "how deep is the router
    backlog" -- is a row-local reshape op over [H, slab] instead of a
    dst-keyed segment reduction over the whole pool (12.7ms vs ~0ms per
    micro-step at 16k hosts; tools/opbench*.py).  Packets enter in bulk
    at window boundaries (engine._exchange) or directly for same-host
    loopback; `stage`/`status` are the only fields mutated in the hot
    loop, elementwise.
    """

    blk: jnp.ndarray      # [P1, C] i32 packed fields (immutable per stay;
                          # C = ICOLS, or NCOLS_UDP for TCP-free worlds)
    # stage/status stay SEPARATE [P1] arrays: packing them into a [P1,2]
    # block made every hot-loop stage read a stride-2 load and cost ~25%
    # of phold throughput for one saved per-window scatter (measured r5).
    stage: jnp.ndarray    # [P1] i32 STAGE_FREE / IN_FLIGHT / RX_QUEUED
    status: jnp.ndarray   # [P1] i32 PDS_* trail

    @property
    def capacity(self) -> int:
        return self.stage.shape[0]

    def times(self):
        """[P1] i64 arrival times (decode of the packed columns)."""
        return dec_i64(self.blk[:, ICOL_TIME_LO], self.blk[:, ICOL_TIME_HI])

    def order_keys(self):
        """[P1] i64 deterministic total-order tiebreak (src << 40) | ctr,
        identical to the outbox pkt_id (reference event.c:110-153)."""
        src = self.blk[:, ICOL_SRC].astype(I64)
        ctr = dec_i64(self.blk[:, ICOL_CTR_LO], self.blk[:, ICOL_CTR_HI])
        return (src << 40) | ctr


def make_inbox(num_hosts: int, slab: int, cols: int = ICOLS) -> Inbox:
    p1 = num_hosts * slab
    return Inbox(
        blk=_zeros((p1, cols), I32),
        stage=_zeros((p1,), I32),
        status=_zeros((p1,), I32),
    )


# ---------------------------------------------------------------------------
# Socket table
# ---------------------------------------------------------------------------

SACK_RANGES = 8  # out-of-order reassembly: byte ranges held past rcv_nxt
SSACK_RANGES = 4  # sender-side sacked-range scoreboard (smaller: holes
                  # refill quickly and every range costs compiled-graph ops)
UDP_RING = 8     # per-UDP-socket datagram ring entries


@struct.dataclass
class SocketTable:
    """[H, S] socket slots; the whole descriptor/transport layer.

    The reference's vtable hierarchy Descriptor->Transport->Socket->TCP/UDP
    (descriptor/socket.h) collapses into one table of int fields; the
    "vtable dispatch" is a vectorized select on `stype`/`tcp_state`.
    """

    stype: jnp.ndarray        # [H,S] i32 SOCK_*
    tcp_state: jnp.ndarray    # [H,S] i32 TCPS_*
    local_port: jnp.ndarray   # [H,S] i32 0 = unbound
    peer_host: jnp.ndarray    # [H,S] i32 -1 = none
    peer_port: jnp.ndarray    # [H,S] i32
    parent: jnp.ndarray       # [H,S] i32 listener slot for accepted children, -1
    accepted: jnp.ndarray     # [H,S] bool child handed to app via accept()
    child_order: jnp.ndarray  # [H,S] i64 SYN pkt_id: deterministic accept order
    backlog: jnp.ndarray      # [H,S] i32 listen backlog

    # --- send side (sequence space, reference tcp.c:125-150) ---
    snd_una: jnp.ndarray      # [H,S] u32 oldest unacked
    snd_nxt: jnp.ndarray      # [H,S] u32 next to transmit
    snd_end: jnp.ndarray      # [H,S] u32 end of app-supplied data
    snd_wnd: jnp.ndarray      # [H,S] i32 peer receive window
    snd_buf_cap: jnp.ndarray  # [H,S] i32 send buffer capacity (bytes)
    cwnd: jnp.ndarray         # [H,S] i32 congestion window (bytes)
    ssthresh: jnp.ndarray     # [H,S] i32
    dup_acks: jnp.ndarray     # [H,S] i32
    recover: jnp.ndarray      # [H,S] u32 fast-recovery high-water mark
    in_recovery: jnp.ndarray  # [H,S] bool
    retrans_nxt: jnp.ndarray  # [H,S] u32 retransmission cursor
    retrans_end: jnp.ndarray  # [H,S] u32 retransmission bound: retx pending
                              # while retrans_nxt < min(retrans_end, snd_nxt).
                              # Fast retransmit/partial ACK set a one-segment
                              # span; RTO sets the full go-back-N window.
    app_closed: jnp.ndarray   # [H,S] bool app called close(); FIN at snd_end

    # --- receive side ---
    rcv_nxt: jnp.ndarray      # [H,S] u32 next expected
    rcv_read: jnp.ndarray     # [H,S] u32 seq consumed by app
    rcv_buf_cap: jnp.ndarray  # [H,S] i32
    # Out-of-order reassembly scoreboard: up to SACK_RANGES disjoint byte
    # ranges [lo, hi) held past rcv_nxt, sorted by distance from rcv_nxt;
    # empty slot encoded as lo == hi.  The vectorized analog of the
    # reference's unordered-input pqueue + SACK list (tcp.c:222-230) and
    # the remora range arithmetic (tcp_retransmit_tally.cc).
    sack_lo: jnp.ndarray      # [H,S,SACK_RANGES] u32
    sack_hi: jnp.ndarray      # [H,S,SACK_RANGES] u32
    fin_seq: jnp.ndarray      # [H,S] u32 peer FIN sequence, 0 = none seen

    # --- timers & RTT (reference tcp.c:175-220) ---
    ts_recent: jnp.ndarray    # [H,S] i64 last in-window segment timestamp (TS.recent)
    srtt: jnp.ndarray         # [H,S] i64 ns, 0 = no sample yet
    rttvar: jnp.ndarray       # [H,S] i64 ns
    rto: jnp.ndarray          # [H,S] i64 ns
    t_rto: jnp.ndarray        # [H,S] i64 retransmit timer expiry, SIMTIME_INVALID = off
    t_delack: jnp.ndarray     # [H,S] i64 delayed-ACK timer
    t_tw: jnp.ndarray         # [H,S] i64 TIME_WAIT / misc timer
    t_persist: jnp.ndarray    # [H,S] i64 zero-window probe timer
    delack_pending: jnp.ndarray  # [H,S] i32 segments since last ACK sent
    # --- receive-buffer autotuning (reference tcp.c:535-561) ---
    at_bytes: jnp.ndarray     # [H,S] i64 bytes delivered since last adjust
    at_last: jnp.ndarray      # [H,S] i64 time of last adjustment
    # --- congestion-control algorithm state (transport/cong.py): CUBIC
    # epoch start + W_max; untouched under Reno ---
    cub_epoch: jnp.ndarray    # [H,S] i64 congestion-avoidance epoch start
    cub_wmax: jnp.ndarray     # [H,S] i32 window before the last reduction
    # --- sender-side SACK scoreboard (reference tcp_retransmit_tally.cc
    # marked-lost/sacked range arithmetic): byte ranges the peer has
    # selectively acknowledged; retransmission skips them ---
    ssack_lo: jnp.ndarray     # [H,S,SSACK_RANGES] u32
    ssack_hi: jnp.ndarray     # [H,S,SSACK_RANGES] u32
    retx_segs: jnp.ndarray    # [H,S] i32 segments retransmitted (telemetry)

    # --- UDP datagram ring ---
    udp_head: jnp.ndarray     # [H,S] i32
    udp_count: jnp.ndarray    # [H,S] i32
    udp_src: jnp.ndarray      # [H,S,UDP_RING] i32
    udp_sport: jnp.ndarray    # [H,S,UDP_RING] i32
    udp_len: jnp.ndarray      # [H,S,UDP_RING] i32
    udp_payload: jnp.ndarray  # [H,S,UDP_RING] i32 arena id

    # --- error & accounting ---
    error: jnp.ndarray        # [H,S] i32 pending socket error (errno-like)
    bytes_sent: jnp.ndarray   # [H,S] i64
    bytes_recv: jnp.ndarray   # [H,S] i64

    # --- per-host socket defaults (reference <host socketsendbuffer
    # socketrecvbuffer>, configuration.h:24-101 -> host.c:162-220): new
    # sockets initialize their buffer caps from these, so a config
    # override applies to every socket the host ever creates.
    def_snd_buf: jnp.ndarray  # [H] i32
    def_rcv_buf: jnp.ndarray  # [H] i32

    @property
    def num_hosts(self) -> int:
        return self.stype.shape[0]

    @property
    def slots(self) -> int:
        return self.stype.shape[1]


def make_socket_table(num_hosts: int, slots: int) -> SocketTable:
    hs = (num_hosts, slots)
    return SocketTable(
        stype=_zeros(hs, I32),
        tcp_state=_zeros(hs, I32),
        local_port=_zeros(hs, I32),
        peer_host=_full(hs, I32, -1),
        peer_port=_zeros(hs, I32),
        parent=_full(hs, I32, -1),
        accepted=_zeros(hs, jnp.bool_),
        child_order=_zeros(hs, I64),
        backlog=_zeros(hs, I32),
        snd_una=_zeros(hs, U32),
        snd_nxt=_zeros(hs, U32),
        snd_end=_zeros(hs, U32),
        snd_wnd=_zeros(hs, I32),
        snd_buf_cap=_zeros(hs, I32),
        cwnd=_zeros(hs, I32),
        ssthresh=_zeros(hs, I32),
        dup_acks=_zeros(hs, I32),
        recover=_zeros(hs, U32),
        in_recovery=_zeros(hs, jnp.bool_),
        retrans_nxt=_zeros(hs, U32),
        retrans_end=_zeros(hs, U32),
        app_closed=_zeros(hs, jnp.bool_),
        rcv_nxt=_zeros(hs, U32),
        rcv_read=_zeros(hs, U32),
        rcv_buf_cap=_zeros(hs, I32),
        sack_lo=_zeros(hs + (SACK_RANGES,), U32),
        sack_hi=_zeros(hs + (SACK_RANGES,), U32),
        fin_seq=_zeros(hs, U32),
        ts_recent=_zeros(hs, I64),
        srtt=_zeros(hs, I64),
        rttvar=_zeros(hs, I64),
        rto=_zeros(hs, I64),
        t_rto=_full(hs, I64, simtime.SIMTIME_INVALID),
        t_delack=_full(hs, I64, simtime.SIMTIME_INVALID),
        t_tw=_full(hs, I64, simtime.SIMTIME_INVALID),
        t_persist=_full(hs, I64, simtime.SIMTIME_INVALID),
        delack_pending=_zeros(hs, I32),
        at_bytes=_zeros(hs, I64),
        at_last=_zeros(hs, I64),
        cub_epoch=_zeros(hs, I64),
        cub_wmax=_zeros(hs, I32),
        ssack_lo=_zeros(hs + (SSACK_RANGES,), U32),
        ssack_hi=_zeros(hs + (SSACK_RANGES,), U32),
        retx_segs=_zeros(hs, I32),
        udp_head=_zeros(hs, I32),
        udp_count=_zeros(hs, I32),
        udp_src=_full(hs + (UDP_RING,), I32, -1),
        udp_sport=_zeros(hs + (UDP_RING,), I32),
        udp_len=_zeros(hs + (UDP_RING,), I32),
        udp_payload=_full(hs + (UDP_RING,), I32, -1),
        error=_zeros(hs, I32),
        bytes_sent=_zeros(hs, I64),
        bytes_recv=_zeros(hs, I64),
        # Defaults match the reference's CONFIG_SEND/RECV_BUFFER_SIZE
        # (definitions.h:101-164); overridden per host by assembly.
        def_snd_buf=_full((num_hosts,), I32, 131072),
        def_rcv_buf=_full((num_hosts,), I32, 174760),
    )


# ---------------------------------------------------------------------------
# Host table (NIC + per-host counters)
# ---------------------------------------------------------------------------


@struct.dataclass
class HostTable:
    """[H] per-host state outside the socket table.

    Token buckets mirror the reference's per-interface up/down buckets with
    1ms refill (network_interface.c:93-190); refill is computed lazily and
    continuously from `last_refill` instead of scheduling a refill event
    per ms per host (smoother than the reference's 1ms quantization;
    capacity is one refill interval + MTU like network_interface.c:192-226).

    CoDel fields implement the RFC 8289 control law of the reference's
    upstream-router queue (router_queue_codel.c:33-56,198-267): target
    sojourn 10ms, interval 100ms, drop-next spacing interval/sqrt(count).
    """

    rng_ctr: jnp.ndarray       # [H] u32 per-host app draw counter
    send_ctr: jnp.ndarray      # [H] i64 per-host packet emission counter (pkt_id low bits)
    cpu_avail: jnp.ndarray     # [H] i64 virtual-CPU available-at time
                               # (reference cpu.c timeCPUAvailable)
    rr_next: jnp.ndarray       # [H] i32 round-robin qdisc cursor
                               # (reference network_interface.c:466-540)
    t_resume: jnp.ndarray      # [H] i64 host has more same-time work (e.g. open
                               # TCP window not fully transmitted); SIMTIME_INVALID = none
    tokens_tx: jnp.ndarray     # [H] i64 bytes available to transmit
    tokens_rx: jnp.ndarray     # [H] i64 bytes available to receive
    last_refill_tx: jnp.ndarray  # [H] i64 last lazy-refill timestamp
    last_refill_rx: jnp.ndarray  # [H] i64 last lazy-refill timestamp
    tx_queued: jnp.ndarray     # [H] i32 packets parked in STAGE_TX_QUEUED
    rx_queued: jnp.ndarray     # [H] i32 packets parked in STAGE_RX_QUEUED
    # CoDel AQM state (reference router_queue_codel.c).
    codel_count: jnp.ndarray       # [H] i32 drops in current dropping cycle
    codel_dropping: jnp.ndarray    # [H] bool in dropping state
    codel_first_above: jnp.ndarray  # [H] i64 when sojourn first exceeded target
    codel_drop_next: jnp.ndarray   # [H] i64 next scheduled drop time
    # Tracker counters (reference tracker.c).
    bytes_sent: jnp.ndarray    # [H] i64
    bytes_recv: jnp.ndarray    # [H] i64
    pkts_sent: jnp.ndarray     # [H] i64
    pkts_recv: jnp.ndarray     # [H] i64
    pkts_dropped_inet: jnp.ndarray   # [H] i64 reliability drops
    pkts_dropped_router: jnp.ndarray  # [H] i64 CoDel/overflow drops
    pkts_dropped_pool: jnp.ndarray   # [H] i64 slab-exhaustion drops of
                                     # protocol-visible packets (the
                                     # fixed-capacity escape hatch; also
                                     # raises ERR_POOL_OVERFLOW)
    acks_thinned: jnp.ndarray        # [H] i64 pure ACKs deliberately shed
                                     # at exchange overflow (ACK-compression
                                     # analog: cumulative ACKing absorbs
                                     # them; NOT an error)

    @property
    def num_hosts(self) -> int:
        return self.rng_ctr.shape[0]


def make_host_table(num_hosts: int) -> HostTable:
    h = (num_hosts,)
    return HostTable(
        rng_ctr=_zeros(h, U32),
        send_ctr=_zeros(h, I64),
        cpu_avail=_zeros(h, I64),
        rr_next=_zeros(h, I32),
        t_resume=_full(h, I64, simtime.SIMTIME_INVALID),
        tokens_tx=_zeros(h, I64),
        tokens_rx=_zeros(h, I64),
        last_refill_tx=_zeros(h, I64),
        last_refill_rx=_zeros(h, I64),
        tx_queued=_zeros(h, I32),
        rx_queued=_zeros(h, I32),
        codel_count=_zeros(h, I32),
        codel_dropping=_zeros(h, jnp.bool_),
        codel_first_above=_zeros(h, I64),
        codel_drop_next=_zeros(h, I64),
        bytes_sent=_zeros(h, I64),
        bytes_recv=_zeros(h, I64),
        pkts_sent=_zeros(h, I64),
        pkts_recv=_zeros(h, I64),
        pkts_dropped_inet=_zeros(h, I64),
        pkts_dropped_router=_zeros(h, I64),
        pkts_dropped_pool=_zeros(h, I64),
        acks_thinned=_zeros(h, I64),
    )


# ---------------------------------------------------------------------------
# Packet capture ring (PCAP analog)
# ---------------------------------------------------------------------------


@struct.dataclass
class CaptureRing:
    """Fixed-capacity ring of sent-packet records, the device-side source
    for PCAP export (reference per-host capture,
    network_interface.c:337-373 + utility/pcap_writer.c).  Present in
    SimState only when capture is enabled, so disabled runs trace without
    any capture cost.  Older records are overwritten when the ring wraps;
    `total` counts lifetime appends so the writer knows."""

    time: jnp.ndarray    # [C] i64 send timestamp
    src: jnp.ndarray     # [C] i32
    dst: jnp.ndarray     # [C] i32
    sport: jnp.ndarray   # [C] i32
    dport: jnp.ndarray   # [C] i32
    proto: jnp.ndarray   # [C] i32
    flags: jnp.ndarray   # [C] i32
    length: jnp.ndarray  # [C] i32 payload bytes
    seq: jnp.ndarray     # [C] u32
    ack: jnp.ndarray     # [C] u32
    kind: jnp.ndarray    # [C] i32 CAP_* direction/disposition
    total: jnp.ndarray   # i64 scalar: lifetime records appended

    @property
    def capacity(self) -> int:
        return self.time.shape[0]


# Capture record kinds: the send direction (recorded at the source
# interface) vs the receive direction (recorded at the destination when
# delivered / when the router dropped it) -- the two per-interface views
# the reference's capture produces (network_interface.c:337-373,415-418).
CAP_SEND = 0
CAP_DELIVER = 1
CAP_RDROP = 2


def make_capture_ring(capacity: int = 1 << 16,
                      shards: int = 1) -> CaptureRing:
    """shards > 1 builds the MESH layout (parallel/mesh.py): the slot
    arrays grow to a multiple of `shards` and partition into per-shard
    segments, and `total` becomes a [shards] cursor vector so every
    shard appends into its own segment with its own cursor.  The drain
    side (observe.write_pcap) merges segments in time order.  shards=1
    keeps the original single-cursor layout byte-for-byte."""
    capacity = -(-capacity // shards) * shards
    total = jnp.asarray(0, I64) if shards == 1 \
        else _zeros((shards,), I64)
    return CaptureRing(
        time=_zeros((capacity,), I64),
        src=_zeros((capacity,), I32),
        dst=_zeros((capacity,), I32),
        sport=_zeros((capacity,), I32),
        dport=_zeros((capacity,), I32),
        proto=_zeros((capacity,), I32),
        flags=_zeros((capacity,), I32),
        length=_zeros((capacity,), I32),
        seq=_zeros((capacity,), U32),
        ack=_zeros((capacity,), U32),
        kind=_zeros((capacity,), I32),
        total=total,
    )


# ---------------------------------------------------------------------------
# Event log ring (leveled, sim-time-stamped; ShadowLogger analog)
# ---------------------------------------------------------------------------

# Log levels (reference support/logger/log_level.c): per-host gating.
LOG_OFF = 0
LOG_WARNING = 1   # drops, resets
LOG_DEBUG = 2     # + deliveries and sends

# Event codes drained into "[simtime] [host] message" lines (observe.py).
LOG_DROP_INET = 1      # reliability drop on the wire
LOG_DROP_ROUTER = 2    # CoDel drop at the destination router
LOG_DROP_TAIL = 3      # interface-buffer tail drop
LOG_DROP_POOL = 4      # slab-capacity drop (capacity escape hatch)
LOG_DELIVER = 5        # packet delivered to a socket
LOG_SEND = 6           # packet placed on the wire
LOG_ACK_THIN = 7       # pure ACKs shed at exchange overflow (not an error)
LOG_NETEM_DOWN = 8     # delivery killed: destination host is netem-down


@struct.dataclass
class LogRing:
    """Bounded device-side event ring, drained and sim-time-sorted by the
    host between chunks -- the two-tier design of the reference's
    ShadowLogger (per-thread queues + helper-thread merge,
    core/logger/shadow_logger.c:25-58) with the device as the "threads"
    and the drain as the merge.  Present in SimState only when logging is
    enabled, so disabled runs trace with zero cost."""

    time: jnp.ndarray    # [C] i64
    host: jnp.ndarray    # [C] i32
    code: jnp.ndarray    # [C] i32 LOG_*
    arg: jnp.ndarray     # [C] i32 event argument (peer, count, bytes)
    total: jnp.ndarray   # i64 lifetime appends (records actually written)
    lost: jnp.ndarray    # i64 records dropped because one append exceeded
                         # the ring capacity (reported by the drain)

    @property
    def capacity(self) -> int:
        return self.time.shape[0]


def make_log_ring(capacity: int = 1 << 16, shards: int = 1) -> LogRing:
    """shards > 1 builds the MESH layout (parallel/mesh.py): slot arrays
    grow to a multiple of `shards` and partition into per-shard segments,
    and `total`/`lost` become [shards] vectors so each shard appends into
    its own segment with its own cursor.  observe.LogDrain merges the
    segments in sim-time order.  shards=1 keeps the original
    single-cursor layout byte-for-byte."""
    capacity = -(-capacity // shards) * shards
    if shards == 1:
        total = jnp.asarray(0, I64)
        lost = jnp.asarray(0, I64)
    else:
        total = _zeros((shards,), I64)
        lost = _zeros((shards,), I64)
    return LogRing(
        time=_zeros((capacity,), I64),
        host=_zeros((capacity,), I32),
        code=_zeros((capacity,), I32),
        arg=_zeros((capacity,), I32),
        total=total,
        lost=lost,
    )


# ---------------------------------------------------------------------------
# Flight recorder (per-window run telemetry; trace.py drains it)
# ---------------------------------------------------------------------------


@struct.dataclass
class FlightRecorder:
    """Fixed-capacity device-side ring recording ONE ROW PER WINDOW --
    the run's black box.  Present in SimState only when installed
    (trace.ensure_flight_recorder), so recorder-less runs trace
    byte-identical graphs, like cap/log/tr/nm.

    A row covers the boundary exchange that OPENED window w plus the
    micro-steps run DURING w.  The ring is written entirely inside the
    compiled window loop and drained at chunk boundaries together with
    the trace counters, so recording adds zero extra host syncs.

    `ex_cnt`/`ex_bytes` are [C, D, D] src->dst LOGICAL-SHARD traffic
    matrices, D = `n_shards` chosen at install time.  On a D-device mesh
    a cell is the packets one shard sent another in that window's
    exchange (derived from the all_to_all send ranking); off-mesh the
    same matrix is computed from host ids, so a single-device run of a
    D-sharded world produces bitwise the same matrices as the mesh run.
    The cur_* scratch holds the current window's matrix between the
    exchange and the row write; the *_sum accumulators are lifetime
    totals that survive ring wrap (bench reads those)."""

    win_start: jnp.ndarray  # [C] i64 window start (ws)
    win_end: jnp.ndarray    # [C] i64 window end (we)
    steps: jnp.ndarray      # [C] i32 micro-steps run in the window
    events: jnp.ndarray     # [C] i64 events drained (deliveries+emissions)
    routed: jnp.ndarray     # [C] i64 packets moved by the opening exchange
    delivered: jnp.ndarray  # [C] i64 packets delivered to sockets
    dropped: jnp.ndarray    # [C] i64 inet+router+pool drops
    killed: jnp.ndarray     # [C] i64 netem delivery kills (0 w/o netem)
    ex_cnt: jnp.ndarray     # [C, D, D] i32 exchange movers per src->dst shard
    ex_bytes: jnp.ndarray   # [C, D, D] i64 exchange payload bytes per pair
    cur_ex_cnt: jnp.ndarray    # [D, D] i32 scratch: this window's matrix
    cur_ex_bytes: jnp.ndarray  # [D, D] i64 scratch
    ex_cnt_sum: jnp.ndarray    # [D, D] i64 lifetime movers (wrap-proof)
    ex_bytes_sum: jnp.ndarray  # [D, D] i64 lifetime bytes
    total: jnp.ndarray      # i64 scalar: lifetime rows written

    @property
    def capacity(self) -> int:
        return self.win_start.shape[0]

    @property
    def n_shards(self) -> int:
        return self.cur_ex_cnt.shape[0]


def make_flight_recorder(capacity: int = 4096,
                         shards: int = 1) -> FlightRecorder:
    return FlightRecorder(
        win_start=_zeros((capacity,), I64),
        win_end=_zeros((capacity,), I64),
        steps=_zeros((capacity,), I32),
        events=_zeros((capacity,), I64),
        routed=_zeros((capacity,), I64),
        delivered=_zeros((capacity,), I64),
        dropped=_zeros((capacity,), I64),
        killed=_zeros((capacity,), I64),
        ex_cnt=_zeros((capacity, shards, shards), I32),
        ex_bytes=_zeros((capacity, shards, shards), I64),
        cur_ex_cnt=_zeros((shards, shards), I32),
        cur_ex_bytes=_zeros((shards, shards), I64),
        ex_cnt_sum=_zeros((shards, shards), I64),
        ex_bytes_sum=_zeros((shards, shards), I64),
        total=jnp.asarray(0, I64),
    )


# ---------------------------------------------------------------------------
# Flowscope (per-flow TCP + per-link NIC telemetry; trace.ScopeDrain)
# ---------------------------------------------------------------------------


@struct.dataclass
class FlowScope:
    """Device-resident network telemetry sampler: a FLOW ring of
    per-sampled-socket TCP rows and a LINK ring of per-host-NIC rows,
    both appended inside the compiled window loop at a sim-time cadence
    (`interval`) and drained at chunk boundaries (trace.ScopeDrain).
    Present in SimState only when installed (trace.ensure_flowscope),
    so scope-less runs trace byte-identical graphs -- the same
    present-or-None contract as cap/log/tr/fr/nm.

    Rows carry CUMULATIVE lifetime counters (bytes sent/recv/acked,
    retransmitted segments, forwarded bytes, drops), so a ring wrap
    loses time resolution but never totals -- the newest surviving row
    of a flow or link still states its exact lifetime sums.  `f_total`/
    `l_total` count lifetime appends (the drain's wrap accounting) and
    `samples` counts sample epochs; `f_lost`/`l_lost` count rows a
    single oversized epoch could not fit (size the rings above
    sampled-rows-per-epoch to keep them zero).

    Host ids are GLOBAL (host_ids: shifted by `hoff` under a mesh).
    Under a mesh each shard samples its local hosts/sockets into its
    own ring segment with its own cursor slice (make_flowscope
    shards=N, the cap/log layout); the drain merges segments in
    sim-time order.  `interval`/`next_due`/`samples` are replicated --
    uniform window predicates advance them identically on every shard.

    Row timestamps are window-quantized (samples fire at the close of
    the first window that reaches `next_due`, stamped at the window
    end), so the exact row times depend on windowing but never on
    chunking -- and sampling never perturbs the simulation itself
    (bitwise trajectory-neutral; tests/test_flowscope.py)."""

    interval: jnp.ndarray   # i64 scalar: sampling cadence (sim ns)
    next_due: jnp.ndarray   # i64 scalar: next sample epoch boundary
    samples: jnp.ndarray    # i64 scalar: lifetime sample epochs taken

    # Flow ring [Cf]: one row per sampled ESTABLISHED-ish TCP socket.
    f_time: jnp.ndarray      # [Cf] i64 sample time (window end)
    f_host: jnp.ndarray      # [Cf] i32 GLOBAL host id
    f_slot: jnp.ndarray      # [Cf] i32 socket slot (host+slot+peer = flow)
    f_peer: jnp.ndarray      # [Cf] i32 peer host id
    f_cwnd: jnp.ndarray      # [Cf] i32 congestion window (bytes)
    f_ssthresh: jnp.ndarray  # [Cf] i32
    f_srtt: jnp.ndarray      # [Cf] i64 smoothed RTT (ns, 0 = no sample)
    f_inflight: jnp.ndarray  # [Cf] i32 bytes in flight (snd_nxt - snd_una)
    f_retx: jnp.ndarray      # [Cf] i32 lifetime retransmitted segments
    f_acked: jnp.ndarray     # [Cf] i64 lifetime bytes acked (sent-inflight)
    f_sent: jnp.ndarray      # [Cf] i64 lifetime stream bytes sent (no retx)
    f_recv: jnp.ndarray      # [Cf] i64 lifetime stream bytes received
    f_total: jnp.ndarray     # i64 scalar | [D]: lifetime rows appended
    f_lost: jnp.ndarray      # i64 scalar | [D]: rows dropped (epoch > ring)

    # Link ring [Cl]: one row per host NIC per sample epoch.
    l_time: jnp.ndarray      # [Cl] i64 sample time (window end)
    l_host: jnp.ndarray      # [Cl] i32 GLOBAL host id
    l_tx: jnp.ndarray        # [Cl] i64 lifetime bytes forwarded (sent)
    l_rx: jnp.ndarray        # [Cl] i64 lifetime bytes received
    l_qdepth: jnp.ndarray    # [Cl] i32 packets parked (tx+rx queues)
    l_cap: jnp.ndarray       # [Cl] i64 netem-scaled up-link capacity (B/s)
    l_drops: jnp.ndarray     # [Cl] i64 lifetime drops (inet+router+pool)
    l_total: jnp.ndarray     # i64 scalar | [D]: lifetime rows appended
    l_lost: jnp.ndarray      # i64 scalar | [D]: rows dropped

    # Static enables (part of the jit cache key, like block presence):
    # a disabled ring's sampling pass traces away entirely and its slot
    # arrays shrink to one slot per shard.
    sample_flows: bool = struct.field(pytree_node=False, default=True)
    sample_links: bool = struct.field(pytree_node=False, default=True)

    @property
    def flow_capacity(self) -> int:
        return self.f_time.shape[0]

    @property
    def link_capacity(self) -> int:
        return self.l_time.shape[0]

    @property
    def n_shards(self) -> int:
        return 1 if self.f_total.ndim == 0 else self.f_total.shape[0]


def make_flowscope(flow_capacity: int = 1 << 16,
                   link_capacity: int = 1 << 14,
                   interval_ns: int = 100_000_000,
                   shards: int = 1,
                   flows: bool = True,
                   links: bool = True) -> FlowScope:
    """Build the sampler block.  `flows=False`/`links=False` disable a
    ring statically: its sampling pass traces away and its slot arrays
    shrink to one slot per shard (the fields must exist for pytree
    stability, but cost nothing).  shards > 1 builds the MESH layout
    (cap/log pattern): slot arrays grow to a multiple of `shards` and
    partition into per-shard segments, cursors become [shards]
    vectors so each shard appends into its own segment."""
    fc = max(flow_capacity if flows else 0, shards)
    lc = max(link_capacity if links else 0, shards)
    fc = -(-fc // shards) * shards
    lc = -(-lc // shards) * shards

    def _cursor():
        return jnp.asarray(0, I64) if shards == 1 else _zeros((shards,), I64)

    return FlowScope(
        interval=jnp.asarray(max(int(interval_ns), 1), I64),
        next_due=jnp.asarray(0, I64),
        samples=jnp.asarray(0, I64),
        f_time=_zeros((fc,), I64),
        f_host=_zeros((fc,), I32),
        f_slot=_zeros((fc,), I32),
        f_peer=_zeros((fc,), I32),
        f_cwnd=_zeros((fc,), I32),
        f_ssthresh=_zeros((fc,), I32),
        f_srtt=_zeros((fc,), I64),
        f_inflight=_zeros((fc,), I32),
        f_retx=_zeros((fc,), I32),
        f_acked=_zeros((fc,), I64),
        f_sent=_zeros((fc,), I64),
        f_recv=_zeros((fc,), I64),
        f_total=_cursor(),
        f_lost=_cursor(),
        l_time=_zeros((lc,), I64),
        l_host=_zeros((lc,), I32),
        l_tx=_zeros((lc,), I64),
        l_rx=_zeros((lc,), I64),
        l_qdepth=_zeros((lc,), I32),
        l_cap=_zeros((lc,), I64),
        l_drops=_zeros((lc,), I64),
        l_total=_cursor(),
        l_lost=_cursor(),
        sample_flows=bool(flows),
        sample_links=bool(links),
    )


# ---------------------------------------------------------------------------
# Packet lineage (sampled per-packet span tracing; trace.LineageDrain)
# ---------------------------------------------------------------------------

# Span stage enum: where in a packet's life a LineageBlock span row was
# written.  A traced packet's life story is the time-ordered chain of its
# span rows (tools/parse.py spans).
SPAN_EMIT = 0      # emission staged at the source (reason set if it died there)
SPAN_STAGE = 1     # parked TX_QUEUED under the uplink token bucket
SPAN_TX = 2        # departed the NIC onto the wire (direct admit or _tx_drain)
SPAN_LINK = 3      # same-host loopback wire hop (bypasses the exchange)
SPAN_EXCHANGE = 4  # moved outbox -> inbox at a window-boundary exchange
SPAN_DELIVER = 5   # delivery attempt at the destination NIC/transport

SPAN_STAGE_NAMES = {
    SPAN_EMIT: "emit",
    SPAN_STAGE: "stage",
    SPAN_TX: "tx",
    SPAN_LINK: "link",
    SPAN_EXCHANGE: "exchange",
    SPAN_DELIVER: "deliver",
}

# Drop-reason enum (span rows; 0 = the hop succeeded).  A nonzero reason
# marks the hop where the packet left the simulation.
LREASON_NONE = 0
LREASON_QDISC = 1      # router/CoDel drop or interface-buffer tail drop
LREASON_LOSS = 2       # reliability draw (baseline wire loss or netem loss)
LREASON_LINK_DOWN = 3  # netem: the src<->dst link is down
LREASON_PARTITION = 4  # netem: endpoints on opposite partition sides
LREASON_HOST_DOWN = 5  # netem: an endpoint host is down
LREASON_ACK_SHED = 6   # pure ACK shed at an overflowing boundary exchange
LREASON_TTL = 7        # reserved: hop-limit expiry (engine has no TTL yet)
LREASON_POOL = 8       # slab-capacity overflow (staging or exchange)

LREASON_NAMES = {
    LREASON_NONE: "none",
    LREASON_QDISC: "qdisc_overflow",
    LREASON_LOSS: "loss",
    LREASON_LINK_DOWN: "link_down",
    LREASON_PARTITION: "partition",
    LREASON_HOST_DOWN: "host_down",
    LREASON_ACK_SHED: "ack_shed",
    LREASON_TTL: "ttl",
    LREASON_POOL: "pool_overflow",
}


@struct.dataclass
class LineageBlock:
    """Sampled per-packet span tracer -- request tracing for packets.
    Present in SimState only when installed (trace.ensure_lineage), so
    lineage-less runs trace byte-identical graphs: the same
    present-or-None contract as cap/log/tr/fr/scope/nm.

    A seeded, deterministic sample of emissions is assigned a nonzero
    i32 trace id at staging (PURPOSE_LINEAGE-keyed on (src, send_ctr),
    core/rng.py), so single-device and mesh runs of the same world
    sample -- and id -- exactly the same packets.  `rate_x1p32` is the
    sample threshold in uint32 space (sample iff keyed bits <= it) and
    rides as TRACED data, so one compiled graph serves every rate.

    The id travels in `pool_id`/`inbox_id`: side arrays shaped like the
    outbox/inbox row axes, moved under the exact permutations the
    engine applies to the packed blocks (staging one-hot merge, the
    exchange scatter / all_to_all trailer column, delivery slot free)
    -- the packed 18/28-column widths are untouched.

    Every hop appends one span row (sim time, GLOBAL host id, SPAN_*
    stage, LREASON_* drop reason) into the span ring.  Under a mesh the
    ring partitions into per-shard segments with [D] cursors (the
    cap/log layout); trace.LineageDrain merges segments in sim-time
    order into spans.jsonl.  Lifetime counters (`n_assigned`, `total`,
    `lost`) survive ring wrap.

    The block only ever observes: installing it never perturbs the
    trajectory (bitwise-neutral, tests/test_lineage.py)."""

    rate_x1p32: jnp.ndarray  # u32 scalar: sample threshold (traced)
    n_assigned: jnp.ndarray  # i64 scalar: lifetime sampled emissions

    pool_id: jnp.ndarray     # [P0] i32 trace id of each outbox row (0=none)
    inbox_id: jnp.ndarray    # [P1] i32 trace id of each inbox row (0=none)

    s_time: jnp.ndarray      # [C] i64 sim time of the hop
    s_id: jnp.ndarray        # [C] i32 trace id (always nonzero)
    s_host: jnp.ndarray      # [C] i32 GLOBAL host id where the hop happened
    s_stage: jnp.ndarray     # [C] i32 SPAN_* stage enum
    s_reason: jnp.ndarray    # [C] i32 LREASON_* drop reason (0 = alive)
    total: jnp.ndarray       # i64 scalar | [D]: lifetime span rows appended
    lost: jnp.ndarray        # i64 scalar | [D]: rows dropped (batch > ring)

    @property
    def capacity(self) -> int:
        return self.s_time.shape[0]

    @property
    def n_shards(self) -> int:
        return 1 if self.total.ndim == 0 else self.total.shape[0]


def lineage_rate_bits(rate: float) -> int:
    """Sample-rate fraction -> uint32 threshold (sample iff
    keyed_bits <= threshold).  rate >= 1.0 traces every packet."""
    r = float(rate)
    if not (0.0 < r <= 1.0):
        raise ValueError(f"lineage sample rate must be in (0, 1], got {r}")
    if r >= 1.0:
        return 0xFFFFFFFF
    return max(0, min(int(round(r * 4294967296.0)) - 1, 0xFFFFFFFF))


def make_lineage(pool_rows: int, inbox_rows: int, rate: float = 0.01,
                 capacity: int = 1 << 16, shards: int = 1) -> LineageBlock:
    """Build the tracer block for a world whose outbox/inbox row axes are
    `pool_rows`/`inbox_rows` (install AFTER mesh/bucket padding, so the
    side arrays match the padded pools).  shards > 1 builds the MESH
    layout (cap/log pattern): the span ring grows to a multiple of
    `shards` and partitions into per-shard segments, cursors become
    [shards] vectors so each shard appends into its own segment."""
    capacity = -(-max(int(capacity), shards) // shards) * shards

    def _cursor():
        return jnp.asarray(0, I64) if shards == 1 else _zeros((shards,), I64)

    return LineageBlock(
        rate_x1p32=jnp.asarray(lineage_rate_bits(rate), U32),
        n_assigned=jnp.asarray(0, I64),
        pool_id=_zeros((pool_rows,), I32),
        inbox_id=_zeros((inbox_rows,), I32),
        s_time=_zeros((capacity,), I64),
        s_id=_zeros((capacity,), I32),
        s_host=_zeros((capacity,), I32),
        s_stage=_zeros((capacity,), I32),
        s_reason=_zeros((capacity,), I32),
        total=_cursor(),
        lost=_cursor(),
    )


# ---------------------------------------------------------------------------
# Invariant sentinel (per-window health checks; trace.SentinelDrain)
# ---------------------------------------------------------------------------


# Violation classes (SentinelBlock.violations bitmask).
SENTINEL_CONSERVATION = 1 << 0  # packet conservation identity broken
SENTINEL_TIME = 1 << 1          # window end not strictly monotone
SENTINEL_BOUNDS = 1 << 2        # stage domain / queue count / cursor bounds
SENTINEL_NONFINITE = 1 << 3     # non-finite float leaf or implausible timer

SENTINEL_CLASS_NAMES = {
    SENTINEL_CONSERVATION: "conservation",
    SENTINEL_TIME: "time",
    SENTINEL_BOUNDS: "bounds",
    SENTINEL_NONFINITE: "nonfinite",
}

# Plausibility ceiling for the TCP timer leaves (srtt/rttvar/rto live in
# i64 ns, so a NaN bit pattern lands as a huge positive integer rather
# than a float NaN; any sane RTT estimate sits far below ten minutes).
SENTINEL_TIMER_MAX_NS = 600 * 1_000_000_000


@struct.dataclass
class SentinelBlock:
    """Per-window invariant monitor -- the run's smoke detector.
    Present in SimState only when installed (trace.ensure_sentinel), so
    sentinel-less runs trace byte-identical graphs: the same
    present-or-None contract as cap/log/tr/fr/scope/nm.

    engine._sentinel_check runs at every window close on cheap
    reductions of state the window already touched: the packet
    conservation identity (emitted = delivered + dropped + thinned +
    still-occupied, bounded by the stage-vs-delivery drop split),
    window-end monotonicity, stage-domain / queue-count / ring-cursor
    bounds, and a finiteness probe over the float leaves plus a
    plausibility ceiling on the i64 TCP timers.  All fields are scalars
    computed from psum/pmin/pmax-reduced inputs, so the block is
    REPLICATED under a mesh (the flight-recorder rule) and bitwise
    identical on every shard.

    The block only ever observes: installing it never perturbs the
    trajectory (bitwise-neutral, tests/test_sentinel.py).  Violations
    are sticky; `first_bad_window`/`first_bad_t` freeze the earliest
    failure so a drain long after the fact still points replay at the
    right window."""

    checks: jnp.ndarray            # i64 lifetime windows checked
    violations: jnp.ndarray        # i32 sticky SENTINEL_* bitmask
    last_violation: jnp.ndarray    # i32 most recent window's bits
    first_bad_window: jnp.ndarray  # i64 window index of first violation, -1
    first_bad_t: jnp.ndarray      # i64 window end (sim ns) at first violation
    last_we: jnp.ndarray          # i64 previous window end (monotonicity)
    resid_low: jnp.ndarray        # i64 conservation lower slack (>= 0 ok)
    resid_high: jnp.ndarray       # i64 conservation upper slack (>= 0 ok)
    nonfinite: jnp.ndarray        # i64 bad float/timer elements last check


def make_sentinel() -> SentinelBlock:
    return SentinelBlock(
        checks=jnp.asarray(0, I64),
        violations=jnp.asarray(0, I32),
        last_violation=jnp.asarray(0, I32),
        first_bad_window=jnp.asarray(-1, I64),
        first_bad_t=jnp.asarray(-1, I64),
        last_we=jnp.asarray(-1, I64),
        resid_low=jnp.asarray(0, I64),
        resid_high=jnp.asarray(0, I64),
        nonfinite=jnp.asarray(0, I64),
    )


# ---------------------------------------------------------------------------
# Statescope digests (per-window state checksums; trace.DigestDrain)
# ---------------------------------------------------------------------------


# Field groups a digest row covers, in column order.  The grouping is
# the diff vocabulary ("the pool diverged at window 41"), so changing
# membership or order is a schema change: bump DIGEST_SCHEMA and diff
# refuses to compare across versions by name instead of mis-aligning
# columns.
DIGEST_GROUPS = ("pool", "inbox", "socks", "hosts", "rng", "netem", "app")
DIGEST_SCHEMA = 1


@struct.dataclass
class DigestBlock:
    """Per-window state checksums -- the divergence tripwire.  Present
    in SimState only when installed (trace.ensure_digests), so
    digest-less runs trace byte-identical graphs: the same
    present-or-None contract as cap/log/tr/fr/scope/nm.

    engine._digest_record runs at window close (cadence `every`
    windows): each SimState leaf is bit-normalized to i64, every
    element hashed against its GLOBAL flat index, and the hashes
    wrapping-summed per DIGEST_GROUPS column and per logical host
    shard.  Summation is commutative, so per-shard columns summed over
    D reproduce the shards=1 digest bitwise -- which is what lets
    `shadow1-tpu diff` compare a mesh run against a single-device run
    column-reduced, and is the property tests/test_statescope.py pins.

    The row ring (`win`/`t_end`/`sums`) is REPLICATED under a mesh:
    each shard computes its local column and one all_gather assembles
    the identical [G, D] row everywhere (the flight-recorder rule).
    `every` is replicated and the cadence predicate is a function of
    the replicated window counter, so every shard takes the same
    branch.  `total` counts lifetime rows (the drain's wrap
    accounting); the block only ever reads trajectory state, so
    installing it is bitwise trajectory-neutral."""

    every: jnp.ndarray  # i64 scalar: digest cadence in windows
    win: jnp.ndarray    # [C] i64 global window index of the row
    t_end: jnp.ndarray  # [C] i64 window end (sim ns)
    sums: jnp.ndarray   # [C, G, D] i64 per-group / per-shard checksums
    total: jnp.ndarray  # i64 scalar: lifetime rows written

    @property
    def capacity(self) -> int:
        return self.win.shape[0]

    @property
    def n_shards(self) -> int:
        return self.sums.shape[2]


def make_digest(capacity: int = 4096, shards: int = 1,
                every: int = 1) -> DigestBlock:
    return DigestBlock(
        every=jnp.asarray(max(1, int(every)), I64),
        win=_zeros((capacity,), I64),
        t_end=_zeros((capacity,), I64),
        sums=_zeros((capacity, len(DIGEST_GROUPS), shards), I64),
        total=jnp.asarray(0, I64),
    )


# ---------------------------------------------------------------------------
# Trace counter block (runtime profiling; trace.py)
# ---------------------------------------------------------------------------


@struct.dataclass
class TraceCounters:
    """Device-side runtime counters for the profiler (trace.py): scalars
    accumulated inside the compiled step and fetched ONCE per drain, so
    profiling costs one extra small transfer per chunk, not per window.
    Present in SimState only when tracing is on (like cap/log), so
    unprofiled runs trace without any counter cost."""

    exchanges: jnp.ndarray       # i64 boundary exchanges that moved packets
    pkts_exchanged: jnp.ndarray  # i64 packets forwarded outbox -> inbox
    occ_max: jnp.ndarray         # i32 max inbox-slab occupancy seen (slots)

    def occupancy_frac(self, state) -> float:
        """Peak inbox-slab fill fraction (host-side convenience)."""
        ki = state.inbox.capacity // state.hosts.num_hosts
        return float(self.occ_max) / max(ki, 1)


def make_trace_counters() -> TraceCounters:
    return TraceCounters(
        exchanges=jnp.asarray(0, I64),
        pkts_exchanged=jnp.asarray(0, I64),
        occ_max=jnp.asarray(0, I32),
    )


# ---------------------------------------------------------------------------
# Whole-simulation state
# ---------------------------------------------------------------------------


@struct.dataclass
class SimState:
    """Everything that evolves during a run; one pytree, checkpointable.

    `pool` is the OUTBOX: per-source slabs holding packets from emission
    until they leave their source (parked TX_QUEUED under the token
    bucket, or IN_FLIGHT awaiting the next window-boundary exchange into
    the destination's inbox).  `inbox` is the per-destination receive
    half (see Inbox)."""

    now: jnp.ndarray          # i64 scalar: current window start
    pool: PacketPool          # outbox, per-SOURCE slabs
    inbox: Inbox              # per-DESTINATION slabs
    socks: SocketTable
    hosts: HostTable
    app: any = struct.field(pytree_node=True, default=None)  # application-model state
    err: jnp.ndarray = struct.field(default=None)  # i32 scalar ERR_* bitmask
    cap: any = struct.field(pytree_node=True, default=None)  # CaptureRing | None
    log: any = struct.field(pytree_node=True, default=None)  # LogRing | None
    # Per-host log level mask (LOG_*), only consulted when log is set.
    log_level: any = struct.field(pytree_node=True, default=None)  # [H] i32
    tr: any = struct.field(pytree_node=True, default=None)  # TraceCounters | None
    # Per-window flight recorder (trace.ensure_flight_recorder): present
    # only when installed, so recorder-less runs trace byte-identical
    # graphs.  Replicated (never sharded) under a mesh -- every shard
    # computes identical rows from psum/all_gather-reduced inputs.
    fr: any = struct.field(pytree_node=True, default=None)  # FlightRecorder | None
    # Per-flow TCP + per-link NIC sampler (trace.ensure_flowscope):
    # present only when installed, so scope-less runs trace
    # byte-identical graphs.  Sharded under a mesh (per-shard ring
    # segments + cursor slices, the cap/log layout).
    scope: any = struct.field(pytree_node=True, default=None)  # FlowScope | None
    # Network dynamics / fault injection (netem/state.py): present only
    # when a fault schedule is installed, so static worlds compile the
    # whole overlay away.
    nm: any = struct.field(pytree_node=True, default=None)  # NetemBlock | None
    # Per-window invariant monitor (trace.ensure_sentinel): present only
    # when installed, so unsupervised runs trace byte-identical graphs.
    # Replicated (never sharded) under a mesh -- every shard computes
    # identical scalars from psum/pmin/pmax-reduced inputs.
    sentinel: any = struct.field(pytree_node=True, default=None)  # SentinelBlock | None
    # Sampled per-packet span tracer (trace.ensure_lineage): present only
    # when installed, so untraced runs trace byte-identical graphs.
    # Sharded under a mesh (per-shard span-ring segments + cursor slices,
    # the cap/log layout); pool_id/inbox_id shard with their pools.
    lineage: any = struct.field(pytree_node=True, default=None)  # LineageBlock | None
    # Per-window state digests (trace.ensure_digests): present only when
    # installed, so digest-less runs trace byte-identical graphs.
    # Replicated (never sharded) under a mesh -- every shard assembles
    # identical rows from all_gather'd per-shard checksum columns.
    dg: any = struct.field(pytree_node=True, default=None)  # DigestBlock | None
    # Telemetry (reference scheduler built-in timers, scheduler.c:266-268):
    n_steps: jnp.ndarray = struct.field(default=None)    # i64 micro-steps
    n_windows: jnp.ndarray = struct.field(default=None)  # i64 windows run
    n_events: jnp.ndarray = struct.field(default=None)   # i64 deliveries+emissions
    # Mesh shard offset (parallel/mesh.py): global host id of this shard's
    # row 0.  None off-mesh -- `state.hoff is None` is a trace-time static,
    # so single-device graphs compile byte-identical to before the field
    # existed.  Set only inside the shard_map body, never persisted.
    hoff: any = struct.field(pytree_node=True, default=None)  # i32 scalar


def host_ids(state, dtype=I32) -> jnp.ndarray:
    """GLOBAL host ids of this state's rows: arange(h) off-mesh, shifted by
    the shard offset under a mesh.  Use wherever a host id feeds RNG keys,
    packet src fields, or comparisons against global-valued ids (app dst
    leaves, packet.src) -- local row indices are only valid for slab
    addressing."""
    ids = jnp.arange(state.hosts.num_hosts, dtype=dtype)
    if state.hoff is None:
        return ids
    return ids + state.hoff.astype(dtype)


def world_count(state) -> int | None:
    """Number of worlds when `state` carries an ensemble's leading world
    axis (ensemble.stack), else None for an ordinary solo state.

    Probes `state.now` -- an i64 scalar in every solo state, so a stacked
    state is unambiguously ndim == 1.  Host-side introspection helpers
    that read row counts off leaf shapes (e.g. `hosts.num_hosts`, which
    returns leaf.shape[0]) are WRONG on a stacked state: slice a world
    out first (`ensemble.world(estate, eparams, k)`) before calling
    them."""
    now = jnp.asarray(state.now)
    if now.ndim == 0:
        return None
    return int(now.shape[0])


def make_sim_state(num_hosts: int, sock_slots: int = 16,
                   pool_capacity: int = 1 << 15, app=None,
                   inbox_capacity: int | None = None,
                   uses_tcp: bool = True) -> SimState:
    # Both pools are partitioned into per-host slabs: the outbox by SOURCE
    # (engine._stage_emissions allocates from the emitting host's slab),
    # the inbox by DESTINATION (engine._exchange fills it at window
    # boundaries).  Capacities round up to a multiple of num_hosts with at
    # least 8 slots per host.  The inbox defaults to the outbox size; size
    # it by expected fan-IN (a popular server needs a deeper inbox slab).
    slab = max(8, -(-pool_capacity // num_hosts))
    if inbox_capacity is None:
        inbox_capacity = pool_capacity
    islab = max(8, -(-inbox_capacity // num_hosts))
    return SimState(
        now=jnp.asarray(0, I64),
        pool=make_packet_pool(num_hosts * slab, cols=pool_cols(uses_tcp)),
        inbox=make_inbox(num_hosts, islab,
                         cols=ICOLS if uses_tcp else NCOLS_UDP),
        socks=make_socket_table(num_hosts, sock_slots),
        hosts=make_host_table(num_hosts),
        app=app,
        err=jnp.asarray(0, I32),
        n_steps=jnp.asarray(0, I64),
        n_windows=jnp.asarray(0, I64),
        n_events=jnp.asarray(0, I64),
    )
