"""The windowed discrete-event engine: conservative PDES as compiled loops.

Semantics preserved from the reference:

* Conservative time windows with min-latency lookahead: all hosts process
  events in [window_start, window_end), then the window advances by the
  topology's minimum cross-host latency ("min time jump",
  /root/reference/src/main/core/master.c:133-159,450-480).  A packet sent
  at t >= window_start arrives at t + latency >= window_end, so hosts are
  independent within a window -- the property the reference enforces with
  per-host queues + barriers (scheduler.c:359-414) and that we exploit to
  advance every host in one vectorized step.

* Deterministic per-host event order: within a host, events execute in
  (time, category, packet-id) order, reproducing the role of the
  reference's total order (time, dstHostID, srcHostID, srcHostEventID)
  (core/work/event.c:110-153).  Between hosts no order is needed --
  windows make them independent -- so the result is bitwise identical for
  any device mesh, any pool capacity, and any chunking of run_until calls.

Data layout (the whole performance story; numbers in tools/opbench*.py):

* OUTBOX (state.pool): per-SOURCE slabs.  Emissions are staged into the
  emitting host's own slab by row-local one-hot merges -- no scatter ops
  in the hot loop (an XLA scatter costs ~1us/update inside a compiled
  loop; a one-hot masked merge fuses for free).

* INBOX (state.inbox): per-DESTINATION slabs, packed into one [P1, C]
  i32 block.  Every per-micro-step reduction the engine needs -- next
  arrival per host, NIC drain candidate, CoDel backlog -- is a row-local
  reshape-min/sum over [H, slab] (~0ms) instead of the dst-keyed
  segment-min over the whole pool that dominated the previous design
  (12.7 ms per micro-step at 16k hosts).

* WINDOW-BOUNDARY EXCHANGE (`_exchange`): packets that left their source
  (stage IN_FLIGHT) move outbox -> inbox in bulk, once per window.  The
  conservative invariant guarantees anything sent during window w arrives
  at >= window_end(w), so arrivals for a window are fully known at its
  start.  The move is one packed i32 row-scatter plus a hierarchical
  rank-by-destination (scatter-add counts over superblocks + an exclusive
  cumsum + in-superblock pairwise ranks): ~5ms per window, amortized over
  the window's micro-steps.  This replaces the reference's per-packet
  push onto locked destination-host queues (worker.c:293-300) with the
  PDES equivalent of an all-to-all collective -- under a sharded mesh the
  scatter IS the ICI all-to-all.

Same-host loopback bypasses the exchange (reference's local path,
network_interface.c:548-555): those packets are inserted straight into
the sender's own inbox slab at staging time, which is row-local.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..trace import phase
from . import emit, nic, rng, simtime
# Reliability-dropped packets are never materialized in the pool (they are
# counted in HostTable.pkts_dropped_inet instead), so PDS_INET_DROPPED is
# deliberately absent here.
from .state import (ERR_POOL_OVERFLOW, I32, I64, U32, PROTO_TCP, PROTO_UDP,
                    STAGE_FREE, STAGE_IN_FLIGHT, STAGE_RX_QUEUED,
                    STAGE_TX_QUEUED, TCP_HEADER_SIZE, UDP_HEADER_SIZE,
                    PDS_INET_SENT, PDS_RCV_SOCKET_PROCESSED,
                    PDS_ROUTER_DROPPED, PDS_ROUTER_ENQUEUED,
                    PDS_SND_CREATED, PDS_SND_INTERFACE_SENT,
                    ICOL_SRC, ICOL_SPORT, ICOL_DPORT, ICOL_PROTO, ICOL_FLAGS,
                    ICOL_SEQ, ICOL_ACK, ICOL_WND, ICOL_LEN, ICOL_PAYLOAD,
                    ICOL_TIME_LO, ICOL_TIME_HI, ICOL_CTR_LO, ICOL_CTR_HI,
                    ICOL_TS_LO, ICOL_TS_HI, ICOL_TSE_LO, ICOL_TSE_HI,
                    ICOL_SACK0_LO, ICOL_SACK0_HI, ICOL_SACK2_HI, ICOLS,
                    OEXT_DST, OEXT_LAT_LO, OEXT_LAT_HI, OEXT_PRIO, ext_base,
                    LOG_WARNING, LOG_DEBUG, LOG_DROP_INET, LOG_DROP_ROUTER,
                    LOG_DROP_TAIL, LOG_DROP_POOL, LOG_DELIVER, LOG_SEND,
                    LOG_NETEM_DOWN,
                    SPAN_EMIT, SPAN_STAGE, SPAN_TX, SPAN_LINK, SPAN_EXCHANGE,
                    SPAN_DELIVER, LREASON_QDISC, LREASON_LOSS,
                    LREASON_HOST_DOWN, LREASON_ACK_SHED, LREASON_POOL,
                    SENTINEL_CONSERVATION, SENTINEL_TIME, SENTINEL_BOUNDS,
                    SENTINEL_NONFINITE, SENTINEL_TIMER_MAX_NS,
                    DIGEST_GROUPS,
                    enc_lo, enc_hi, dec_i64, SimState, host_ids)
# Fault/dynamics overlay operators (netem/apply.py).  Every call site
# guards on `state.nm is None` (a trace-time pytree check), so worlds
# without a fault schedule compile the overlay away entirely.
from ..netem import apply as netem_apply

INV = simtime.SIMTIME_INVALID

# Mesh axis name the sharded entry (parallel/mesh.py) maps hosts over.
# Defined here (not imported from parallel/) so core never depends on the
# parallel package; parallel.sharding.HOST_AXIS must match.
MESH_AXIS = "hosts"


def _mesh_reduce(x, lax_op, jnp_op):
    """Cross-shard min/max.  The TPU lowers a 64-bit all-reduce only as
    a sum, so 64-bit operands (simulated time, i64 counters) gather and
    reduce locally instead -- exact, since min/max do not depend on
    order; 32-bit operands keep the one-collective form.  Both scope as
    the `mesh_min` phase, nested in the phase that asks."""
    with phase("mesh_min"):
        if jnp.dtype(x.dtype).itemsize == 8:
            return jnp_op(jax.lax.all_gather(x, MESH_AXIS), axis=0)
        return lax_op(x, MESH_AXIS)


def mesh_min(x):
    return _mesh_reduce(x, jax.lax.pmin, jnp.min)


def mesh_max(x):
    return _mesh_reduce(x, jax.lax.pmax, jnp.max)


def _on_mesh(state: SimState) -> bool:
    """Trace-time static: is this trace running inside the shard_map body
    of parallel.mesh_run_until?  Off-mesh (hoff None) every mesh branch
    below traces away, keeping the single-device graph byte-identical."""
    return state.hoff is not None


def _lrows(state: SimState, vec):
    """Slice a [H_global] per-host vector down to this shard's local rows
    (identity off-mesh).  Only needed for the few per-host inputs that
    stay replicated under the mesh because they are also gathered by
    global ids (params.host_vertex)."""
    if state.hoff is None:
        return vec
    return jax.lax.dynamic_slice_in_dim(vec, state.hoff,
                                        state.hosts.num_hosts)


def _uses_tcp(app) -> bool:
    """Static app capability: apps that never open TCP sockets (pure-UDP
    phold) let the whole TCP machine trace away from the compiled step."""
    return getattr(app, "uses_tcp", True)


def _may_loopback(app) -> bool:
    """Static app capability: apps that never send to their own host let
    the loopback insert path (an [H*E]-row scatter per micro-step) trace
    away entirely."""
    return getattr(app, "may_loopback", True)


def _bitcast_i32_u32(x):
    return jax.lax.bitcast_convert_type(x.astype(I32), U32)


class RxPkt:
    """Field registers of the (at most one) packet delivered to each host
    this micro-step -- [H] vectors decoded from the inbox block."""

    __slots__ = ("src", "sport", "dport", "proto", "flags", "seq", "ack",
                 "wnd", "length", "payload_id", "time", "ts", "ts_echo",
                 "pkt_id", "sack_lo", "sack_hi")

    def __init__(self, row, keys_row, time_row):
        self.src = row[:, ICOL_SRC]
        self.sport = row[:, ICOL_SPORT]
        self.dport = row[:, ICOL_DPORT]
        self.proto = row[:, ICOL_PROTO]
        self.flags = row[:, ICOL_FLAGS]
        self.seq = _bitcast_i32_u32(row[:, ICOL_SEQ])
        self.ack = _bitcast_i32_u32(row[:, ICOL_ACK])
        self.wnd = row[:, ICOL_WND]
        self.length = row[:, ICOL_LEN]
        self.payload_id = row[:, ICOL_PAYLOAD]
        self.time = time_row
        if row.shape[1] >= ICOLS:
            self.ts = dec_i64(row[:, ICOL_TS_LO], row[:, ICOL_TS_HI])
            self.ts_echo = dec_i64(row[:, ICOL_TSE_LO], row[:, ICOL_TSE_HI])
            self.sack_lo = _bitcast_i32_u32(
                row[:, ICOL_SACK0_LO:ICOL_SACK2_HI + 1:2])
            self.sack_hi = _bitcast_i32_u32(
                row[:, ICOL_SACK0_HI:ICOL_SACK2_HI + 2:2])
        else:
            # Narrow (TCP-free) inbox: the TCP machine is traced away, so
            # these registers are never consumed; keep them as zeros for
            # shape stability.
            z = jnp.zeros_like(time_row)
            self.ts = z
            self.ts_echo = z
            self.sack_lo = jnp.zeros((row.shape[0], 3), U32)
            self.sack_hi = jnp.zeros((row.shape[0], 3), U32)
        self.pkt_id = keys_row


def _cap_append(state: SimState, mask, *, time_v, src, dst, sport, dport,
                proto, flags, length, seq, ack, kind) -> SimState:
    """Append masked flat records to the capture ring (both traffic
    directions route through here; traced away when capture is off).

    One batch larger than the ring would wrap onto itself and make the
    surviving record per slot scatter-order-dependent; keep the first
    `c` records of such a batch instead (deterministic) -- size the ring
    above the per-step record volume to never hit this.  `total` must
    then advance by what was *written*, not staged, or the writer would
    treat never-written slots as valid records."""
    cap = state.cap
    c = cap.capacity        # local segment size under a mesh shard
    if cap.total.ndim == 1 and cap.total.shape[0] != 1:
        raise ValueError(
            "sharded capture ring outside a mesh: a ring built with "
            "make_capture_ring(shards=N) only runs under "
            "parallel.mesh_run_until (each shard needs its own cursor "
            "slice); build it with shards=1 for single-device runs")
    tot0 = cap.total.reshape(())   # scalar, or this shard's [1] cursor
    crank = jnp.cumsum(mask) - 1
    n_new = jnp.minimum(jnp.sum(mask).astype(I64), c)
    pos = ((tot0 + crank) % c).astype(I32)
    idx = jnp.where(mask & (crank < c), pos, c)  # c = dropped write

    def cw(a, val, dtype=None):
        v = val.reshape(-1) if hasattr(val, "reshape") else val
        if dtype is not None:
            v = v.astype(dtype)
        return a.at[idx].set(v, mode="drop")

    return state.replace(cap=cap.replace(
        time=cw(cap.time, time_v),
        src=cw(cap.src, src),
        dst=cw(cap.dst, dst),
        sport=cw(cap.sport, sport),
        dport=cw(cap.dport, dport),
        proto=cw(cap.proto, proto),
        flags=cw(cap.flags, flags),
        length=cw(cap.length, length),
        seq=cw(cap.seq, seq),
        ack=cw(cap.ack, ack),
        kind=cap.kind.at[idx].set(kind, mode="drop"),
        total=cap.total + n_new,
    ))


def _log_append(state: SimState, mask, code: int, level: int, time_v,
                host_v, arg_v):
    """Append one event per set mask element into the log ring (traced
    away entirely when logging is off).  `mask`/`time_v`/`host_v`/`arg_v`
    are flat arrays of equal length; per-host level gating applies.

    `host_v` carries GLOBAL host ids (identical to local rows off-mesh):
    the ring records global ids for the drain, while the level lookup
    shifts them back to this shard's local log_level rows."""
    if state.log is None:
        return state
    lg = state.log
    c = lg.capacity         # local segment size under a mesh shard
    if lg.total.ndim == 1 and lg.total.shape[0] != 1:
        raise ValueError(
            "sharded log ring outside a mesh: a ring built with "
            "make_log_ring(shards=N) only runs under "
            "parallel.mesh_run_until (each shard needs its own cursor "
            "slice); build it with shards=1 for single-device runs")
    tot0 = lg.total.reshape(())    # scalar, or this shard's [1] cursor
    loc = host_v if state.hoff is None \
        else host_v - state.hoff.astype(host_v.dtype)
    lvl_ok = state.log_level[jnp.clip(loc, 0,
                                      state.log_level.shape[0] - 1)] >= level
    m = mask & lvl_ok
    rank = jnp.cumsum(m) - 1
    n_tot = jnp.sum(m).astype(I64)
    n_new = jnp.minimum(n_tot, c)
    pos = ((tot0 + rank) % c).astype(I32)
    idx = jnp.where(m & (rank < c), pos, c)
    return state.replace(log=lg.replace(
        time=lg.time.at[idx].set(time_v, mode="drop"),
        host=lg.host.at[idx].set(host_v.astype(I32), mode="drop"),
        code=lg.code.at[idx].set(code, mode="drop"),
        arg=lg.arg.at[idx].set(arg_v.astype(I32), mode="drop"),
        total=lg.total + n_new,
        lost=lg.lost + (n_tot - n_new),
    ))


def _lineage_append(state: SimState, mask, *, time_v, id_v, host_v, stage,
                    reason_v=0):
    """Append one span row per set mask element into the lineage ring
    (traced away entirely when no tracer is installed).  `mask`/`time_v`/
    `id_v`/`host_v` are flat arrays of equal length; untraced rows
    (id 0) are masked out here so call sites pass raw side-array
    gathers.  `host_v` carries GLOBAL host ids; `stage` is a static
    SPAN_* code and `reason_v` an LREASON_* scalar or flat array.

    The overflow policy is the capture ring's: one batch larger than
    the ring keeps its first `c` rows deterministically, and `lost`
    counts what a bigger ring would have kept."""
    if state.lineage is None:
        return state
    ln = state.lineage
    c = ln.capacity         # local segment size under a mesh shard
    if ln.total.ndim == 1 and ln.total.shape[0] != 1:
        raise ValueError(
            "sharded lineage ring outside a mesh: a tracer built with "
            "make_lineage(shards=N) only runs under "
            "parallel.mesh_run_until (each shard needs its own cursor "
            "slice); build it with shards=1 for single-device runs")
    tot0 = ln.total.reshape(())    # scalar, or this shard's [1] cursor
    m = mask & (id_v != 0)
    rank = jnp.cumsum(m) - 1
    n_tot = jnp.sum(m).astype(I64)
    n_new = jnp.minimum(n_tot, c)
    pos = ((tot0 + rank) % c).astype(I32)
    idx = jnp.where(m & (rank < c), pos, c)
    return state.replace(lineage=ln.replace(
        s_time=ln.s_time.at[idx].set(time_v, mode="drop"),
        s_id=ln.s_id.at[idx].set(id_v.astype(I32), mode="drop"),
        s_host=ln.s_host.at[idx].set(host_v.astype(I32), mode="drop"),
        s_stage=ln.s_stage.at[idx].set(stage, mode="drop"),
        s_reason=ln.s_reason.at[idx].set(
            reason_v if not hasattr(reason_v, "astype")
            else reason_v.astype(I32), mode="drop"),
        total=ln.total + n_new,
        lost=ln.lost + (n_tot - n_new),
    ))


# ---------------------------------------------------------------------------
# Next-event scan (replaces priority-queue peeks)
# ---------------------------------------------------------------------------


def _aux_times(state: SimState, params, app):
    """Per-host earliest non-packet event: timers, app, re-ticks."""
    socks, hosts = state.socks, state.hosts
    t_h = hosts.t_resume
    if _uses_tcp(app):
        t_tmr = jnp.minimum(
            jnp.minimum(jnp.min(socks.t_rto, axis=1),
                        jnp.min(socks.t_persist, axis=1)),
            jnp.minimum(jnp.min(socks.t_delack, axis=1),
                        jnp.min(socks.t_tw, axis=1)),
        )
        t_h = jnp.minimum(t_h, t_tmr)
    if app is not None:
        t_h = jnp.minimum(t_h, app.next_time(state))
    return t_h


def _cpu_clamp(state: SimState, params, t_h):
    """Virtual CPU gate (reference cpu_isBlocked + event deferral,
    cpu.c:56-75, event.c:71-84): a host whose accumulated CPU backlog
    exceeds the threshold cannot tick before the backlog drains back to
    it, so its events execute late by exactly the built-up delay.

    Like the reference's --cpu-threshold (options.c: default -1 =
    disabled), a negative threshold turns blocking off entirely; wake
    times are rounded to cpu_precision_ns."""
    prec = jnp.maximum(params.cpu_precision_ns, 1)
    ready = state.hosts.cpu_avail - params.cpu_threshold_ns
    rem = ready % prec
    ready = ready - rem + jnp.where(rem >= prec // 2, prec, 0)
    clamp = (params.cpu_ns_per_event > 0) & (t_h != INV) & \
        (params.cpu_threshold_ns >= 0)
    return jnp.where(clamp, jnp.maximum(t_h, ready), t_h)


def _scan_all(state: SimState, params, app):
    """Per-host next event time [H] + its global min.

    Arrival candidates come from the inbox only: IN_FLIGHT entries drive
    the clock (their arrival instant); RX_QUEUED backlog (arrival in the
    past, waiting on rx tokens) is owned by the t_resume wake machinery,
    so it never drags virtual time backward.  Packets still in the outbox
    are invisible here by design -- the conservative window invariant
    puts their arrivals beyond the current window, and the boundary
    exchange makes them visible before the next window's scan."""
    ib = state.inbox
    h = state.hosts.num_hosts
    ki = ib.capacity // h
    t2 = ib.times().reshape(h, ki)
    drive = (ib.stage == STAGE_IN_FLIGHT).reshape(h, ki)
    t_in = jnp.min(jnp.where(drive, t2, jnp.asarray(INV, I64)), axis=1)
    t_h = jnp.minimum(t_in, _aux_times(state, params, app))
    t_h = _cpu_clamp(state, params, t_h)
    return t_h, jnp.min(t_h)


def next_times(state: SimState, params, app):
    """Per-host earliest pending event time [H] and its global min."""
    return _scan_all(state, params, app)


def _outbox_pending(state: SimState):
    """Global earliest arrival among packets still awaiting the boundary
    exchange (scalar i64; INV if none).  Keeps the outer window loop from
    terminating while traffic is still in flight toward the inbox."""
    pool = state.pool
    t = jnp.where(pool.stage == STAGE_IN_FLIGHT, pool.time,
                  jnp.asarray(INV, I64))
    return jnp.min(t)


# ---------------------------------------------------------------------------
# Window-boundary exchange: outbox IN_FLIGHT -> inbox slabs
# ---------------------------------------------------------------------------


def _superblock(n: int, h: int) -> int:
    """Items per rank superblock of the mesh exchange's `_rank_by_dst`.
    Memory: the pairwise rank cube is n*M bytes and the per-block count
    table is (n/M)*h*4 bytes, so the sweet spot is M ~ sqrt(4h); clamp
    to [64, 512] and keep both sides bounded at 10k-host scale (n can
    exceed a million items)."""
    m = int((4 * max(h, 1)) ** 0.5)
    m = max(64, min(512, (m // 64) * 64 if m >= 64 else 64))
    return min(m, max(64, n))


def _rank_by_dst(mask, dstp, h, m):
    """Per-item rank among masked same-destination items, in flat order
    (hierarchical: scatter-add superblock counts + exclusive cumsum +
    in-superblock pairwise ranks).  Returns ([npad] rank, [H] totals).
    The mesh exchange's ranking (send buckets by shard, then received
    rows by local destination); the single-device core ranks by a keyed
    sort instead (`_exchange_core`)."""
    npad = dstp.shape[0]
    blkid = jnp.arange(npad, dtype=I32) // m
    b = npad // m
    ones = jnp.where(mask, 1, 0).astype(I32)
    cnt = jnp.zeros((b, h), I32).at[blkid, dstp].add(ones, mode="drop")
    csum = jnp.cumsum(cnt, axis=0)
    off = csum - cnt                                   # exclusive over blocks
    total = csum[-1]                                   # [H] items per dst
    d3 = dstp.reshape(b, m)
    l3 = mask.reshape(b, m)
    eq = (d3[:, :, None] == d3[:, None, :]) & l3[:, None, :]
    lower = jnp.tril(jnp.ones((m, m), bool), -1)[None]
    rank_in = jnp.sum(eq & lower, axis=2, dtype=I32).reshape(-1)
    return off.reshape(-1)[blkid * h + dstp] + rank_in, total


def _keyed_order(key, idx):
    """Stable sort of the flat row indices `idx` by i32 `key`: returns
    (sorted keys, source row of each sorted position).  Equal keys keep
    flat order."""
    return jax.lax.sort((key, idx), num_keys=1, is_stable=True)


def _seg_starts(keys, bounds):
    """Sorted position of the first key >= each of `bounds` (i32), for
    sorted i32 `keys`.  Two levels instead of a binary search's ~20
    dependent gathers (each a device op of its own): count the 128-key
    blocks that lie wholly below each bound (one compare against the
    block maxima), then the keys below it in the one block where it
    falls (one row gather)."""
    n = keys.shape[0]
    nb = -(-n // 128)
    k2 = jnp.pad(keys, (0, nb * 128 - n),
                 constant_values=jnp.iinfo(jnp.int32).max).reshape(nb, 128)
    blk = jnp.sum(k2[None, :, -1] < bounds[:, None], axis=1, dtype=I32)
    row = k2[jnp.minimum(blk, nb - 1)]
    inrow = jnp.sum(row < bounds[:, None], axis=1, dtype=I32)
    return jnp.minimum(blk * 128 + jnp.where(blk < nb, inrow, 0), n)


def _exchange_core(pool, ib, h, params, ret_slots=False):
    """Slab machinery of the boundary exchange, free of SimState
    packaging: order movers by destination, deliver them into inbox
    free slots, clear the outbox stage.  Returns (pool, inbox, total,
    total_prot, n_free) -- the three [H] per-destination tallies are
    what the accounting tail (_exchange_body) derives drops, trace
    counters and recorder rows from.  `ret_slots` (lineage tracing)
    appends (take, row, ok): the [P1] slot map (slot takes a mover /
    the mover's outbox row) and the [P0] placed-mover mask in flat
    order, so the tail can move trace ids under the identical map.

    Two steps; neither builds a table of hosts x row blocks:

    1. ORDER: one stable sort of the outbox rows keyed by destination
       (non-movers past every destination).  Each destination's movers
       form one segment of the sorted order, in flat order; segment
       starts and per-destination totals are read off the sorted keys.
    2. DELIVER: destination-side.  The j-th free slot of destination d
       (ascending slot order) takes the mover at sorted position
       start[d] + j when j < total[d]; every slab is one row gather of
       the outbox plus a `where` against its old bytes, so slots that
       take no mover keep theirs (stale bytes included).

    Split out so the megakernel path can run it as ONE single-block
    pallas call (megakernel.exchange_call): every op here is integer
    slab shuffling, so it is fusion-context stable (see the "f32
    stability" section of docs/megakernel.md)."""
    p0 = pool.capacity
    ki = ib.capacity // h
    ic = ib.blk.shape[1]          # ICOLS, or NCOLS_UDP for TCP-free worlds

    moving = pool.stage == STAGE_IN_FLIGHT             # [P0], src-major order
    dst = jnp.clip(pool.dst, 0, h - 1)
    idx = jnp.arange(p0, dtype=I32)

    # --- movers ordered by destination, flat (src-major) order within
    # one.  Flat order == (src, emission counter) order within a window
    # because outbox slots free only at boundaries, so allocation indices
    # are monotone across the window's micro-steps -- this reproduces the
    # reference's (srcHostID, srcHostEventID) tiebreak (event.c:110-153).
    keys, src = _keyed_order(jnp.where(moving, dst, h).astype(I32), idx)
    bnd = _seg_starts(keys, jnp.arange(h + 1, dtype=I32))
    start = bnd[:h]                                     # [H] segment starts
    total = bnd[1:] - start                             # [H] movers per dst

    free2 = (ib.stage == STAGE_FREE).reshape(h, ki)
    n_free = jnp.sum(free2, axis=1, dtype=I32)          # [H]

    # --- ACK-before-data shedding (TCP worlds, overflow windows only):
    # when a destination slab can't take every mover, deliberately shed
    # pure ACKs first -- the vectorized analog of ACK compression under
    # router pressure.  Cumulative ACKing absorbs the loss (the next ACK
    # supersedes the shed one), so only DATA/control drops are protocol-
    # visible and only they raise ERR_POOL_OVERFLOW.  Implemented as a
    # class-keyed re-sort: within a destination, protected movers come
    # first in flat order, then pure ACKs in flat order (segment starts
    # are unchanged).  Slot positions don't affect delivery order
    # ((time, pkt_id) row-min), so the re-order changes only WHO
    # overflows, deterministically.
    if ic >= ICOLS:
        blk_f = pool.blk
        from ..transport.tcp import pure_ack as _pure_ack
        ack = _pure_ack(blk_f[:, ICOL_PROTO], blk_f[:, ICOL_FLAGS],
                        blk_f[:, ICOL_LEN]) & moving
        overflow = jnp.any(total > n_free)

        def two_class(_):
            k2, src2 = _keyed_order(
                jnp.where(moving, 2 * dst + ack, 2 * h).astype(I32), idx)
            prot_end = _seg_starts(k2, 2 * jnp.arange(h, dtype=I32) + 1)
            return src2, prot_end - start

        src, total_prot = jax.lax.cond(
            overflow & jnp.any(ack), two_class,
            lambda _: (src, total), None)
    else:
        total_prot = total

    # --- destination-side slot map: free rank j of each slot (exclusive
    # count of free slots before it in its slab), and the outbox row of
    # the mover of effective rank j, if there is one.
    fr = jnp.cumsum(free2, axis=1, dtype=I32) - free2
    take = (free2 & (fr < total[:, None])).reshape(-1)          # [P1]
    row = src[jnp.clip(start[:, None] + fr, 0, p0 - 1)].reshape(-1)

    # --- forward the packed rows verbatim: the outbox block's first `ic`
    # columns ARE the inbox layout; only the TIME columns need splicing
    # from the authoritative `time` array (the block's copy went stale if
    # _tx_drain restamped the departure).
    vals = jnp.concatenate(
        [pool.blk[:, :ICOL_TIME_LO],
         enc_lo(pool.time)[:, None], enc_hi(pool.time)[:, None],
         pool.blk[:, ICOL_TIME_HI + 1:ic]], axis=1)       # [P0, ic]

    ib = ib.replace(
        blk=jnp.where(take[:, None], vals[row], ib.blk),
        stage=jnp.where(take, STAGE_IN_FLIGHT, ib.stage),
        status=jnp.where(take, pool.status[row], ib.status)
        if params.pds_trail else ib.status,
    )

    # Movers leave the outbox whether they fit or overflowed; who
    # overflowed (and whether it was a shed ACK or a counted drop) is
    # the accounting tail's business, derived from the tallies below.
    pool = pool.replace(stage=jnp.where(moving, STAGE_FREE, pool.stage))
    if ret_slots:
        # Placed movers in flat order: effective rank (sorted position
        # less segment start) below the destination's free slots.
        pos = jnp.zeros((p0,), I32).at[src].set(idx)     # inverse order
        ok = moving & (pos - start[dst] < n_free[dst])
        return pool, ib, total, total_prot, n_free, (take, row, ok)
    return pool, ib, total, total_prot, n_free


def _exchange_body(state: SimState, params, fused: bool = False) -> SimState:
    hosts = state.hosts
    h = hosts.num_hosts
    p0 = state.pool.capacity
    ki = state.inbox.capacity // h
    moving = state.pool.stage == STAGE_IN_FLIGHT        # pre-clear copy
    dst = jnp.clip(state.pool.dst, 0, h - 1)

    if fused:
        from . import megakernel as mk
        pool, ib, total, total_prot, n_free = mk.exchange_call(
            state.pool, state.inbox, h, params)
    elif state.lineage is not None:
        # Trace ids ride the IDENTICAL slot map the packed rows take:
        # each slot that takes a mover gathers its pool_id entry into
        # inbox_id, moved rows clear, and each placed/overflowed mover
        # gets an EXCHANGE/DELIVER-reason span.  Pure observation on
        # side arrays -- pool/inbox bytes are untouched.
        pool, ib, total, total_prot, n_free, (take_l, row_l, ok_l) = \
            _exchange_core(state.pool, state.inbox, h, params,
                           ret_slots=True)
        ln = state.lineage
        state = state.replace(lineage=ln.replace(
            inbox_id=jnp.where(take_l, ln.pool_id[row_l], ln.inbox_id),
            pool_id=jnp.where(moving, 0, ln.pool_id)))
        now_p = jnp.broadcast_to(state.now, (p0,))
        state = _lineage_append(state, ok_l, time_v=now_p, id_v=ln.pool_id,
                                host_v=dst, stage=SPAN_EXCHANGE)
        # Overflowed movers die here: shed pure ACKs vs counted drops
        # (the two-class re-order puts acks last exactly when drops
        # exist, so a dropped pure ack under overflow IS a shed one).
        if state.inbox.blk.shape[1] >= ICOLS:
            from ..transport.tcp import pure_ack as _pure_ack_l
            shed_l = _pure_ack_l(
                state.pool.blk[:, ICOL_PROTO], state.pool.blk[:, ICOL_FLAGS],
                state.pool.blk[:, ICOL_LEN]) & moving
        else:
            shed_l = jnp.zeros_like(moving)
        state = _lineage_append(
            state, moving & ~ok_l, time_v=now_p, id_v=ln.pool_id, host_v=dst,
            stage=SPAN_EXCHANGE,
            reason_v=jnp.where(shed_l, LREASON_ACK_SHED, LREASON_POOL))
    else:
        pool, ib, total, total_prot, n_free = _exchange_core(
            state.pool, state.inbox, h, params)

    # Profiler counter block (trace.py), present only when a run opted
    # in: packets moved this exchange + peak destination-slab occupancy.
    if state.tr is not None:
        fit = jnp.minimum(total, n_free)                # [H] movers placed
        occ = jnp.max(ki - n_free + fit)                # [H] -> max slots used
        state = state.replace(tr=state.tr.replace(
            exchanges=state.tr.exchanges + 1,
            pkts_exchanged=state.tr.pkts_exchanged
            + jnp.sum(fit.astype(I64)),
            occ_max=jnp.maximum(state.tr.occ_max, occ.astype(I32))))

    # Flight recorder (state.FlightRecorder): this window's src->dst
    # LOGICAL-SHARD traffic matrix, counted over offered movers.  The
    # shard of a host is id // (h // D), matching the mesh partition, so
    # a single-device run of a D-sharded world writes bitwise the same
    # matrix the mesh exchange derives from its all_to_all ranking.
    # Pool rows are src-major (slab per source host), so a row's source
    # shard is just row // (p0 // D).
    if state.fr is not None:
        dm = state.fr.n_shards
        src_sh = jnp.arange(p0, dtype=I32) // (p0 // dm)
        dst_sh = (dst // (h // dm)).astype(I32)
        ones_m = jnp.where(moving, 1, 0).astype(I32)
        byt_m = jnp.where(moving, state.pool.blk[:, ICOL_LEN], 0).astype(I64)
        state = state.replace(fr=state.fr.replace(
            cur_ex_cnt=jnp.zeros((dm, dm), I32).at[src_sh, dst_sh]
            .add(ones_m),
            cur_ex_bytes=jnp.zeros((dm, dm), I64).at[src_sh, dst_sh]
            .add(byt_m)))

    # Shed pure ACKs are accounted as thinning; DATA/control overflow is
    # a counted drop and raises the capacity escape-hatch flag.
    drops_all = jnp.maximum(total - n_free, 0).astype(I64)
    data_drops = jnp.minimum(
        drops_all, jnp.maximum(total_prot - n_free, 0).astype(I64))
    acks_shed = drops_all - data_drops
    hosts = hosts.replace(
        pkts_dropped_pool=hosts.pkts_dropped_pool + data_drops,
        acks_thinned=hosts.acks_thinned + acks_shed)
    err = state.err | jnp.where(jnp.any(data_drops > 0), ERR_POOL_OVERFLOW,
                                0).astype(state.err.dtype)
    state = state.replace(pool=pool, inbox=ib, hosts=hosts, err=err)
    if state.log is not None:
        from .state import LOG_ACK_THIN
        rows = jnp.arange(h, dtype=I32)
        now_v = jnp.broadcast_to(state.now, (h,))
        state = _log_append(state, data_drops > 0, LOG_DROP_POOL,
                            LOG_WARNING, now_v, rows, data_drops)
        state = _log_append(state, acks_shed > 0, LOG_ACK_THIN,
                            LOG_WARNING, now_v, rows, acks_shed)
    return state


def _exchange_body_mesh(state: SimState, params) -> SimState:
    """Boundary exchange across a device mesh: the dst-bucketed
    all-to-all the single-device scatter becomes when hosts shard.

    Three stages, each reusing the single-device machinery at a
    different granularity:

    1. SEND BUCKETING: movers rank by destination SHARD (`_rank_by_dst`
       with h = n_shards) in local flat (src-major) order, then scatter
       their spliced rows -- plus a global-dst trailer column (and the
       status trail when enabled) -- into a [D*B, C+] send buffer of D
       fixed-size blocks.  B = local pool capacity is an exact bound:
       a shard can never have more movers than outbox slots.

    2. COLLECTIVE: one tiled `lax.all_to_all` swaps block d of every
       shard to shard d.  Received block s holds sender s's movers in
       sender-local flat order, so concatenated blocks s=0..D-1 are in
       GLOBAL flat (src-major) order -- exactly the order the
       single-device rank walks, which is what keeps the per-dst rank
       (and therefore slot assignment, overflow choice, and ACK-shed
       choice) bitwise identical to the single-device run.

    3. LOCAL SPLICE: the received rows re-rank by LOCAL destination and
       take free inbox slots in ascending order -- the unchanged
       single-device tail, with the two ACK-shed gate predicates
       (overflow anywhere / any pure ACK among movers) reduced across
       shards first: they are global `any`s on one device, and shards
       must agree on the shed-vs-keep regime or slot layouts (including
       stale bytes under later writes) diverge leaf-for-leaf."""
    pool, ib, hosts = state.pool, state.inbox, state.hosts
    h = hosts.num_hosts                   # local hosts on this shard
    p0 = pool.capacity                    # local outbox rows
    p1 = ib.capacity
    ki = p1 // h
    ic = ib.blk.shape[1]
    d = jax.lax.psum(1, MESH_AXIS)        # static shard count
    hg = h * d                            # global hosts

    moving = pool.stage == STAGE_IN_FLIGHT          # [p0] local src-major
    dst_g = jnp.clip(pool.dst, 0, hg - 1)           # global dst ids
    dev = dst_g // h                                # destination shard

    # --- stage 1: rank by destination shard, in local flat order.
    m = _superblock(p0, d)
    npad = -(-p0 // m) * m
    pad = npad - p0
    devp = jnp.pad(dev, (0, pad))
    mvp = jnp.pad(moving, (0, pad))
    brank, bt = _rank_by_dst(mvp, devp, d, m)

    # Flight recorder: `bt` is this shard's movers per destination shard
    # -- exactly one row of the src->dst traffic matrix.  all_gather
    # stacks the rows src-major, leaving the full [D, D] matrix
    # replicated on every shard (the recorder block stays replicated).
    if state.fr is not None:
        lenp = jnp.pad(pool.blk[:, ICOL_LEN], (0, pad))
        bby = jnp.zeros((d,), I64).at[devp].add(
            jnp.where(mvp, lenp, 0).astype(I64))
        state = state.replace(fr=state.fr.replace(
            cur_ex_cnt=jax.lax.all_gather(bt, MESH_AXIS).astype(I32),
            cur_ex_bytes=jax.lax.all_gather(bby, MESH_AXIS)))

    # Spliced rows exactly as the single-device exchange forwards them
    # (TIME columns refreshed from the authoritative `time` array).
    vals = jnp.concatenate(
        [pool.blk[:, :ICOL_TIME_LO],
         enc_lo(pool.time)[:, None], enc_hi(pool.time)[:, None],
         pool.blk[:, ICOL_TIME_HI + 1:ic]], axis=1)        # [p0, ic]
    trail = [dst_g[:, None]]
    if params.pds_trail:
        trail.append(pool.status[:, None])
    if state.lineage is not None:
        # Trace ids travel the collective as one extra trailer column,
        # so they ride the exact permutation the packed rows take; the
        # packed row width itself is untouched.
        trail.append(state.lineage.pool_id[:, None])
    row = jnp.pad(jnp.concatenate([vals] + trail, axis=1),
                  ((0, pad), (0, 0)))                      # [npad, cs]
    cs = row.shape[1]

    b = p0                                 # bucket capacity (exact bound)
    send_idx = jnp.where(mvp, devp * b + jnp.clip(brank, 0, b - 1), d * b)
    sb = jnp.full((d * b, cs), -1, I32).at[send_idx].set(row, mode="drop")

    # --- stage 2: the collective.  Received block s = sender s's bucket
    # for this shard, preserving sender-local order.
    rb = jax.lax.all_to_all(sb, MESH_AXIS, split_axis=0, concat_axis=0,
                            tiled=True)                    # [d*b, cs]

    # --- stage 3: local splice (the single-device tail on rb rows).
    rdst_g = rb[:, ic]                     # -1 marks bucket padding
    rvalid = rdst_g >= 0
    rdst = jnp.clip(rdst_g - state.hoff, 0, h - 1)         # local dst row

    n = d * b
    m2 = _superblock(n, h)
    npad2 = -(-n // m2) * m2
    pad2 = npad2 - n
    rdstp = jnp.pad(rdst, (0, pad2))
    rvp = jnp.pad(rvalid, (0, pad2))
    rank, total = _rank_by_dst(rvp, rdstp, h, m2)

    free2 = (ib.stage == STAGE_FREE).reshape(h, ki)
    ids = jnp.arange(ki, dtype=I32)[None, :]
    n_free = jnp.sum(free2, axis=1, dtype=I32)

    if ic >= ICOLS:
        from ..transport.tcp import pure_ack as _pure_ack
        pure_ack = _pure_ack(rb[:, ICOL_PROTO], rb[:, ICOL_FLAGS],
                             rb[:, ICOL_LEN])
        ackp = jnp.pad(pure_ack, (0, pad2)) & rvp
        # GLOBAL gate predicates (see docstring): reduce before the cond.
        overflow = mesh_max(jnp.any(total > n_free).astype(I32)) > 0
        any_ack = mesh_max(jnp.any(ackp).astype(I32)) > 0

        def two_class(_):
            rank_prot, total_prot = _rank_by_dst(rvp & ~ackp, rdstp, h, m2)
            r = jnp.where(ackp, total_prot[rdstp] + (rank - rank_prot),
                          rank_prot)
            return r, total_prot

        rank_eff, total_prot = jax.lax.cond(
            overflow & any_ack, two_class, lambda _: (rank, total), None)
    else:
        rank_eff, total_prot = rank, total

    order2 = jnp.argsort(jnp.where(free2, ids, ids + ki), axis=1).astype(I32)
    within = order2.reshape(-1)[rdstp * ki + jnp.clip(rank_eff, 0, ki - 1)]
    ok = rvp & (rank_eff < n_free[rdstp])
    islot = jnp.where(ok, rdstp * ki + within, p1)

    rvals = jnp.pad(rb[:, :ic], ((0, pad2), (0, 0)))
    ib = ib.replace(
        blk=ib.blk.at[islot].set(rvals, mode="drop"),
        stage=ib.stage.at[islot].set(STAGE_IN_FLIGHT, mode="drop"),
        status=ib.status.at[islot].set(jnp.pad(rb[:, ic + 1], (0, pad2)),
                                       mode="drop")
        if params.pds_trail else ib.status,
    )

    if state.lineage is not None:
        # Receive side of the trailer column: splice arriving trace ids
        # into this shard's inbox_id under the same islot, clear the ids
        # of every local mover (they all left, placed or not), and write
        # spans.  Hosts are GLOBAL dst ids and the time is the uniform
        # window-open `now`, so the mesh span multiset matches the
        # single-device exchange row for row.
        ln = state.lineage
        lin_col = ic + 1 + (1 if params.pds_trail else 0)
        rlin = jnp.pad(rb[:, lin_col], (0, pad2))
        state = state.replace(lineage=ln.replace(
            inbox_id=ln.inbox_id.at[islot].set(rlin, mode="drop"),
            pool_id=jnp.where(moving, 0, ln.pool_id)))
        now_p = jnp.broadcast_to(state.now, rlin.shape)
        rdst_p = jnp.pad(rdst_g, (0, pad2))
        state = _lineage_append(state, ok, time_v=now_p, id_v=rlin,
                                host_v=rdst_p, stage=SPAN_EXCHANGE)
        if ic >= ICOLS:
            shed_l = ackp
        else:
            shed_l = jnp.zeros_like(rvp)
        state = _lineage_append(
            state, rvp & ~ok, time_v=now_p, id_v=rlin, host_v=rdst_p,
            stage=SPAN_EXCHANGE,
            reason_v=jnp.where(shed_l, LREASON_ACK_SHED, LREASON_POOL))

    if state.tr is not None:
        # Local partials; pkts_exchanged / occ_max are finalized across
        # shards by mesh_run_until (psum of the delta / pmax).
        fit = jnp.minimum(total, n_free)
        occ = jnp.max(ki - n_free + fit)
        state = state.replace(tr=state.tr.replace(
            exchanges=state.tr.exchanges + 1,
            pkts_exchanged=state.tr.pkts_exchanged
            + jnp.sum(fit.astype(I64)),
            occ_max=jnp.maximum(state.tr.occ_max, occ.astype(I32))))

    pool = pool.replace(stage=jnp.where(moving, STAGE_FREE, pool.stage))
    drops_all = jnp.maximum(total - n_free, 0).astype(I64)
    data_drops = jnp.minimum(
        drops_all, jnp.maximum(total_prot - n_free, 0).astype(I64))
    acks_shed = drops_all - data_drops
    hosts = hosts.replace(
        pkts_dropped_pool=hosts.pkts_dropped_pool + data_drops,
        acks_thinned=hosts.acks_thinned + acks_shed)
    # err is a per-shard partial here; mesh_run_until ORs it across
    # shards before returning (nothing inside the run branches on it).
    err = state.err | jnp.where(jnp.any(data_drops > 0), ERR_POOL_OVERFLOW,
                                0).astype(state.err.dtype)
    state = state.replace(pool=pool, inbox=ib, hosts=hosts, err=err)
    if state.log is not None:
        # Mesh parity with the single-device tail: records carry GLOBAL
        # host ids (the drain maps them to names) and land in this
        # shard's log segment.
        from .state import LOG_ACK_THIN
        rows_g = host_ids(state, I32)
        now_v = jnp.broadcast_to(state.now, (h,))
        state = _log_append(state, data_drops > 0, LOG_DROP_POOL,
                            LOG_WARNING, now_v, rows_g, data_drops)
        state = _log_append(state, acks_shed > 0, LOG_ACK_THIN,
                            LOG_WARNING, now_v, rows_g, acks_shed)
    return state


def _exchange(state: SimState, params, fused: bool = False) -> SimState:
    """Run the boundary exchange iff anything moved this window.
    `fused` routes the slab core through the single-block pallas call
    (megakernel.exchange_call); the mesh body keeps its own all-to-all
    exchange regardless -- its collectives cannot live inside a kernel."""
    moving = jnp.any(state.pool.stage == STAGE_IN_FLIGHT)
    if _on_mesh(state):
        # The mesh body contains collectives, so every shard must take
        # the same branch: any mover anywhere runs the exchange on all.
        moving = mesh_max(moving.astype(I32)) > 0
        return jax.lax.cond(moving,
                            lambda s: _exchange_body_mesh(s, params),
                            lambda s: s, state)
    return jax.lax.cond(moving,
                        lambda s: _exchange_body(s, params, fused=fused),
                        lambda s: s, state)


# ---------------------------------------------------------------------------
# Flight recorder: per-window row write (state.FlightRecorder)
# ---------------------------------------------------------------------------


def _fr_snapshot(state: SimState):
    """Window-open bookkeeping for the flight recorder: zero the exchange
    scratch matrix (a skipped exchange must record zero traffic, and the
    cond may bypass the body entirely) and capture the counters whose
    per-window deltas become the row.  Traced away when no recorder is
    installed."""
    fr = state.fr
    state = state.replace(fr=fr.replace(
        cur_ex_cnt=jnp.zeros_like(fr.cur_ex_cnt),
        cur_ex_bytes=jnp.zeros_like(fr.cur_ex_bytes)))
    snap = (state.n_events,
            state.n_steps,
            jnp.sum(state.hosts.pkts_recv.astype(I64)),
            jnp.sum(state.hosts.pkts_dropped_inet.astype(I64))
            + jnp.sum(state.hosts.pkts_dropped_router.astype(I64))
            + jnp.sum(state.hosts.pkts_dropped_pool.astype(I64)),
            jnp.asarray(0, I64) if state.nm is None
            else state.nm.killed.astype(I64))
    return state, snap


def _fr_record(state: SimState, snap, ws, we) -> SimState:
    """Append one row for the window that just closed: the exchange that
    opened it (scratch matrix) plus the micro-step activity inside it
    (counter deltas vs the _fr_snapshot).  Under a mesh the shard-local
    deltas psum to globals, so the replicated recorder block stays
    bitwise identical on every shard -- and identical to a single-device
    run of the same world with the same chunking."""
    fr = state.fr
    mesh = _on_mesh(state)
    ev0, steps0, recv0, drop0, kill0 = snap
    d_ev = state.n_events - ev0
    d_recv = jnp.sum(state.hosts.pkts_recv.astype(I64)) - recv0
    d_drop = (jnp.sum(state.hosts.pkts_dropped_inet.astype(I64))
              + jnp.sum(state.hosts.pkts_dropped_router.astype(I64))
              + jnp.sum(state.hosts.pkts_dropped_pool.astype(I64))) - drop0
    d_kill = (jnp.asarray(0, I64) if state.nm is None
              else state.nm.killed.astype(I64) - kill0)
    if mesh:
        # n_steps is uniform across shards (uniform loop predicates);
        # these four are shard-local partials inside the window loop.
        d_ev = jax.lax.psum(d_ev, MESH_AXIS)
        d_recv = jax.lax.psum(d_recv, MESH_AXIS)
        d_drop = jax.lax.psum(d_drop, MESH_AXIS)
        if state.nm is not None:
            d_kill = jax.lax.psum(d_kill, MESH_AXIS)
    idx = (fr.total % fr.capacity).astype(I32)
    return state.replace(fr=fr.replace(
        win_start=fr.win_start.at[idx].set(ws),
        win_end=fr.win_end.at[idx].set(we),
        steps=fr.steps.at[idx].set((state.n_steps - steps0).astype(I32)),
        events=fr.events.at[idx].set(d_ev.astype(I64)),
        routed=fr.routed.at[idx].set(jnp.sum(fr.cur_ex_cnt.astype(I64))),
        delivered=fr.delivered.at[idx].set(d_recv),
        dropped=fr.dropped.at[idx].set(d_drop),
        killed=fr.killed.at[idx].set(d_kill),
        ex_cnt=fr.ex_cnt.at[idx].set(fr.cur_ex_cnt),
        ex_bytes=fr.ex_bytes.at[idx].set(fr.cur_ex_bytes),
        ex_cnt_sum=fr.ex_cnt_sum + fr.cur_ex_cnt.astype(I64),
        ex_bytes_sum=fr.ex_bytes_sum + fr.cur_ex_bytes,
        total=fr.total + 1))


# ---------------------------------------------------------------------------
# Invariant sentinel: per-window health checks (state.SentinelBlock)
# ---------------------------------------------------------------------------


def _sentinel_counters(state: SimState):
    """Shard-local conservation ledger at a window boundary: lifetime
    emission/delivery/drop sums plus the live slot census.  Taken at
    window OPEN (before the exchange, which thins acks and drops data)
    and again at close; the per-window deltas satisfy

        d_sent - d_recv - d_router - d_thinned - d_occupied
            in [0, d_inet + d_pool + d_killed]

    exactly: every packet placed in the pool (pkts_sent) leaves the
    system through delivery, a router drop, ack thinning, a
    delivery-side inet/pool drop or netem kill, or still occupies a
    slot -- and the stage-side halves of the inet/pool counters are
    non-negative.  Seeded worlds and mid-run installs are immune
    because only deltas are checked."""
    h = state.hosts
    occ = (jnp.sum((state.pool.stage != STAGE_FREE).astype(I64))
           + jnp.sum((state.inbox.stage != STAGE_FREE).astype(I64)))
    return (jnp.sum(h.pkts_sent.astype(I64)),
            jnp.sum(h.pkts_recv.astype(I64)),
            jnp.sum(h.pkts_dropped_router.astype(I64)),
            jnp.sum(h.acks_thinned.astype(I64)),
            jnp.sum(h.pkts_dropped_inet.astype(I64)),
            jnp.sum(h.pkts_dropped_pool.astype(I64)),
            jnp.asarray(0, I64) if state.nm is None
            else state.nm.killed.astype(I64),
            occ)


def _sentinel_check(state: SimState, snap, ws, we) -> SimState:
    """Run every invariant probe for the window that just closed and
    fold the result into the sentinel block.  Under a mesh the deltas
    psum and the ok-flags pmin/pmax to globals first (the _fr_record
    rule), so the replicated block stays bitwise identical per shard.
    Only the sentinel block is written: installing it never perturbs
    the trajectory."""
    sn = state.sentinel
    mesh = _on_mesh(state)

    # -- packet conservation (window delta vs the open snapshot) --------
    d = [b - a for a, b in zip(snap, _sentinel_counters(state))]
    if mesh:
        d = [jax.lax.psum(x, MESH_AXIS) for x in d]
    d_sent, d_recv, d_rtr, d_ack, d_inet, d_pool, d_kill, d_occ = d
    resid_low = d_sent - d_recv - d_rtr - d_ack - d_occ
    resid_high = d_inet + d_pool + d_kill - resid_low
    # Overflow windows (err bit set) legitimately leak the identity --
    # the ERR_* flag is already the loud signal for those.
    err_any = state.err
    if mesh:
        err_any = mesh_max(err_any)
    v_cons = ((resid_low < 0) | (resid_high < 0)) & (err_any == 0)

    # -- window-time monotonicity ---------------------------------------
    # we/ws are uniform across shards (pmin'd predicates), so this needs
    # no reduction.
    v_time = (we <= sn.last_we) | (we < ws)

    # -- stage domain / queue accounting / ring cursor bounds -----------
    ok = (jnp.all((state.pool.stage >= STAGE_FREE)
                  & (state.pool.stage <= STAGE_IN_FLIGHT))
          & jnp.all((state.inbox.stage >= STAGE_FREE)
                    & (state.inbox.stage <= STAGE_RX_QUEUED)
                    & (state.inbox.stage != STAGE_TX_QUEUED))
          & jnp.all(state.hosts.tx_queued >= 0)
          & jnp.all(state.hosts.rx_queued >= 0)
          & (jnp.sum(state.hosts.tx_queued.astype(I64))
             == jnp.sum((state.pool.stage == STAGE_TX_QUEUED).astype(I64)))
          & (jnp.sum(state.hosts.rx_queued.astype(I64))
             == jnp.sum((state.inbox.stage == STAGE_RX_QUEUED)
                        .astype(I64))))
    if state.fr is not None:
        ok = ok & (state.fr.total >= 0)
    if state.cap is not None:
        ok = ok & jnp.all(state.cap.total >= 0)
    if state.log is not None:
        ok = ok & jnp.all(state.log.total >= 0)
    if state.scope is not None:
        ok = ok & jnp.all(state.scope.f_total >= 0) \
            & jnp.all(state.scope.l_total >= 0)
    if mesh:
        ok = mesh_min(ok.astype(I32)) > 0
    v_bounds = ~ok

    # -- finiteness probe over the float islands + timer plausibility --
    # The float-dtype filter is a trace-time static, so int-only worlds
    # pay nothing here beyond the three timer-leaf range checks.
    bad = jnp.asarray(0, I64)
    for leaf in jax.tree_util.tree_leaves(state):
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact):
            bad = bad + jnp.sum(~jnp.isfinite(leaf), dtype=I64)
    # srtt/rttvar/rto live in i64 ns: a NaN bit pattern poisoning them
    # lands as a huge positive integer, so a range ceiling catches it.
    for t in (state.socks.srtt, state.socks.rttvar, state.socks.rto):
        bad = bad + jnp.sum((t < 0) | (t > SENTINEL_TIMER_MAX_NS),
                            dtype=I64)
    if mesh:
        bad = mesh_max(bad)
    v_fin = bad > 0

    bits = (jnp.where(v_cons, SENTINEL_CONSERVATION, 0)
            | jnp.where(v_time, SENTINEL_TIME, 0)
            | jnp.where(v_bounds, SENTINEL_BOUNDS, 0)
            | jnp.where(v_fin, SENTINEL_NONFINITE, 0)).astype(I32)
    win = state.n_windows - 1  # the just-closed window's global index
    fresh = (bits != 0) & (sn.first_bad_window < 0)
    return state.replace(sentinel=sn.replace(
        checks=sn.checks + 1,
        violations=sn.violations | bits,
        last_violation=bits,
        first_bad_window=jnp.where(fresh, win, sn.first_bad_window),
        first_bad_t=jnp.where(fresh, we, sn.first_bad_t),
        last_we=jnp.asarray(we, I64),
        resid_low=resid_low,
        resid_high=resid_high,
        nonfinite=bad))


# ---------------------------------------------------------------------------
# Statescope digests: per-window state checksums (state.DigestBlock)
# ---------------------------------------------------------------------------


def _mix64(x):
    """murmur3 fmix64 in i64 (XLA integer arithmetic wraps two's
    complement and logical shifts act on the bit pattern, so this is
    bit-identical to the canonical u64 finalizer)."""
    s = jnp.asarray(33, I64)
    x = x ^ jax.lax.shift_right_logical(x, s)
    x = x * (-49064778989728563)       # 0xFF51AFD7ED558CCD
    x = x ^ jax.lax.shift_right_logical(x, s)
    x = x * (-4265267296055464877)     # 0xC4CEB9FE1A85EC53
    return x ^ jax.lax.shift_right_logical(x, s)


def _dg_bits(x):
    """Bit-normalize a state leaf to i64: floats by bitcast (so the
    digest sees f32 islands bitwise, not approximately), narrower ints
    by extension.  Deterministic on both the mesh and off-mesh paths."""
    x = jnp.asarray(x)
    if x.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(x, I32).astype(I64)
    if x.dtype == jnp.float64:
        return jax.lax.bitcast_convert_type(x, I64)
    return x.astype(I64)


_M64 = (1 << 64) - 1


def _dg_tag(group: int, leaf_idx: int) -> int:
    """Distinct i64 constant per (group, leaf): the element hash keys on
    it, so equal values at equal indices in different leaves still
    contribute different terms.  Host-side fmix64 (python ints)."""
    x = ((group << 32) ^ leaf_idx ^ 0x5851F42D4C957F2D) & _M64
    x ^= x >> 33
    x = (x * 0xFF51AFD7ED558CCD) & _M64
    x ^= x >> 33
    x = (x * 0xC4CEB9FE1A85EC53) & _M64
    x ^= x >> 33
    return x - (1 << 64) if x >= (1 << 63) else x


def _digest_group_leaves(state: SimState) -> dict:
    """DIGEST_GROUPS name -> the state leaves that group covers.  The
    RNG counters get their own column (divergence there means the
    *sampling* went different ways, the first thing to rule out), so
    the hosts group excludes them by identity.

    The netem group drops `nm.killed`: under a mesh each shard holds a
    per-shard PARTIAL of that counter (parallel/mesh.py finalizes it by
    psum only at launch end), so mid-run it cannot be digested
    shard-invariantly; kills still surface through the pool/inbox state
    they mutate."""
    h = state.hosts
    rng_leaves = [h.rng_ctr, h.send_ctr]
    nm_leaves = ([l for l in jax.tree_util.tree_leaves(state.nm)
                  if l is not state.nm.killed]
                 if state.nm is not None else [])
    return {
        "pool": jax.tree_util.tree_leaves(state.pool),
        "inbox": jax.tree_util.tree_leaves(state.inbox),
        "socks": jax.tree_util.tree_leaves(state.socks),
        "hosts": [l for l in jax.tree_util.tree_leaves(h)
                  if not any(l is r for r in rng_leaves)],
        "rng": rng_leaves,
        "netem": nm_leaves,
        "app": jax.tree_util.tree_leaves(state.app),
    }


def _digest_sums(state: SimState) -> jnp.ndarray:
    """[G, D] i64 checksum matrix of the current state: per DIGEST_GROUPS
    row, per logical-host-shard column.

    Each element contributes `_mix64(bits + _mix64(global_index + tag))`
    (keyed on the GLOBAL flat index, so a permutation of equal values
    still diverges) and a group checksum is the WRAPPING i64 SUM of its
    contributions.  Summation is commutative and element ownership is
    exact, so the [G, D] matrix is bitwise identical between a D-shard
    mesh run and a single-device run installed with shards=D -- and
    summing columns over D reproduces the shards=1 digest.  Replicated
    leaves (netem overlay, scalars) contribute once, into column 0.

    Under a mesh each shard computes its local column and one
    all_gather assembles the identical full matrix on every shard (the
    flight-recorder replication rule)."""
    dg = state.dg
    D = dg.n_shards
    mesh = _on_mesh(state)
    h = state.hosts.num_hosts
    row_axes = (h, state.pool.capacity, state.inbox.capacity)
    groups = _digest_group_leaves(state)
    cols, repl = [], []
    for g, name in enumerate(DIGEST_GROUPS):
        col = jnp.zeros((1 if mesh else D,), I64)
        rep = jnp.asarray(0, I64)
        for i, leaf in enumerate(groups[name]):
            v = _dg_bits(leaf).reshape(-1)
            tag = _dg_tag(g, i)
            # The netem overlay is REPLICATED under a mesh (every shard
            # holds the full arrays), so its leaves must not take the
            # leading-axis shard rule even off-mesh -- group-level
            # classification keeps the two paths identical.
            sharded = (name != "netem" and jnp.ndim(leaf) >= 1
                       and leaf.shape[0] in row_axes)
            if sharded:
                if mesh:
                    # Global flat offset of this shard's element 0: the
                    # leading axis is a multiple of the host axis, so
                    # rows stay contiguous chunks under flattening.
                    off = state.hoff.astype(I64) * (v.shape[0] // h)
                else:
                    off = jnp.asarray(0, I64)
                idx = jnp.arange(v.shape[0], dtype=I64) + off
                contrib = _mix64(v + _mix64(idx + tag))
                if mesh:
                    col = col + jnp.sum(contrib, dtype=I64)[None]
                else:
                    col = col + contrib.reshape(D, -1).sum(
                        axis=1, dtype=I64)
            else:
                idx = jnp.arange(v.shape[0], dtype=I64)
                rep = rep + jnp.sum(_mix64(v + _mix64(idx + tag)),
                                    dtype=I64)
        cols.append(col)
        repl.append(rep)
    col_m = jnp.stack(cols)  # [G, 1] local under mesh; [G, D] off-mesh
    if mesh:
        col_m = jax.lax.all_gather(col_m[:, 0], MESH_AXIS).T  # [G, D]
    return col_m.at[:, 0].add(jnp.stack(repl))


def _digest_record(state: SimState, we) -> SimState:
    """Append one digest row when the just-closed window lands on the
    cadence.  `n_windows` is replicated (uniform window predicates), so
    every shard takes the same branch -- the all_gather inside the
    taken branch is collective-safe, the `_exchange` cond rule."""
    dg = state.dg
    win = state.n_windows - 1  # the just-closed window's global index
    due = (win % dg.every) == 0

    def rec(s):
        d = s.dg
        sums = _digest_sums(s)
        idx = (d.total % d.capacity).astype(I32)
        return s.replace(dg=d.replace(
            win=d.win.at[idx].set(win),
            t_end=d.t_end.at[idx].set(jnp.asarray(we, I64)),
            sums=d.sums.at[idx].set(sums),
            total=d.total + 1))

    return jax.lax.cond(due, rec, lambda s: s, state)


# ---------------------------------------------------------------------------
# Flowscope: cadence-gated flow/link sampling (state.FlowScope)
# ---------------------------------------------------------------------------


def _u32_dist(a, b):
    """i32 distance a-b in u32 sequence space (local copy of the
    transport's wrap-safe diff; core must not import transport)."""
    return (a.astype(U32) - b.astype(U32)).astype(I32)


def _ring_append(arrays, values, tot0, c, mask):
    """Masked bulk append into one ring segment (the _log_append
    recipe): first-`c`-of-batch deterministic overflow, drop-sentinel
    scatter.  Returns (updated arrays dict, n_new, n_lost)."""
    rank = jnp.cumsum(mask) - 1
    n_tot = jnp.sum(mask).astype(I64)
    n_new = jnp.minimum(n_tot, c)
    pos = ((tot0 + rank) % c).astype(I32)
    idx = jnp.where(mask & (rank < c), pos, c)  # c = dropped write
    out = {k: arrays[k].at[idx].set(v.reshape(-1).astype(arrays[k].dtype),
                                    mode="drop")
           for k, v in values.items()}
    return out, n_new, n_tot - n_new


def _scope_sample(state: SimState, ctx, we) -> SimState:
    """One flowscope sample epoch, taken when the closing window reached
    the cadence boundary (`we >= next_due`); otherwise an exact no-op.
    Traced away entirely when no scope block is installed.

    Flow rows: every TCP socket past LISTEN (handshake through
    teardown) on this shard's hosts.  Link rows: every local host NIC.
    Host ids are GLOBAL; rows land in this shard's ring segment under
    its own cursor.  `we` is uniform across shards (pmin'd window
    predicates) and next_due/samples replicated, so every shard takes
    the same branch here -- the cond is collective-safe."""
    from .state import SOCK_TCP, TCPS_CLOSED, TCPS_LISTEN

    scope = state.scope
    if scope.f_total.ndim == 1 and scope.f_total.shape[0] != 1:
        raise ValueError(
            "sharded flowscope outside a mesh: a block built with "
            "make_flowscope(shards=N) only runs under "
            "parallel.mesh_run_until (each shard needs its own cursor "
            "slice); build it with shards=1 for single-device runs")

    socks, hosts = state.socks, state.hosts
    h = hosts.num_hosts
    bw_up = ctx[0]
    gids = host_ids(state, I32)

    def _take(scope):
        if scope.sample_flows:
            s_n = socks.slots
            live = (socks.stype == SOCK_TCP) & \
                (socks.tcp_state != TCPS_CLOSED) & \
                (socks.tcp_state != TCPS_LISTEN)
            fm = live.reshape(-1)
            inflight = _u32_dist(socks.snd_nxt, socks.snd_una)
            acked = jnp.maximum(
                socks.bytes_sent - jnp.maximum(inflight, 0).astype(I64), 0)
            c = scope.flow_capacity
            arrays = {k: getattr(scope, "f_" + k) for k in (
                "time", "host", "slot", "peer", "cwnd", "ssthresh",
                "srtt", "inflight", "retx", "acked", "sent", "recv")}
            values = {
                "time": jnp.broadcast_to(we, (h * s_n,)),
                "host": jnp.broadcast_to(gids[:, None], (h, s_n)),
                "slot": jnp.broadcast_to(
                    jnp.arange(s_n, dtype=I32)[None, :], (h, s_n)),
                "peer": socks.peer_host,
                "cwnd": socks.cwnd,
                "ssthresh": socks.ssthresh,
                "srtt": socks.srtt,
                "inflight": inflight,
                "retx": socks.retx_segs,
                "acked": acked,
                "sent": socks.bytes_sent,
                "recv": socks.bytes_recv,
            }
            out, n_new, n_lost = _ring_append(
                arrays, values, scope.f_total.reshape(()), c, fm)
            scope = scope.replace(
                f_total=scope.f_total + n_new,
                f_lost=scope.f_lost + n_lost,
                **{"f_" + k: v for k, v in out.items()})

        if scope.sample_links:
            c = scope.link_capacity
            arrays = {k: getattr(scope, "l_" + k) for k in (
                "time", "host", "tx", "rx", "qdepth", "cap", "drops")}
            values = {
                "time": jnp.broadcast_to(we, (h,)),
                "host": gids,
                "tx": hosts.bytes_sent,
                "rx": hosts.bytes_recv,
                "qdepth": hosts.tx_queued + hosts.rx_queued,
                "cap": bw_up.astype(I64),
                "drops": (hosts.pkts_dropped_inet
                          + hosts.pkts_dropped_router
                          + hosts.pkts_dropped_pool),
            }
            out, n_new, n_lost = _ring_append(
                arrays, values, scope.l_total.reshape(()), c,
                jnp.ones((h,), bool))
            scope = scope.replace(
                l_total=scope.l_total + n_new,
                l_lost=scope.l_lost + n_lost,
                **{"l_" + k: v for k, v in out.items()})

        return scope.replace(
            samples=scope.samples + 1,
            next_due=(we // scope.interval + 1) * scope.interval)

    scope = jax.lax.cond(we >= scope.next_due, _take, lambda s: s, scope)
    return state.replace(scope=scope)


# ---------------------------------------------------------------------------
# Phase A: inbox enqueue -> NIC receive (token bucket + CoDel) -> delivery
# ---------------------------------------------------------------------------


def _wire_bytes(proto, length):
    """On-the-wire size charged against token buckets (payload + header;
    reference packet_getTotalSize with CONFIG_HEADER_SIZE_*)."""
    return length + jnp.where(proto == PROTO_TCP, TCP_HEADER_SIZE,
                              UDP_HEADER_SIZE)


def _rx_phase(state: SimState, params, em, tick_t, active, app,
              window_end, bw_dn=None, alive=None, aux_bound=None):
    """Arrivals: router enqueue (stage flip), NIC token/CoDel drain of one
    packet per host, transport delivery, inbox slot free.

    Merges the reference's _worker_runDeliverPacketTask -> router_enqueue
    -> networkinterface_receivePackets -> socket_pushInPacket chain
    (worker.c:236-241, router.c:104-123, network_interface.c:421-455)
    into row-local ops over the destination slabs."""
    from ..transport import tcp as tcp_mod
    from ..transport import udp as udp_mod

    ib, hosts = state.inbox, state.hosts
    h = hosts.num_hosts
    p1 = ib.capacity
    ki = p1 // h

    t_arr = ib.times()
    t2 = t_arr.reshape(h, ki)
    st2 = ib.stage.reshape(h, ki)

    # Router enqueue: wire arrivals whose time has come join the upstream
    # router queue (a stage tag flip; `time` keeps the arrival instant so
    # CoDel can compute sojourn).
    due = (st2 == STAGE_IN_FLIGHT) & (t2 <= tick_t[:, None]) & \
        active[:, None]

    # Interface receive buffer (reference <host interfacebuffer>): a
    # bounded router backlog tail-drops the latest arrivals beyond
    # capacity before CoDel sees them.  Rank dues within the row by
    # (time, id) so the drop order is deterministic.  The ranking is an
    # [H, slab, slab] comparison cube, so it only exists in the compiled
    # step when some host actually configures a buffer bound (STATIC
    # params.has_iface_buf; the default unbounded case traces it away).
    k2 = ib.order_keys().reshape(h, ki)
    if params.has_iface_buf:
        # The deterministic tail-drop ranking materializes an [H, ki, ki]
        # comparison cube per micro-step.  That is affordable only for
        # modest inbox slabs; fail loudly at trace time instead of
        # letting one configured host OOM/compile-explode a large world
        # (tools/opbench.py economics; ADVICE r3).
        if h * ki * ki > (1 << 28):
            raise ValueError(
                f"<host interfacebuffer> needs an [H={h}, k={ki}, k={ki}] "
                f"ranking cube (> 2^28 elements) in the compiled step; "
                f"shrink the inbox slab (--pool-slab) or drop the "
                f"interfacebuffer bound for worlds this large")
        cap = params.iface_buf_pkts
        bounded = cap > 0
        later = due[:, None, :] & (
            (t2[:, None, :] < t2[:, :, None]) |
            ((t2[:, None, :] == t2[:, :, None]) &
             (k2[:, None, :] < k2[:, :, None])))
        due_rank = jnp.sum(later & due[:, :, None], axis=2, dtype=I32)
        room = jnp.maximum(cap - hosts.rx_queued, 0)
        tail_drop = due & bounded[:, None] & (due_rank >= room[:, None])
        due = due & ~tail_drop
    else:
        tail_drop = jnp.zeros_like(due)

    # Tail drops are receive-side events a masked receiver must see in
    # its capture (they are exactly the overflow traffic an operator
    # enables capture to diagnose); only traced when a host configures
    # an interface buffer.
    if state.cap is not None and params.has_iface_buf:
        from .state import CAP_RDROP
        # Capture records carry GLOBAL host ids (identity off-mesh).
        rows_b = jnp.broadcast_to(
            host_ids(state, I32)[:, None], (h, ki))
        td_mask = (tail_drop & params.pcap_mask[:, None]).reshape(-1)
        blk = ib.blk
        state = _cap_append(
            state, td_mask,
            time_v=jnp.broadcast_to(tick_t[:, None], (h, ki)),
            src=blk[:, ICOL_SRC], dst=rows_b,
            sport=blk[:, ICOL_SPORT], dport=blk[:, ICOL_DPORT],
            proto=blk[:, ICOL_PROTO], flags=blk[:, ICOL_FLAGS],
            length=blk[:, ICOL_LEN],
            seq=_bitcast_i32_u32(blk[:, ICOL_SEQ]),
            ack=_bitcast_i32_u32(blk[:, ICOL_ACK]), kind=CAP_RDROP)

    st2 = jnp.where(due, STAGE_RX_QUEUED, st2)
    st2 = jnp.where(tail_drop, STAGE_FREE, st2)
    if params.pds_trail:
        status = jnp.where(due.reshape(-1),
                           ib.status | PDS_ROUTER_ENQUEUED, ib.status)
        status = jnp.where(tail_drop.reshape(-1),
                           status | PDS_ROUTER_DROPPED, status)
    else:
        status = ib.status
    hosts = hosts.replace(
        pkts_dropped_router=hosts.pkts_dropped_router +
        jnp.sum(tail_drop, axis=1),
        rx_queued=hosts.rx_queued + jnp.sum(due, axis=1, dtype=I32))

    if state.lineage is not None and params.has_iface_buf:
        # Interface-buffer tail drops end a traced packet's life at the
        # router: one DELIVER/qdisc span, then the freed slot's id
        # clears.
        td_f = tail_drop.reshape(-1)
        ln = state.lineage
        state = _lineage_append(
            state, td_f,
            time_v=jnp.broadcast_to(tick_t[:, None], (h, ki)).reshape(-1),
            id_v=ln.inbox_id,
            host_v=jnp.broadcast_to(host_ids(state, I32)[:, None],
                                    (h, ki)).reshape(-1),
            stage=SPAN_DELIVER, reason_v=LREASON_QDISC)
        state = state.replace(lineage=state.lineage.replace(
            inbox_id=jnp.where(td_f, 0, state.lineage.inbox_id)))

    # -- delivery rounds -----------------------------------------------------
    # Round 0 delivers each host's earliest queued packet at tick_t, like
    # the reference's one-event-per-pop.  Apps that declare `rx_batch` > 1
    # (bursty TCP fan-in) get extra rounds that may also consume arrivals
    # slightly in the FUTURE of tick_t -- legal as long as no other event
    # (timer, app wake, re-tick) lies between tick_t and the arrival, and
    # bounded by a small span so timers armed during the batch cannot be
    # outrun.  Each round uses the ARRIVAL's own time as its clock, so
    # ACK stamps, RTT samples, and timer arms are exact per packet.
    d_rounds = max(1, int(getattr(app, "rx_batch", 1)))
    ids = jnp.arange(ki, dtype=I32)[None, :]
    rows = jnp.arange(h, dtype=I32)
    # Packet SRC columns carry GLOBAL host ids; under a mesh the local row
    # index must be shifted before comparing against them (loopback test).
    rows_g = host_ids(state, I32)
    boot = tick_t < params.bootstrap_end
    if bw_dn is None:
        assert state.hoff is None, \
            "mesh runs must pass the window ctx (local bw slices)"
        bw_dn = netem_apply.rate(state.nm, params.bw_down_Bps)
    tokens, last = nic.refill(hosts.tokens_rx, hosts.last_refill_rx,
                              bw_dn, tick_t, active)
    hosts = hosts.replace(last_refill_rx=last)
    if d_rounds > 1:
        span = simtime.SIMTIME_ONE_MILLISECOND
        # Ordering invariant for future-delivery rounds: the bound uses
        # _aux_times evaluated at batch START, so any timer ARMED DURING
        # the batch must not be able to fire inside the remaining span --
        # i.e. every armable timer delay must exceed `span`.  A future
        # sub-ms timer (e.g. pacing) would silently reorder events; this
        # trace-time check turns that into a loud failure.
        from ..transport import tcp as _tcp_c
        _min_timer = min(_tcp_c.RTO_MIN, _tcp_c.DELACK_DELAY,
                         _tcp_c.TIMEWAIT_DELAY)
        assert _min_timer > span, (
            f"rx_batch future-delivery span ({span} ns) must stay below "
            f"every armable TCP timer delay (min {_min_timer} ns); a "
            f"timer armed mid-batch could otherwise fire inside the "
            f"batch and be outrun")
        # The megakernel path pre-computes _aux_times OUTSIDE the Pallas
        # kernel (it reads app state the kernel does not carry) and
        # passes the per-host slice in as `aux_bound`; both expressions
        # are evaluated at batch start, so they are bitwise identical.
        aux0 = (_aux_times(state, params, app)
                if aux_bound is None else aux_bound)
        bound = jnp.minimum(aux0, tick_t + span)
        bound = jnp.minimum(bound, window_end - 1)
    else:
        bound = tick_t

    delivered_n = jnp.zeros((h,), I32)
    state = state.replace(hosts=hosts)
    for r in range(d_rounds):
        limit = tick_t if r == 0 else bound
        hosts = state.hosts
        # Candidates: the queued backlog, plus (rounds > 0, unbounded
        # interface buffers only) in-flight arrivals within the bound.
        cand = st2 == STAGE_RX_QUEUED
        if r > 0 and not params.has_iface_buf:
            cand = cand | ((st2 == STAGE_IN_FLIGHT) &
                           (t2 <= limit[:, None]))
        cand = cand & active[:, None]
        tq = jnp.where(cand, t2, jnp.asarray(INV, I64))
        tmin = jnp.min(tq, axis=1)
        at_t = cand & (tq == tmin[:, None])
        kq = jnp.where(at_t, k2, jnp.asarray(INV, I64))
        kmin = jnp.min(kq, axis=1)
        at = at_t & (kq == kmin[:, None])
        col = jnp.min(jnp.where(at, ids, ki), axis=1)
        have = active & (col < ki) & (tmin <= limit)
        col = jnp.clip(col, 0, ki - 1)
        flat = rows * ki + col
        was_queued = have & (st2.reshape(-1)[flat] == STAGE_RX_QUEUED)
        t_eff = jnp.maximum(tick_t, jnp.where(have, tmin, 0))

        # One packed gather for every field of the chosen packet.
        row = ib.blk[flat]                              # [H, ICOLS]
        pkt = RxPkt(row, jnp.where(have, kmin, 0),
                    jnp.where(have, tmin, 0))

        # NIC rx: token bucket + CoDel (at the packet's own instant --
        # tokens accrue up to t_eff so a packet the reference would fund
        # at its arrival time is funded here too).
        if r > 0:
            tokens, last = nic.refill(tokens, hosts.last_refill_rx,
                                      bw_dn, t_eff, have)
            hosts = hosts.replace(last_refill_rx=last)
        size = _wire_bytes(pkt.proto, pkt.length).astype(I64) * nic.SCALE
        loop = pkt.src == rows_g
        free_pass = loop | boot
        funded = have & (free_pass | (tokens >= size))

        sojourn = jnp.maximum(t_eff - pkt.time, 0)
        rx_q_now = hosts.rx_queued
        backlog_after = rx_q_now - jnp.where(was_queued, 1, 0)
        hosts, drop = nic.codel_dequeue(hosts, funded & ~loop, t_eff,
                                        sojourn, backlog_after)
        deliver = funded & ~drop
        # Netem delivery gate: a packet reaching a DOWN destination is
        # lost at the interface (in-flight packets when the host crashed,
        # plus loopback sends that bypass the staging drop).  The slot
        # still frees (funded), so nothing strands.
        if state.nm is not None:
            up = alive if alive is not None else \
                netem_apply.alive(state.nm)
            nm_kill = deliver & ~up
            deliver = deliver & ~nm_kill
        else:
            nm_kill = None

        tokens = tokens - jnp.where(funded & ~free_pass, size, 0)
        hosts = hosts.replace(tokens_rx=tokens)

        # Inbox slot release + status trail for everything dequeued.
        oh = (ids == col[:, None])
        st2 = jnp.where(oh & funded[:, None], STAGE_FREE, st2)
        if params.pds_trail:
            fm = (oh & (funded & drop)[:, None]).reshape(-1)
            status = jnp.where(fm, status | PDS_ROUTER_ENQUEUED |
                               PDS_ROUTER_DROPPED, status)
            dm = (oh & deliver[:, None]).reshape(-1)
            status = jnp.where(dm, status | PDS_ROUTER_ENQUEUED |
                               PDS_RCV_SOCKET_PROCESSED, status)

        hosts = hosts.replace(
            rx_queued=rx_q_now -
            jnp.where(funded & was_queued, 1, 0).astype(I32),
            pkts_dropped_router=hosts.pkts_dropped_router +
            jnp.where(drop, 1, 0),
        )
        if nm_kill is not None:
            hosts = hosts.replace(
                pkts_dropped_inet=hosts.pkts_dropped_inet +
                jnp.where(nm_kill, 1, 0))
            state = state.replace(nm=state.nm.replace(
                killed=state.nm.killed + jnp.sum(nm_kill)))

        if r == d_rounds - 1:
            # Wake-ups: backlog remains -> re-tick now; starved -> when
            # tokens accrue for this packet.
            t_tok = tick_t + nic.time_until(size - tokens, bw_dn)
            t_res = jnp.where(
                have & ~funded, t_tok,
                jnp.where(funded & (hosts.rx_queued > 0), tick_t,
                          jnp.asarray(INV, I64)))
            hosts = hosts.replace(
                t_resume=jnp.minimum(hosts.t_resume, t_res))

        state = state.replace(
            inbox=ib.replace(stage=st2.reshape(-1), status=status),
            hosts=hosts)
        ib = state.inbox

        if state.lineage is not None:
            # Every funded dequeue ends this hop: delivered (reason 0),
            # CoDel/router-dropped (qdisc), or killed at a down host.
            # Read the slot's id before the freed slot clears it.
            ln = state.lineage
            lid_h = jnp.where(have, ln.inbox_id[flat], 0)
            reason_h = jnp.where(drop, LREASON_QDISC, 0)
            if nm_kill is not None:
                reason_h = jnp.where(nm_kill, LREASON_HOST_DOWN, reason_h)
            state = _lineage_append(state, funded, time_v=t_eff,
                                    id_v=lid_h, host_v=rows_g,
                                    stage=SPAN_DELIVER, reason_v=reason_h)
            freed = (oh & funded[:, None]).reshape(-1)
            state = state.replace(lineage=state.lineage.replace(
                inbox_id=jnp.where(freed, 0, state.lineage.inbox_id)))

        # Event log (traced away when disabled).  Records carry GLOBAL
        # host ids (rows_g == rows off-mesh).
        if state.log is not None:
            if r == 0:
                rows2 = jnp.broadcast_to(rows_g[:, None],
                                         (h, ki)).reshape(-1)
                src_col = state.inbox.blk[:, ICOL_SRC]
                t_flat = jnp.broadcast_to(tick_t[:, None],
                                          (h, ki)).reshape(-1)
                state = _log_append(state, tail_drop.reshape(-1),
                                    LOG_DROP_TAIL, LOG_WARNING, t_flat,
                                    rows2, src_col)
            state = _log_append(state, drop, LOG_DROP_ROUTER, LOG_WARNING,
                                t_eff, rows_g, pkt.src)
            if nm_kill is not None:
                state = _log_append(state, nm_kill, LOG_NETEM_DOWN,
                                    LOG_WARNING, t_eff, rows_g, pkt.src)
            state = _log_append(state, deliver, LOG_DELIVER, LOG_DEBUG,
                                t_eff, rows_g, pkt.src)

        # Receive-direction capture (reference captures both directions
        # per interface, network_interface.c:337-373,415-418): delivered
        # packets AND received-but-router-dropped ones, at the receive
        # instant.
        if state.cap is not None:
            from .state import CAP_DELIVER, CAP_RDROP
            rec_rx = (deliver | drop) & params.pcap_mask
            state = _cap_append(
                state, rec_rx, time_v=t_eff, src=pkt.src, dst=rows_g,
                sport=pkt.sport, dport=pkt.dport, proto=pkt.proto,
                flags=pkt.flags, length=pkt.length, seq=pkt.seq,
                ack=pkt.ack,
                kind=jnp.where(drop, CAP_RDROP, CAP_DELIVER))

        # Transport delivery (each round stamps at the arrival's time).
        udp_mask = deliver & (pkt.proto == PROTO_UDP)
        socks, _accepted = udp_mod.deliver(state.socks, udp_mask, pkt.src,
                                           pkt.sport, pkt.dport,
                                           pkt.length, pkt.payload_id)
        state = state.replace(socks=socks)
        if _uses_tcp(app):
            tcp_mask = deliver & (pkt.proto == PROTO_TCP)
            reply_slot = emit.SLOT_RX_REPLY if r == 0 \
                else emit.NUM_SLOTS + r - 1

            def _arrivals(args, _pkt=pkt, _mask=tcp_mask, _t=t_eff,
                          _slot=reply_slot):
                s_, e_ = args
                return tcp_mod.process_arrivals(s_, params, e_, _t, _pkt,
                                                _mask, reply_slot=_slot)

            if params.kernel_diet:
                # KERNEL-DIET GATE: rounds with no TCP arrival anywhere
                # skip the whole per-round arrival machine (socket
                # match, ACK clocking, reassembly).  Exact skip: every
                # write in process_arrivals is masked by (a subset of)
                # tcp_mask, and emit.put under a false mask is the
                # identity.
                state, em = jax.lax.cond(jnp.any(tcp_mask), _arrivals,
                                         lambda a: a, (state, em))
            else:
                state, em = _arrivals((state, em))

        hosts = state.hosts
        hosts = hosts.replace(
            pkts_recv=hosts.pkts_recv + jnp.where(deliver, 1, 0),
            bytes_recv=hosts.bytes_recv + jnp.where(deliver, pkt.length,
                                                    0),
        )
        state = state.replace(hosts=hosts)
        delivered_n = delivered_n + jnp.where(deliver, 1, 0)
        if r == 0:
            t_post = jnp.where(deliver, t_eff, tick_t)
        else:
            t_post = jnp.where(deliver, jnp.maximum(t_post, t_eff), t_post)
    return state, em, delivered_n, t_post


# ---------------------------------------------------------------------------
# Emission staging (packets leave their source this tick)
# ---------------------------------------------------------------------------


def _route(params, vs, vd, src, ctr):
    """Packed routing lookup + per-packet jitter draw: ONE row gather for
    (latency, jitter, reliability).  Jitter perturbs latency uniformly in
    +/- the pair's amplitude, keyed by (src, per-src counter) so the same
    packet draws the same perturbation wherever its departure is computed
    (reference carries per-edge jitter, topology.c:81-105).

    Returns (latency_ns i64, reliability f32)."""
    if not params.has_jitter:
        # STATIC no-jitter world: the perturbation is provably zero
        # (jit == 0 makes the where() drop delta), so the keyed-uniform
        # hash chain traces away entirely and the routing gather narrows
        # to the leading (lat, rel) columns.  RNG draws are functionally
        # keyed -- skipping one consumes nothing -- so this is bitwise-
        # neutral.
        lat, rel = params.route_narrow(vs, vd)
        return jnp.maximum(lat, simtime.SIMTIME_ONE_NANOSECOND), rel
    lat, jit, rel = params.route(vs, vd)
    key = rng.purpose_key(params.seed_key, rng.PURPOSE_JITTER)
    u = rng.keyed_uniform(key, src, ctr.astype(jnp.uint32),
                          (ctr >> 32).astype(jnp.uint32))
    delta = ((2.0 * u - 1.0) * jit.astype(jnp.float32)).astype(I64)
    lat = jnp.maximum(lat + jnp.where(jit > 0, delta, 0),
                      simtime.SIMTIME_ONE_NANOSECOND)
    return lat, rel


def _free_slot_pick(free2, rank2):
    """Scatter/sort-free slab allocation: `free2` [H,K] marks free slots,
    `rank2` [H,E] is each emission's 0-based ordinal among its host's
    allocations this tick.  Returns [H,E] slot columns such that the r-th
    allocation takes the r-th free slot in ascending index order (callers
    must mask by rank2 < n_free).  Pure cumsum + one-hot -- an argsort
    here costs milliseconds in host-major layout."""
    h, k = free2.shape
    pos = jnp.cumsum(free2, axis=1) - 1           # rank of each free slot
    ids = jnp.arange(k, dtype=I32)[None, None, :]
    onehot = free2[:, None, :] & (pos[:, None, :] == rank2[:, :, None]) & \
        (rank2 >= 0)[:, :, None]
    return jnp.sum(jnp.where(onehot, ids, 0), axis=2, dtype=I32)


def _patched_rows(em, src2, ctr2, time_v, send_t, lat, stage_v, status_v):
    """[H,E,C+2] staging rows: the emission block with the engine-owned
    columns patched in (SRC, TIME, CTR, TS, LAT) plus the merge-scratch
    STAGE/STATUS columns.  Pure slicing + stacking; one concatenate.
    Width-adaptive: a narrow (TCP-free) emission block has no TS/TSE/SACK
    columns to carry, so those pieces vanish from the concatenate and the
    merge downstream shrinks with them."""
    eb = em.blk
    base = ext_base(eb.shape[2])

    def c(x):
        return x[:, :, None].astype(I32)

    pieces = [
        c(src2),                                   # ICOL_SRC
        eb[:, :, 1:ICOL_TIME_LO],                  # SPORT..PAYLOAD
        c(enc_lo(time_v)), c(enc_hi(time_v)),      # ICOL_TIME_*
        c(enc_lo(ctr2)), c(enc_hi(ctr2)),          # ICOL_CTR_*
    ]
    if base >= ICOLS:
        pieces += [
            c(enc_lo(send_t)), c(enc_hi(send_t)),  # ICOL_TS_*
            eb[:, :, ICOL_TSE_LO:base + 1],        # TSE, SACK, DST
        ]
    else:
        pieces += [eb[:, :, base + OEXT_DST:base + OEXT_DST + 1]]
    pieces += [
        c(enc_lo(lat)), c(enc_hi(lat)),            # OEXT_LAT_*
        eb[:, :, base + OEXT_PRIO:base + OEXT_PRIO + 1],
        c(stage_v), c(status_v),                   # stage/status scratch
    ]
    return jnp.concatenate(pieces, axis=2)


def _stage_emissions(state: SimState, params, em: emit.Emissions, tick_t,
                     active, app, bw_up=None):
    """Assign pkt_ids, apply routing latency + reliability drops, and
    merge staged emissions into free OUTBOX slots of the emitting host's
    own slab -- direct to IN_FLIGHT when the tx token bucket covers them,
    else parked in TX_QUEUED.  Same-host loopback packets go straight
    into the sender's inbox slab with a 1ns delay (reference local path,
    network_interface.c:548-555).

    The reference equivalent is the interface send path + worker_sendPacket
    (/root/reference/src/main/host/network_interface.c:466-540,
    src/main/core/worker.c:243-304): qdisc select under token budget,
    reliability draw, latency lookup, push event to the destination host
    queue.  The bootstrap period bypasses bandwidth
    (network_interface.c:432-434,522)."""
    pool, hosts = state.pool, state.hosts
    h, e = em.valid.shape
    p0 = pool.capacity
    ko = p0 // h

    valid = em.valid
    rank = jnp.cumsum(valid, axis=1) - 1              # [H,E] within-host order
    counts = jnp.sum(valid, axis=1).astype(I64)       # [H]
    ctr = hosts.send_ctr                               # [H]

    # GLOBAL source ids: they key the jitter/drop RNG draws and ride the
    # packet SRC column, so they must be mesh-invariant (identity arange
    # off-mesh).
    src2 = jnp.broadcast_to(host_ids(state, I32)[:, None], (h, e))
    ctr2 = ctr[:, None] + rank

    if state.lineage is not None:
        # Lineage sampling + trace-id assignment, functionally keyed by
        # (src, per-src emission counter): any mesh shape samples -- and
        # ids -- exactly the same packets.  The threshold rides as
        # TRACED data (state.lineage.rate_x1p32), so one compiled graph
        # serves every sample rate.  Ids are odd-ended 31-bit positives
        # ((bits >> 1) | 1), so 0 stays the "untraced" sentinel;
        # collisions are possible and harmless (docs/observability.md).
        lkey = rng.purpose_key(params.seed_key, rng.PURPOSE_LINEAGE)
        lc_lo = ctr2.astype(jnp.uint32)
        lc_hi = (ctr2 >> 32).astype(jnp.uint32)
        sampled = valid & (rng.keyed_bits(lkey, src2, lc_lo, lc_hi)
                           <= state.lineage.rate_x1p32)
        lid2 = jnp.where(sampled, ((rng.keyed_bits(lkey, lc_lo, lc_hi, src2)
                                    >> jnp.uint32(1)) | jnp.uint32(1))
                         .astype(I32), 0)
    else:
        sampled = None
        lid2 = None

    # Routing: latency (+ per-packet jitter) + reliability, loopback
    # shortcut.  vs is the emitting host's own vertex -- a broadcast, not
    # a gather.  host_vertex stays replicated under the mesh (em.dst holds
    # global ids), so the own-vertex broadcast slices it to local rows.
    vs = jnp.broadcast_to(_lrows(state, params.host_vertex)[:, None],
                          (h, e))
    vd = params.host_vertex[jnp.clip(em.dst, 0, params.host_vertex.shape[0] - 1)]
    lat, rel = _route(params, vs, vd, src2, ctr2)
    if state.nm is not None:
        # Fault overlay BEFORE the loopback override: blocked pairs
        # (endpoint down / link down / partitioned) get rel 0.0 and die
        # through the ordinary reliability drop below; loopback stays
        # exempt from link faults.
        rel_base = rel
        lat, rel = netem_apply.route_overlay(state.nm, src2, em.dst,
                                             lat, rel)
    loop = em.dst == src2
    lat = jnp.where(loop, simtime.SIMTIME_ONE_NANOSECOND, lat)
    rel = jnp.where(loop, 1.0, rel)

    if params.has_loss or state.nm is not None:
        drop_key = rng.purpose_key(params.seed_key,
                                   rng.PURPOSE_PACKET_DROP)
        u = rng.keyed_uniform(drop_key, src2, ctr2.astype(jnp.uint32),
                              (ctr2 >> 32).astype(jnp.uint32))
        dropped = valid & (u >= rel)
    else:
        # STATIC loss-free world with no fault overlay: every rel is
        # exactly 1.0 and keyed_uniform draws in [0, 1), so u >= rel can
        # never hold -- the whole drop hash chain traces away (the
        # keyed draw consumes nothing, so skipping it is bitwise-
        # neutral).
        dropped = jnp.zeros_like(valid)
    if state.nm is not None:
        # Injected-fault kills: dropped here but the BASE draw would have
        # survived -- exactly the packets netem killed (blocked pairs or
        # added loss), separated from baseline wire unreliability.
        nm_kill = dropped & (u < rel_base)
        state = state.replace(nm=state.nm.replace(
            killed=state.nm.killed + jnp.sum(nm_kill)))
    live = valid & ~dropped
    lb = live & loop if _may_loopback(app) else jnp.zeros_like(live)
    nl = live & ~lb

    # --- outbox slab allocation for non-loopback emissions: free slots in
    # ascending index order; the r-th live emission takes the r-th free
    # slot.  (Allocation order is monotone across a window's micro-steps
    # because outbox slots free only at boundaries -- see _exchange.)
    free = (pool.stage == STAGE_FREE).reshape(h, ko)
    ids = jnp.arange(ko, dtype=I32)[None, :]
    n_free = jnp.sum(free, axis=1)
    nl_rank = jnp.where(nl, jnp.cumsum(nl, axis=1) - 1, -1)  # [H,E] 0-based
    within = _free_slot_pick(free, nl_rank)
    have_slot = nl & (nl_rank >= 0) & (nl_rank < n_free[:, None])
    placed = have_slot                                  # outbox-placed

    send_t = jnp.where(em.t_send > 0, em.t_send,
                       jnp.broadcast_to(tick_t[:, None], (h, e)))
    arr_t = send_t + lat

    # --- NIC tx admission: direct-admit under the token budget, else park
    # in TX_QUEUED for _tx_drain (FIFO is preserved because any backlog
    # forces parking).
    if bw_up is None:
        assert state.hoff is None, \
            "mesh runs must pass the window ctx (local bw slices)"
        bw_up = netem_apply.rate(state.nm, params.bw_up_Bps)
    tokens, last = nic.refill(hosts.tokens_tx, hosts.last_refill_tx,
                              bw_up, tick_t, active)
    sizes = _wire_bytes(em.proto, em.length).astype(I64) * nic.SCALE
    sizes_nl = jnp.where(placed, sizes, 0)
    prefix = jnp.cumsum(sizes_nl, axis=1)
    boot2 = (tick_t < params.bootstrap_end)[:, None]
    ok_budget = (hosts.tx_queued == 0)[:, None] & (prefix <= tokens[:, None])
    admit = placed & (boot2 | ok_budget)
    spent = jnp.sum(jnp.where(admit & ~boot2, sizes, 0), axis=1)
    tokens = tokens - spent
    parked = placed & ~admit
    # A parked packet stamped in the future (rx_batch reply lanes) is
    # invisible to _select_tx_slab until its send instant; arm a wake
    # there or it strands until an unrelated event ticks the host.
    t_park = jnp.min(jnp.where(parked, send_t, jnp.asarray(INV, I64)),
                     axis=1)
    hosts = hosts.replace(
        tokens_tx=tokens, last_refill_tx=last,
        t_resume=jnp.minimum(hosts.t_resume, t_park),
        tx_queued=hosts.tx_queued + jnp.sum(parked, axis=1).astype(I32))

    stage_v = jnp.where(admit, STAGE_IN_FLIGHT, STAGE_TX_QUEUED)
    time_v = jnp.where(admit, arr_t, send_t)
    status_v = jnp.where(
        admit,
        PDS_SND_CREATED | PDS_SND_INTERFACE_SENT | PDS_INET_SENT,
        PDS_SND_CREATED)

    # --- scatter-free merge into the outbox slab rows: ONE one-hot merge
    # of the whole packed row (round 4 did ~21 per-field merges here; the
    # step cost at small H is kernel-count-bound, see PERF.md).
    oh = (within[:, :, None] == ids[:, None, :]) & have_slot[:, :, None]
    hit = jnp.any(oh, axis=1)

    pc = pool.blk.shape[1]                             # world block width
    val3 = _patched_rows(em, src2, ctr2, time_v, send_t, lat,
                         stage_v, status_v)            # [H,E,pc+2]
    v = jnp.sum(jnp.where(oh[:, :, :, None], val3[:, :, None, :], 0),
                axis=1, dtype=I32)                     # [H,Ko,pc+2]
    blk3 = pool.blk.reshape(h, ko, pc)
    hit3 = hit[:, :, None]
    pool = pool.replace(
        blk=jnp.where(hit3, v[:, :, :pc], blk3).reshape(-1, pc),
        stage=jnp.where(hit, v[:, :, pc],
                        pool.stage.reshape(h, ko)).reshape(-1),
        status=jnp.where(hit, v[:, :, pc + 1],
                         pool.status.reshape(h, ko)).reshape(-1)
        if params.pds_trail else pool.status,
        time=jnp.where(hit, dec_i64(v[:, :, ICOL_TIME_LO],
                                    v[:, :, ICOL_TIME_HI]),
                       pool.time.reshape(h, ko)).reshape(-1),
    )
    state = state.replace(pool=pool, hosts=hosts)

    if state.lineage is not None:
        # Trace ids enter the outbox side array under the SAME one-hot
        # merge as the packed rows: every freshly claimed slot gets its
        # emission's id (0 when untraced), untouched slots keep theirs.
        ln = state.lineage
        lv = jnp.sum(jnp.where(oh, lid2[:, :, None], 0), axis=1, dtype=I32)
        state = state.replace(lineage=ln.replace(
            pool_id=jnp.where(hit, lv,
                              ln.pool_id.reshape(h, ko)).reshape(-1)))

    # --- loopback: straight into the sender's own inbox slab (row-local
    # allocation; the block write is an [H*E]-row scatter, traced away
    # when the app never loops back).
    lb_placed = jnp.zeros_like(lb)
    if _may_loopback(app):
        state, lb_placed = _loopback_insert(state, params, em, lb, src2,
                                            ctr2, send_t, lin_ids=lid2)

    all_placed = placed | lb_placed
    overflow = jnp.any(live & ~all_placed & ~lb) | jnp.any(lb & ~lb_placed)
    sent_bytes = jnp.sum(jnp.where(all_placed, em.length, 0),
                         axis=1).astype(I64)
    hosts = state.hosts
    hosts = hosts.replace(
        send_ctr=ctr + counts,
        pkts_sent=hosts.pkts_sent + jnp.sum(all_placed, axis=1),
        bytes_sent=hosts.bytes_sent + sent_bytes,
        pkts_dropped_inet=hosts.pkts_dropped_inet + jnp.sum(dropped, axis=1),
        pkts_dropped_pool=hosts.pkts_dropped_pool +
        jnp.sum(live & ~all_placed, axis=1),
    )
    err = state.err | jnp.where(overflow, ERR_POOL_OVERFLOW,
                                0).astype(jnp.int32)
    state = state.replace(hosts=hosts, err=err)

    # Event log (traced away when disabled).
    if state.log is not None:
        hostf = src2.reshape(-1)
        timef = send_t.reshape(-1)
        dstf = em.dst.reshape(-1)
        state = _log_append(state, dropped.reshape(-1), LOG_DROP_INET,
                            LOG_WARNING, timef, hostf, dstf)
        state = _log_append(state, (live & ~all_placed).reshape(-1),
                            LOG_DROP_POOL, LOG_WARNING, timef, hostf, dstf)
        state = _log_append(state, all_placed.reshape(-1), LOG_SEND,
                            LOG_DEBUG, timef, hostf, dstf)

    if state.lineage is not None:
        # One EMIT span per sampled emission -- with the death reason
        # when it never left the source (reliability draw, netem block,
        # slab overflow) -- then the hop the survivors took: parked
        # under the token bucket (STAGE), straight onto the wire (TX),
        # or the loopback shortcut (LINK).
        reason2 = jnp.where(dropped, LREASON_LOSS, 0)
        if state.nm is not None:
            br = netem_apply.block_reason(state.nm, src2, em.dst)
            reason2 = jnp.where(dropped & (br > 0), br, reason2)
        reason2 = jnp.where(live & ~all_placed, LREASON_POOL, reason2)
        lhost = src2.reshape(-1)
        ltime = send_t.reshape(-1)
        lidf = lid2.reshape(-1)
        state = _lineage_append(state, sampled.reshape(-1), time_v=ltime,
                                id_v=lidf, host_v=lhost, stage=SPAN_EMIT,
                                reason_v=reason2.reshape(-1))
        state = _lineage_append(state, (parked & sampled).reshape(-1),
                                time_v=ltime, id_v=lidf, host_v=lhost,
                                stage=SPAN_STAGE)
        state = _lineage_append(state, (admit & sampled).reshape(-1),
                                time_v=ltime, id_v=lidf, host_v=lhost,
                                stage=SPAN_TX)
        state = _lineage_append(state, (lb_placed & sampled).reshape(-1),
                                time_v=ltime, id_v=lidf, host_v=lhost,
                                stage=SPAN_LINK)
        state = state.replace(lineage=state.lineage.replace(
            n_assigned=state.lineage.n_assigned
            + jnp.sum(sampled).astype(I64)))

    # Packet capture (PCAP analog; only traced when a CaptureRing is
    # installed): record every placed emission at send time.
    if state.cap is not None:
        from .state import CAP_SEND
        # Send direction records for marked SOURCES only; a marked
        # destination's inbound view is the CAP_DELIVER/CAP_RDROP records
        # written at delivery (_rx_phase) -- a dst-gated send record here
        # would never be exported and only pressure the ring.
        rec = all_placed & params.pcap_mask[:, None]
        state = _cap_append(
            state, rec.reshape(-1), time_v=send_t, src=src2, dst=em.dst,
            sport=em.sport, dport=em.dport, proto=em.proto, flags=em.flags,
            length=em.length, seq=em.seq, ack=em.ack, kind=CAP_SEND)
    return state, all_placed


def _loopback_insert(state: SimState, params, em, lb, src2, ctr2,
                     send_t, lin_ids=None):
    """Insert loopback emissions into the sender's own inbox slab.
    Arrival = send + 1ns (reference network_interface.c:548-555).
    `lin_ids` [H,E] carries lineage trace ids into the claimed slots'
    inbox_id rows (present exactly when the tracer is installed)."""
    ib = state.inbox
    h, e = lb.shape
    p1 = ib.capacity
    ki = p1 // h

    free2 = (ib.stage == STAGE_FREE).reshape(h, ki)
    n_free = jnp.sum(free2, axis=1)
    lb_rank = jnp.where(lb, jnp.cumsum(lb, axis=1) - 1, -1)
    within = _free_slot_pick(free2, lb_rank)
    ok = lb & (lb_rank >= 0) & (lb_rank < n_free[:, None])
    # src2 carries GLOBAL ids (they ride the SRC column); slab addressing
    # is local, so shift back under a mesh.
    src_l = src2 if state.hoff is None \
        else src2 - state.hoff.astype(I32)
    islot = jnp.where(ok, src_l * ki + within, p1).reshape(-1)

    # Packed rows in inbox layout: the emission block's first ICOLS
    # columns with SRC/TIME/CTR/TS patched (arrival = send + 1ns).
    arr = send_t + simtime.SIMTIME_ONE_NANOSECOND

    def c(x):
        return x[:, :, None].astype(I32)

    ic = ib.blk.shape[1]          # ICOLS, or NCOLS_UDP for TCP-free worlds
    pieces = [
        c(src2),
        em.blk[:, :, 1:ICOL_TIME_LO],
        c(enc_lo(arr)), c(enc_hi(arr)),
        c(enc_lo(ctr2)), c(enc_hi(ctr2)),
    ]
    if ic >= ICOLS:
        pieces += [c(enc_lo(send_t)), c(enc_hi(send_t)),
                   em.blk[:, :, ICOL_TSE_LO:ICOLS]]
    vals = jnp.concatenate(pieces, axis=2).reshape(-1, ic)

    pds = PDS_SND_CREATED | PDS_SND_INTERFACE_SENT | PDS_INET_SENT
    ib = ib.replace(
        blk=ib.blk.at[islot].set(vals, mode="drop"),
        stage=ib.stage.at[islot].set(STAGE_IN_FLIGHT, mode="drop"),
        status=ib.status.at[islot].set(pds, mode="drop")
        if params.pds_trail else ib.status,
    )
    state = state.replace(inbox=ib)
    if state.lineage is not None and lin_ids is not None:
        ln = state.lineage
        state = state.replace(lineage=ln.replace(
            inbox_id=ln.inbox_id.at[islot].set(
                jnp.where(ok, lin_ids, 0).reshape(-1), mode="drop")))
    return state, ok


def _select_tx_slab(pool, tick_t, active, h):
    """Pick per SOURCE host the earliest due TX_QUEUED packet.

    Two-phase row-min (time, then within-slab index) over the source's
    own slab -- deterministic and free of any packed-key time bound.
    Returns ([H] pool index or -1, [P] chosen mask)."""
    p = pool.capacity
    k = p // h
    stage2 = pool.stage.reshape(h, k)
    time2 = pool.time.reshape(h, k)
    due = (stage2 == STAGE_TX_QUEUED) & (time2 <= tick_t[:, None]) & \
        active[:, None]
    td = jnp.where(due, time2, jnp.asarray(INV, I64))
    tmin = jnp.min(td, axis=1)
    ids = jnp.arange(k, dtype=I32)[None, :]
    at = due & (td == tmin[:, None])
    j = jnp.min(jnp.where(at, ids, k), axis=1)
    have = j < k
    j = jnp.clip(j, 0, k - 1)
    slot_of_host = jnp.where(have, jnp.arange(h, dtype=I32) * k + j, -1)
    chosen = ((ids == j[:, None]) & have[:, None]).reshape(-1)
    return slot_of_host, chosen


def _tx_drain(state: SimState, params, tick_t, active, bw_up=None):
    """Drain one parked TX_QUEUED packet per host onto the wire, gated by
    the upstream token bucket (reference _networkinterface_sendPackets,
    network_interface.c:519-561: dequeue under token budget, then
    router_forward -> worker_sendPacket).

    KERNEL-DIET GATE: apps that never park (unbounded bandwidth, or
    sends always under budget) pay only a cheap any() here instead of
    replaying the slab row-min + packed gather every micro-step.  The
    skip is exact -- with no TX_QUEUED packet anywhere the body reduces
    to the bare token refill (have/funded/chosen all false leave pool,
    tx_queued and t_resume bitwise untouched), and the refill itself
    stays unconditional so token/timestamp state never diverges."""
    if bw_up is None:
        assert state.hoff is None, \
            "mesh runs must pass the window ctx (local bw slices)"
        bw_up = netem_apply.rate(state.nm, params.bw_up_Bps)
    if not params.kernel_diet:
        return _tx_drain_body(state, params, tick_t, active, bw_up)

    def _refill_only(s):
        tokens, last = nic.refill(s.hosts.tokens_tx,
                                  s.hosts.last_refill_tx,
                                  bw_up, tick_t, active)
        return s.replace(hosts=s.hosts.replace(tokens_tx=tokens,
                                               last_refill_tx=last))

    return jax.lax.cond(
        jnp.any(state.pool.stage == STAGE_TX_QUEUED),
        lambda s: _tx_drain_body(s, params, tick_t, active, bw_up),
        _refill_only, state)


def _tx_drain_body(state: SimState, params, tick_t, active, bw_up,
                   skip_refill=False):
    pool, hosts = state.pool, state.hosts
    h = hosts.num_hosts

    slot_of_host, chosen = _select_tx_slab(pool, tick_t, active, h)
    have = slot_of_host >= 0
    slot = jnp.clip(slot_of_host, 0, pool.capacity - 1)

    if skip_refill:
        # Megakernel path: _stage_emissions already refilled the tx
        # bucket at this same instant, so a second refill accrues
        # exactly 0 tokens (dt=0; tokens never exceed capacity).
        tokens, last = hosts.tokens_tx, hosts.last_refill_tx
    else:
        tokens, last = nic.refill(hosts.tokens_tx, hosts.last_refill_tx,
                                  bw_up, tick_t, active)
    # One packed row gather for every field of the chosen packet.
    row = pool.blk[slot]                                 # [H, C]
    size = _wire_bytes(row[:, ICOL_PROTO], row[:, ICOL_LEN]).astype(I64) \
        * nic.SCALE
    boot = tick_t < params.bootstrap_end
    funded = have & (boot | (tokens >= size))
    tokens = tokens - jnp.where(funded & ~boot, size, 0)

    # Departure: arrival = now + the latency fixed at staging (which
    # already includes this packet's keyed jitter draw, so departure needs
    # no routing lookup; the reliability draw also happened at staging, so
    # loss is independent of queueing).
    eb = ext_base(pool.blk.shape[1])
    arr = tick_t + dec_i64(row[:, eb + OEXT_LAT_LO], row[:, eb + OEXT_LAT_HI])
    ko = pool.capacity // h
    funded_b = jnp.broadcast_to(funded[:, None], (h, ko)).reshape(-1)
    arr_b = jnp.broadcast_to(arr[:, None], (h, ko)).reshape(-1)
    chosen_dep = chosen & funded_b
    pool = pool.replace(
        stage=jnp.where(chosen_dep, STAGE_IN_FLIGHT, pool.stage),
        time=jnp.where(chosen_dep, arr_b, pool.time),
        status=jnp.where(chosen_dep,
                         pool.status | PDS_SND_INTERFACE_SENT | PDS_INET_SENT,
                         pool.status) if params.pds_trail else pool.status,
    )

    hosts = hosts.replace(
        tokens_tx=tokens, last_refill_tx=last,
        tx_queued=hosts.tx_queued - jnp.where(funded, 1, 0).astype(I32))

    t_tok = tick_t + nic.time_until(size - tokens, bw_up)
    t_res = jnp.where(
        have & ~funded, t_tok,
        jnp.where(funded & (hosts.tx_queued > 0), tick_t,
                  jnp.asarray(INV, I64)))
    hosts = hosts.replace(t_resume=jnp.minimum(hosts.t_resume, t_res))
    state = state.replace(pool=pool, hosts=hosts)
    if state.lineage is not None:
        # A parked packet departing the NIC: the row stays in place
        # (stage flip only), so the side array needs no move -- just the
        # TX hop span at the drain instant.
        state = _lineage_append(state, funded, time_v=tick_t,
                                id_v=state.lineage.pool_id[slot],
                                host_v=host_ids(state, I32), stage=SPAN_TX)
    return state


# ---------------------------------------------------------------------------
# Micro-step and loops
# ---------------------------------------------------------------------------


def _window_ctx(state: SimState, params):
    """Window-invariant inputs of the micro-step, hoisted out of the
    inner while body: the netem overlay only changes at window
    boundaries (netem_apply.advance runs before the window's ticks), so
    the effective NIC rates and the host-liveness mask are constant
    across every micro-step of a window.  Returns (bw_up, bw_dn, alive);
    alive is None for worlds without a fault overlay.

    Under a mesh the bw params arrive pre-sliced to local rows (shard_map
    in_specs) while the nm overlay stays replicated, so the overlay
    factors are sliced to match (netem_apply.rate_rows/alive_rows)."""
    if state.hoff is None:
        return (netem_apply.rate(state.nm, params.bw_up_Bps),
                netem_apply.rate(state.nm, params.bw_down_Bps),
                None if state.nm is None else netem_apply.alive(state.nm))
    h = state.hosts.num_hosts
    return (netem_apply.rate_rows(state.nm, params.bw_up_Bps,
                                  state.hoff, h),
            netem_apply.rate_rows(state.nm, params.bw_down_Bps,
                                  state.hoff, h),
            None if state.nm is None
            else netem_apply.alive_rows(state.nm, state.hoff, h))


def _microstep_core(state: SimState, params, app, t_h, window_end,
                    ctx=None):
    """Advance every host's earliest pending event (< window_end)."""
    from ..transport import tcp as tcp_mod

    if ctx is None:
        with phase("bounds"):
            ctx = _window_ctx(state, params)
    bw_up, bw_dn, alive = ctx

    h = state.hosts.num_hosts
    if _uses_tcp(app) and state.inbox.blk.shape[1] < ICOLS:
        raise ValueError(
            "this world's inbox was built narrow (uses_tcp=False in "
            "make_sim_state) but the app uses TCP; TCP segments need the "
            "TS/SACK inbox columns")
    if _uses_tcp(app):
        # Extra reply lanes for rx_batch delivery rounds beyond the first
        # (each round's TCP reply needs its own emission slot).
        n_lanes = emit.NUM_SLOTS + max(0, int(getattr(app, "rx_batch", 1))
                                       - 1)
    else:
        # Pure-UDP apps may batch several sends per tick into extra lanes
        # (app_tx_lanes), each stamped with its own t_send.
        n_lanes = emit.SLOT_APP + max(1, int(getattr(app, "app_tx_lanes",
                                                     1)))
    # The staging block matches the world's outbox width: TCP-free worlds
    # stage 18-column rows (no TS/TSE/SACK), shrinking both emit.put's
    # row stack and the staging merge (PERF.md round 7).
    # Each phase below runs under its trace.PHASES scope; the tick's
    # set-up rides with `rx`.
    with phase("rx"):
        active = t_h < window_end
        tick_t = jnp.where(active, t_h, window_end)

        # Active hosts' resume flags are re-armed by this tick's phases;
        # inactive hosts keep theirs (token-accrual wake-ups must survive).
        state = state.replace(
            hosts=state.hosts.replace(t_resume=jnp.where(
                active, jnp.asarray(INV, I64), state.hosts.t_resume)))
        em = emit.empty(h, n_lanes, cols=state.pool.blk.shape[1])

        # Phase A: arrivals through the destination slab (router queue,
        # NIC rx tokens + CoDel, transport delivery).
        state, em, delivered_n, t_post = _rx_phase(
            state, params, em, tick_t, active, app, window_end,
            bw_dn=bw_dn, alive=alive)

    # Phases B-D run at the POST-BATCH per-host instant: when rx_batch
    # rounds consumed arrivals slightly after tick_t, every downstream
    # effect (timer arming, app reaction, transmitted segments) is
    # stamped at-or-after its cause.  The batching bound guarantees no
    # timer/app event was due inside (tick_t, t_post], so ordering is
    # preserved.  For rx_batch=1 apps t_post == tick_t exactly.
    if _uses_tcp(app):
        with phase("tcp_timers"):
            state, em = tcp_mod.run_timers(state, params, em, t_post,
                                           active)

    # Phase C: application tick.
    if app is not None:
        with phase("app"):
            if getattr(app, "wants_window_end", False):
                # The window bound lets the app pre-emit future sends that
                # provably precede its next possible arrival (send
                # batching).
                state, em = app.on_tick(state, params, em, t_post, active,
                                        window_end=window_end)
            else:
                state, em = app.on_tick(state, params, em, t_post, active)

    # Phase D: TCP transmission, merge staged emissions into the outbox
    # (direct-admit or park) or own inbox (loopback), then drain parked
    # packets through the tx bucket.
    if _uses_tcp(app):
        with phase("tcp_tx"):
            state, em = tcp_mod.transmit(state, params, em, t_post, active)
    with phase("stage"):
        state, placed = _stage_emissions(state, params, em, t_post, active,
                                         app, bw_up=bw_up)
    with phase("tx"):
        state = _tx_drain(state, params, t_post, active, bw_up=bw_up)

    # Virtual CPU accounting (reference cpu_updateTime + cpu_addDelay,
    # cpu.c:77-108): every delivered packet and staged emission costs
    # cpu_ns_per_event.  Costs accumulate exactly; precision rounding
    # happens where the backlog is consulted (_cpu_clamp), so per-step
    # increments smaller than the precision are never lost.
    with phase("cpu"):
        cpu_on = params.cpu_ns_per_event > 0
        events = delivered_n.astype(I64) + \
            jnp.sum(em.valid, axis=1).astype(I64)
        cost = params.cpu_ns_per_event * events
        avail = jnp.maximum(state.hosts.cpu_avail, tick_t)
        new_avail = jnp.where(cpu_on & active, avail + cost,
                              state.hosts.cpu_avail)
        state = state.replace(
            hosts=state.hosts.replace(cpu_avail=new_avail),
            n_steps=state.n_steps + 1,
            n_events=state.n_events + jnp.sum(events),
        )
    return state


def microstep(state: SimState, params, app, t_h, window_end):
    """One micro-step (public wrapper).  Dispatches to the fused Pallas
    path when params.megakernel applies (trace-time static), so tooling
    that lowers this wrapper (tools/kernelcount.py) sees the graph the
    window loop actually runs."""
    from . import megakernel as mk
    if mk.enabled(state, params, app):
        st, _t_h, _gmin = mk.microstep_fused(state, params, app, t_h,
                                             window_end)
        return st
    return _microstep_core(state, params, app, t_h, window_end)


def _window_body_ref(state: SimState, params, app, t_target):
    """One whole conservative window, reference implementations only:
    boundary exchange -> per-window scan -> window bounds -> netem
    advance -> hoisted window ctx -> the micro-step while loop -> window
    close.  This is the interior of K_WINDOW
    (megakernel.window_fused): it runs INSIDE a Pallas region, so it
    must not launch nested kernels (fused=False throughout) and must
    not touch the window-close instrumentation blocks (scope/sentinel/
    dg ride outside the kernel; fr/tr ride through because the exchange
    writes them with integer scatter-adds).  Off-mesh only -- the
    loop-driving pmin collectives cannot live inside a kernel.

    Returns (state, t_h, gmin, ws, we); the op sequence per phase is
    the same one the main-graph window body traces, which is what the
    persistent path's bitwise contract rests on (docs/megakernel.md,
    "Persistent window kernel")."""
    with phase("exchange"):
        st = _exchange(state, params, fused=False)
    with phase("scan"):
        t_h, gmin = _scan_all(st, params, app)
    with phase("bounds"):
        ws = jnp.maximum(st.now, gmin)
        we = jnp.minimum(ws + params.min_latency_ns, t_target)
        if st.nm is not None:
            st = st.replace(nm=netem_apply.advance(st.nm, we))
        ctx = _window_ctx(st, params)

    def icond(icarry):
        _s, _th, g = icarry
        return g < we

    def ibody(icarry):
        s, th, _ = icarry
        s = _microstep_core(s, params, app, th, we, ctx=ctx)
        with phase("scan"):
            th2, g2 = _scan_all(s, params, app)
        return s, th2, g2

    st, t_h, gmin = jax.lax.while_loop(icond, ibody, (st, t_h, gmin))
    with phase("close"):
        st = st.replace(now=we, n_windows=st.n_windows + 1)
    return st, t_h, gmin, ws, we


@functools.partial(jax.jit, static_argnames=("app",))
def run_until(state: SimState, params, app, t_target):
    """Run windows until simulated time reaches t_target (jitted whole)."""
    return run_until_impl(state, params, app, t_target)


def run_until_impl(state: SimState, params, app, t_target):
    """Window-loop body shared by the jitted single-device entry above
    and the shard_map body of parallel.mesh_run_until.

    Mesh mode (state.hoff set) changes exactly three things, all gated
    at trace time so the single-device graph is byte-identical:

    * the two loop-driving reductions -- per-window global min event
      time and earliest outbox-pending arrival -- get a cross-shard
      `pmin`, making every loop predicate uniform across shards (the
      reference's `master_slaveFinishedCurrentRound` window-advance
      reduction, master.c:450-480, as one collective);
    * `_exchange` takes the all-to-all body (and a pmax'd predicate);
    * `_window_ctx` slices the replicated netem overlay to local rows.

    Uniform predicates guarantee identical window/micro-step trip counts
    on every shard, which is what lets collectives live inside the
    while_loops at all -- and makes n_steps/n_windows/now replicated for
    free.

    Ensemble mode (ensemble/__init__.py) needs NO changes here, and must
    never get any: under `jax.vmap` the while_loops batch by running
    while ANY world's predicate holds and select-freezing finished
    lanes, so each world advances by its own per-world gmin -- worlds
    never synchronize each other's windows, and a finished world's state
    is carried through untouched (the select keeps it bitwise frozen).
    Keeping this function vmap-transparent is what makes an ensemble
    world bitwise equal to its solo run AND keeps ensemble-absent runs
    lowering byte-identical HLO (the tier-0 pins in
    tests/test_ensemble.py check both)."""
    from . import megakernel as mk
    t_target = jnp.asarray(t_target, I64)
    mesh = _on_mesh(state)
    fused = mk.enabled(state, params, app)
    persistent = mk.persistent_enabled(state, params, app)

    # Every op below runs under a trace.PHASES scope (the window records
    # taken at window open belong to `close`, with the rest of them).
    def scan(s):
        with phase("scan"):
            t_h, gmin = _scan_all(s, params, app)
            if mesh:
                gmin = mesh_min(gmin)
        return t_h, gmin

    def outbox_pending(s):
        with phase("scan"):
            g = _outbox_pending(s)
            if mesh:
                g = mesh_min(g)
        return g

    def window_cond(carry):
        st, _t_h, gmin, gout = carry
        g = jnp.minimum(gmin, gout)
        return (st.now < t_target) & (g < t_target)

    def window_body(carry):
        st, _, _, _ = carry
        with phase("close"):
            if st.fr is not None:
                st, fr_snap = _fr_snapshot(st)
            if st.sentinel is not None:
                # Conservation ledger at window open, before the exchange
                # (which thins acks and drops data mid-identity).
                sn_snap = _sentinel_counters(st)
        if persistent:
            # K_WINDOW: the whole window -- exchange, scan, bounds,
            # netem advance, and the micro-step while loop -- as ONE
            # Pallas region (megakernel.window_fused), so the window
            # costs O(1) kernel launches.  The window-close
            # instrumentation blocks are only touched here, outside the
            # fused region: scope/sentinel/dg are stripped around the
            # call (the kernel never reads them) and their hooks run on
            # the ws/we scalars the kernel emits; fr/tr ride through
            # because the exchange writes them inside (integer
            # scatter-adds, fusion-context stable).  The scope ctx is
            # recomputed from the post-advance overlay -- netem factors
            # are all-integer, so the recompute is bitwise.
            scope_b, sent_b, dg_b = st.scope, st.sentinel, st.dg
            core = st.replace(scope=None, sentinel=None, dg=None)
            core, t_h, gmin, ws, we = mk.window_fused(
                core, params, app, t_target)
            with phase("close"):
                st = core.replace(scope=scope_b, sentinel=sent_b, dg=dg_b)
                if st.fr is not None:
                    st = _fr_record(st, fr_snap, ws, we)
                if st.scope is not None:
                    st = _scope_sample(st, _window_ctx(st, params), we)
                if st.sentinel is not None:
                    st = _sentinel_check(st, sn_snap, ws, we)
                if st.dg is not None:
                    st = _digest_record(st, we)
            return st, t_h, gmin, outbox_pending(st)
        # Boundary exchange first: everything in flight becomes visible
        # in the destination slabs before the window's scan.
        with phase("exchange"):
            st = _exchange(st, params, fused=fused and not mesh)
        t_h, gmin = scan(st)
        with phase("bounds"):
            ws = jnp.maximum(st.now, gmin)
            we = jnp.minimum(ws + params.min_latency_ns, t_target)
            if st.nm is not None:
                # Apply every fault event inside this window before any
                # of its ticks: an event takes effect at the start of the
                # conservative window containing its timestamp (install()
                # already shrank the lookahead for sub-1.0 latency
                # scales).
                st = st.replace(nm=netem_apply.advance(st.nm, we))

            # Hoist the window-invariant micro-step inputs here: the
            # inner while body closes over them, so XLA computes them
            # once per window instead of once per micro-step.
            ctx = _window_ctx(st, params)

        def icond(icarry):
            _s, _th, g = icarry
            return g < we

        def ibody(icarry):
            s, th, _ = icarry
            if fused:
                # The fused transport kernel already emits the post-step
                # per-host scan (bitwise _scan_all), so the re-scan
                # collapses to the cross-shard reduction.
                s, th2, g2 = mk.microstep_fused(s, params, app, th, we,
                                                ctx=ctx)
                if mesh:
                    g2 = mesh_min(g2)
            else:
                s = _microstep_core(s, params, app, th, we, ctx=ctx)
                th2, g2 = scan(s)
            return s, th2, g2

        st, t_h, gmin = jax.lax.while_loop(icond, ibody, (st, t_h, gmin))
        with phase("close"):
            st = st.replace(now=we, n_windows=st.n_windows + 1)
            if st.fr is not None:
                st = _fr_record(st, fr_snap, ws, we)
            if st.scope is not None:
                # Sample at window close: the cadence check and cursors
                # are replicated, so every shard takes the same branch.
                st = _scope_sample(st, ctx, we)
            if st.sentinel is not None:
                st = _sentinel_check(st, sn_snap, ws, we)
            if st.dg is not None:
                # Digest at window close: the cadence predicate is a
                # function of the replicated window counter, so every
                # shard takes the same branch around the gather inside.
                st = _digest_record(st, we)
        return st, t_h, gmin, outbox_pending(st)

    t_h0, gmin0 = scan(state)
    state, _, _, _ = jax.lax.while_loop(
        window_cond, window_body,
        (state, t_h0, gmin0, outbox_pending(state)))
    with phase("close"):
        if state.nm is not None:
            # Catch up through idle spans the window loop skipped, so the
            # cursor (and every counter derived from it) is canonical at
            # t_target regardless of how the run was chunked.
            state = state.replace(nm=netem_apply.advance(state.nm,
                                                         t_target))
        return state.replace(now=t_target)


# One device launch covers this much simulated time: long enough to
# amortize the per-call dispatch and host sync (the compiled executable
# is reused -- t_target is traced), short enough that host-side drains,
# checkpoints and progress lines keep a bounded cadence.
CHUNK_NS = 2 * simtime.SIMTIME_ONE_SECOND


def run_chunked(state: SimState, params, app, t_target: int,
                chunk_ns: int = CHUNK_NS):
    """Host-side loop of bounded `run_until` launches up to t_target.

    When a profiler is active (trace.install), each launch is recorded
    as a `device_step` span; in sync mode the launch is blocked on so
    the span measures device execution rather than async dispatch."""
    from .. import trace

    t = int(state.now)
    t_target = int(t_target)
    prof = trace.current()
    while t < t_target:
        t = min(t + chunk_ns, t_target)
        with prof.span("device_step", t_ns=t):
            state = run_until(state, params, app, t)
            if prof.sync:
                jax.block_until_ready(state)
    return state
