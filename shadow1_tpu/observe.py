"""Observability: per-host heartbeats + run summary (tracker analog).

The reference Tracker logs per-host heartbeat CSV lines (bytes in/out,
allocation, socket occupancy) at a configurable interval through the
shadow logger (/root/reference/src/main/host/tracker.c:419-607), consumed
by src/tools/parse-shadow.py.  Here the per-host counters already live in
dense device arrays (HostTable), so a heartbeat is one device_get of the
counter block per interval, diffed host-side and appended to
`heartbeat.csv` in the data directory; `tools/parse.py` aggregates them.

The run summary includes an object census (live sockets and packet-pool
occupancy by lifecycle stage) -- the analog of the reference's
ObjectCounter leak check printed at slave teardown (slave.c:480-498).

Heartbeats are host-side samples of whatever counters happen to be on
the device when the chunk boundary lands; for *sim-time-accurate*
per-flow and per-link series use the device-resident flowscope instead
(`--scope`, trace.ensure_flowscope/ScopeDrain, docs/observability.md),
which samples inside the jitted window loop at an exact sim-time
cadence.  LogDrain's sharded segment-merge protocol below is the
pattern ScopeDrain follows for its rings.
"""

from __future__ import annotations

import json
import os

import numpy as np

from . import trace
from .core import simtime
from .core.state import (SOCK_FREE, SOCK_TCP, SOCK_UDP, STAGE_FREE,
                         STAGE_IN_FLIGHT, STAGE_RX_QUEUED, STAGE_TX_QUEUED)

SEC = simtime.SIMTIME_ONE_SECOND

_FIELDS = ("bytes_sent", "bytes_recv", "pkts_sent", "pkts_recv",
           "pkts_dropped_inet", "pkts_dropped_router")


_pack_heartbeat_jit = None


def _pack_heartbeat(hosts):
    # Jitted once at first use (a fresh jax.jit wrapper per call would
    # retrace and recompile every heartbeat).
    global _pack_heartbeat_jit
    if _pack_heartbeat_jit is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def pack(hosts):
            rows = [getattr(hosts, f).astype(jnp.int64) for f in _FIELDS]
            rows.append(hosts.tx_queued.astype(jnp.int64))
            rows.append(hosts.rx_queued.astype(jnp.int64))
            return jnp.stack(rows)

        _pack_heartbeat_jit = pack
    return _pack_heartbeat_jit(hosts)


class Tracker:
    """Appends per-host heartbeat rows; one instance per run.

    Ensemble runs share one heartbeat.csv across W per-world trackers:
    `world` prefixes every row with a world column (and the header with
    `world,`), `write_header=False` keeps trackers 1..W-1 from
    truncating what world 0 wrote -- the drain-layer world-column
    convention (docs/ensemble.md)."""

    HEADER = ("time_s,host,bytes_sent_per_s,bytes_recv_per_s,"
              "pkts_sent,pkts_recv,drops_inet,drops_router,"
              "tx_queued,rx_queued\n")

    def __init__(self, data_dir: str, hostnames, interval_s: int = 1,
                 per_host_interval_s=None, world: int | None = None,
                 write_header: bool = True):
        self.dir = data_dir
        self.world = world
        self.hostnames = list(hostnames)
        self.interval_ns = interval_s * SEC
        h = len(self.hostnames)
        # Per-host heartbeat frequency (reference <host
        # heartbeatfrequency>); 0 = the global default interval.
        per = np.zeros(h, np.int64) if per_host_interval_s is None \
            else np.asarray(per_host_interval_s, np.int64)
        self.per_host_ns = np.where(per > 0, per * SEC, self.interval_ns)
        # The cadence the RUN LOOP must sample at: the finest interval any
        # host configured (else a host asking for finer-than-global rows
        # silently got the coarser global cadence; ADVICE r3).
        self.sample_interval_ns = int(min(self.interval_ns,
                                          self.per_host_ns.min())) \
            if h else self.interval_ns
        self._next_row = np.zeros(h, np.int64)
        self._last_row_t = np.zeros(h, np.int64)
        os.makedirs(data_dir, exist_ok=True)
        self.path = os.path.join(data_dir, "heartbeat.csv")
        if write_header:
            with open(self.path, "w") as f:
                f.write(self.HEADER if world is None
                        else "world," + self.HEADER)
        self._last = {f: np.zeros(h, np.int64) for f in _FIELDS}
        self._last_t = 0  # _last rows advance per written heartbeat row

    def heartbeat(self, state, now_ns: int):
        with trace.current().span("heartbeat", t_ns=int(now_ns)):
            self._heartbeat(state, now_ns)

    def _heartbeat(self, state, now_ns: int):
        # ONE device buffer, ONE transfer: per-buffer fetches each cost
        # their own device-to-host sync, and heartbeats fire once per
        # simulated second.
        packed = np.asarray(_pack_heartbeat(state.hosts))
        trace.current().transfer(packed.nbytes, count=1)
        n = len(_FIELDS)
        cur = {f: packed[i] for i, f in enumerate(_FIELDS)}
        txq, rxq = packed[n], packed[n + 1]
        with open(self.path, "a") as f:
            for i, name in enumerate(self.hostnames):
                if now_ns < self._next_row[i]:
                    continue
                self._next_row[i] = now_ns + self.per_host_ns[i]
                # Rates divide by the PER-HOST elapsed time (a host on a
                # 5s cadence accumulates 5s of deltas per row).
                dt_s = max((now_ns - self._last_row_t[i]) / SEC, 1e-9)
                self._last_row_t[i] = now_ns
                d = {k: int(cur[k][i] - self._last[k][i]) for k in _FIELDS}
                if self.world is not None:
                    f.write(f"{self.world},")
                f.write(f"{now_ns / SEC:.3f},{name},"
                        f"{d['bytes_sent'] / dt_s:.1f},"
                        f"{d['bytes_recv'] / dt_s:.1f},"
                        f"{d['pkts_sent']},{d['pkts_recv']},"
                        f"{d['pkts_dropped_inet']},{d['pkts_dropped_router']},"
                        f"{int(txq[i])},{int(rxq[i])}\n")
                # Baseline advances ONLY for written rows, so skipped
                # hosts' deltas accumulate into their next row instead of
                # vanishing.
                for k in _FIELDS:
                    self._last[k][i] = cur[k][i]
        self._last_t = now_ns

    def summary(self, summary: dict, state):
        summary = dict(summary)
        summary["object_census"] = census(state)
        with open(os.path.join(self.dir, "summary.json"), "w") as f:
            json.dump(summary, f, indent=2)


def write_pcap(path: str, cap, ip_of_host=None, host_filter=None):
    """Write a CaptureRing to a classic pcap file (LINKTYPE_RAW IPv4).

    The ring stores packet *metadata*; each record is synthesized as an
    IPv4 + TCP/UDP header whose total-length field reflects the real
    payload size (a truncated capture: incl_len = header bytes,
    orig_len = header + payload) -- the same information the reference's
    per-interface capture exposes (utility/pcap_writer.c).

    ip_of_host: optional callable host_index -> 32-bit IP (e.g. from the
    DNS registry); defaults to 10.x.y.z derived from the index.
    host_filter: optional host index -- that host's per-interface view:
    its SENT records plus its RECEIVE-direction records (deliveries and
    router drops), like the reference's per-host logpcap capture which
    records both directions (network_interface.c:337-373,415-418).
    Without a filter, only send-direction records are kept so the global
    wire view lists each packet once.
    """
    import struct as pystruct

    from .core.state import CAP_SEND

    if ip_of_host is None:
        def ip_of_host(i):
            return (10 << 24) | (int(i) & 0xFFFFFF)

    t = np.asarray(cap.time)
    # A sharded ring (make_capture_ring shards=N, mesh runs) has a [N]
    # cursor vector and per-shard segments; a single-device ring is the
    # N=1 degenerate case with a scalar cursor.
    tot_a = np.atleast_1d(np.asarray(cap.total))
    shards = tot_a.shape[0]
    c = t.shape[0]
    per = c // shards
    segs = []
    for s in range(shards):
        total = int(tot_a[s])
        n = min(total, per)
        # Oldest-first order within the segment; wraps at `total % per`.
        start = total % per if total > per else 0
        segs.append(s * per + (np.arange(n) + start) % per)
    order = np.concatenate(segs)
    if shards > 1:
        # Merge shard segments into global time order (stable, so the
        # shard-major walk breaks ties deterministically).
        order = order[np.argsort(t[order], kind="stable")]

    src = np.asarray(cap.src)
    dst = np.asarray(cap.dst)
    kind = np.asarray(cap.kind)
    if host_filter is not None:
        keep = ((src[order] == host_filter) & (kind[order] == CAP_SEND)) | \
            ((dst[order] == host_filter) & (kind[order] != CAP_SEND))
        order = order[keep]
    else:
        order = order[kind[order] == CAP_SEND]
    sport = np.asarray(cap.sport)
    dport = np.asarray(cap.dport)
    proto = np.asarray(cap.proto)
    flags = np.asarray(cap.flags)
    length = np.asarray(cap.length)
    seq = np.asarray(cap.seq)
    ack = np.asarray(cap.ack)

    with open(path, "wb") as f:
        # pcap global header: magic, v2.4, tz 0, sigfigs 0, snaplen,
        # linktype 101 (LINKTYPE_RAW: raw IPv4/IPv6).
        f.write(pystruct.pack("<IHHiIII", 0xA1B2C3D4, 2, 4, 0, 0, 65535, 101))
        for k in order:
            is_tcp = int(proto[k]) == 6
            l4 = (pystruct.pack(">HHIIBBHHH", int(sport[k]) & 0xFFFF,
                                int(dport[k]) & 0xFFFF, int(seq[k]),
                                int(ack[k]), 5 << 4, int(flags[k]) & 0x3F,
                                65535, 0, 0)
                  if is_tcp else
                  pystruct.pack(">HHHH", int(sport[k]) & 0xFFFF,
                                int(dport[k]) & 0xFFFF,
                                8 + int(length[k]), 0))
            tot_len = 20 + len(l4) + int(length[k])
            ip = pystruct.pack(">BBHHHBBHII", 0x45, 0, tot_len & 0xFFFF, 0,
                               0, 64, int(proto[k]) & 0xFF, 0,
                               ip_of_host(int(src[k])),
                               ip_of_host(int(dst[k])))
            rec = ip + l4
            ts_ns = int(t[k])
            f.write(pystruct.pack("<IIII", ts_ns // 1_000_000_000,
                                  (ts_ns % 1_000_000_000) // 1000,
                                  len(rec), tot_len))
            f.write(rec)
    return len(order)


_LOG_MSG = {
    1: "packet to host {arg} dropped on the wire (reliability)",
    2: "router dropped packet from host {arg} (CoDel)",
    3: "router tail-dropped packet from host {arg} (interface buffer)",
    4: "packet-pool capacity drop ({arg})",
    5: "delivered packet from host {arg}",
    6: "sent packet to host {arg}",
    7: "thinned {arg} pure ACKs at exchange overflow",
    8: "netem: inbound packet from host {arg} killed (host down)",
}


class LogDrain:
    """Drains the device LogRing into sim-time-ordered text lines:

        [  1.234567890] [hostname] message

    The two-tier ShadowLogger analog (core/logger/shadow_logger.c:25-58):
    the device ring buffers records, the host merges and writes them
    between chunks.  Overflow (more records than ring capacity between
    drains) is reported, not silently lost.

    Sharded rings (make_log_ring shards=N, mesh runs) drain per shard
    segment and merge into global sim-time order; record host ids are
    global on every layout, so the hostname mapping is unchanged.

    `world` prefixes every line with a `[w<k>]` tag; `path` may be an
    already-open shared file (ensemble runs interleave W worlds' lines
    into one shadow.log; trace._open_sink ownership rules)."""

    def __init__(self, path, hostnames, world: int | None = None):
        self.path = path
        self.hostnames = list(hostnames)
        self.world = world
        self._last_total = 0
        self._last_tot = None   # [shards] per-segment cursors, lazy
        self._lost_reported = 0
        self._f, self._own = trace._open_sink(path)

    def drain(self, state):
        with trace.current().span("log_drain"):
            return self._drain(state)

    def _drain(self, state):
        import jax
        lg = state.log
        if lg is None:
            return 0
        tot_a, lost_a = jax.device_get((lg.total, lg.lost))
        tot_a = np.atleast_1d(np.asarray(tot_a, np.int64))
        lost_a = np.atleast_1d(np.asarray(lost_a, np.int64))
        shards = tot_a.shape[0]
        trace.current().transfer(16, count=1)
        lost = int(lost_a.sum())
        if lost > self._lost_reported:
            self._f.write(f"[log] WARNING: {lost - self._lost_reported} "
                          f"records lost inside oversized appends\n")
            self._lost_reported = lost
        if self._last_tot is None:
            self._last_tot = np.zeros(shards, np.int64)
        total = int(tot_a.sum())
        if total == self._last_total:
            return 0
        t, host, code, arg = jax.device_get(
            (lg.time, lg.host, lg.code, lg.arg))
        trace.current().transfer(
            t.nbytes + host.nbytes + code.nbytes + arg.nbytes, count=1)
        per = t.shape[0] // shards
        new = total - self._last_total
        wrap_lost = 0
        parts = []
        for s in range(shards):
            total_s = int(tot_a[s])
            ns = total_s - int(self._last_tot[s])
            if ns <= 0:
                continue
            if ns > per:
                wrap_lost += ns - per
                start = total_s - per
            else:
                start = int(self._last_tot[s])
            parts.append(s * per + (np.arange(start, total_s) % per))
            self._last_tot[s] = total_s
        if wrap_lost:
            self._f.write(f"[log] WARNING: {wrap_lost} records lost "
                          f"(ring capacity {per})\n")
        idx = np.concatenate(parts)
        order = np.argsort(t[idx], kind="stable")
        wtag = "" if self.world is None else f"[w{self.world}] "
        for k in idx[order]:
            name = self.hostnames[host[k]] if host[k] < len(self.hostnames) \
                else str(host[k])
            msg = _LOG_MSG.get(int(code[k]), f"event {code[k]}")
            self._f.write(f"[{t[k] / SEC:13.9f}] {wtag}[{name}] "
                          + msg.format(arg=int(arg[k])) + "\n")
        self._f.flush()
        self._last_total = total
        return new

    def close(self):
        if self._own:
            self._f.close()


def census(state) -> dict:
    """Live-object census from the dense tables (ObjectCounter analog).

    Packets live in the source-side outbox (state.pool) until the window
    exchange, then in the destination-side inbox; both are counted."""
    stage = np.asarray(state.pool.stage)
    istage = np.asarray(state.inbox.stage)
    stype = np.asarray(state.socks.stype)
    return {
        "packets_free": int((stage == STAGE_FREE).sum())
        + int((istage == STAGE_FREE).sum()),
        "packets_tx_queued": int((stage == STAGE_TX_QUEUED).sum()),
        "packets_in_flight": int((stage == STAGE_IN_FLIGHT).sum())
        + int((istage == STAGE_IN_FLIGHT).sum()),
        "packets_rx_queued": int((istage == STAGE_RX_QUEUED).sum()),
        "sockets_free": int((stype == SOCK_FREE).sum()),
        "sockets_udp": int((stype == SOCK_UDP).sum()),
        "sockets_tcp": int((stype == SOCK_TCP).sum()),
    }


def _si(v: float) -> str:
    """Compact SI-ish rate formatting: 1234567 -> '1.23M'."""
    for div, suffix in ((1e9, "G"), (1e6, "M"), (1e3, "k")):
        if v >= div:
            return f"{v / div:.2f}{suffix}"
    return f"{v:.0f}"


class Progress:
    """One-line live status for long runs (the CLI's --progress): sim
    time covered, event rate, window rate, and a wall-clock ETA, written
    to stderr at most once per `min_interval_s` of wall time.

    Each report costs one small device_get (n_events + n_windows, both
    replicated scalars under a mesh) and a `progress` profiler span, at
    chunk cadence -- cheap enough to leave on for multi-hour runs, which
    is the point (the reference prints its own heartbeat lines through
    the logger; our heartbeats go to CSV, so silence needed a channel).
    """

    def __init__(self, stop_ns: int, out=None, min_interval_s: float = 2.0,
                 start_ns: int = 0):
        import sys
        import time as _time
        self.stop_ns = int(stop_ns)
        # start_ns anchors the percentage/ETA for spans that begin
        # mid-run (a checkpoint replay): progress covers
        # [start_ns, stop_ns], not [0, stop_ns].
        self.start_ns = int(start_ns)
        self.out = out if out is not None else sys.stderr
        self.min_interval = min_interval_s
        self._clock = _time.perf_counter
        self._wall_last = self._clock()
        self._ev_last = 0
        self._win_last = 0
        self._t_last = self.start_ns

    def update(self, state, t_ns: int, force: bool = False):
        now = self._clock()
        dt = now - self._wall_last
        if not force and dt < self.min_interval:
            return
        import jax
        with trace.current().span("progress"):
            ev, wins = (int(v) for v in jax.device_get(
                (state.n_events, state.n_windows)))
            trace.current().transfer(16, count=1)
        dt = max(dt, 1e-9)
        ev_s = (ev - self._ev_last) / dt
        win_s = (wins - self._win_last) / dt
        sim_per_wall = ((int(t_ns) - self._t_last) / SEC) / dt
        remain_s = max(self.stop_ns - int(t_ns), 0) / SEC
        if sim_per_wall > 0 and remain_s / sim_per_wall < 360000:
            e = int(remain_s / sim_per_wall)
            eta = f"{e // 3600}:{(e // 60) % 60:02d}:{e % 60:02d}"
        else:
            eta = "-:--:--"
        pct = 100.0 * (int(t_ns) - self.start_ns) \
            / max(self.stop_ns - self.start_ns, 1)
        self.out.write(
            f"[progress] sim {int(t_ns) / SEC:.1f}s/"
            f"{self.stop_ns / SEC:.1f}s ({pct:.0f}%) | "
            f"{_si(ev_s)} ev/s | {wins} windows ({win_s:.1f}/s) | "
            f"ETA {eta}\n")
        self.out.flush()
        self._wall_last = now
        self._ev_last = ev
        self._win_last = wins
        self._t_last = int(t_ns)
