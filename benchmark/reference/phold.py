"""Plain reference for PHOLD (Fujimoto 1990): what every correct run of a
closed PHOLD population must show, checked host by host, and how much
work it does, from a plain simulation of the same semantics.

PHOLD's semantics, as the program's PHOLD application states them: every
host starts with `msgs_per_host` messages and a first send time drawn
from an exponential of mean `mean_delay_ns`.  A host that holds messages
sends one at its send time, to a host drawn uniformly from the others,
and, if it still holds one, draws its next send time that far again.  A
message arrives `latency_ns` after it is sent and becomes one more
message held; the host's next send time is the earlier of the one it had
and the arrival time plus a fresh draw.  Links of reliability 1.0 lose
nothing; a full receive queue drops a message and counts the drop.  So at
the end of any launch, with nothing but the answers the program reports:

* clock: the simulated clock stands at the launch's target;
* ledger: host h has sent + held == msgs_per_host + received;
* lost: every message of the population is held, in flight, queued at a
  socket, or counted as dropped;
* late: nothing is left scheduled before the clock (a held message's next
  send, a packet's delivery time);
* inet_drops: reliable links dropped nothing;
* bad_err: no error bit is raised but the counted-drop bit the
  configuration allows;
* self_sends: no message in flight is addressed to its sender;
* dest_skew: the messages in flight spread evenly over the distances
  (destination - source) mod hosts: the largest |z| of their counts in
  64 equal bins of distance against the uniform draw's expectation;
* rate_off: the messages received by the clock, against the number a
  plain simulation of the semantics above receives by the same time with
  its own draws, in percent (|program / plain - 1|).  A delay drawn at
  another mean, a latency skipped or shortened, or a population run short
  moves it.

This module imports nothing of the program: it reads numpy arrays.
"""

from __future__ import annotations

import numpy as np

# Answers read from the program's state after the window ("()" calls a
# view of the state).
FIELDS = {
    "now": "now",
    "err": "err",
    "sent": "app.sent",
    "recv": "app.recv",
    "pending": "app.pending",
    "next_send": "app.next_send",
    "pool_stage": "pool.stage",
    "pool_time": "pool.time",
    "pool_src": "pool.src",
    "pool_dst": "pool.dst",
    "inbox_stage": "inbox.stage",
    "inbox_time": "inbox.times()",
    "inbox_blk": "inbox.blk",
    "queued": "socks.udp_count",
    "drop_pool": "hosts.pkts_dropped_pool",
    "drop_inet": "hosts.pkts_dropped_inet",
}

# The inbox holds each host's arrivals in a slab of its own rows; the
# sender is column 0 of a row.
INBOX_SRC_COL = 0
DEST_BINS = 64

# The exact counts have the limit 0; dest_skew and rate_off have limits
# set between the sound runs' largest reading and the smallest reading of
# the faults that move them (PERF.md gives both).
LIMITS = {"clock": 0, "ledger": 0, "lost": 0, "late": 0, "inet_drops": 0,
          "bad_err": 0, "self_sends": 0, "dest_skew": 10.0, "rate_off": 5.0}


def plain_received(num_hosts, msgs, latency_ns, mean_delay_ns, horizon_ns,
                   seed):
    """Messages received by `horizon_ns` in a plain simulation of PHOLD's
    semantics, with draws of its own from `seed`.

    It runs in windows one latency long: every message sent in a window
    arrives in the next, so within a window each host's events depend on
    nothing but its own arrivals, and all hosts take their next event
    together, one event a host per round."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 7])
    n, lat = int(num_hosts), float(latency_ns)
    hosts = np.arange(n)
    held = np.full(n, int(msgs), np.int64)
    nxt = np.maximum(rng.exponential(mean_delay_ns, n), 1.0)
    arr_t = np.empty(0)                 # this window's arrivals, by host
    arr_d = np.empty(0, np.int64)
    received = 0
    w = 0.0
    while w < horizon_ns:
        w_end = min(w + lat, float(horizon_ns))
        order = np.lexsort((arr_t, arr_d))
        arr_t, arr_d = arr_t[order], arr_d[order]
        ptr = np.searchsorted(arr_d, hosts, "left")
        end = np.searchsorted(arr_d, hosts, "right")
        out_t, out_d = [], []
        live = hosts
        while live.size:
            p, e = ptr[live], end[live]
            has = p < e
            t_arr = np.where(has, arr_t[np.minimum(p, len(arr_t) - 1)]
                             if len(arr_t) else np.inf, np.inf)
            t_snd = np.where(held[live] > 0, nxt[live], np.inf)
            do_arr = (t_arr <= t_snd) & (t_arr < w_end)
            do_snd = ~do_arr & (t_snd < w_end)
            a = live[do_arr]
            if a.size:
                fresh = t_arr[do_arr] + np.maximum(
                    rng.exponential(mean_delay_ns, a.size), 1.0)
                nxt[a] = np.where(held[a] > 0, np.minimum(nxt[a], fresh),
                                  fresh)
                held[a] += 1
                ptr[a] += 1
                received += int(a.size)
            s = live[do_snd]
            if s.size:
                t = nxt[s]
                off = 1 + np.minimum(
                    (rng.random(s.size) * (n - 1)).astype(np.int64), n - 2)
                out_t.append(t + lat)
                out_d.append((s + off) % n)
                held[s] -= 1
                nxt[s] = t + np.maximum(
                    rng.exponential(mean_delay_ns, s.size), 1.0)
            live = live[do_arr | do_snd]
        # Arrivals due after the horizon are never taken in.
        arr_t = np.concatenate(out_t) if out_t else np.empty(0)
        arr_d = np.concatenate(out_d) if out_d else np.empty(0, np.int64)
        w = w_end
    return received


def dest_skew(src, dst, num_hosts, bins=DEST_BINS):
    """Largest |z| over `bins` equal bins of (dst - src) mod num_hosts of
    the counts against a uniform draw over the other hosts."""
    n = int(num_hosts)
    dist = (dst.astype(np.int64) - src.astype(np.int64)) % n
    dist = dist[dist != 0]
    if len(dist) == 0:     # nothing in flight to another host
        return float("inf")
    got = np.bincount(dist * bins // n, minlength=bins)
    width = np.bincount(np.arange(1, n) * bins // n, minlength=bins)
    want = len(dist) * width / (n - 1)
    return float(np.max(np.abs(got - want) / np.sqrt(want)))


def check(kw, inputs, launches, states, allowed_err):
    """({name: value}, attempted, failed) over the launches' clocks and
    the checked states' hosts."""
    del inputs  # the program draws PHOLD's inputs from the seed itself
    msgs = int(kw["msgs_per_host"])
    out = dict.fromkeys(LIMITS, 0)
    out["clock"] = sum(int(now != target) for target, now in launches)
    attempted, failed = len(launches), out["clock"]
    for s in states:
        now = int(s["now"])
        n = len(s["sent"])
        sent, recv = s["sent"].astype(np.int64), s["recv"].astype(np.int64)
        pending = s["pending"].astype(np.int64)
        ledger = np.abs(sent + pending - msgs - recv)
        late_host = (pending > 0) & (s["next_send"] < now)
        pool_live = s["pool_stage"] != 0
        inbox_live = s["inbox_stage"] != 0
        in_flight = int(pool_live.sum()) + int(inbox_live.sum())
        drops = int(s["drop_pool"].sum()) + int(s["drop_inet"].sum())
        held = int(pending.sum()) + in_flight + int(s["queued"].sum())
        out["ledger"] += int(ledger.sum())
        out["lost"] += abs(msgs * n - held - drops)
        out["late"] += (int(late_host.sum())
                        + int((s["pool_time"][pool_live] < now).sum())
                        + int((s["inbox_time"][inbox_live] < now).sum()))
        if float(kw.get("reliability", 1.0)) == 1.0:
            out["inet_drops"] += int(s["drop_inet"].sum())
        out["bad_err"] |= int(s["err"]) & ~int(allowed_err)
        slab = len(s["inbox_stage"]) // n
        src = np.concatenate([s["pool_src"][pool_live],
                              s["inbox_blk"][inbox_live, INBOX_SRC_COL]])
        dst = np.concatenate([s["pool_dst"][pool_live],
                              np.flatnonzero(inbox_live) // slab])
        out["self_sends"] += int((src == dst).sum())
        out["dest_skew"] = max(out["dest_skew"], dest_skew(src, dst, n))
        plain = plain_received(n, msgs, kw["latency_ns"], kw["mean_delay_ns"],
                               now, kw["seed"])
        out["rate_off"] = max(out["rate_off"], 100.0 * abs(
            int(recv.sum()) - plain) / max(plain, 1))
        attempted += n
        failed += int(((ledger != 0) | late_host).sum())
    return out, attempted, failed
