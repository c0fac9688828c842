"""Plain reference for onion circuits: what every pass of a world of
client -> relays -> server TCP chains must show, circuit by circuit.

Circuit c holds hosts c * (hops + 2) + k: k = 0 is the client, which
writes `bytes_per_circuit` bytes and closes; k = 1 .. hops are relays,
each forwarding every byte it receives to the next host; the last is the
server.  TCP delivers a stream exactly once and in order, however many
segments the relays' full queues drop (those drops are counted, and
retransmission repairs them).  So once a pass has run to its span:

* clock: the simulated clock stands at each launch's target;
* short: each server received exactly the circuit's bytes;
* relay_off: each relay forwarded exactly the circuit's bytes;
* recv_off: each relay's and server's sockets took in exactly the
  circuit's bytes, and each client's took in none (cells flow one way);
* undone: each server marked its circuit complete within the pass;
* too_fast: no circuit completed sooner than its bytes can cross hops + 1
  links of the configured latency at the configured bandwidth after its
  client started (the client start times are the benchmark's inputs);
* slowest_ms: the longest time, in milliseconds, from a client's start
  to its server's completion.  Every circuit has hosts of its own and the
  same bytes to carry, so each takes what one circuit alone takes; a
  slower transport (a smaller window, a longer recovery, a late
  retransmission) or slower links move it.  Its limit lies between the
  sound runs' largest reading and the smallest of a transport slowed on
  purpose (PERF.md gives both);
* inet_drops: reliable links dropped nothing;
* bad_err: no error bit is raised but the counted-drop bit the
  configuration allows.

This module imports nothing of the program: it reads numpy arrays.
"""

from __future__ import annotations

import numpy as np

FIELDS = {
    "now": "now",
    "err": "err",
    "forwarded": "app.forwarded",
    "done_t": "app.done_t",
    "bytes_recv": "socks.bytes_recv",
    "drop_inet": "hosts.pkts_dropped_inet",
}

LIMITS = {"clock": 0, "short": 0, "relay_off": 0, "recv_off": 0,
          "undone": 0, "too_fast": 0, "slowest_ms": 650.0, "inet_drops": 0,
          "bad_err": 0}

UNSET = (1 << 63) - 1   # a time not set yet (the largest int64)


def check(kw, inputs, launches, states, allowed_err):
    """({name: value}, attempted, failed) over the launches' clocks and
    every circuit of every checked pass."""
    hops = int(kw.get("hops", 3))
    per = hops + 2
    total = int(kw["bytes_per_circuit"])
    start = np.asarray(inputs["client_start_ns"], np.int64)
    floor_ns = (hops + 1) * int(kw["latency_ns"]) + total * 10**9 // int(
        kw["bw_Bps"])
    out = dict.fromkeys(LIMITS, 0)
    out["clock"] = sum(int(now != target) for target, now in launches)
    attempted, failed = len(launches), out["clock"]
    for s in states:
        n = len(s["forwarded"]) // per
        fwd = s["forwarded"].astype(np.int64).reshape(n, per)
        recv = s["bytes_recv"].astype(np.int64).sum(axis=1).reshape(n, per)
        done = s["done_t"].astype(np.int64).reshape(n, per)[:, -1]
        short = np.abs(fwd[:, -1] - total)
        relay = np.abs(fwd[:, 1:-1] - total).sum(axis=1)
        rcv = np.abs(recv[:, 1:] - total).sum(axis=1) + np.abs(recv[:, 0])
        undone = (done == UNSET) | (done > int(s["now"]))
        fast = ~undone & (done - start < floor_ns)
        out["short"] += int(short.sum())
        out["relay_off"] += int(relay.sum())
        out["recv_off"] += int(rcv.sum())
        out["undone"] += int(undone.sum())
        out["too_fast"] += int(fast.sum())
        took = np.where(undone, UNSET, done - start)
        out["slowest_ms"] = max(out["slowest_ms"],
                                float(took.max()) / 1e6 if n else 0.0)
        if float(kw.get("reliability", 1.0)) == 1.0:
            out["inet_drops"] += int(s["drop_inet"].sum())
        out["bad_err"] |= int(s["err"]) & ~int(allowed_err)
        attempted += n
        failed += int(((short + relay + rcv) != 0).sum()
                      + (undone | fast).sum())
    return out, attempted, failed
