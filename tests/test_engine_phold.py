"""End-to-end engine tests with the phold workload (UDP path).

Mirrors the reference's determinism suite strategy
(/root/reference/src/test/determinism/): the simulation trajectory must be
bitwise identical however the execution is chopped up.  Here the analog of
"same result with different worker counts" is "same result with different
window batchings and pool capacities".
"""

import jax.numpy as jnp
import pytest

from shadow1_tpu import sim
from shadow1_tpu.core import simtime

MS = simtime.SIMTIME_ONE_MILLISECOND


def _counters(state):
    a = state.app
    return (int(a.sent.sum()), int(a.recv.sum()), int(a.pending.sum()),
            int(state.hosts.pkts_dropped_inet.sum()), int(state.err))


def test_phold_runs_and_conserves_messages():
    state, params, app = sim.build_phold(
        num_hosts=8, latency_ns=10 * MS, stop_time=500 * MS, seed=3)
    out = sim.run(state, params, app)
    sent, recv, pending, dropped, err = _counters(out)
    assert err == 0
    assert sent > 0 and recv > 0
    # Messages are conserved: every message is pending, in flight, or was
    # dropped by the (perfect-reliability) network -- here never dropped.
    inflight = int((out.pool.stage != 0).sum()) + \
        int((out.inbox.stage != 0).sum())
    assert dropped == 0
    assert pending + inflight + int(out.socks.udp_count.sum()) == 8
    assert sent == recv + inflight + int(out.socks.udp_count.sum())
    assert int(out.now) == 500 * MS


def test_run_until_one_compile_for_both_stop_forms():
    """sim.run with until=None (params.stop_time, an i64 array) and with
    an explicit int must key the same run_until compile."""
    from shadow1_tpu.core import engine
    state, params, app = sim.build_phold(
        num_hosts=8, latency_ns=10 * MS, stop_time=500 * MS, seed=3)
    before = engine.run_until._cache_size()
    a = sim.run(state, params, app)
    b = sim.run(state, params, app, until=500 * MS)
    assert engine.run_until._cache_size() - before <= 1
    assert _counters(a) == _counters(b)


@pytest.mark.tier0
def test_phold_deterministic_across_window_batching():
    state, params, app = sim.build_phold(
        num_hosts=8, latency_ns=10 * MS, stop_time=400 * MS, seed=7)
    one_shot = sim.run(state, params, app, until=400 * MS)
    stepped = state
    for t in (100 * MS, 200 * MS, 300 * MS, 400 * MS):
        stepped = sim.run(stepped, params, app, until=t)
    assert _counters(one_shot) == _counters(stepped)
    assert jnp.array_equal(one_shot.app.next_send, stepped.app.next_send)
    assert jnp.array_equal(one_shot.hosts.send_ctr, stepped.hosts.send_ctr)


def test_phold_deterministic_across_pool_capacity():
    k1 = sim.build_phold(num_hosts=6, latency_ns=5 * MS,
                         stop_time=200 * MS, seed=11, pool_capacity=256)
    k2 = sim.build_phold(num_hosts=6, latency_ns=5 * MS,
                         stop_time=200 * MS, seed=11, pool_capacity=4096)
    o1 = sim.run(*k1)
    o2 = sim.run(*k2)
    assert _counters(o1)[:4] == _counters(o2)[:4]
    assert jnp.array_equal(o1.app.sent, o2.app.sent)
    assert jnp.array_equal(o1.app.recv, o2.app.recv)


def test_phold_lossy_network_drops():
    state, params, app = sim.build_phold(
        num_hosts=8, latency_ns=10 * MS, reliability=0.5,
        stop_time=500 * MS, seed=5)
    out = sim.run(state, params, app)
    sent, recv, pending, dropped, err = _counters(out)
    assert err == 0
    assert dropped > 0
    # Conservation including drops: every sent message was received, is in
    # flight, queued, or dropped. (Dropped messages leave the population.)
    inflight = int((out.pool.stage != 0).sum()) + \
        int((out.inbox.stage != 0).sum())
    assert sent == recv + inflight + int(out.socks.udp_count.sum()) + dropped
