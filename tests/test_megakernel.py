"""Megakernel bitwise-neutrality and op-count tests.

The fused micro-step path (core/megakernel.py, params.megakernel) is
only admissible because it is VALUE-IDENTICAL to the reference phase
graph: the kernel bodies call the same `_rx_phase` / `_stage_emissions`
/ `_tx_drain_body` / `_exchange_core` implementations on blocked rows,
and every f32 transcendental stays in the main XLA graph where both
paths compile it identically (docs/megakernel.md, "f32 stability").
These tests enforce that at the strongest level available: every leaf
of the final state pytree must be bitwise equal with the megakernel on
and off, across rx_batch modes, both run entry points (one jitted
run_until vs the host-side chunked loop), a lossy bulk-TCP world with
real retransmissions, a netem link-flap world that exercises the fused
exchange's drop path, and an 8-device mesh world (sim.run(devices=8)).

The lowering-level tests pin the flag's graph discipline: megakernel
OFF must lower with no trace of the kernels (the reference oracle is
the pre-megakernel graph, byte-for-byte reproducible), ON must actually
change the graph, and the compiled fused run_until must hold the op
diet the round was measured at (kernel-unit n_ops <= 0.6x reference,
tools/kernelcount.py semantics).

The persistent window kernel (params.persistent, K_WINDOW in
core/megakernel.py) compiles the WHOLE window body -- exchange,
micro-step loop, netem advance, bookkeeping -- into one Pallas region.
It holds the same contract one level up: persistent-on must be bitwise
leaf-for-leaf equal to persistent-off across the same world battery
(including fully-instrumented worlds -- flight recorder, sentinel,
digests, flowscope -- which ride the fused AND persistent paths,
docs/megakernel.md), persistent-off must lower byte-identical to the
per-phase fused graph that existed before the flag, and the launch
metric (tools/kernelcount.py `launches`: top-level op count of the
run_until while-body) must stay collapsed >= 5x.
"""

import importlib.util
import os

import jax
import numpy as np
import pytest

from shadow1_tpu import netem, sim
from shadow1_tpu.core import engine, simtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEC = simtime.SIMTIME_ONE_SECOND
MS = simtime.SIMTIME_ONE_MILLISECOND


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _assert_bitwise(fused, ref, label):
    la, ta = jax.tree_util.tree_flatten_with_path(fused)
    lb, tb = jax.tree_util.tree_flatten(ref)
    assert ta == jax.tree_util.tree_flatten(fused)[1]  # sanity
    assert len(la) == len(lb), f"{label}: leaf count diverged"
    for (path, x), y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (
            f"{label}: leaf {jax.tree_util.keystr(path)} diverged")


def _fused(world):
    """The world with the fused statics set explicitly (the defaults are
    the reference graph): megakernel on, persistent window kernel on.
    Off the TPU the kernels run in Pallas interpret mode."""
    state, params, app = world
    return state, params.replace(megakernel=True, persistent=True), app


def _phold(**kw):
    kw.setdefault("num_hosts", 16)
    kw.setdefault("msgs_per_host", 2)
    kw.setdefault("mean_delay_ns", 10 * MS)
    kw.setdefault("stop_time", 2 * SEC)
    kw.setdefault("pool_capacity", 16 * 8)
    kw.setdefault("seed", 7)
    return _fused(sim.build_phold(**kw))


class TestPholdNeutrality:
    @pytest.mark.tier0
    @pytest.mark.parametrize("rx_batch", [1, 2])
    def test_run_until_bitwise_identical(self, rx_batch):
        state, params, app = _phold(rx_batch=rx_batch)
        fused = engine.run_until(state, params, app, SEC)
        ref = engine.run_until(state, params.replace(megakernel=False),
                               app, SEC)
        assert int(fused.app.recv.sum()) > 0, "no traffic simulated"
        _assert_bitwise(fused, ref, f"phold rx_batch={rx_batch}")

    @pytest.mark.parametrize("chunk_ms", [200, 500])
    def test_chunked_bitwise_identical(self, chunk_ms):
        # Hold the chunking fixed; fused vs reference must then be
        # bitwise on every leaf including window/rng bookkeeping.
        state, params, app = _phold()
        fused = engine.run_chunked(state, params, app, SEC,
                                   chunk_ns=chunk_ms * MS)
        ref = engine.run_chunked(state, params.replace(megakernel=False),
                                 app, SEC, chunk_ns=chunk_ms * MS)
        _assert_bitwise(fused, ref, f"phold chunked {chunk_ms}ms")

    def test_netem_link_flap_bitwise_identical(self):
        # A link flap exercises the fused exchange's overflow/drop path
        # and the netem overlay advancing between windows.
        state, params, app = _phold(msgs_per_host=4)
        tl = netem.timeline()
        tl.link_down(2, 5, at=100 * MS).link_up(2, 5, at=600 * MS)
        tl.link_down(1, 9, at=200 * MS).link_up(1, 9, at=SEC)
        state, params = netem.install(state, params, tl)
        fused = engine.run_until(state, params, app, SEC)
        ref = engine.run_until(state, params.replace(megakernel=False),
                               app, SEC)
        _assert_bitwise(fused, ref, "phold netem link-flap")

    def test_mesh_8dev_bitwise_identical(self):
        # The mesh path keeps the reference exchange (collectives can't
        # live inside a kernel) but runs the fused micro-step per shard;
        # fused-on-mesh must match reference-on-mesh leaf for leaf.
        state, params, app = _phold(stop_time=300 * MS)
        fused = sim.run(state, params, app, until=200 * MS, devices=8)
        ref = sim.run(state, params.replace(megakernel=False), app,
                      until=200 * MS, devices=8)
        assert int(fused.n_steps) > 0
        _assert_bitwise(fused, ref, "phold mesh devices=8")


class TestTcpNeutrality:
    """A lossy bulk-transfer world drives every gated phase body inside
    the kernels: drops arm RTO timers, retransmissions queue segments
    (_tx_drain parks and drains), and arrivals thread the TCP state
    machine through K_DELIVER/K_TRANSPORT."""

    @pytest.mark.parametrize("reliability", [1.0, 0.97])
    def test_bulk_bitwise_identical(self, reliability):
        state, params, app = _fused(sim.build_bulk(
            num_hosts=4, bytes_per_client=30_000,
            reliability=reliability, stop_time=4 * SEC, seed=11))
        fused = engine.run_until(state, params, app, 3 * SEC)
        ref = engine.run_until(state, params.replace(megakernel=False),
                               app, 3 * SEC)
        assert int(fused.err) == 0
        assert int(fused.socks.bytes_recv.sum()) > 0, "no bytes moved"
        _assert_bitwise(fused, ref, f"bulk rel={reliability}")


class TestPersistentNeutrality:
    """params.persistent routes whole windows through K_WINDOW (one
    persistent Pallas region per window) instead of the per-phase fused
    launch train.  Every world that runs through it must be bitwise
    leaf-for-leaf equal to the persistent-off trajectory -- including
    the f32 islands (phold's f64 log1p tick, cubic's f32 cbrt), which
    hold the in-kernel contract documented in docs/megakernel.md."""

    @pytest.mark.tier0
    @pytest.mark.parametrize("rx_batch", [1, 2])
    def test_run_until_bitwise_identical(self, rx_batch):
        state, params, app = _phold(rx_batch=rx_batch)
        on = engine.run_until(state, params, app, SEC)
        off = engine.run_until(state, params.replace(persistent=False),
                               app, SEC)
        assert int(on.app.recv.sum()) > 0, "no traffic simulated"
        _assert_bitwise(on, off, f"persistent phold rx_batch={rx_batch}")

    @pytest.mark.parametrize("chunk_ms", [200, 500])
    def test_chunked_bitwise_identical(self, chunk_ms):
        state, params, app = _phold()
        on = engine.run_chunked(state, params, app, SEC,
                                chunk_ns=chunk_ms * MS)
        off = engine.run_chunked(state,
                                 params.replace(persistent=False),
                                 app, SEC, chunk_ns=chunk_ms * MS)
        _assert_bitwise(on, off, f"persistent chunked {chunk_ms}ms")

    @pytest.mark.parametrize("cong", ["reno", "cubic"])
    def test_bulk_lossy_bitwise_identical(self, cong):
        # Drops arm RTO timers and retransmissions inside the window
        # loop; the congestion window math runs in-kernel -- cubic's
        # f32 cbrt is the sharpest in-kernel-contract probe in tree.
        state, params, app = _fused(sim.build_bulk(
            num_hosts=4, bytes_per_client=30_000,
            reliability=0.97, stop_time=4 * SEC, seed=11))
        params = params.replace(cong=cong)
        on = engine.run_until(state, params, app, 3 * SEC)
        off = engine.run_until(state, params.replace(persistent=False),
                               app, 3 * SEC)
        assert int(on.err) == 0
        assert int(on.socks.bytes_recv.sum()) > 0, "no bytes moved"
        _assert_bitwise(on, off, f"persistent bulk rel=0.97 {cong}")

    def test_netem_link_flap_bitwise_identical(self):
        # The netem overlay advances INSIDE K_WINDOW (the while_loop
        # over timeline events rides the kernel); the flap exercises
        # both the in-kernel advance and the drop path.
        state, params, app = _phold(msgs_per_host=4)
        tl = netem.timeline()
        tl.link_down(2, 5, at=100 * MS).link_up(2, 5, at=600 * MS)
        tl.link_down(1, 9, at=200 * MS).link_up(1, 9, at=SEC)
        state, params = netem.install(state, params, tl)
        on = engine.run_until(state, params, app, SEC)
        off = engine.run_until(state, params.replace(persistent=False),
                               app, SEC)
        _assert_bitwise(on, off, "persistent netem link-flap")

    def test_mesh_8dev_bitwise_identical(self):
        # Mesh worlds carry halo offsets, so persistent_enabled defers
        # to the per-phase fused path -- the flag must be inert there,
        # not faulting or diverging.
        state, params, app = _phold(stop_time=300 * MS)
        on = sim.run(state, params, app, until=200 * MS, devices=8)
        off = sim.run(state, params.replace(persistent=False), app,
                      until=200 * MS, devices=8)
        assert int(on.n_steps) > 0
        _assert_bitwise(on, off, "persistent mesh devices=8")

    def test_instrumented_world_bitwise_identical(self):
        # The instrumentation audit (docs/megakernel.md): flight
        # recorder, sentinel, digests and flowscope worlds run the
        # fused AND persistent paths -- the envelope strips the
        # host-facing blocks around the kernel and replays their
        # window-close bookkeeping outside it, so the full pytree
        # (rings included) must match both persistent-off and the
        # reference oracle leaf for leaf.
        from shadow1_tpu import trace
        state, params, app = _phold(msgs_per_host=4)
        state = trace.ensure_counters(state)
        state = trace.ensure_flight_recorder(state, capacity=256)
        state = trace.ensure_sentinel(state)
        state = trace.ensure_digests(state, every=2, capacity=256)
        state = trace.ensure_flowscope(state, flow_capacity=1 << 10,
                                       link_capacity=1 << 8,
                                       interval_ns=100 * MS)
        from shadow1_tpu.core import megakernel as mk
        assert mk.enabled(state, params, app)
        assert mk.persistent_enabled(state, params, app)
        on = engine.run_until(state, params, app, SEC)
        off = engine.run_until(state, params.replace(persistent=False),
                               app, SEC)
        ref = engine.run_until(state, params.replace(megakernel=False),
                               app, SEC)
        assert int(on.fr.total) > 0, "flight recorder recorded nothing"
        assert int(on.dg.total) > 0, "digests recorded nothing"
        _assert_bitwise(on, off, "instrumented persistent vs fused")
        _assert_bitwise(on, ref, "instrumented persistent vs reference")


class TestGraphIdentity:
    def test_megakernel_off_lowers_clean_and_reproducibly(self):
        # The reference oracle really is the pre-megakernel graph: no
        # kernel machinery in the lowering, and two independent builds
        # of the same world lower byte-identical.
        s1, p1, a1 = _phold()
        s2, p2, a2 = _phold()
        off = p1.replace(megakernel=False)
        t1 = engine.run_until.lower(s1, off, a1, SEC).as_text()
        t2 = engine.run_until.lower(
            s2, p2.replace(megakernel=False), a2, SEC).as_text()
        assert t1 == t2, "megakernel-off lowering is not reproducible"
        assert "megakernel" not in t1

    def test_megakernel_flag_changes_the_graph(self):
        state, params, app = _phold()
        on = engine.run_until.lower(state, params, app, SEC).as_text()
        off = engine.run_until.lower(
            state, params.replace(megakernel=False), app, SEC).as_text()
        assert on != off, "megakernel flag traced no kernels"

    def test_persistent_off_lowers_reproducibly(self):
        # The persistent-off graph is the per-phase fused path exactly
        # as it existed before the flag: two independent builds must
        # lower byte-identical (the byte-identity against the
        # pre-persistent tree was verified once at introduction; this
        # pins that the off path stays deterministic and untouched by
        # the flag's machinery).
        s1, p1, a1 = _phold()
        s2, p2, a2 = _phold()
        t1 = engine.run_until.lower(
            s1, p1.replace(persistent=False), a1, SEC).as_text()
        t2 = engine.run_until.lower(
            s2, p2.replace(persistent=False), a2, SEC).as_text()
        assert t1 == t2, "persistent-off lowering is not reproducible"

    def test_persistent_flag_changes_the_graph(self):
        # K_WINDOW really engages: the persistent lowering is a
        # different (and smaller -- one region replaces the unrolled
        # launch train) program than the per-phase fused one.
        state, params, app = _phold()
        on = engine.run_until.lower(state, params, app, SEC).as_text()
        off = engine.run_until.lower(
            state, params.replace(persistent=False), app,
            SEC).as_text()
        assert on != off, "persistent flag traced no window kernel"
        assert len(on) < len(off), (len(on), len(off))

    @pytest.mark.slow
    def test_fused_op_count_pin(self):
        # The round-9 judgment metric, pinned: the compiled per-phase
        # fused run_until must keep kernel-unit n_ops at <= 0.6x the
        # reference graph on the kernelcount fixed world (measured
        # 4,211 vs 7,365 when recorded; see PERF.md round 9).
        kc = _load_tool("kernelcount")
        fused = kc.phase_counts(megakernel=True,
                                persistent=False)["run_until"]
        ref = kc.phase_counts(megakernel=False,
                              persistent=False)["run_until"]
        assert fused["n_pallas"] >= 3, fused
        assert ref["n_pallas"] == 0, ref
        assert ref["n_ops"] == ref["n_ops_flat"], ref
        assert fused["n_ops"] <= 0.6 * ref["n_ops"], (fused, ref)

    @pytest.mark.slow
    def test_persistent_launch_count_pin(self):
        # The round-10 judgment metric, pinned: `launches` (the
        # top-level op count of the run_until while-body -- the
        # per-window dispatch surface) must collapse >= 5x with the
        # persistent kernel on (measured 323 vs 3,359 when recorded;
        # see PERF.md round 10), through a single Pallas region.
        kc = _load_tool("kernelcount")
        per = kc.phase_counts(megakernel=True,
                              persistent=True)["run_until"]
        fused = kc.phase_counts(megakernel=True,
                                persistent=False)["run_until"]
        assert per["n_pallas"] == 1, per
        assert per["launches"] * 5 <= fused["launches"], (per, fused)
        assert per["n_ops"] < fused["n_ops"], (per, fused)
