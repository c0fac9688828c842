"""Invariant sentinel: the in-loop smoke detector's contract.

docs/robustness.md promises:

* Structural zero cost when absent: sentinel=None is a trace-time
  static, so a world that never had the block and one that had it
  attached then detached lower to byte-identical HLO and a zero
  kernelcount delta (the flowscope/flight-recorder rule).
* Bitwise trajectory neutrality when present: the probes only READ
  state the window already touched and write only their own block, so
  every non-sentinel leaf of the final state is bitwise identical --
  on phold (both rx_batch semantics), on lossy bulk TCP, and across a
  mesh.
* Mesh replication: the block reduces with psum/pmin/pmax before
  folding, so the drained row matches the single-device run exactly.
* Detection: host-injectable corruption in each poisonable class
  (nonfinite timers, queue-count desync, time rollback) trips the
  matching SENTINEL_* bit within one window, and SentinelDrain.check
  raises a SentinelViolation naming the first bad window.

The conservation probe is delta-based BY DESIGN (the window-open
snapshot absorbs host-injected counter poison), so it has no
host-injection test here; it guards in-window engine bugs only.
"""

import importlib.util
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow1_tpu import netem, shapes, sim, trace
from shadow1_tpu.core import engine, simtime, state as state_mod
from shadow1_tpu.parallel import make_mesh, mesh_run_chunked

SEC = simtime.SIMTIME_ONE_SECOND

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# float64 NaN reinterpreted as i64 -- the silent-corruption bit pattern
# the nonfinite probe's timer ceiling exists to catch.
NAN_BITS = 9221120237041090560


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lossy_bulk(**over):
    kw = dict(num_hosts=6, bytes_per_client=1 << 14, reliability=0.9,
              stop_time=8 * SEC)
    kw.update(over)
    return sim.build_bulk(**kw)


def _poison_srtt(state, value=NAN_BITS):
    srtt = np.asarray(state.socks.srtt).copy()
    srtt[0, 1] = np.int64(value)
    return state.replace(
        socks=state.socks.replace(srtt=jnp.asarray(srtt)))


class TestStructuralCost:
    @pytest.mark.parametrize("fused", [False, True])
    def test_sentinel_absent_graph_identical_and_zero_kernel_delta(
            self, fused):
        # sentinel=None is a trace-time static: attach-then-detach
        # lowers to byte-identical HLO, so the kernelcount delta is 0
        # -- on the reference graph and the fused one.
        state, params, app = _lossy_bulk()
        params = params.replace(megakernel=fused, persistent=fused)
        txt = engine.run_until.lower(state, params, app, SEC).as_text()
        rt = trace.ensure_sentinel(state).replace(sentinel=None)
        txt_rt = engine.run_until.lower(rt, params, app, SEC).as_text()
        assert txt == txt_rt
        kc = _load_tool("kernelcount")
        assert kc.hlo_counts(txt) == kc.hlo_counts(txt_rt)
        sn = trace.ensure_sentinel(state)
        txt_sn = engine.run_until.lower(sn, params, app, SEC).as_text()
        assert txt_sn != txt  # the probes really trace in when present

    def test_shape_key_discriminates_sentinel(self):
        state, params, app = _lossy_bulk()
        k0 = shapes.shape_key(state, params)
        k1 = shapes.shape_key(trace.ensure_sentinel(state), params)
        assert k0 != k1
        assert "sentinel" in shapes.key_manifest(k1)["blocks"]

    def test_ensure_is_idempotent_and_seeds_last_we(self):
        state, params, app = _lossy_bulk()
        s1 = trace.ensure_sentinel(state)
        assert trace.ensure_sentinel(s1) is s1
        # last_we seeds from the current sim time so a mid-run install
        # never trips the monotonicity probe on its first window.
        assert int(s1.sentinel.last_we) == int(state.now)


class TestTrajectoryNeutrality:
    def _assert_neutral(self, bare, watched):
        assert watched.sentinel is not None and bare.sentinel is None
        la, ta = jax.tree_util.tree_flatten(bare)
        lb, tb = jax.tree_util.tree_flatten(
            watched.replace(sentinel=None))
        assert ta == tb
        for x, y in zip(la, lb):
            assert np.array_equal(np.asarray(x), np.asarray(y))

    @pytest.mark.parametrize("rx_batch", [1, 2])
    def test_phold_bitwise_neutral(self, rx_batch):
        state, params, app = sim.build_phold(
            num_hosts=8, msgs_per_host=4, stop_time=2 * SEC,
            rx_batch=rx_batch)
        bare = engine.run_chunked(state, params, app, 2 * SEC)
        watched = engine.run_chunked(
            trace.ensure_sentinel(state), params, app, 2 * SEC)
        self._assert_neutral(bare, watched)
        row = trace.SentinelDrain().check(watched)
        assert row["checks"] == int(watched.n_windows)
        assert row["violations"] == 0 and row["classes"] == []

    def test_lossy_bulk_bitwise_neutral(self):
        state, params, app = _lossy_bulk()
        bare = engine.run_chunked(state, params, app, 4 * SEC)
        watched = engine.run_chunked(
            trace.ensure_sentinel(state), params, app, 4 * SEC)
        self._assert_neutral(bare, watched)
        assert trace.SentinelDrain().check(watched)["violations"] == 0

    def test_netem_link_flap_bitwise_neutral(self):
        # Link flaps drop packets mid-flight -- the conservation probe
        # must book them under the inet-drop split, not trip.
        MS = simtime.SIMTIME_ONE_MILLISECOND
        state, params, app = sim.build_phold(
            num_hosts=16, msgs_per_host=4, mean_delay_ns=10 * MS,
            stop_time=2 * SEC, pool_capacity=16 * 8, seed=7)
        tl = netem.timeline()
        tl.link_down(2, 5, at=100 * MS).link_up(2, 5, at=600 * MS)
        tl.link_down(1, 9, at=200 * MS).link_up(1, 9, at=SEC)
        state, params = netem.install(state, params, tl)
        bare = engine.run_chunked(state, params, app, SEC)
        watched = engine.run_chunked(
            trace.ensure_sentinel(state), params, app, SEC)
        self._assert_neutral(bare, watched)
        assert trace.SentinelDrain().check(watched)["violations"] == 0

    def test_mesh_8dev_bitwise_neutral(self):
        # Sentinel-on-mesh must match bare-on-mesh leaf for leaf; the
        # replicated block reduces cross-shard before folding.
        state, params, app = _lossy_bulk(num_hosts=8)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            bare = sim.run(state, params, app, until=2 * SEC, devices=8)
            watched = sim.run(trace.ensure_sentinel(state), params, app,
                              until=2 * SEC, devices=8)
        self._assert_neutral(bare, watched)
        assert trace.SentinelDrain().check(watched)["violations"] == 0


class TestMeshParity:
    """Single device vs 4-shard mesh on the conftest's 8 virtual CPU
    devices: the psum/pmin/pmax-reduced block drains the same row."""

    def test_row_matches_single_vs_mesh(self):
        state, params, app = _lossy_bulk(num_hosts=8)
        state = trace.ensure_sentinel(state)
        out1 = engine.run_chunked(state, params, app, 4 * SEC)
        mesh = make_mesh(jax.devices()[:4])
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            out4 = mesh_run_chunked(state, params, app, 4 * SEC,
                                    mesh=mesh)
        r1 = trace.SentinelDrain().check(out1)
        r4 = trace.SentinelDrain().check(out4)
        assert r1 == r4
        assert r1["violations"] == 0
        assert r1["checks"] == int(out1.n_windows) > 0


class TestInjection:
    """Each host-poisonable violation class trips within one window."""

    def _first_window(self, state, params, app):
        out = engine.run_chunked(state, params, app, SEC)
        return out, trace.SentinelDrain().drain(out)

    def test_nan_timer_trips_nonfinite(self):
        state, params, app = _lossy_bulk()
        out, row = self._first_window(
            _poison_srtt(trace.ensure_sentinel(state)), params, app)
        assert "nonfinite" in row["classes"]
        assert row["first_bad_window"] == 0  # caught in the FIRST window
        # row["nonfinite"] is the LAST window's probe count, not sticky:
        # the TCP machine overwrites the poisoned lane once the slot
        # goes active, so only the sticky bit and the frozen first-bad
        # coordinates survive to the drain -- which is the point.
        with pytest.raises(trace.SentinelViolation) as ei:
            trace.SentinelDrain().check(out)
        assert "replay" in str(ei.value)
        assert ei.value.row["violations"] == row["violations"]

    def test_negative_timer_trips_nonfinite(self):
        state, params, app = _lossy_bulk()
        out, row = self._first_window(
            _poison_srtt(trace.ensure_sentinel(state), value=-1),
            params, app)
        assert "nonfinite" in row["classes"]

    def test_queue_desync_trips_bounds(self):
        # A tx_queued count with no matching STAGE_TX_QUEUED pool entry:
        # the queue-accounting identity breaks immediately.
        state, params, app = _lossy_bulk()
        state = trace.ensure_sentinel(state)
        txq = np.asarray(state.hosts.tx_queued).copy()
        txq[0] += 3
        state = state.replace(
            hosts=state.hosts.replace(tx_queued=jnp.asarray(txq)))
        out, row = self._first_window(state, params, app)
        assert "bounds" in row["classes"]
        assert row["first_bad_window"] == 0

    def test_time_rollback_trips_time(self):
        # last_we poisoned into the far future: every subsequent window
        # end fails strict monotonicity.
        state, params, app = _lossy_bulk()
        state = trace.ensure_sentinel(state)
        state = state.replace(sentinel=state.sentinel.replace(
            last_we=jnp.asarray(10 ** 18, state_mod.I64)))
        out, row = self._first_window(state, params, app)
        assert "time" in row["classes"]

    def test_violations_are_sticky_and_first_window_frozen(self):
        state, params, app = _lossy_bulk()
        out = engine.run_chunked(
            _poison_srtt(trace.ensure_sentinel(state)), params, app,
            4 * SEC)
        row = trace.SentinelDrain().drain(out)
        # Many windows later the sticky bit and the frozen first-bad
        # coordinates still point at window 0.
        assert row["checks"] == int(out.n_windows) > 1
        assert "nonfinite" in row["classes"]
        assert row["first_bad_window"] == 0
        assert 0 < row["first_bad_t"] <= SEC


class TestDrainProtocol:
    def test_drain_without_block_is_none(self):
        state, params, app = _lossy_bulk()
        sd = trace.SentinelDrain()
        assert sd.drain(state) is None
        assert sd.check(state) is None  # no block, nothing to raise

    def test_sentinel_classes_decodes_bitmask(self):
        assert trace.sentinel_classes(0) == []
        assert trace.sentinel_classes(
            state_mod.SENTINEL_CONSERVATION) == ["conservation"]
        assert trace.sentinel_classes(
            state_mod.SENTINEL_TIME
            | state_mod.SENTINEL_NONFINITE) == ["time", "nonfinite"]

    def test_clean_check_returns_row(self):
        state, params, app = _lossy_bulk()
        out = engine.run_chunked(
            trace.ensure_sentinel(state), params, app, SEC)
        sd = trace.SentinelDrain()
        row = sd.check(out)
        assert row["violations"] == 0
        assert sd.row is row  # cached for the supervisor's crash path


class TestBenchdiffSentinelGate:
    """benchdiff refuses sentinel-on vs sentinel-off (different traced
    graphs) and supervised vs bare (different host loops); unstamped
    legacy files stay comparable -- the checkpoint/megakernel rule."""

    BASE = {"metric": "phold_events_per_sec", "value": 1000.0,
            "wall_sec": 10.0,
            "config": {"sentinel": False, "supervise": False}}

    def _write(self, tmp_path, name, data):
        p = tmp_path / name
        p.write_text(json.dumps(data))
        return str(p)

    def test_sentinel_mismatch_refused(self, tmp_path):
        new = json.loads(json.dumps(self.BASE))
        new["config"]["sentinel"] = True
        bd = _load_tool("benchdiff")
        rc = bd.main([self._write(tmp_path, "old.json", self.BASE),
                      self._write(tmp_path, "new.json", new)])
        assert rc == 2

    def test_supervise_mismatch_refused(self, tmp_path):
        new = json.loads(json.dumps(self.BASE))
        new["config"]["supervise"] = True
        bd = _load_tool("benchdiff")
        rc = bd.main([self._write(tmp_path, "old.json", self.BASE),
                      self._write(tmp_path, "new.json", new)])
        assert rc == 2

    def test_matching_and_legacy_compare(self, tmp_path):
        bd = _load_tool("benchdiff")
        same = json.loads(json.dumps(self.BASE))
        assert bd.main([self._write(tmp_path, "a.json", self.BASE),
                        self._write(tmp_path, "b.json", same)]) == 0
        legacy = json.loads(json.dumps(self.BASE))
        del legacy["config"]["sentinel"]
        del legacy["config"]["supervise"]
        stamped = json.loads(json.dumps(self.BASE))
        stamped["config"]["sentinel"] = True
        stamped["config"]["supervise"] = True
        assert bd.main([self._write(tmp_path, "c.json", legacy),
                        self._write(tmp_path, "d.json", stamped)]) == 0
