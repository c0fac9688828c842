"""Sharded-engine tests: the determinism-across-meshes contract.

The reference guarantees identical results across worker counts
(/root/reference/src/test/determinism/CMakeLists.txt:7-15: same config,
-w 2, byte-for-byte diff of 50 host stdouts).  The TPU rebuild's claim is
stronger (core/rng.py, parallel/sharding.py): bitwise-identical
trajectories for ANY device mesh, because every reduction is an
integer min/sum and every random draw is functionally keyed.  These tests
verify that claim on the 8-virtual-device CPU platform the conftest forces.
"""

import warnings

import jax
import jax.numpy as jnp
import pytest

from shadow1_tpu import netem, sim
from shadow1_tpu.core import engine, simtime
from shadow1_tpu.parallel import (make_mesh, mesh_run_until,
                                  pad_world_to_mesh, sharded_run_until)

MS = simtime.SIMTIME_ONE_MILLISECOND
SEC = simtime.SIMTIME_ONE_SECOND


def _assert_trees_equal(a, b):
    la, _ = jax.tree_util.tree_flatten_with_path(a)
    lb, _ = jax.tree_util.tree_flatten_with_path(b)
    assert len(la) == len(lb)
    for (pa, xa), (_pb, xb) in zip(la, lb):
        name = "/".join(str(p) for p in pa)
        assert jnp.array_equal(xa, xb), f"leaf {name} differs"


class TestShardedDeterminism:
    def test_phold_8dev_mesh_matches_single_device(self):
        kw = dict(num_hosts=16, msgs_per_host=2,
                  latency_ns=10 * MS, stop_time=300 * MS,
                  pool_capacity=1 << 10, seed=4)
        state, params, app = sim.build_phold(**kw)
        single = engine.run_until(state, params, app, 300 * MS)

        state2, params2, _ = sim.build_phold(**kw)
        mesh = make_mesh(jax.devices()[:8])
        sharded = sharded_run_until(state2, params2, app, 300 * MS, mesh)

        assert int(sharded.app.sent.sum()) > 0
        assert int(sharded.err) == 0
        _assert_trees_equal(single, jax.device_get(sharded))

    def test_bulk_tcp_2dev_mesh_matches_single_device(self):
        # TCP + reliability drops + bandwidth caps through the sharded
        # engine: the full stack must be mesh-invariant, not just phold.
        kw = dict(num_hosts=4, server=0, bytes_per_client=60_000,
                  latency_ns=5 * MS, reliability=0.95, stop_time=30 * SEC,
                  bw_down_Bps=500_000, seed=6)
        state, params, app = sim.build_bulk(**kw)
        single = engine.run_until(state, params, app, 30 * SEC)
        assert [int(p) for p in single.app.phase[1:]] == [2, 2, 2]

        state2, params2, _ = sim.build_bulk(**kw)
        mesh = make_mesh(jax.devices()[:2])
        sharded = sharded_run_until(state2, params2, app, 30 * SEC, mesh)
        _assert_trees_equal(single, jax.device_get(sharded))


class TestMeshRunUntil:
    """The explicit shard_map engine (parallel/mesh.py): leaf-for-leaf
    bitwise equality against single-device execution, for every world
    flavor and for multiple chunkings of the same horizon.  This is the
    determinism contract of docs/parallel.md, verified on the 8-virtual-
    device CPU mesh the conftest forces."""

    @pytest.mark.tier0
    @pytest.mark.parametrize("rx_batch", [1, 2])
    def test_phold_8dev_bitwise_and_chunking_invariant(self, rx_batch):
        t_end = 300 * MS
        state, params, app = sim.build_phold(
            16, stop_time=t_end, rx_batch=rx_batch, seed=4)
        mesh = make_mesh(jax.devices()[:8])

        # Chunking 1: one launch.
        ref = engine.run_until(state, params, app, t_end)
        out = mesh_run_until(state, params, app, t_end, mesh=mesh)
        assert int(out.n_events) > 0
        _assert_trees_equal(jax.device_get(ref), jax.device_get(out))

        # Chunking 2: three launches, same chunk boundaries both sides
        # (chunk boundaries insert extra windows, so the comparison must
        # chunk the single-device run identically).
        ref2, out2 = state, state
        for t in (100 * MS, 200 * MS, t_end):
            ref2 = engine.run_until(ref2, params, app, t)
            out2 = mesh_run_until(out2, params, app, t, mesh=mesh)
        _assert_trees_equal(jax.device_get(ref2), jax.device_get(out2))

    def test_netem_linkflap_phold_8dev_bitwise(self):
        # Fault injection under the mesh: the overlay is replicated, its
        # cursor advances identically on every shard, and the killed
        # counter is finalized by psum of per-shard partials.  The flap
        # targets a CROSS-SHARD link (hosts 1 and 9 live on different
        # shards of the 8-device mesh).
        t_end = 400 * MS
        state, params, app = sim.build_phold(16, stop_time=t_end, seed=4)
        tl = netem.timeline()
        tl.link_down(1, 9, at=50 * MS).link_up(1, 9, at=150 * MS)
        tl.host_flap(3, down_at=80 * MS, up_at=220 * MS)
        tl.bandwidth_scale(0.25, at=100 * MS, host=5)
        state, params = netem.install(state, params, tl)

        ref = engine.run_until(state, params, app, t_end)
        mesh = make_mesh(jax.devices()[:8])
        out = mesh_run_until(state, params, app, t_end, mesh=mesh)
        assert int(out.nm.killed) == int(ref.nm.killed)
        _assert_trees_equal(jax.device_get(ref), jax.device_get(out))

    @pytest.mark.slow
    def test_tcp_bulk_8dev_bitwise(self):
        # The full TCP machine through the all-to-all exchange, one host
        # per shard: exercises the pure-ACK shed regime's globally
        # reduced gate predicates.
        t_end = 2 * SEC
        state, params, app = sim.build_bulk(
            8, bytes_per_client=1 << 16, stop_time=t_end)
        ref = engine.run_until(state, params, app, t_end)
        mesh = make_mesh(jax.devices()[:8])
        out = mesh_run_until(state, params, app, t_end, mesh=mesh)
        assert int(out.socks.bytes_recv[0].sum()) > 0
        _assert_trees_equal(jax.device_get(ref), jax.device_get(out))

    def test_nondivisible_world_pads_then_matches(self):
        # 12 hosts on 8 devices: pad_world_to_mesh grows the world to 16
        # with inert hosts (warning names the padded leaves), and the
        # PADDED world -- a different world from the 12-host one, see
        # pad_state_to_mesh's docstring -- is still bitwise identical
        # between mesh and single-device execution.
        t_end = 300 * MS
        state, params, app = sim.build_phold(12, stop_time=t_end, seed=4)
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            ps, pp = pad_world_to_mesh(state, params, 8)
        msgs = "\n".join(str(w.message) for w in rec)
        assert "padded world from 12 to 16 hosts" in msgs
        assert "hosts, socks, pool, inbox" in msgs
        assert ps.hosts.num_hosts == 16
        assert pp.host_vertex.shape[0] == 16
        assert ps.pool.capacity // 16 == state.pool.capacity // 12

        ref = engine.run_until(ps, pp, app, t_end)
        mesh = make_mesh(jax.devices()[:8])
        out = mesh_run_until(ps, pp, app, t_end, mesh=mesh)
        _assert_trees_equal(jax.device_get(ref), jax.device_get(out))
        # Padded hosts are inert: no app state, nothing ever sent.
        assert int(out.app.sent[12:].sum()) == 0

    def test_nondivisible_world_raises_naming_pad_helper(self):
        state, params, app = sim.build_phold(12, stop_time=SEC)
        mesh = make_mesh(jax.devices()[:8])
        with pytest.raises(ValueError, match="pad_world_to_mesh"):
            mesh_run_until(state, params, app, SEC, mesh=mesh)

    def test_scalar_cursor_log_ring_is_rejected_with_recipe(self):
        # A ring built for one shard has a single cursor the 8 shards
        # would race on; the refusal names the shards= recipe.  Sharded
        # ring runs themselves are covered in test_mesh_observe.py.
        from shadow1_tpu.core import state as state_mod

        state, params, app = sim.build_phold(16, stop_time=SEC)
        state = state.replace(log=state_mod.make_log_ring(1 << 8))
        mesh = make_mesh(jax.devices()[:8])
        with pytest.raises(ValueError, match=r"shards=8"):
            mesh_run_until(state, params, app, SEC, mesh=mesh)


class TestParamSpecs:
    def test_every_netparams_leaf_has_explicit_spec(self):
        # Placement is a name table, not a dtype heuristic: every leaf of
        # a real NetParams must resolve, [H] vectors shard, scalars + the
        # PRNG key replicate.
        from jax.sharding import PartitionSpec as P
        from shadow1_tpu.parallel import sharding as sh

        mesh = make_mesh(jax.devices("cpu")[:8])
        _, params, _ = sim.build_phold(
            num_hosts=16, msgs_per_host=1,
            stop_time=simtime.SIMTIME_ONE_SECOND)
        placed = sh.shard_params(params, mesh)
        hspec = P(sh.HOST_AXIS)
        assert placed.host_vertex.sharding.spec == hspec
        assert placed.bw_up_Bps.sharding.spec == hspec
        assert placed.seed_key.sharding.spec == P()
        assert placed.stop_time.sharding.spec == P()

    def test_param_specs_cover_every_world_flavor(self):
        # Completeness audit: build every world flavor we ship and check
        # that every pytree leaf of its NetParams has an explicit entry
        # in PARAM_SPECS -- a new NetParams field without a placement
        # must fail HERE, not surface as a shard-time guess.  The
        # reverse direction too: a stale PARAM_SPECS entry naming a
        # removed field is equally an error.
        from shadow1_tpu.parallel import sharding as sh

        def leaf_names(params):
            flat, _ = jax.tree_util.tree_flatten_with_path(params)
            return {sh._leaf_name(path) for path, _leaf in flat}

        worlds = {}
        _, worlds["phold"], _ = sim.build_phold(16, stop_time=SEC)
        _, worlds["tcp"], _ = sim.build_bulk(
            4, bytes_per_client=1 << 12, stop_time=SEC)
        st, params, _ = sim.build_phold(16, stop_time=SEC)
        tl = netem.timeline().host_flap(3, down_at=MS, up_at=2 * MS)
        _, worlds["netem"] = netem.install(st, params, tl)
        _, worlds["narrow-pool"], _ = sim.build_phold(
            16, stop_time=SEC, pool_capacity=1 << 7)
        # Bucket-padded flavor: the only one whose hosts_real is an
        # actual leaf (None elsewhere, hence invisible to the audit).
        from shadow1_tpu import shapes
        st, params, _ = sim.build_phold(12, stop_time=SEC,
                                        pool_capacity=12 * 8)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            _, worlds["bucketed"] = shapes.pad_world_to_bucket(st, params)

        seen = set()
        for flavor, params in worlds.items():
            names = leaf_names(params)
            unmapped = names - set(sh.PARAM_SPECS)
            assert not unmapped, (
                f"{flavor} world has NetParams leaves with no "
                f"PARAM_SPECS placement: {sorted(unmapped)}")
            seen |= names
        stale = set(sh.PARAM_SPECS) - seen
        assert not stale, f"PARAM_SPECS names unknown leaves: {sorted(stale)}"

    def test_unknown_leaf_is_an_error_not_a_guess(self):
        from shadow1_tpu.parallel import sharding as sh

        mesh = make_mesh(jax.devices("cpu")[:8])
        fake = {"host_vertex": jnp.zeros(16, jnp.int32),
                "mystery_field": jnp.zeros(16, jnp.uint32)}
        with pytest.raises(ValueError, match="mystery_field"):
            sh.shard_params(fake, mesh)


class TestDryrunEntry:
    def test_dryrun_multichip_self_provisions(self):
        # The driver imports and calls this directly; it must work even
        # though this process already initialized an (8-virtual-device)
        # backend -- and also when it hasn't enough devices (covered by
        # the subprocess path on the real-TPU side).
        import __graft_entry__ as g
        g.dryrun_multichip(8)


class TestTgenMesh:
    """The config-built tgen interpreter on a mesh: its server pass reads
    the PEER's app registers (a cross-shard gather under sharding) and its
    zero row is a live program, so it exercises both the app-side
    all_gather and the PAD_VALUES padding protocol."""

    def _load(self):
        import os
        from shadow1_tpu.config import assemble
        path = os.path.join(os.path.dirname(__file__), "..", "examples",
                            "tgen-2host", "shadow.config.xml")
        return assemble.load(path)

    def test_tgen_pad_rows_are_inert(self):
        asm = self._load()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            st, _pr = pad_world_to_mesh(asm.state, asm.params, 8)
        a = st.app
        INV = simtime.SIMTIME_INVALID
        # PAD_VALUES fills, not zeros: cur=0 would be node 0's program and
        # t_next=0 a tick due at t=0.
        assert (a.cur[2:] == -1).all()
        assert (a.start_t[2:] == INV).all()
        assert (a.stop_t[2:] == INV).all()
        assert (a.wait_until[2:] == INV).all()
        assert (a.t_next[2:] == INV).all()
        # ... so the interpreter never schedules a padded host.
        assert (asm.app.next_time(st)[2:] == INV).all()

    @pytest.mark.slow
    def test_tgen_2host_mesh_bitwise(self):
        # Full file-transfer config (client at t=2, 500 kB exchange)
        # padded 2 -> 8 hosts and sharded one host per device; both
        # streams must complete and the trajectory must match the padded
        # world on a single device bitwise.
        asm = self._load()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            st, pr = pad_world_to_mesh(asm.state, asm.params, 8)
        t = 5 * SEC
        ref = engine.run_until(st, pr, asm.app, t)
        mesh = make_mesh(jax.devices()[:8])
        out = mesh_run_until(st, pr, asm.app, t, mesh=mesh)
        assert int(out.err) == 0
        assert int(out.app.streams_done.sum()) == 2
        _assert_trees_equal(jax.device_get(ref), jax.device_get(out))


class TestSimRunDevices:
    @pytest.mark.parametrize("committed", [False, True],
                             ids=["as_built", "committed_to_device0"])
    def test_sim_run_devices_matches_single_device_chunked(self,
                                                           committed):
        # sim.run(devices=N) is the library front door to the mesh path;
        # chunk boundaries mirror engine.run_chunked's, so the result is
        # bitwise-comparable to the single-device chunked run.  On an
        # accelerator build_on_host leaves the world committed to
        # jax.devices()[0]; the mesh must lay it out itself (on the v5e
        # it raised "incompatible devices" before, PR 21).
        kw = dict(num_hosts=16, msgs_per_host=2, latency_ns=10 * MS,
                  stop_time=200 * MS, pool_capacity=1 << 10, seed=9)
        state, params, app = sim.build_phold(**kw)
        if committed:
            state, params = jax.device_put((state, params),
                                           jax.devices()[0])
        ref = engine.run_chunked(state, params, app, 200 * MS)
        out = sim.run(state, params, app, until=200 * MS, devices=8)
        _assert_trees_equal(jax.device_get(ref), jax.device_get(out))

    def test_sim_run_devices_composes_with_profiler(self):
        # The profiler used to be refused under devices>1; it now
        # composes: counter deltas finalize across shards, so the
        # fetched telemetry equals the single-device profiled run's.
        from shadow1_tpu import trace
        kw = dict(num_hosts=8, msgs_per_host=1, stop_time=100 * MS,
                  pool_capacity=1 << 9)
        state, params, app = sim.build_phold(**kw)
        p1 = trace.Profiler()
        ref = sim.run(state, params, app, until=100 * MS, profiler=p1)

        state2, params2, _ = sim.build_phold(**kw)
        p8 = trace.Profiler()
        out = sim.run(state2, params2, app, until=100 * MS,
                      profiler=p8, devices=8)
        assert p8.metrics()["device_counters"] == \
            p1.metrics()["device_counters"]
        _assert_trees_equal(jax.device_get(ref), jax.device_get(out))
