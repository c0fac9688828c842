"""Shape-bucket tests: the pad-to-bucket bitwise-identity contract.

The shapes subsystem (shadow1_tpu/shapes/, docs/shapes.md) promises two
things at once, and these tests hold it to both:

* SHARING -- different-sized worlds padded into one bucket trace ONE
  run_until graph (the compile-tax amortization the subsystem exists
  for), verified through the jit cache size.

* NEUTRALITY -- a padded world's real-host rows are BITWISE identical
  to the exact-size world's trajectory, leaf for leaf, at any horizon
  (the property mesh padding explicitly does NOT have: pad_state_to_mesh
  builds a different world; pad_world_to_bucket must not).  Verified by
  `_assert_real_rows_equal`, which reshapes per-host slabs so padded
  pool/inbox leaves compare row-for-row against the exact layout.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow1_tpu import netem, shapes, sim
from shadow1_tpu.core import engine, simtime
from shadow1_tpu.shapes.key import VERTEX_LADDER, shape_key

MS = simtime.SIMTIME_ONE_MILLISECOND
SEC = simtime.SIMTIME_ONE_SECOND


def _bucket(state, params):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return shapes.pad_world_to_bucket(state, params)


def _assert_real_rows_equal(exact, padded, h: int, hp: int):
    """Leaf-for-leaf bitwise equality of the exact-size state against the
    real-host rows of the padded state.  Scalars compare directly; [h]-
    leading leaves compare their first h rows; [h*k]-leading per-host
    slabs (pool/inbox blocks) compare through a (hosts, slab) reshape so
    row i of the exact layout meets row i of the padded layout."""
    le, _ = jax.tree_util.tree_flatten_with_path(exact)
    lp, _ = jax.tree_util.tree_flatten_with_path(padded)
    assert len(le) == len(lp), "padded state changed pytree structure"
    bad = []
    for (pa, xe), (_pb, xp) in zip(le, lp):
        name = "/".join(str(p) for p in pa)
        xe, xp = np.asarray(xe), np.asarray(xp)
        if xe.shape == xp.shape:
            same = np.array_equal(xe, xp)
        elif (xe.ndim >= 1 and xe.shape[0] % h == 0
              and xp.shape[0] == (xe.shape[0] // h) * hp
              and xe.shape[1:] == xp.shape[1:]):
            k = xe.shape[0] // h
            rest = xe.shape[1:]
            same = np.array_equal(xp.reshape((hp, k) + rest)[:h],
                                  xe.reshape((h, k) + rest))
        else:
            same = False
        if not same:
            bad.append(name)
    assert not bad, f"padded world diverged on real-host rows: {bad}"


def _run_both(state, params, app, t):
    """(exact trajectory, padded trajectory, h, hp) at horizon t."""
    sb, pb = _bucket(state, params)
    exact = engine.run_until(state, params, app, t)
    padded = engine.run_until(sb, pb, app, t)
    return exact, padded, int(state.hosts.num_hosts), int(
        sb.hosts.num_hosts)


class TestShapeKeyLadder:
    def test_bucket_rounds_up_the_host_ladder(self):
        s, p, _ = sim.build_phold(20, stop_time=SEC, pool_capacity=20 * 8)
        key = shape_key(s, p)
        assert key.hosts == 20
        b = shapes.bucket_for(key)
        assert b.hosts == 64
        # Every other determinant is preserved exactly: slabs never
        # bucket (overflow drops are trajectory-visible).
        assert (b.pool_slab, b.inbox_slab, b.cols, b.icols) == (
            key.pool_slab, key.inbox_slab, key.cols, key.icols)

    def test_bucket_is_identity_on_exact_rungs(self):
        s, p, _ = sim.build_phold(64, stop_time=SEC, pool_capacity=64 * 8)
        key = shape_key(s, p)
        assert shapes.bucket_for(key) is key

    def test_vertices_round_their_own_ladder(self):
        # phold's vertex count is min(H, 256): a 20-host world has a
        # 20-vertex route_blk, which rounds up VERTEX_LADDER to 64.
        s, p, _ = sim.build_phold(20, stop_time=SEC, pool_capacity=20 * 8)
        b = shapes.bucket_for(shape_key(s, p))
        assert b.vertices == 64
        assert 64 in VERTEX_LADDER

    def test_beyond_ladder_hosts_stay_exact(self):
        s, p, _ = sim.build_phold(20, stop_time=SEC, pool_capacity=20 * 8)
        key = dataclasses.replace(shape_key(s, p), hosts=2_000_000)
        assert shapes.bucket_for(key).hosts == 2_000_000

    def test_large_slab_worlds_bucket_like_any_other(self):
        # Hosts round up the ladder whatever the slab, and the slab
        # itself never rounds (trajectory-visible).
        s, p, _ = sim.build_phold(20, stop_time=SEC, pool_capacity=20 * 8)
        key = dataclasses.replace(shape_key(s, p),
                                  hosts=9_000, pool_slab=128)
        b = shapes.bucket_for(key)
        assert (b.hosts, b.pool_slab) == (16_384, 128)
        key_in = dataclasses.replace(key, hosts=20_000)
        assert shapes.bucket_for(key_in).hosts == 65_536
        key_ok = dataclasses.replace(key, pool_slab=8, inbox_slab=8)
        assert shapes.bucket_for(key_ok).hosts == 16_384


class TestPadWorldToBucket:
    def test_exact_boundary_world_passes_through_untouched(self):
        # Identity means the SAME objects: the compiled graph (and its
        # kernel counts) of an exact-boundary world cannot change under
        # bucketing, trivially.
        s, p, _ = sim.build_phold(64, stop_time=SEC, pool_capacity=64 * 8)
        s2, p2 = shapes.pad_world_to_bucket(s, p)
        assert s2 is s and p2 is p
        assert p2.hosts_real is None

    def test_exact_boundary_world_compiles_nothing_new(self):
        # Kernelcount/compile neutrality, measured: run the exact world,
        # bucket it (identity), run again -- the jit cache must not grow.
        s, p, a = sim.build_phold(64, stop_time=400 * MS,
                                  pool_capacity=64 * 8)
        out = engine.run_until(s, p, a, 400 * MS)
        jax.block_until_ready(out)
        before = engine.run_until._cache_size()
        s2, p2 = shapes.pad_world_to_bucket(s, p)
        out2 = engine.run_until(s2, p2, a, 400 * MS)
        jax.block_until_ready(out2)
        assert engine.run_until._cache_size() == before

    def test_double_bucketing_is_idempotent_or_refused(self):
        s, p, _ = sim.build_phold(20, stop_time=SEC, pool_capacity=20 * 8)
        sb, pb = _bucket(s, p)
        # A bucketed world sits exactly on its bucket: re-bucketing is
        # the identity (idempotent, same objects) ...
        sb2, pb2 = shapes.pad_world_to_bucket(sb, pb)
        assert sb2 is sb and pb2 is pb
        # ... but padding it AGAIN into a larger bucket would stack a
        # second hosts_real on the first, and is refused.
        bigger = dataclasses.replace(shape_key(sb, pb), hosts=256)
        with pytest.raises(ValueError, match="hosts_real"):
            shapes.pad_world_to_bucket(sb, pb, bucket=bigger)

    def test_shrinking_bucket_is_refused(self):
        s, p, _ = sim.build_phold(20, stop_time=SEC, pool_capacity=20 * 8)
        key = shape_key(s, p)
        small = dataclasses.replace(key, hosts=16, vertices=16)
        with pytest.raises(ValueError, match="smaller"):
            shapes.pad_world_to_bucket(s, p, bucket=small)

    def test_padded_rows_stay_inert(self):
        s, p, a = sim.build_phold(20, stop_time=SEC, pool_capacity=20 * 8)
        sb, pb = _bucket(s, p)
        out = engine.run_until(sb, pb, a, SEC)
        assert int(out.app.sent[20:].sum()) == 0
        assert int(out.hosts.pkts_sent[20:].sum()) == 0


class TestBitwiseNeutrality:
    @pytest.mark.parametrize("rx_batch", [1, 2])
    def test_phold_padded_matches_exact_at_two_horizons(self, rx_batch):
        # The global-draw app: phold picks destinations over the WHOLE
        # host count, the one draw padding would perturb without
        # params.hosts_real.  Two horizons so a divergence cannot hide
        # behind a lucky endpoint.
        s, p, a = sim.build_phold(20, msgs_per_host=2, stop_time=2 * SEC,
                                  pool_capacity=20 * 8, seed=4,
                                  rx_batch=rx_batch)
        for t in (700 * MS, 2 * SEC):
            exact, padded, h, hp = _run_both(s, p, a, t)
            assert (h, hp) == (20, 64)
            _assert_real_rows_equal(exact, padded, h, hp)

    def test_lossy_bulk_tcp_padded_matches_exact(self):
        # Retransmission machinery under packet loss, plus the route_blk
        # re-layout (6 vertices -> 16): the full TCP state machine must
        # not see the padding.
        s, p, a = sim.build_bulk(6, bytes_per_client=1 << 14,
                                 reliability=0.9, stop_time=8 * SEC)
        for t in (3 * SEC, 8 * SEC):
            exact, padded, h, hp = _run_both(s, p, a, t)
            assert (h, hp) == (6, 64)
            _assert_real_rows_equal(exact, padded, h, hp)

    def test_netem_linkflap_padded_matches_exact(self):
        # Fault injection: the netem overlay pads with up/neutral rows,
        # and the flap schedule (cursor, kills) must advance identically.
        t_end = 600 * MS
        s, p, a = sim.build_phold(20, stop_time=t_end, seed=4,
                                  pool_capacity=20 * 8)
        tl = netem.timeline()
        tl.link_down(1, 9, at=50 * MS).link_up(1, 9, at=250 * MS)
        tl.host_flap(3, down_at=80 * MS, up_at=400 * MS)
        s, p = netem.install(s, p, tl)
        exact, padded, h, hp = _run_both(s, p, a, t_end)
        assert int(padded.nm.cursor) == int(exact.nm.cursor)
        assert int(padded.nm.killed) == int(exact.nm.killed)
        _assert_real_rows_equal(exact, padded, h, hp)

    def test_mesh_sharded_bucketed_run_matches_single_device(self):
        # bucket=True composes with devices=N inside sim.run: the 20-host
        # world buckets to 64 (divisible by 8, so the mesh pass is an
        # identity -- no double padding) and the sharded trajectory is
        # bitwise the single-device bucketed one.
        t_end = 400 * MS
        s, p, a = sim.build_phold(20, stop_time=t_end, seed=4,
                                  pool_capacity=20 * 8)
        sb, pb = _bucket(s, p)
        ref = engine.run_until(sb, pb, a, t_end)
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            out = sim.run(s, p, a, until=t_end, devices=8, bucket=True)
        # Exactly one padding pass: the bucket one.  A second "padded
        # world" warning would mean mesh padding re-padded the bucket.
        pads = [w for w in rec if "padded world" in str(w.message)]
        assert len(pads) == 1 and "shape bucket" in str(pads[0].message)
        la, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(ref))
        lb, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(out))
        for (pa, xa), (_pb, xb) in zip(la, lb):
            name = "/".join(str(q) for q in pa)
            assert jnp.array_equal(xa, xb), f"leaf {name} differs"

    def test_mesh_pad_of_bucketed_world_is_identity(self):
        # PAD_VALUES agreement, the degenerate way: every HOST_LADDER
        # rung divides every power-of-two device count up to 64, so
        # pad_world_to_mesh after bucketing has nothing to do and returns
        # the same objects.
        from shadow1_tpu.parallel import pad_world_to_mesh
        s, p, a = sim.build_phold(20, stop_time=SEC, pool_capacity=20 * 8)
        sb, pb = _bucket(s, p)
        sm, pm = pad_world_to_mesh(sb, pb, 8)
        assert sm is sb and pm is pb


class TestCompileSharing:
    def test_three_sizes_one_bucket_one_graph(self):
        # The acceptance sweep: three differently-sized worlds share the
        # 64-host bucket and cost run_until at most ONE new graph.
        worlds = []
        for h in (40, 48, 56):
            s, p, a = sim.build_phold(h, stop_time=300 * MS, seed=4,
                                      pool_capacity=h * 8)
            worlds.append(_bucket(s, p) + (a,))
        assert {int(s.hosts.num_hosts) for s, _p, _a in worlds} == {64}
        before = engine.run_until._cache_size()
        outs = [engine.run_until(s, p, a, 300 * MS) for s, p, a in worlds]
        jax.block_until_ready(outs)
        assert engine.run_until._cache_size() - before <= 1
        # And they are different worlds: the trajectories differ.
        sent = [int(o.hosts.pkts_sent.sum()) for o in outs]
        assert len(set(sent)) == 3
