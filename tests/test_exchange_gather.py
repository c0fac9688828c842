"""Parity of the single-device boundary exchange core.

`engine._exchange_core` orders movers by one keyed sort and delivers
them by a destination-side row gather.  Its contract is the plain
semantics below, leaf for leaf: each destination's movers rank in flat
(src-major) order; on an overflow window with pure ACKs among the movers,
protected movers rank first and pure ACKs after them; the mover of rank
j takes the destination slab's j-th free slot in ascending slot order if
there is one and is dropped otherwise; slots that take no mover keep
their bytes, stale ones included.  `_reference` renders that in numpy
and the cases compare every output, the lineage slot map included.
"""

import collections

import jax
import numpy as np
import pytest

from shadow1_tpu.core import engine
from shadow1_tpu.core.state import (
    ICOL_FLAGS, ICOL_LEN, ICOL_PROTO, ICOL_TIME_HI, ICOL_TIME_LO, ICOLS,
    NCOLS_UDP, OEXT_COLS, OEXT_DST, PROTO_TCP, PROTO_UDP, STAGE_FREE,
    STAGE_IN_FLIGHT, STAGE_RX_QUEUED, STAGE_TX_QUEUED, TCP_FLAG_ACK,
    Inbox, PacketPool)

Params = collections.namedtuple("Params", "pds_trail")

_core = jax.jit(engine._exchange_core, static_argnums=(2, 3, 4))


def _world(seed, *, h=16, ko=8, ki=8, tcp=True, p_move=0.5, p_ack=0.0,
           p_free=0.5, dst_range=None, zero_free=(), one_dst=None):
    """A crafted outbox and inbox: random packed rows (garbage bytes in
    every inbox slot, free ones included), movers with probability
    `p_move`, pure ACKs among TCP movers with probability `p_ack`."""
    rng = np.random.default_rng(seed)
    ic = ICOLS if tcp else NCOLS_UDP
    cols = ic + OEXT_COLS
    p0, p1 = h * ko, h * ki
    blk = rng.integers(0, 2**31 - 1, (p0, cols), dtype=np.int32)
    lo, hi = dst_range if dst_range is not None else (0, h)
    blk[:, ic + OEXT_DST] = (one_dst if one_dst is not None
                             else rng.integers(lo, hi, p0))
    blk[:, ICOL_PROTO] = PROTO_TCP if tcp else PROTO_UDP
    blk[:, ICOL_LEN] = rng.integers(1, 1500, p0)
    blk[:, ICOL_FLAGS] = TCP_FLAG_ACK
    ack = rng.random(p0) < p_ack
    blk[ack, ICOL_LEN] = 0
    stage = np.where(rng.random(p0) < p_move, STAGE_IN_FLIGHT,
                     rng.choice([STAGE_FREE, STAGE_TX_QUEUED,
                                 STAGE_RX_QUEUED], p0)).astype(np.int32)
    pool = PacketPool(
        blk=blk, stage=stage,
        time=rng.integers(0, 2**40, p0, dtype=np.int64),
        status=rng.integers(0, 8, p0, dtype=np.int32))
    istage = np.where(rng.random(p1) < p_free, STAGE_FREE,
                      rng.choice([STAGE_IN_FLIGHT, STAGE_RX_QUEUED],
                                 p1)).astype(np.int32)
    for d in zero_free:
        istage[d * ki:(d + 1) * ki] = STAGE_RX_QUEUED
    ib = Inbox(blk=rng.integers(-2**31, 2**31 - 1, (p1, ic), dtype=np.int32),
               stage=istage,
               status=rng.integers(0, 8, p1, dtype=np.int32))
    return pool, ib, h


def _reference(pool, ib, h, pds_trail):
    """The exchange's semantics, one mover at a time."""
    p0, p1 = pool.stage.shape[0], ib.stage.shape[0]
    ki, ic = p1 // h, ib.blk.shape[1]
    moving = pool.stage == STAGE_IN_FLIGHT
    dst = np.clip(pool.blk[:, -OEXT_COLS + OEXT_DST], 0, h - 1)
    ack = ((pool.blk[:, ICOL_PROTO] == PROTO_TCP)
           & (pool.blk[:, ICOL_LEN] == 0)
           & (pool.blk[:, ICOL_FLAGS] == TCP_FLAG_ACK) & moving)
    if ic < ICOLS:
        ack[:] = False

    def ranks(mask):
        rank, tot = np.zeros(p0, np.int64), np.zeros(h, np.int32)
        for i in np.flatnonzero(mask):
            rank[i] = tot[dst[i]]
            tot[dst[i]] += 1
        return rank, tot

    rank, total = ranks(moving)
    free2 = (ib.stage == STAGE_FREE).reshape(h, ki)
    n_free = free2.sum(axis=1).astype(np.int32)
    total_prot = total
    if ic >= ICOLS and (total > n_free).any() and ack.any():
        rank_p, total_prot = ranks(moving & ~ack)
        rank_a, _ = ranks(ack)
        rank = np.where(ack, total_prot[dst] + rank_a, rank_p)

    vals = np.array(pool.blk[:, :ic])
    vals[:, ICOL_TIME_LO] = pool.time & (2**31 - 1)
    vals[:, ICOL_TIME_HI] = pool.time >> 31
    blk, stage = np.array(ib.blk), np.array(ib.stage)
    status = np.array(ib.status)
    take, row = np.zeros(p1, bool), np.full(p1, -1)
    ok = np.zeros(p0, bool)
    for i in np.flatnonzero(moving):
        d = dst[i]
        if rank[i] < n_free[d]:
            slot = d * ki + np.flatnonzero(free2[d])[rank[i]]
            blk[slot], stage[slot] = vals[i], STAGE_IN_FLIGHT
            if pds_trail:
                status[slot] = pool.status[i]
            take[slot], row[slot], ok[i] = True, i, True
    return dict(
        pool_blk=pool.blk, pool_stage=np.where(moving, STAGE_FREE,
                                               pool.stage),
        pool_time=pool.time, pool_status=pool.status,
        ib_blk=blk, ib_stage=stage, ib_status=status,
        total=total, total_prot=total_prot, n_free=n_free,
        take=take, row=row, ok=ok)


CASES = {
    "no_movers": dict(p_move=0.0),
    "no_overflow": dict(p_move=0.3, p_ack=0.3, p_free=1.0),
    "overflow_data_only": dict(p_move=0.9, p_free=0.3),
    "overflow_acks_interleaved": dict(p_move=0.9, p_ack=0.4, p_free=0.3),
    "acks_no_overflow": dict(p_move=0.4, p_ack=0.5, p_free=0.9),
    "one_destination": dict(p_move=0.6, p_ack=0.3, one_dst=5),
    "zero_free_slots": dict(p_move=0.7, p_ack=0.2, zero_free=(0, 3, 7)),
    "pds_trail": dict(p_move=0.8, p_ack=0.3, p_free=0.4),
    "udp_width": dict(tcp=False, p_move=0.8, p_free=0.4),
    "udp_width_overflow_one_dst": dict(tcp=False, p_move=0.8, one_dst=2),
    "dst_out_of_range": dict(p_move=0.7, p_ack=0.3, dst_range=(-6, 22)),
    "uneven_slabs": dict(h=12, ko=5, ki=11, p_move=0.8, p_ack=0.3),
}
PDS = {"pds_trail"}


@pytest.mark.parametrize("seed", [0, 1, 2, 3000000021])
@pytest.mark.parametrize("case", sorted(CASES))
def test_core_matches_reference(case, seed):
    pool, ib, h = _world(seed, **CASES[case])
    pds = case in PDS
    ref = _reference(pool, ib, h, pds)
    p2, i2, total, tprot, n_free, (take, row, ok) = _core(
        pool, ib, h, Params(pds), True)
    got = dict(
        pool_blk=p2.blk, pool_stage=p2.stage, pool_time=p2.time,
        pool_status=p2.status, ib_blk=i2.blk, ib_stage=i2.stage,
        ib_status=i2.status, total=total, total_prot=tprot, n_free=n_free,
        take=take, ok=ok)
    for k, v in got.items():
        v = np.asarray(v)
        assert v.dtype == np.asarray(ref[k]).dtype or k in (
            "take", "ok"), (k, v.dtype)
        np.testing.assert_array_equal(v, ref[k], err_msg=k)
    t = np.asarray(take)
    np.testing.assert_array_equal(np.asarray(row)[t], ref["row"][t])
    # The plain call returns the same leaves as the lineage call.
    plain = _core(pool, ib, h, Params(pds), False)
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves((p2, i2, total, tprot,
                                               n_free))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cases_reach_their_regimes():
    """The crafted cases exercise what they are named for."""
    def ref(case, seed=0):
        pool, ib, h = _world(seed, **CASES[case])
        return pool, _reference(pool, ib, h, False)

    _, r = ref("no_movers")
    assert r["total"].sum() == 0 and not r["take"].any()
    _, r = ref("no_overflow")
    assert (r["total"] <= r["n_free"]).all() and r["total"].sum() > 0
    _, r = ref("overflow_data_only")
    assert (r["total"] > r["n_free"]).any()
    assert (r["total_prot"] == r["total"]).all()
    _, r = ref("overflow_acks_interleaved")
    assert (r["total"] > r["n_free"]).any()
    assert (r["total_prot"] < r["total"]).any()
    _, r = ref("acks_no_overflow")
    assert (r["total"] <= r["n_free"]).all()
    _, r = ref("one_destination")
    assert r["total"][5] == r["total"].sum() > r["n_free"][5]
    _, r = ref("zero_free_slots")
    assert (r["n_free"][[0, 3, 7]] == 0).all()
    assert (r["total"][[0, 3, 7]] > 0).all()
    pool, r = ref("dst_out_of_range")
    d = pool.blk[:, -OEXT_COLS + OEXT_DST]
    mv = pool.stage == STAGE_IN_FLIGHT
    assert (mv & (d < 0)).any() and (mv & (d >= 16)).any()


@pytest.mark.parametrize("n, h", [(1, 1), (127, 3), (128, 16), (129, 16),
                                  (4096, 1000), (70001, 1000)])
def test_segment_starts_match_binary_search(n, h):
    """`_seg_starts` on sorted keys, bounds past the largest key
    included, is numpy's left-side searchsorted."""
    rng = np.random.default_rng(n * 7 + h)
    keys = np.sort(rng.integers(0, h + 1, n)).astype(np.int32)
    bounds = np.arange(h + 2, dtype=np.int32)
    got = jax.jit(engine._seg_starts)(keys, bounds)
    np.testing.assert_array_equal(
        np.asarray(got), np.searchsorted(keys, bounds, side="left"))
