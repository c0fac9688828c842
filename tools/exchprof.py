"""Component ablation of the single-device boundary exchange
(engine._exchange_body / _exchange_core) on the real chip.

    python tools/exchprof.py [num_hosts]
"""

from __future__ import annotations

import sys
import time

import numpy as np

import shadow1_tpu  # noqa: F401
import jax
import jax.numpy as jnp

from shadow1_tpu import sim
from shadow1_tpu.core import engine, simtime
from shadow1_tpu.core.state import STAGE_FREE, STAGE_IN_FLIGHT, I32, I64

NUM_HOSTS = int(sys.argv[1]) if len(sys.argv) > 1 else 16384


def timeloop(name, state0, params, body):
    res = {}
    for iters in (20, 80):
        def run(st):
            def cond(c):
                return c[0] < iters

            def b(c):
                i, s = c
                s = body(s)
                s = s.replace(now=s.now + 1)
                return i + 1, s

            return jax.lax.while_loop(cond, b, (jnp.asarray(0, I32), st))

        jf = jax.jit(run)
        out = jf(state0)
        np.asarray(out[1].now)
        ts = []
        for trial in range(3):
            st2 = state0.replace(now=state0.now + trial)
            t0 = time.perf_counter()
            out = jf(st2)
            np.asarray(out[1].now)
            ts.append(time.perf_counter() - t0)
        res[iters] = min(ts)
    slope = (res[80] - res[20]) / 60 * 1e3
    print(f"{name:44s} {slope:8.3f} ms/iter", flush=True)
    return slope


def main():
    state, params, app = sim.build_phold(
        num_hosts=NUM_HOSTS, msgs_per_host=4,
        mean_delay_ns=10 * simtime.SIMTIME_ONE_MILLISECOND,
        stop_time=10 * simtime.SIMTIME_ONE_SECOND,
        pool_capacity=NUM_HOSTS * 8, rx_batch=2)  # bench world config
    state = engine.run_until(state, params, app,
                             50 * simtime.SIMTIME_ONE_MILLISECOND)
    jax.block_until_ready(state)

    timeloop("exchange_body full", state, params,
             lambda s: engine._exchange_body(s, params))

    # Variant bodies copied from _exchange_core (single-device) with
    # parts disabled: the keyed sort, the segment bounds read off the
    # sorted keys, the destination-side slot map, the row gather.
    from shadow1_tpu.core.state import (ICOL_TIME_LO, ICOL_TIME_HI,
                                        enc_lo, enc_hi)

    def variant(s, *, do_sort=True, do_bounds=True, do_slots=True,
                do_gather=True):
        pool, ib, hosts = s.pool, s.inbox, s.hosts
        h = hosts.num_hosts
        p0 = pool.capacity
        ki = ib.capacity // h
        moving = pool.stage == STAGE_IN_FLIGHT
        dst = jnp.clip(pool.dst, 0, h - 1)
        idx = jnp.arange(p0, dtype=I32)
        key = jnp.where(moving, dst, h).astype(I32)
        if do_sort:
            keys, src = engine._keyed_order(key, idx)
        else:
            keys, src = key, idx
        if do_bounds:
            bnd = engine._seg_starts(keys, jnp.arange(h + 1, dtype=I32))
        else:
            bnd = jnp.arange(h + 1, dtype=I32) * (p0 // h) + keys[0] * 0
        start, total = bnd[:h], bnd[1:] - bnd[:h]
        free2 = (ib.stage == STAGE_FREE).reshape(h, ki)
        if do_slots:
            fr = jnp.cumsum(free2, axis=1, dtype=I32) - free2
            take = (free2 & (fr < total[:, None])).reshape(-1)
            row = src[jnp.clip(start[:, None] + fr, 0, p0 - 1)].reshape(-1)
        else:
            take = free2.reshape(-1) & (total.sum() > 0)
            row = jnp.arange(ib.capacity, dtype=I32) % p0 + src[0] * 0
        ic = ib.blk.shape[1]
        vals = jnp.concatenate(
            [pool.blk[:, :ICOL_TIME_LO],
             enc_lo(pool.time)[:, None], enc_hi(pool.time)[:, None],
             pool.blk[:, ICOL_TIME_HI + 1:ic]], axis=1)
        if do_gather:
            ib = ib.replace(
                blk=jnp.where(take[:, None], vals[row], ib.blk),
                stage=jnp.where(take, STAGE_IN_FLIGHT, ib.stage),
                status=jnp.where(take, pool.status[row], ib.status))
        else:
            # keep a data dependence on the whole take/row/vals pipeline
            ib = ib.replace(stage=ib.stage + (jnp.sum(row, dtype=I32) * 0) +
                            (jnp.sum(take, dtype=I32) * 0) +
                            (jnp.sum(vals[:, 0], dtype=I32) * 0))
        pool = pool.replace(stage=jnp.where(moving, STAGE_FREE, pool.stage))
        return s.replace(pool=pool, inbox=ib)

    timeloop("variant full (sanity)", state, params,
             lambda s: variant(s))
    timeloop("no row gather", state, params,
             lambda s: variant(s, do_gather=False))
    timeloop("no slot map", state, params,
             lambda s: variant(s, do_slots=False))
    timeloop("no segment bounds", state, params,
             lambda s: variant(s, do_bounds=False))
    timeloop("no keyed sort", state, params,
             lambda s: variant(s, do_sort=False))


if __name__ == "__main__":
    main()
