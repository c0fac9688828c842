"""Layout probes for the inbox/outbox engine redesign, slope-timed.

Per-case cost is measured as the SLOPE of wall time vs while_loop
iteration count (50 vs 400), isolating the true per-iteration cost from
the per-call dispatch overhead.  Sync is a scalar fetch, and every timed
call uses fresh input contents.

    python tools/opbench2.py [H] [K]
"""

from __future__ import annotations

import sys
import time

import numpy as np

import shadow1_tpu  # noqa: F401  (x64)
import jax
import jax.numpy as jnp

I32, I64 = jnp.int32, jnp.int64
INV = (1 << 62) - 1

H = int(sys.argv[1]) if len(sys.argv) > 1 else 16384
K = int(sys.argv[2]) if len(sys.argv) > 2 else 8
P = H * K
C = 16
S = 16
E = 7


def bench(name, carry, body):
    res = {}
    for iters in (50, 400):
        def run(c, iters=iters):
            def cond(s):
                return s[0] < iters

            def b(s):
                i = s[0]
                out = body(s[1:], i)
                return (i + 1,) + tuple(out)

            return jax.lax.while_loop(cond, b,
                                      (jnp.asarray(0, I32),) + tuple(c))

        jf = jax.jit(run)
        out = jf(carry)
        np.asarray(out[-1].reshape(-1)[0])  # sync via data fetch
        ts = []
        for trial in range(1, 4):
            c2 = jax.tree_util.tree_map(lambda x: x + trial, carry)
            jax.block_until_ready(c2)
            t0 = time.perf_counter()
            out = jf(c2)
            np.asarray(out[-1].reshape(-1)[0])
            ts.append(time.perf_counter() - t0)
        res[iters] = sorted(ts)[1]
    slope = (res[400] - res[50]) / 350 * 1e3
    print(f"{name:58s} {slope:8.3f} ms/iter  (call overhead "
          f"{res[50]*1e3 - slope*50:6.1f} ms)")
    return slope


def main():
    print(f"H={H} K={K} P={P} C={C} dev={jax.devices()}")
    key = jax.random.PRNGKey(0)
    tkh = jax.random.randint(key, (K, H), 0, 1 << 40, dtype=I64)
    acc0 = jnp.asarray(0, I64)
    blk = jax.random.randint(key, (P, C), 0, 1 << 30, dtype=I32)
    stage = jax.random.randint(key, (K, H), 0, 3, dtype=I32)

    def perturb(t, i):
        return t + i.astype(t.dtype)

    # control cases
    def b_ctl(c, i):
        t, a = c
        t = perturb(t, i)
        dst = (t.reshape(-1) % H).astype(I32)
        m = jax.ops.segment_min(t.reshape(-1), dst, num_segments=H)
        return t, a + m.min()
    bench("control: segment_min i64 by dst [P]->[H]", (tkh, acc0), b_ctl)

    def b1(c, i):
        t, a = c
        t = perturb(t, i)
        tmin = jnp.min(t, axis=0)
        key2 = t * 3 + 1
        kmin = jnp.min(jnp.where(t == tmin[None, :], key2, INV), axis=0)
        return t, a + tmin.min() + kmin.min()
    bench("two-phase i64 min axis0 [K,H]", (tkh, acc0), b1)

    def b1b(c, i):
        t, a = c
        t = perturb(t, i)
        t2 = t.reshape(-1).reshape(H, K)
        tmin = jnp.min(t2, axis=1)
        return t, a + tmin.min()
    bench("i64 min axis1 [H,K] (bad layout control)", (tkh, acc0), b1b)

    def b3(c, i):
        t, blk_, st_, a = c
        blk_ = blk_ + (i % 2)
        lo = blk_[:, 0].astype(I64)
        hi = blk_[:, 1].astype(I64)
        tt = ((hi << 31) | lo).reshape(H, K).T
        live = st_ > 0
        m = jnp.min(jnp.where(live, tt, INV), axis=0)
        return t, blk_, st_, a + m.min()
    bench("decode 2 cols [P,C] -> i64 [K,H].T + masked min",
          (tkh, blk, stage, acc0), b3)

    def b4(c, i):
        t, a = c
        t = perturb(t, i)
        alloc = jnp.broadcast_to(((jnp.arange(E, dtype=I32) + i) % K)[:, None],
                                 (E, H))
        onehot = alloc[:, None, :] == jnp.arange(K, dtype=I32)[None, :, None]
        out = t
        for n in range(16):
            em = t[:E] + n
            upd = jnp.sum(jnp.where(onehot, em[:, None, :], 0), axis=0)
            out = out + upd
        return out, c[1] + out[0, 0]
    bench(f"one-hot merge [E={E},H]->[K,H], 16 i64 fields", (tkh, acc0), b4)

    def b5(c, i):
        t, blk_, st_, a = c
        idx = ((t.reshape(-1) % P) * 7 % P).astype(I32)
        vals = jnp.broadcast_to(t.reshape(-1)[:, None], (P, C)).astype(I32)
        blk_ = blk_.at[idx].set(vals, mode="drop")
        kk = idx % K
        dd = idx // K
        st_ = st_.at[kk, dd].set(1, mode="drop")
        t = perturb(t, i)
        return t, blk_, st_, a + blk_[0, 0].astype(I64) + st_[0, 0].astype(I64)
    bench(f"boundary: scatter [P,{C}] i32 rows + [K,H] i32 2-D",
          (tkh, blk, stage, acc0), b5)

    def b5c(c, i):
        t, blk_, st_, a = c
        nn = P // 4
        idx = ((t.reshape(-1)[:nn] % P) * 7 % P).astype(I32)
        vals = jnp.broadcast_to(t.reshape(-1)[:nn, None], (nn, C)).astype(I32)
        blk_ = blk_.at[idx].set(vals, mode="drop")
        t = perturb(t, i)
        return t, blk_, st_, a + blk_[0, 0].astype(I64)
    bench(f"boundary: scatter [N=P/4,{C}] i32 rows only",
          (tkh, blk, stage, acc0), b5c)

    def b6(c, i):
        t, st_, a = c
        st_ = st_ + (i % 2)
        o = jnp.argsort(st_, axis=0)
        return t, st_, a + o.astype(I64).max() + t[0, 0]
    bench("argsort axis0 [K,H] i32", (tkh, stage, acc0), b6)

    tabSH = jnp.zeros((S, H), I32)

    def b7(c, i):
        t, tab, a = c
        slot = (jnp.arange(H, dtype=I32) + i) % S
        onehot = slot[None, :] == jnp.arange(S, dtype=I32)[:, None]
        s = a
        out = tab
        for n in range(12):
            g = jnp.sum(jnp.where(onehot, tab + n, 0), axis=0, dtype=I32)
            out = jnp.where(onehot, (g + 1)[None, :], out)
            s = s + g.sum().astype(I64)
        return t, out, s + t[0, 0]
    bench("one-hot gather+scatter [S,H], 12 fields", (tkh, tabSH, acc0), b7)

    tabHS = jnp.zeros((H, S), I32)

    def b8(c, i):
        t, tab, a = c
        slot = (jnp.arange(H, dtype=I32) + i) % S
        onehot = slot[:, None] == jnp.arange(S, dtype=I32)[None, :]
        s = a
        out = tab
        for n in range(12):
            g = jnp.sum(jnp.where(onehot, tab + n, 0), axis=1, dtype=I32)
            out = jnp.where(onehot, (g + 1)[:, None], out)
            s = s + g.sum().astype(I64)
        return t, out, s + t[0, 0]
    bench("one-hot gather+scatter [H,S], 12 fields", (tkh, tabHS, acc0), b8)

    def b8b(c, i):
        t, tab, a = c
        rows = jnp.arange(H)
        slot = (rows.astype(I32) + i) % S
        s = a
        out = tab
        for n in range(12):
            g = (tab + n)[rows, slot]
            out = out.at[rows, slot].set(g + 1)
            s = s + g.sum().astype(I64)
        return t, out, s + t[0, 0]
    bench("indexed gather+scatter [H,S], 12 fields (current)",
          (tkh, tabHS, acc0), b8b)

    def b9(c, i):
        t, blk_, a = c
        blk_ = blk_ + (i % 2)
        idx = ((t[0] % P)).astype(I32)
        g = blk_[idx]  # [H, C]
        s = a
        for n in range(C):
            s = s + g[:, n].astype(I64).sum()
        return t, blk_, s
    bench(f"delivery: packed gather [H,{C}] + col decode", (tkh, blk, acc0), b9)

    def b9b(c, i):
        t, a = c
        t = perturb(t, i)
        idx = (t[0] % P).astype(I32)
        fs = [t.reshape(-1) + n for n in range(12)]
        g = sum(f[idx] for f in fs)
        return t, a + g.sum()
    bench("delivery: 12 separate [P] gathers at [H] idx", (tkh, acc0), b9b)

    G = max(1, 512 // K)
    B = max(1, H // G)
    M = G * K

    def b10(c, i):
        t, a = c
        t = perturb(t, i)
        dst = (t.reshape(-1) % H).astype(I32)
        live = (t.reshape(-1) % 3) == 0
        blkid = (jnp.arange(P, dtype=I32) // M)
        cnt = jnp.zeros((B, H), I32).at[blkid, dst].add(
            jnp.where(live, 1, 0), mode="drop")
        off = jnp.cumsum(cnt, axis=0) - cnt
        d3 = dst.reshape(B, M)
        l3 = live.reshape(B, M)
        eq = (d3[:, :, None] == d3[:, None, :]) & l3[:, None, :]
        lower = jnp.tril(jnp.ones((M, M), bool), -1)[None]
        rank_in = jnp.sum(eq & lower, axis=2).reshape(-1)
        rank = off[blkid, dst] + rank_in
        return t, a + rank.astype(I64).max() + t[0, 0]
    bench(f"rank pipeline [P] items, B={B} M={M}", (tkh, acc0), b10)


if __name__ == "__main__":
    main()
