"""AOT warm cache: pre-compile the standard bucket set.

The package enables JAX's persistent compilation cache at import
(shadow1_tpu/__init__.py: JAX_COMPILATION_CACHE_DIR where set, else
`.jax_cache/` in the checkout).  `warm_buckets` builds one canonical world
per (app flavor, host bucket), pads it into its bucket
(pad_world_to_bucket -- so the compiled graph is the SHARED one every
bucketed world of that shape hits, hosts_real included), and AOT
lowers + compiles run_until.  The resulting executables land in the
persistent cache; later processes that trace the same graph skip the
backend compile entirely, and `profile.compiles` / `compile_ms`
(trace.py) make the win directly measurable.

Front ends: `shadow1-tpu warm` (cli.py) and tools/warmcache.py.

A warm entry only helps worlds whose ShapeKey AND jit statics match the
canonical flavor, so the canonical worlds are deliberately the sweep
configurations: fixed per-host slab (pool_capacity = H * slab -- a
fixed TOTAL capacity would make the slab vary with H and fragment the
buckets), default flags, default app configs.  Sweeps with custom
shapes can warm themselves by running their smallest member first.
"""

from __future__ import annotations

import time

import jax

from ..core import engine, simtime

# Host buckets warmed by default: the small end of shapes.HOST_LADDER.
# The big rungs cost real compile time and memory, so they are opt-in
# (--buckets).
STANDARD_HOST_BUCKETS = (64, 256, 1024, 4096)

# Canonical per-host slabs (see module docstring): phold is the
# UDP-only/narrow-block flavor, bulk the TCP/wide-block flavor.  tgen/
# onion/gossip match their sim.py builder defaults, which the example
# ladder and scenario sweeps use.
PHOLD_SLAB = 8
BULK_SLAB = 32

# Flowscope config of the scope-present flavor ("bulk-scope"): the
# --scope default interval and both rings, so `--scope flows,links`
# sweeps hit the warm cache.  Non-default intervals reuse the same
# graph (the cadence is traced data, not a jit static); non-default
# ring CAPACITIES do not.
SCOPE_INTERVAL_NS = 100_000_000

WARM_APPS = ("phold", "bulk", "tgen", "onion", "gossip", "bulk-scope")


def _canonical_world(app_name: str, bucket_hosts: int):
    """A canonical world STRICTLY below the bucket size, so
    pad_world_to_bucket actually pads (installing hosts_real) and the
    compiled graph is the bucket-shared one, not the exact-size one."""
    from .. import sim
    h = max(2, bucket_hosts - 1)
    if app_name == "phold":
        s, p, a = sim.build_phold(num_hosts=h,
                                  pool_capacity=h * PHOLD_SLAB,
                                  stop_time=simtime.SIMTIME_ONE_SECOND)
    elif app_name in ("bulk", "bulk-scope"):
        s, p, a = sim.build_bulk(num_hosts=h,
                                 bytes_per_client=1 << 16,
                                 pool_capacity=h * BULK_SLAB,
                                 stop_time=simtime.SIMTIME_ONE_SECOND)
        if app_name == "bulk-scope":
            from .. import trace
            s = trace.ensure_flowscope(s, interval_ns=SCOPE_INTERVAL_NS)
    elif app_name == "tgen":
        s, p, a = sim.build_tgen(num_hosts=h,
                                 stop_time=simtime.SIMTIME_ONE_SECOND)
    elif app_name == "onion":
        # build_onion sizes by circuits (client + hops relays + server
        # per circuit, 5 hosts each at the default 3 hops); the biggest
        # circuit count still strictly below the bucket.
        s, p, a = sim.build_onion(
            num_circuits=max(1, (bucket_hosts - 1) // 5),
            bytes_per_circuit=1 << 16,
            stop_time=simtime.SIMTIME_ONE_SECOND)
    elif app_name == "gossip":
        s, p, a = sim.build_gossip(num_hosts=h,
                                   stop_time=simtime.SIMTIME_ONE_SECOND)
    else:
        raise ValueError(f"warm: unknown app flavor {app_name!r} "
                         f"(known: {', '.join(WARM_APPS)})")
    return s, p, a


def warm_buckets(buckets=None, apps=("phold", "bulk"), log=None):
    """Pre-lower and compile run_until for each (app, bucket) into the
    persistent XLA cache.  Returns a list of records
    {app, bucket_hosts, real_hosts, lower_s, compile_s}.  A bucket that
    is already cached still pays the (cheap) trace+lower, but its
    compile_s collapses to the cache-read time."""
    from .bucket import pad_world_to_bucket

    if buckets is None:
        buckets = STANDARD_HOST_BUCKETS
    # Cache compiles of any duration: the default 2s write floor
    # (shadow1_tpu/__init__.py) would silently skip fast CPU compiles,
    # making `warm` a no-op exactly where it is cheapest to test.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    records = []
    for hb in buckets:
        for app_name in apps:
            state, params, app = _canonical_world(app_name, int(hb))
            real = int(state.hosts.num_hosts)
            state, params = pad_world_to_bucket(state, params)
            # Warm the flavor that runs: the world's own megakernel/
            # persistent statics (both ShapeKey fields), which default to
            # the reference graph, the one path every backend compiles.
            t0 = time.perf_counter()
            lowered = engine.run_until.lower(
                state, params, app, simtime.SIMTIME_ONE_SECOND)
            t1 = time.perf_counter()
            lowered.compile()
            t2 = time.perf_counter()
            rec = {"app": app_name, "bucket_hosts": int(hb),
                   "real_hosts": real,
                   "megakernel": bool(params.megakernel),
                   "persistent": bool(params.persistent),
                   "lower_s": round(t1 - t0, 3),
                   "compile_s": round(t2 - t1, 3)}
            records.append(rec)
            if log is not None:
                log(rec)
    return records
