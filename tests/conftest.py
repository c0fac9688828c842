"""Test configuration: force an 8-device virtual CPU platform.

Multi-chip hardware is not available in CI; shardings are validated on a
virtual 8-device CPU mesh exactly as the driver's dryrun does.  Must run
before jax is imported anywhere.
"""

import os
import sys

# Force CPU: tests never take an accelerator (a chip belongs to one
# process, and the TPU paths are rehearsed by compiling for a described
# topology in tests/test_tpu_compile.py instead).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import pytest  # noqa: E402


def run_udp_pingpong_sim(workdir, binp, rounds, server_name="server",
                         seed=23):
    """Shared two-host UDP ping-pong sim run (used by the substrate test
    and the OS-equivalence dual-run): returns (server_proc, client_proc,
    final_state, substrate)."""
    import jax.numpy as jnp

    import shadow1_tpu
    from shadow1_tpu.core import simtime
    from shadow1_tpu.core.params import make_net_params
    from shadow1_tpu.core.state import make_sim_state
    from shadow1_tpu.routing.synthetic import uniform_full_mesh
    from shadow1_tpu.substrate import Substrate, bridge, devapp

    MS = simtime.SIMTIME_ONE_MILLISECOND
    SEC = simtime.SIMTIME_ONE_SECOND

    def _build():
        lat, rel = uniform_full_mesh(2, 5 * MS)
        params = make_net_params(
            latency_ns=lat, reliability=rel, host_vertex=jnp.arange(2),
            bw_up_Bps=jnp.full(2, 1 << 30),
            bw_down_Bps=jnp.full(2, 1 << 30),
            seed=seed, stop_time=30 * SEC)
        state = make_sim_state(2, sock_slots=8, pool_capacity=1 << 10)
        state = state.replace(app=devapp.init_state(2))
        return state, params

    state, params = shadow1_tpu.build_on_host(_build)
    sip, cip = (10 << 24) | 1, (10 << 24) | 2
    sub = Substrate(resolve_ip={sip: 0, cip: 1}.get,
                    workdir=str(workdir),
                    resolve_name={"server": sip}.get,
                    host_ip={0: sip, 1: cip}.get)
    ps = sub.spawn(0, [binp, "server", "5353", str(rounds)])
    pc = sub.spawn(1, [binp, "client", "5353", str(rounds), server_name])
    out = bridge.run(sub, state, params, devapp.SubstrateTx(), 30 * SEC)
    return ps, pc, out, sub


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running redundancy tests excluded from the tier-1 "
        "sweep (`-m 'not slow'`); run explicitly before perf-sensitive "
        "merges")
    config.addinivalue_line(
        "markers",
        "tier0: the <5-minute smoke subset (tools/smoke.py, `-m tier0`):"
        " at least one bitwise pin per subsystem, for a fast "
        "did-I-break-determinism signal before the full tier-1 sweep")


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Free compiled executables + trace caches between test modules.

    A full-suite run accumulates dozens of distinct compiled worlds in
    one process; past ~70% of the suite the XLA CPU compiler has twice
    segfaulted/aborted on a FRESH compile (the same test passes alone in
    a clean process).  Bounding per-process compiler state avoids the
    crash; the persistent on-disk cache keeps recompiles cheap."""
    yield
    jax.clear_caches()
