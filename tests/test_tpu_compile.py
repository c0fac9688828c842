"""Compile the main path for a described TPU v5e (no chip attached).

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide, section 2).  These
tests guard what the chip would refuse at no chip time:

* the reference `run_until` -- the default path -- compiles for a UDP
  (phold) world and a lossy bulk-TCP world;
* asking for the fused Pallas path on a TPU fails loudly at trace time
  with megakernel.FusedPathUnavailable, naming the Mosaic refusal,
  instead of running the kernels in interpret mode on the chip.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and the driver runs the
suite under several workers that each import every test file.  Keep
these tests in this one file.  JAX's persistent cache is off around the
compiles: an entry written for a described chip cannot be read back
without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from shadow1_tpu import sim
from shadow1_tpu.core import engine, megakernel, simtime

SEC = simtime.SIMTIME_ONE_SECOND
MS = simtime.SIMTIME_ONE_MILLISECOND


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        if hasattr(x, "ndim") else x, tree)


def _lower(world, sharding):
    state, params, app = world
    t = jax.ShapeDtypeStruct((), jnp.int64, sharding=sharding)
    return engine.run_until.lower(_shapes(state, sharding),
                                  _shapes(params, sharding), app, t)


def _phold():
    return sim.build_phold(num_hosts=16, msgs_per_host=4,
                           mean_delay_ns=10 * MS, stop_time=SEC,
                           pool_capacity=16 * 8, rx_batch=2)


def _lossy_bulk():
    return sim.build_bulk(num_hosts=6, bytes_per_client=1 << 14,
                          reliability=0.9, stop_time=8 * SEC)


@pytest.mark.parametrize("build", [_phold, _lossy_bulk],
                         ids=["phold", "lossy_bulk"])
def test_reference_run_until_compiles_for_v5e(one_chip, build):
    world = build()
    assert not world[1].megakernel, "the default must be the reference graph"
    compiled = _lower(world, one_chip).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0


@pytest.mark.parametrize("persistent", [False, True])
def test_fused_path_refused_on_tpu(one_chip, monkeypatch, persistent):
    # What jax.default_backend() would say on the chip; the described
    # topology cannot set it, so the test steers it.
    monkeypatch.setattr(megakernel, "_interpret", lambda: False)
    state, params, app = _phold()
    params = params.replace(megakernel=True, persistent=persistent)
    with pytest.raises(megakernel.FusedPathUnavailable,
                       match="Mosaic compiler refuses"):
        _lower((state, params, app), one_chip)
