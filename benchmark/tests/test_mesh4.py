"""phold-64k.mesh4 at a small size on four virtual CPU devices: the
harness's whole run (world, warm launch, window, the check) is correct
on the sound program, and not under the control, under each fault of the
phold reference (benchmark/faults.py), or under the one fault that only
a mesh can have: a cross-chip exchange whose `all_to_all` keeps each
shard's blocks where they are.  The compile readers find the mesh's
window loop by its name.  One child process runs them all, since the
device count is fixed when JAX starts."""

import json
import os
import subprocess
import sys

import pytest

import faults
from test_faults import CAUGHT_BY, RELATIVE

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

CHILD = r"""
import json
import faults, run
bench = run.load_json("BENCHMARK.json")
small = {"num_hosts": 1024, "pool_capacity": 8192}


def keep_own_blocks(x, axis_name, split_axis, concat_axis, tiled=False,
                    **kw):
    # every shard keeps its send buffer: no row crosses to another chip
    return x


def cell(plant=None, program_kw=None):
    res, _ = run.run_cell(bench, "phold-64k.mesh4", 2**31 + 11, 1.0,
                          require_chip=False, overrides=small, plant=plant,
                          program_kw=program_kw)
    return {"correct": res["correct"], "spans": res["spans"],
            "checks": res["checks"]}


out = {"sound": cell()}
rec = {"spans": out["sound"]["spans"]}
out["readers"] = {
    name: run.load_module(f"benchmark/metrics/{name}.py").read(rec)
    for name in ("trace_s", "load_s", "recompiles")}
for kind in faults.APPLIES["phold"]:
    fn, kw_fn, ctx = faults.plant(kind, "phold")
    with ctx:
        out[kind] = cell(fn, kw_fn)
with faults._swap("jax.lax", "all_to_all", keep_own_blocks):
    out["all_to_all"] = cell()
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([BENCH, ROOT]))
    proc = subprocess.run([sys.executable, "-c", CHILD], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=2400)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_mesh_cell_runs_and_checks_on_four_devices(mesh_runs):
    sound = mesh_runs["sound"]
    assert sound["correct"] is True, sound["checks"]
    readers = mesh_runs["readers"]
    assert readers["trace_s"] > 0 and readers["load_s"] > 0
    assert readers["recompiles"] == 0
    control = mesh_runs["control"]
    assert control["correct"] is False
    assert control["checks"]["lost"]["value"] > 0


@pytest.mark.parametrize("kind, check", [
    *((k, CAUGHT_BY["phold"][k]) for k in faults.APPLIES["phold"]),
    # rows meant for another chip never reach it: hardly a message is
    # received (rate_off 98.5% at this size and seed, sound 0.68%)
    ("all_to_all", "rate_off")])
def test_broken_mesh_path_is_not_correct(mesh_runs, kind, check):
    c = mesh_runs[kind]["checks"][check]
    if check in RELATIVE:
        # A small world reads noisily: the fault has to read several
        # times what the sound run does.
        sound = mesh_runs["sound"]["checks"][check]["value"]
        assert c["value"] > 4 * sound + 1, mesh_runs[kind]["checks"]
    else:
        assert mesh_runs[kind]["correct"] is False
        assert c["value"] > c["limit"], mesh_runs[kind]["checks"]
