"""tools/phaseprof.py: device self time per trace.PHASES scope.

A TPU trace names each op event on its `XLA Ops` line by the op's HLO
line; the tool maps those names through the compiled program's HLO
text to phases.  A recorded-shape slice of one (a `while` around two
scoped ops, an unscoped copy, an op of no program) reduces to the right
phases, and so does a traced CPU world."""

import importlib.util
import os

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 12000000 duration_ps: 2000000 }
    events { metadata_id: 5 offset_ps: 15000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.7 = s32[] while(s32[] %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.3 = s32[8] fusion(s32[8] %a)" } }
  event_metadata { key: 3 value { id: 3 name: "%all-reduce.1 = s32[] all-reduce(s32[] %b)" } }
  event_metadata { key: 4 value { id: 4 name: "%copy.2 = s32[8] copy(s32[8] %c)" } }
  event_metadata { key: 5 value { id: 5 name: "%copy-start.4 = s32[8] copy-start(s32[8] %d)" } }
}
"""

XSPACE_HLO = """
ENTRY %main (p: s32[]) -> s32[] {
  %p = s32[] parameter(0)
  %fusion.3 = s32[8] fusion(s32[8] %a), kind=kLoop, calls=%f, metadata={op_name="jit(run_until)/while/body/while/body/rx/add"}
  %all-reduce.1 = s32[] all-reduce(s32[] %b), metadata={op_name="jit(mesh_run_until)/while/body/scan/mesh_min/pmin"}
  %copy.2 = s32[8] copy(s32[8] %c)
  ROOT %while.7 = s32[] while(s32[] %p), condition=%c, body=%b, metadata={op_name="jit(run_until)/while"}
}
"""


@pytest.fixture(scope="module")
def phaseprof():
    spec = importlib.util.spec_from_file_location(
        "phaseprof", os.path.join(REPO, "tools", "phaseprof.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tpu_name_stacks_reduce_to_phases(phaseprof, tmp_path):
    from jax.profiler import ProfileData
    raw = ProfileData.text_proto_to_serialized_xspace(XSPACE)
    (tmp_path / "t.xplane.pb").write_bytes(raw)
    ops = phaseprof.load_ops(str(tmp_path))
    assert [o[0] for o in ops["/device:TPU:0"]] == [
        "while.7", "fusion.3", "all-reduce.1", "copy.2", "copy-start.4"]
    table = phaseprof.phase_table(ops, phaseprof.hlo_phases(XSPACE_HLO))
    row = table["/device:TPU:0"]
    # the while's 10 us less its two ops' 7 us, plus the 2 us copy; the
    # copy-start belongs to no program in the map
    assert row == pytest.approx({"rx": 3e-6, "mesh_min": 4e-6,
                                 "unscoped": 5e-6, "other": 1e-6})


HLO = """
%fused_computation.1 (p.0: s32[8]) -> s32[8] {
  %p.0 = s32[8] parameter(0)
  ROOT %add.9 = s32[8] add(s32[8] %p.0, s32[8] %p.0), metadata={op_name="jit(run_until)/while/body/exchange/cond/branch_1_fun/add"}
}

%branch (q: s32[8]) -> s32[8] {
  %q = s32[8] parameter(0)
  %sort.4 = s32[8] sort(s32[8] %q), dimensions={0}, to_apply=%compare
  ROOT %neg.2 = s32[8] negate(s32[8] %sort.4), metadata={op_name="jit(run_until)/while/body/exchange/cond/branch_1_fun/neg"}
}

ENTRY %main (a: s32[8]) -> s32[8] {
  %a = s32[8] parameter(0)
  %fusion.12 = s32[8] fusion(s32[8] %a), kind=kLoop, calls=%fused_computation.1
  %copy.5 = s32[8] copy(s32[8] %fusion.12)
  %mul.3 = s32[8] multiply(s32[8] %copy.5, s32[8] %a), metadata={op_name="jit(run_until)/while/body/while/body/stage/mul"}
  ROOT %while.2 = s32[8] while(s32[8] %mul.3), condition=%c, body=%b, metadata={op_name="jit(run_until)/while"}
}
"""


def test_ops_xla_makes_take_the_phase_around_them(phaseprof):
    phases = phaseprof.hlo_phases(HLO)
    assert phases["fusion.12"] == "exchange"     # the ops it fuses
    assert phases["sort.4"] == "exchange"        # its consumer's
    assert phases["copy.5"] == "stage"
    assert phases["mul.3"] == "stage"
    assert phases["while.2"] is None             # a loop's own op
    ops = {"/device:TPU:0": [("fusion.12", 0, 5),
                             ("while.2", 10, 30),
                             ("mul.3", 12, 20),
                             ("copy.9", 40, 41)]}
    row = phaseprof.phase_table(ops, phases)["/device:TPU:0"]
    assert row == pytest.approx({"exchange": 5e-9, "stage": 8e-9,
                                 "unscoped": 12e-9, "other": 1e-9})


def test_cpu_world_splits_by_phase(phaseprof):
    args = phaseprof.argparse.Namespace(
        world="phold", hosts=64, circuits=0, devices=1, warm_ms=20,
        chunk_ms=20, launches=2)
    table, steps = phaseprof.profile_world(args)
    row = table["cpu"]
    assert steps > 0
    assert {"exchange", "scan", "rx", "app", "stage", "tx"} <= set(row)
    scoped = sum(v for k, v in row.items() if k in phaseprof.trace.PHASES)
    assert scoped > 0.5 * sum(row.values())
    assert jax.default_backend() == "cpu"
