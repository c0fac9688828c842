"""trace_s, load_s and recompiles read the program's compile record
(shadow1_tpu.trace.compile_spans) in the run's own process; here they
read a synthetic record."""

import pytest

import run

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
REC = {"spans": {"runtime": 10.0, "build": 5.0, "warm": 30.0}}


def _read(name, spans, monkeypatch, rec=REC):
    from shadow1_tpu import trace
    monkeypatch.setattr(trace, "compile_spans", lambda: list(spans))
    return run.load_module(f"benchmark/metrics/{name}.py").read(rec)


# The warm launch: the window loop's trace holds nested traces of inner
# jitted functions, then its lowering and its compile (or cache load);
# eager ops of the world build come before it.
WARM = [
    (TRACE, "iota", 1.0, 1.1), (LOWER, "jit(iota)", 1.1, 1.2),
    (COMPILE, "jit(iota)", 1.2, 1.5),
    (TRACE, "_where", 2.5, 2.75), (TRACE, "run_until", 2.0, 6.0),
    (LOWER, "jit(_where)", 2.75, 2.8), (LOWER, "jit(run_until)", 6.0, 8.0),
    (COMPILE, "jit(run_until)", 8.0, 20.0),
]


def test_first_window_loop_phases(monkeypatch):
    assert _read("trace_s", WARM, monkeypatch) == pytest.approx(6.0)
    assert _read("load_s", WARM, monkeypatch) == pytest.approx(12.0)
    assert _read("recompiles", WARM, monkeypatch) == 0


def test_a_second_window_loop_trace_is_a_recompile(monkeypatch):
    again = WARM + [(TRACE, "run_until", 40.0, 41.0),
                    (LOWER, "jit_run_until", 41.0, 41.5),
                    (COMPILE, "jit_run_until", 41.5, 50.0)]
    assert _read("recompiles", again, monkeypatch) == 1
    # set-up reads the first of each phase only
    assert _read("trace_s", again, monkeypatch) == pytest.approx(6.0)
    assert _read("load_s", again, monkeypatch) == pytest.approx(12.0)


def test_the_mesh_names_its_window_loop(monkeypatch):
    mesh = [(TRACE, "mesh_run_until", 0.0, 3.0),
            (LOWER, "jit_mesh_run_until", 3.0, 4.5),
            (COMPILE, "jit(mesh_run_until)", 4.5, 9.0)]
    assert _read("trace_s", mesh, monkeypatch) == pytest.approx(4.5)
    assert _read("load_s", mesh, monkeypatch) == pytest.approx(4.5)
    assert _read("recompiles", mesh, monkeypatch) == 0


@pytest.mark.parametrize("name", ["trace_s", "load_s", "recompiles"])
def test_a_renamed_window_loop_reads_nothing(name, monkeypatch):
    renamed = [(ev, fun.replace("run_until", "advance"), s, e)
               for ev, fun, s, e in WARM]
    assert _read(name, renamed, monkeypatch) is None


@pytest.mark.parametrize("name", ["trace_s", "load_s", "recompiles"])
def test_outside_a_run_there_is_nothing_to_read(name, monkeypatch):
    assert _read(name, WARM, monkeypatch, rec={}) is None


@pytest.mark.parametrize("name", ["trace_s", "load_s", "recompiles"])
def test_a_program_without_the_record_reads_nothing(name, monkeypatch):
    from shadow1_tpu import trace
    monkeypatch.delattr(trace, "compile_spans")
    mod = run.load_module(f"benchmark/metrics/{name}.py")
    assert mod.read(REC) is None
