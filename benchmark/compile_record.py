"""The window loop's part of the program's compile record, for the
set-up readers (trace_s, load_s, recompiles).

The program keeps JAX's compile-phase spans in one list,
`shadow1_tpu.trace.compile_spans()`: (event, fun_name, start_s, end_s).
The window loop is `run_until`, or `mesh_run_until` on a mesh; its trace
event names the function, its lowering and compile events the module
(`jit(run_until)`, or `jit_run_until` in other JAX versions)."""

LOOP = ("run_until", "mesh_run_until")
PREFIX = "/jax/core/compile/"


def _is_loop(fun_name):
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        fun_name = fun_name[4:-1]
    return fun_name.removeprefix("jit_") in LOOP


def loop_spans(rec):
    """[(phase, seconds)] of the window loop's compile phases in this
    process, oldest first; phase is `jaxpr_trace`,
    `jaxpr_to_mlir_module` or `backend_compile`.  None outside a run's
    record, or where the program keeps no compile record (an older
    build)."""
    if not rec.get("spans"):
        return None
    try:
        from shadow1_tpu.trace import compile_spans
    except ImportError:
        return None
    return [(event.removeprefix(PREFIX).removesuffix("_duration"),
             end - start)
            for event, fun_name, start, end in compile_spans()
            if event.startswith(PREFIX) and _is_loop(fun_name)]
