"""trace_s (s): the window loop's first Python trace plus its first
lowering to MLIR in this process, both of which run even when the
executable then loads from the compile cache (program span: the
program's compile record, benchmark/compile_record.py).  None where the
program keeps no such record, or holds no such event."""

import compile_record

PHASES = ("jaxpr_trace", "jaxpr_to_mlir_module")


def read(rec):
    first = {}
    for phase, seconds in compile_record.loop_spans(rec) or ():
        if phase in PHASES:
            first.setdefault(phase, seconds)
    if len(first) < len(PHASES):
        return None
    return sum(first.values())
