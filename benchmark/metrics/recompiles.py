"""recompiles (count): Python traces of the window loop in this process
after its first (program counter: the program's compile record,
benchmark/compile_record.py).  A trace inside the window is a launch
that did not reuse the warm launch's executable.  None where the program
keeps no such record, or holds no such trace."""

import compile_record


def read(rec):
    n = sum(1 for phase, _s in compile_record.loop_spans(rec) or ()
            if phase == "jaxpr_trace")
    return n - 1 if n else None
