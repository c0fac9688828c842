"""load_s (s): the window loop's first backend compile in this process,
which is a compile, or a load from the persistent compile cache (program
span: the program's compile record, benchmark/compile_record.py).  None
where the program keeps no such record, or holds no such event."""

import compile_record


def read(rec):
    for phase, seconds in compile_record.loop_spans(rec) or ():
        if phase == "backend_compile":
            return seconds
    return None
