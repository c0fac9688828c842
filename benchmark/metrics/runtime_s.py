"""runtime_s (s): wall seconds from the start of run.py to the devices
found: the imports of JAX and the program, and the accelerator runtime's
start (host clock, span `runtime`)."""


def read(rec):
    return rec.get("spans", {}).get("runtime")
