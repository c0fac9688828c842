"""step_us (us): device-busy time of the traced slice per micro-step, the
busy time averaged over the cell's chips and divided by the micro-steps
the state counted over the same launches (device trace, state counter)."""


def read(rec):
    tr, steps = rec.get("trace"), rec.get("steps_slice")
    if not tr or not tr["busy_ns"] or not steps:
        return None
    busy = sum(tr["busy_ns"].values()) / len(tr["busy_ns"])
    return busy / 1e3 / steps
