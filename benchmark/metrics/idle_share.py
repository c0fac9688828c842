"""idle_share (%): the share of the traced slice in which no operation ran
on the device, averaged over the cell's chips (device trace)."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["busy_ns"] or tr["window_ns"] <= 0:
        return None
    busy = sum(tr["busy_ns"].values()) / len(tr["busy_ns"])
    return 100.0 * (1.0 - busy / tr["window_ns"])
