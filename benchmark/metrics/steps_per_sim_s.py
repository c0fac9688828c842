"""steps_per_sim_s (steps/sim-s): micro-steps the state counted over the
whole window, per simulated second advanced in it (program counter)."""


def read(rec):
    steps, sim_s = rec.get("steps_window"), rec.get("sim_s_window")
    if not steps or not sim_s:
        return None
    return steps / sim_s
