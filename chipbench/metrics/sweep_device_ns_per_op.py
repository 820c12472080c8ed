"""sweep_device_ns_per_op: device time of the sweep program (found in the
trace by its jit name) per simulated NMP op completed in the traced
window, in nanoseconds: the simulator's device cost per simulated event."""


def read(rec: dict):
    t = rec.get("trace")
    ops = rec.get("traced_ops", 0)
    if not t or t["program_s"] <= 0 or ops <= 0:
        return None
    return 1e9 * t["program_s"] / ops
