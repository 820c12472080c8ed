"""sweep_device_us_per_agent_fire: device time of the sweep program in the
traced window (found in the trace by its jit name) over the epochs of the
window's calls in which the batch's DQN step ran, in microseconds: what
one agent step costs the device, with the epoch scan it rides on."""


def read(rec: dict):
    t = rec.get("trace")
    fires = rec.get("agent_fires")
    if not t or t["program_s"] <= 0 or not fires:
        return None
    return 1e6 * t["program_s"] / fires
