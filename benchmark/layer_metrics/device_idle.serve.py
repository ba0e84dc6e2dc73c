"""Share of the traced window in which no operation ran on the device:
1 - union of the device's operation intervals over the window."""


def read(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
