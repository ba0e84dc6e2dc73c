"""Share of the device's idle time that no span of the program explains:
100 x (idle - the idle booked under spans named ``serving.*``) / idle,
with idle = window - busy of the reduced trace. What is left lies under
the benchmark's own spans, under none, or in rows the reduction lumped
together."""

PREFIX = "serving."


def read(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    idle = trace["window_s"] - trace["busy_s"]
    if idle <= 0:
        return 0.0
    named = sum(seconds for name, seconds in trace["idle_gaps"]
                if name.startswith(PREFIX))
    return 100.0 * (idle - named) / idle
