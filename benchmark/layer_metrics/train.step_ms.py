"""Median host time from one step's end to the next: batch drawn,
uploaded, the step dispatched and ended by ``block_until_ready``."""
import statistics


def read(obs):
    steps = obs.get("step_s", ())
    if not steps:
        return None
    return 1e3 * statistics.median(steps)
