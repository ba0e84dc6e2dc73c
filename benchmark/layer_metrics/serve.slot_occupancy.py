"""Mean over steps of occupied slots over the engine's slots, read from
the scheduler after each step."""


def read(obs):
    steps = obs.get("steps", ())
    if not steps:
        return None
    return 100.0 * sum(s["active_slots"] for s in steps) / (
        len(steps) * obs["max_slots"])
