"""Share of the engine's recent decode steps dispatched while the tokens
of the step before were still on the device: how much of the time the
host runs a step ahead of the chip, so that the device has the next
program queued while the host accepts, schedules and admits. From
``Engine.stats()["ahead"]["share"]``; nothing on a program that keeps no
such account (the parent) or before its first decode step."""


def read(obs):
    ahead = obs.get("counters", {}).get("ahead")
    if not ahead:
        return None
    return ahead["share"]
