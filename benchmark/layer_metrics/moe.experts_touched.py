"""The share of the experts held here that received at least one row in
a decode step, a mean over the engine's recent decode steps and the
expert layers: what a decode step's expert weight stream is
proportional to. From ``Engine.stats()["moe"]``; nothing on a program
without the counters."""


def read(obs):
    moe = obs.get("counters", {}).get("moe")
    if not moe or not moe["experts_held"]:
        return None
    return 100.0 * moe["experts_touched"] / moe["experts_held"]
