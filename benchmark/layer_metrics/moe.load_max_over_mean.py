"""How unevenly a decode step's rows fall on the experts held here: the
largest load of one expert over the mean load (pairs routed here over
experts held), a mean over the engine's recent decode steps and the
expert layers. 1 is a perfectly even step; the grouped matmul's longest
group, and a deployment's slowest chip, grow with it. From
``Engine.stats()["moe"]``; nothing on a program without the counters."""


def read(obs):
    moe = obs.get("counters", {}).get("moe")
    if not moe:
        return None
    obs["log"]("moe.load_max_over_mean: %.2f pairs a layer a step on %d "
               "experts held, largest load %.2f, over %d decode steps"
               % (moe["pairs"], moe["experts_held"], moe["load_max"],
                  moe["recent_steps"]))
    return moe["load_max_over_mean"]
