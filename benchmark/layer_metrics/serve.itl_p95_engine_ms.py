"""95th percentile of the time between consecutive output tokens of one
request, each stamped by the engine when its step's tokens reached the
host (a prefill's token and the same step's first decode token get their
own stamps). From ``Engine.stats()["itl_ms"]``, over the engine's recent
gaps; nothing when the program keeps no such account."""


def read(obs):
    itl = obs.get("counters", {}).get("itl_ms")
    if not itl:
        return None
    outside = obs.get("end_to_end", {}).get("itl_p95_ms")
    obs["log"]("serve.itl_p95_engine_ms: %.4f ms by the engine's stamps "
               "(median %.4f), %s ms by the benchmark's"
               % (itl["p95"], itl["p50"],
                  "not read" if outside is None else "%.4f" % outside))
    return itl["p95"]
