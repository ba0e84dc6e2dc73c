"""The part of a decode step the device does not cover: the engine's own
medians of a step's host phases but the readback (scheduling, the
uploads, the dispatch, the accept loop), added up. From
``Engine.stats()["host_ms"]``, the engine's stamps at each boundary over
its recent steps in which no prefill ran; nothing when the program keeps
no such account."""
import statistics

PHASES = ("schedule", "upload", "dispatch", "accept")


def read(obs):
    host = obs.get("counters", {}).get("host_ms")
    if not host:
        return None
    value = sum(host[phase] for phase in PHASES)
    walls = [s["wall_s"] for s in obs.get("steps", ()) if not s["prefills"]]
    obs["log"]("serve.host_ms: %s; with the readback's %.4f ms the engine "
               "accounts for %.4f ms of a decode step, whose median wall "
               "from outside is %s ms"
               % (", ".join("%s %.4f" % (p, host[p]) for p in PHASES),
                  host["readback"], value + host["readback"],
                  "%.4f" % (1e3 * statistics.median(walls)) if walls
                  else "not read"))
    return value
