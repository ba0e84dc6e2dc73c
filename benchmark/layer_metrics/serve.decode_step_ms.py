"""Median wall time of an ``Engine.step()`` in which no prefill ran: one
whole decode step, host and device, ended by the token readback. From
the benchmark's own stamps around the call."""
import statistics


def read(obs):
    walls = [s["wall_s"] for s in obs.get("steps", ()) if not s["prefills"]]
    if not walls:
        return None
    obs["log"]("serve.decode_step_ms: %d decode-only steps of %d"
               % (len(walls), len(obs["steps"])))
    return 1e3 * statistics.median(walls)
