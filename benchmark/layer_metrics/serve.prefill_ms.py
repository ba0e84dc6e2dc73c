"""Median cost of one prefill: over the ``Engine.step()`` calls in which
k >= 1 prefills ran, (wall - the median decode-only step) / k. From the
benchmark's own stamps and the program's ``prefill_runs`` counter."""
import statistics


def read(obs):
    steps = obs.get("steps", ())
    decode = [s["wall_s"] for s in steps if not s["prefills"]]
    mixed = [s for s in steps if s["prefills"]]
    if not decode or not mixed:
        return None
    base = statistics.median(decode)
    obs["log"]("serve.prefill_ms: %d steps ran a prefill, at most %d in one"
               % (len(mixed), max(s["prefills"] for s in mixed)))
    return 1e3 * statistics.median(
        (s["wall_s"] - base) / s["prefills"] for s in mixed)
