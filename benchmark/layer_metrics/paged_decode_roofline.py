"""Roofline share of the paged decode kernel over the traced steps: the
least time the chip could take for the K/V bytes and the FLOPs of its
calls (one call a layer a step, from the live context lengths read after
each step and the configuration's shapes) over the kernel's device time,
by name, in the trace. Lengths are read after a step has released what
finished in it, so the bytes are counted a little low, never high."""
import peaks

KERNEL = "paged_decode"


def read(obs):
    trace, steps = obs.get("trace"), obs.get("traced_steps", ())
    if not trace or not steps or KERNEL not in trace["op_seconds"]:
        return None
    cfg, family = obs["config"], obs["family"]
    least = 0.0
    bounds = set()
    for s in steps:
        flops, moved = family.paged_decode_cost(
            cfg, s["context_tokens"], s["rows"])
        seconds, bound = peaks.least_seconds(flops, moved, obs["peaks"])
        least += seconds * cfg["num_hidden_layers"]
        bounds.add(bound)
    calls = trace["op_calls"][KERNEL]
    expected = len(steps) * cfg["num_hidden_layers"]
    obs["log"]("paged_decode_roofline: %d calls in the trace (%d steps x "
               "%d layers = %d), %.6f s on the device, least %.6f s, "
               "bound by %s" % (calls, len(steps),
                                cfg["num_hidden_layers"], expected,
                                trace["op_seconds"][KERNEL], least,
                                "/".join(sorted(bounds))))
    # steps whose kernels fell outside the trace would count work the
    # measured time does not hold
    least *= min(calls / expected, 1.0)
    return 100.0 * least / (trace["op_seconds"][KERNEL] * trace["chips"])
