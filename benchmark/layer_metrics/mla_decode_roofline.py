"""Roofline share of the absorbed latent-attention decode kernel over the
traced steps: the least time the chip could take for the FLOPs and the
bytes of its calls (``family.mla_decode_cost`` from the live context
lengths read after each step and the configuration's shapes: a cached
row read once for every head and both dots) over the kernel's device
time, by name, in the trace. The calls a step are what the mix's
``kernels`` entry says the compiled decode step holds (a key of the
configuration, or a number), not a layer count taken by name. Lengths
are read after a step has released what finished in it, so the work is
counted a little low, never high. Nothing on a program without the
kernel (the parent)."""
import peaks

KERNEL = "mla_decode"


def read(obs):
    trace, steps = obs.get("trace"), obs.get("traced_steps", ())
    if not trace or not steps or KERNEL not in trace["op_seconds"]:
        return None
    cfg, family = obs["config"], obs["family"]
    calls_a_step = obs["traffic"]["kernels"][KERNEL]
    if isinstance(calls_a_step, str):
        calls_a_step = cfg[calls_a_step]
    least = 0.0
    bounds = set()
    for s in steps:
        flops, moved = family.mla_decode_cost(
            cfg, s["context_tokens"], s["rows"])
        seconds, bound = peaks.least_seconds(flops, moved, obs["peaks"])
        least += seconds * calls_a_step
        bounds.add(bound)
    calls = trace["op_calls"][KERNEL]
    expected = len(steps) * calls_a_step
    obs["log"]("mla_decode_roofline: %d calls in the trace (%d steps x %d "
               "calls = %d), %.6f s on the device, least %.6f s, bound by "
               "%s" % (calls, len(steps), calls_a_step, expected,
                       trace["op_seconds"][KERNEL], least,
                       "/".join(sorted(bounds))))
    # steps whose kernels fell outside the trace would count work the
    # measured time does not hold
    least *= min(calls / expected, 1.0)
    return 100.0 * least / (trace["op_seconds"][KERNEL] * trace["chips"])
