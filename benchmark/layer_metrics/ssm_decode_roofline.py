"""Roofline share of the Mamba-2 decode kernel over the traced steps: the
least time the chip could take for the bytes and the FLOPs of its calls
(``family.ssm_decode_cost`` for the slots that held a sequence after each
step: a slot's float32 state read once and written once, its inputs and
its output) over the kernel's device time, by name, in the trace. The
calls a step are what the mix's ``kernels`` entry says the compiled
decode step holds (a key of the configuration, or a number). The kernel
also carries the idle slots' rows through, which no algorithm needs and
is not counted, so the share is a little low where slots are idle, never
high. Nothing on a program without the kernel (the parent)."""
import peaks

KERNEL = "ssm_decode"


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
        flops, moved = family.ssm_decode_cost(cfg, s["rows"])
        seconds, bound = peaks.least_seconds(flops, moved, obs["peaks"])
        least += seconds * calls_a_step
        bounds.add(bound)
    calls = trace["op_calls"][KERNEL]
    expected = len(steps) * calls_a_step
    obs["log"]("ssm_decode_roofline: %d calls in the trace (%d steps x %d "
               "calls = %d), %.6f s on the device, least %.6f s, bound by "
               "%s" % (calls, len(steps), calls_a_step, expected,
                       trace["op_seconds"][KERNEL], least,
                       "/".join(sorted(bounds))))
    # steps whose kernels fell outside the trace would count work the
    # measured time does not hold
    least *= min(calls / expected, 1.0)
    return 100.0 * least / (trace["op_seconds"][KERNEL] * trace["chips"])
