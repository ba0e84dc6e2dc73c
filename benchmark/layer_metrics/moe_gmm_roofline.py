"""Roofline share of the grouped expert matmul over the traced programs:
the least time the chip could take for what the routed experts of every
traced program must move and compute (``family.moe_gmm_cost``: the
weights of the experts that received a row, once a layer a program, the
token rows in and out, 6 x hidden x width FLOPs a pair) over the
kernel's device time, by name, in the trace.

The counts are the program's own: every compiled step hands back, a
layer at a time, the pairs routed to the experts held here and the
experts that received a row (``Engine.stats()["moe"]["calls"]``, newest
last; the traced programs are the last ones the engine ran). They are
counted low where unsure: a program's pairs are scaled to its real rows
(a prefill's padding and a decode step's idle slots are routed too, and
no algorithm needs them), a layer's bound is taken over its two calls
together, and programs whose kernels fell outside the trace are scaled
away. Nothing on a program without the counters."""
import peaks

KERNEL = "moe_gmm"
CALLS_A_LAYER = 2       # gate/up, then down


def read(obs):
    trace, steps = obs.get("trace"), obs.get("traced_steps", ())
    moe = obs.get("counters", {}).get("moe")
    if (not trace or not steps or not moe
            or KERNEL not in trace["op_seconds"]):
        return None
    cfg, family = obs["config"], obs["family"]
    programs = sum(1 + s["prefills"] for s in steps)
    calls = moe["calls"][-programs:]
    least = 0.0
    bounds = set()
    for rows, rows_run, _decode, pairs, touched in calls:
        real = rows / max(rows_run, 1)
        for layer_pairs, layer_touched in zip(pairs, touched):
            needed = layer_pairs * real
            flops, moved = family.moe_gmm_cost(
                cfg, rows, needed, min(layer_touched, needed))
            seconds, bound = peaks.least_seconds(flops, moved, obs["peaks"])
            least += seconds
            bounds.add(bound)
    seen = trace["op_calls"][KERNEL]
    expected = sum(len(c[3]) for c in calls) * CALLS_A_LAYER
    obs["log"]("moe_gmm_roofline: %d calls in the trace (%d programs, %d "
               "expected), %.6f s on the device, least %.6f s, bound by %s"
               % (seen, len(calls), expected, trace["op_seconds"][KERNEL],
                  least, "/".join(sorted(bounds))))
    # programs whose kernels fell outside the trace would count work the
    # measured time does not hold
    least *= min(seen / max(expected, 1), 1.0)
    return 100.0 * least / (trace["op_seconds"][KERNEL] * trace["chips"])
