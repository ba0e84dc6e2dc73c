"""Roofline share of the decode step's differential-attention layers over
the traced steps: the least time the chip could take for what those
layers must move and compute (``family.diff_decode_step_cost``: the
attention projections read once a step; per live slot the full layer's
rows read by it and by each cross layer, min(length, window) rows of
each window layer's ring, one K/V row written to the pages and to each
ring, a query and an output row a layer) over the device time that
``scope_time`` books to the ``attn`` scope in the decode-step programs
of the trace: the ``diff_decode`` kernels and everything else the
layers run there (projections, norms, the row writes).

Lengths are read after a step has released what finished in it, so the
work is counted a little low, never high; traced steps whose decode
program fell outside the trace are scaled away. Nothing on a family
without the cost function, a trace without the scope, or when the trace
cannot be read."""
import peaks
import scope_time

KIND = "attn"


def decode_seconds():
    """(device seconds under ``attn`` in decode-step programs, runs of
    those programs) in the traced window, from the newest trace."""
    loaded = scope_time.load(scope_time.find_xplane())
    spans = loaded["spans"]
    if not spans:
        raise ValueError("the trace holds no bench.* span")
    table = scope_time.by_scope(
        loaded, min(s for _, s, _ in spans),
        max(s + d for _, s, d in spans))
    seconds = sum(s for (module, _, kind), s in table["scopes"].items()
                  if kind == KIND and "decode" in module)
    runs = sum(row["runs"] for (module, _), row in table["programs"].items()
               if "decode" in module)
    return seconds, runs


def read(obs):
    trace, steps = obs.get("trace"), obs.get("traced_steps", ())
    family = obs.get("family")
    cost = getattr(family, "diff_decode_step_cost", None)
    if not trace or not steps or cost is None:
        return None
    try:
        seconds, runs = decode_seconds()
    except Exception as exc:            # never out of a reader: run.py
        obs["log"]("diff_decode_roofline: nothing to read (%s: %s)"
                   % (type(exc).__name__, exc))
        return None
    if not seconds or not runs:
        obs["log"]("diff_decode_roofline: no %s time in a decode program"
                   % KIND)
        return None
    least = 0.0
    bounds = set()
    for s in steps:
        flops, moved = cost(obs["config"], s["rows"], s["context_tokens"])
        step_s, bound = peaks.least_seconds(flops, moved, obs["peaks"])
        least += step_s
        bounds.add(bound)
    obs["log"]("diff_decode_roofline: %d decode programs in the trace for "
               "%d steps, %.6f s under %s on the device, least %.6f s, "
               "bound by %s" % (runs, len(steps), seconds, KIND, least,
                                "/".join(sorted(bounds))))
    least *= min(runs / len(steps), 1.0)
    return 100.0 * least / seconds
