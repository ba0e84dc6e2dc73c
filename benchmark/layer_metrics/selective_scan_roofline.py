"""Roofline share of the prefills' Mamba-1 layers over the traced window:
the least time the chip could take for the Mamba-1 layers of the traced
prefills (``family.selective_scan_prefill_cost`` at the engine's mean
real rows a prefill: each layer's weights read once; x, z, dt, B and C
in and y out a row; 2 FLOPs a matmul weight a row and 6 a state element
a row) over the device time that ``scope_time`` books to the ``ssm``
scope in the prefill programs of the trace: the ``selective_scan``
kernels and everything else run there, the gated memory units (which a
prefill runs on its last row alone) among them, and the padded rows of a
bucket. So the share is a lower bound. Nothing on a family without the
cost function, an engine that keeps no YOCO account (the parent), a
trace with no prefill, or when the trace cannot be read."""
import peaks
import scope_time

KIND = "ssm"


def prefill_seconds():
    """(device seconds under ``ssm`` in prefill programs, runs of those
    programs) in the traced window, from the newest trace."""
    loaded = scope_time.load(scope_time.find_xplane())
    spans = loaded["spans"]
    if not spans:
        raise ValueError("the trace holds no bench.* span")
    table = scope_time.by_scope(
        loaded, min(s for _, s, _ in spans),
        max(s + d for _, s, d in spans))
    seconds = sum(s for (module, _, kind), s in table["scopes"].items()
                  if kind == KIND and "prefill" in module)
    runs = sum(row["runs"] for (module, _), row in table["programs"].items()
               if "prefill" in module)
    return seconds, runs


def read(obs):
    family = obs.get("family")
    cost = getattr(family, "selective_scan_prefill_cost", None)
    yoco = (obs.get("counters") or {}).get("yoco")
    if not obs.get("trace") or cost is None or not yoco:
        return None
    try:
        seconds, runs = prefill_seconds()
    except Exception as exc:            # never out of a reader: run.py
        obs["log"]("selective_scan_roofline: nothing to read (%s: %s)"
                   % (type(exc).__name__, exc))
        return None
    if not seconds or not runs:
        obs["log"]("selective_scan_roofline: no %s time in a prefill "
                   "program" % KIND)
        return None
    rows = yoco["prompt_rows_per_prefill"]
    flops, moved = cost(obs["config"], rows)
    one, bound = peaks.least_seconds(flops, moved, obs["peaks"])
    obs["log"]("selective_scan_roofline: %d prefill programs in the trace, "
               "%.6f s under %s on the device, least %.6f s a prefill of "
               "%.0f rows, bound by %s"
               % (runs, seconds, KIND, one, rows, bound))
    return 100.0 * one * runs / seconds
