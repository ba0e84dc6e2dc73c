"""Roofline share of the decode step's Gated DeltaNet layers over the
traced steps: the least time the chip could take for what those layers
must move and compute (each layer's weights read once a step; each live
slot's float32 state read once and written once and its convolution tail
read and written; the token rows in and out; 2 FLOPs a weight a row and
6 a state element a slot) over the device time that ``scope_time`` books
to the ``gdn`` scope in the decode-step programs of the trace.

Reckoned from the configuration's ``linear_*`` keys, its
``linear_attention_layers`` count and each traced step's live slots (the
rows that held a sequence after the step) alone, so it needs no family
function and reads every family with such layers. Lengths are read after
a step has released what finished in it, so the work is counted a little
low, never high; traced steps whose decode program fell outside the
trace are scaled away. Whatever else a model runs under the scope (a
layer's norms and its residual add, GigaChat3.5's sandwich norms) is in
the device time and not in the least time, so the share is a lower bound. Nothing on a configuration without Gated DeltaNet
layers, a trace without the scope, or when the trace cannot be read."""
import peaks
import scope_time

KIND = "gdn"
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def least_seconds(cfg, rows, peak):
    """(seconds, bound) of one decode step's Gated DeltaNet layers over
    ``rows`` live slots."""
    h = cfg["hidden_size"]
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    kernel = cfg["linear_conv_kernel_dim"]
    size = DTYPE_BYTES[cfg["torch_dtype"]]
    conv_dim = 2 * hk * dk + hv * dv
    weights = (h * (conv_dim + hv * dv) + h * 2 * hv + hv * dv * h)
    state = hv * dk * dv
    moved = (weights + conv_dim * kernel) * size + rows * (
        2 * state * 4 + 2 * (kernel - 1) * conv_dim * size + 2 * h * size)
    flops = rows * (2 * weights + 6 * state)
    layers = cfg["linear_attention_layers"]
    return peaks.least_seconds(flops * layers, moved * layers, peak)


def decode_seconds(obs):
    """(device seconds under ``gdn`` in decode-step programs, runs of
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
    cfg = obs.get("config") or {}
    if not trace or not steps or not cfg.get("linear_attention_layers"):
        return None
    try:
        seconds, runs = decode_seconds(obs)
    except Exception as exc:            # never out of a reader: run.py
        obs["log"]("gdn_decode_roofline: nothing to read (%s: %s)"
                   % (type(exc).__name__, exc))
        return None
    if not seconds or not runs:
        obs["log"]("gdn_decode_roofline: no %s time in a decode program"
                   % KIND)
        return None
    least = 0.0
    bounds = set()
    for s in steps:
        step_s, bound = least_seconds(cfg, s["rows"], obs["peaks"])
        least += step_s
        bounds.add(bound)
    obs["log"]("gdn_decode_roofline: %d decode programs in the trace for %d "
               "steps, %.6f s under %s on the device, least %.6f s, bound "
               "by %s" % (runs, len(steps), seconds, KIND, least,
                          "/".join(sorted(bounds))))
    least *= min(runs / len(steps), 1.0)
    return 100.0 * least / seconds
