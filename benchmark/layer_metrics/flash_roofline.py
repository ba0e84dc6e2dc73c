"""Roofline share of the flash attention kernels over the traced steps:
the least time for the FLOPs and bytes of every call the trace holds
(``flash_fwd``, ``flash_dq``, ``flash_dkv``; a recomputed forward is a
call) over their device time, by name."""
import peaks

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def read(obs):
    trace = obs.get("trace")
    if not trace or not any(k in trace["op_seconds"] for k in KERNELS):
        return None
    least = spent = 0.0
    for kernel in KERNELS:
        calls = trace["op_calls"].get(kernel, 0)
        if not calls:
            continue
        flops, moved = obs["family"].flash_cost(
            obs["config"], kernel, obs["batch"], obs["seq_len"])
        seconds, bound = peaks.least_seconds(flops, moved, obs["peaks"])
        on_device = trace["op_seconds"][kernel] * trace["chips"]
        obs["log"]("flash_roofline: %s x %d, %.6f s on the device, least "
                   "%.6f s, bound by %s"
                   % (kernel, calls, on_device, seconds * calls, bound))
        least += seconds * calls
        spent += on_device
    return 100.0 * least / spent
