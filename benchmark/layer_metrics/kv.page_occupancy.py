"""Mean over steps of the share of usable KV pages that requests hold:
1 - free_blocks / usable_blocks, read from the allocator after each
step."""


def read(obs):
    steps = obs.get("steps", ())
    if not steps:
        return None
    free = sum(s["free_blocks"] for s in steps) / len(steps)
    return 100.0 * (1.0 - free / obs["usable_blocks"])
