"""Slots whose Mamba-2 state a decode step read and wrote, a mean over
the engine's recent decode steps: what the ``ssm_decode`` kernel's bytes
scale with, 8.5 MB a slot a step at the published shapes. From
``Engine.stats()["ssm"]["active_slots"]``; nothing on a program without
state-space layers (the parent)."""


def read(obs):
    ssm = obs.get("counters", {}).get("ssm")
    if not ssm:
        return None
    return ssm["active_slots"]
