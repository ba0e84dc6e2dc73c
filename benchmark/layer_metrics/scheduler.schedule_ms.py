"""Median time a decode-only step spends deciding: expiring, admitting
(here: finding that nothing fits) and growing pages or preempting. From
``Engine.stats()["host_ms"]["schedule"]``, the engine's stamps around its
calls into the scheduler and the KV allocator; nothing when the program
keeps no such account."""


def read(obs):
    host = obs.get("counters", {}).get("host_ms")
    return host["schedule"] if host else None
