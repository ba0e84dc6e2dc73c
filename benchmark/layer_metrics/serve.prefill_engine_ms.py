"""Median time of one prefill as the engine lives it: from building the
prompt's ids to its first token on the host. From
``Engine.stats()["prefill_ms"]``, the engine's own stamps over its recent
prefills; nothing when the program keeps no such account."""


def read(obs):
    return obs.get("counters", {}).get("prefill_ms")
