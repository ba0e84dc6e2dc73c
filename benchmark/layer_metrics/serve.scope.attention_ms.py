"""Device milliseconds a traced step under attention: the layers' ``attn``
and ``mla`` scopes, and the attention kernels (``flash_fwd``,
``flash_dq``, ``flash_dkv``, ``paged_decode``, ``paged_mixed``,
``mla_decode``) where one carries no scope. Device seconds of the traced
window booked to the class, over ``len(obs["traced_steps"])``: of an
average ``Engine.step()``'s device time (the decode step and its share
of the prefills), how much is this. From ``scope_time`` (the trace
joined to every program's HLO ``op_name``s); nothing when the trace or a
cross-check fails."""
import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "attention")
