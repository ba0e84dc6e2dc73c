"""Device milliseconds a traced step under the model's two ends: ``embed``
(the token gather) and ``lm_head`` (final norm, projection, the argmax
or the cross-entropy, the counters that ride back behind the tokens).
Device seconds of the traced window booked to the class, over
``len(obs["traced_step_s"])``: of a training step's device time, how
much is this. From ``scope_time`` (the trace joined to every program's
HLO ``op_name``s); nothing when the trace or a cross-check fails."""
import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "head")
