"""Device milliseconds a traced step under no scope at all: what the
program has not named (XLA's own copies, parameters' layout changes); it
should stay a few per cent. Device seconds of the traced window booked
to the class, over ``len(obs["traced_steps"])``: of an average
``Engine.step()``'s device time (the decode step and its share of the
prefills), how much is this. From ``scope_time`` (the trace joined to
every program's HLO ``op_name``s); nothing when the trace or a cross-
check fails."""
import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "unnamed")
