"""Mean wall time of the engine's decode steps in the profiled window, from
the engine's own ``repro_serve_decode_step_seconds`` histogram."""
from layer import decode_step_ms


def read(ctx):
    return decode_step_ms(ctx)
