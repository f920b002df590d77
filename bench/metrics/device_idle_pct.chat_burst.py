"""Share of the profiled window in which no operation ran on the device."""
from layer import idle_pct


def read(ctx):
    return idle_pct(ctx)
