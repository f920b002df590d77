"""Model operations of the served tokens over the window at the int8 peak."""
from layer import mfu_pct


def read(ctx):
    return mfu_pct(ctx)
