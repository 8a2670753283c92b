"""Share of the traced window in which no operation ran on the device."""
from bench.lib import readers


def read(ctx):
    return readers.idle_share(ctx)
