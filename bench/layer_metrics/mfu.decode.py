"""Operations the served tokens required over the chip's peak, across the traced window."""
from bench.lib import readers


def read(ctx):
    return readers.mfu(ctx, readers.lm_flops(ctx))
