"""Model step: mean device time of one run of the decode program in the
traced window (device trace), in ms."""
from bench import readers


def read(ctx):
    return readers.step_ms(ctx, "decode")
