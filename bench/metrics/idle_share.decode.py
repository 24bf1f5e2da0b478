"""Device: share of the traced window with no operation on the chip, in %
(decode-heavy cells)."""
from bench import readers


def read(ctx):
    return readers.idle_share(ctx)
