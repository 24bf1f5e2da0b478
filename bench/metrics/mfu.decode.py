"""Model step: model operations of the tokens served in the traced window
over the window and the int8 peak, in % (decode-heavy cells)."""
from bench import readers


def read(ctx):
    return readers.mfu(ctx)
