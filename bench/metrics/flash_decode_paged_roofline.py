"""Kernels: roofline share of flash_decode_paged in the decode program
(device trace; attended lengths from the host counters), in %."""
from bench import readers


def read(ctx):
    return readers.kernel_share(ctx, "flash_decode_paged", "decode")
