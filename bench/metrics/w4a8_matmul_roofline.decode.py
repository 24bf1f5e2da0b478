"""Kernels: roofline share of w4a8_matmul in the decode program (device
trace; shapes from the compiled program), in %."""
from bench import readers


def read(ctx):
    return readers.kernel_share(ctx, "w4a8_matmul", "decode")
