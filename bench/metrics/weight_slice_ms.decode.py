"""Model step: device time per decode-program run of the ``decoder``
loop's per-layer copies of the stacked packed weights that become kernel
operands (device trace, classified by the compiled program's scopes), in
ms."""
from bench import phases


def read(ctx):
    return phases.class_ms(ctx, "weight_slice")
