"""KV pool: device time per decode-program run of the operations that
move pool bytes: the ``kv_write`` scope and the ``decoder`` loop's own
slices and restacks of the pool arrays (device trace, classified by the
compiled program's scopes), in ms."""
from bench import phases


def read(ctx):
    return phases.class_ms(ctx, "kv_pool")
