"""Scheduler: mean host time per decode step with no decode program
queued, from the end of one ``serve.decode.wait`` span to the end of the
next ``serve.decode.dispatch`` (engine spans; window's decode steps
outside the traced interval), in ms."""
from bench import phases


def read(ctx):
    return phases.host_gap_ms(ctx)
