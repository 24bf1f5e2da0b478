"""Roofline bounds of the served path's kernels, one module per kernel
name: ``bound_s(ctx, prog, events, peaks)`` gives the least seconds the
chip could take for a program's traced calls of that kernel."""
