"""Executables JAX compiled or loaded inside the measured window (count).

A non-zero count means a program (for example a new pair-schedule
ladder) compiled while users wait on the simulation.
"""


def read(ctx):
    return ctx.compiles
