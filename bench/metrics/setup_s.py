"""Seconds from process start to the window's start.

JAX and TPU start-up, data and plan, the warm-up call (compile or cache
load), and the measured call's first eval period.
"""


def read(ctx):
    return ctx.window.t0_s - ctx.t_start
