"""Wall time of the window over the rounds completed in it, in ms.

The window holds whole eval periods: every dispatch, eval and host gap
between the end of the measured call's first eval and its last.
"""


def read(ctx):
    return ctx.window.seconds / ctx.window.rounds * 1e3
