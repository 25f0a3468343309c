"""Device time of the halo exchange per round, in ms.

Summed durations of the `collective-permute` events of the profiler
trace of the profiled period, averaged over the chips, over that
period's rounds. Nothing is read where the trace holds none (one
chip).
"""

OP = "collective-permute"


def read(ctx):
    names = [n for n in ctx.device.op_seconds if OP in n]
    if not names:
        return None
    return sum(ctx.device.op_seconds[n] for n in names) \
        / ctx.device_rounds * 1e3
