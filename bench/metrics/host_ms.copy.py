"""Host time per round in `run_fl`'s `copy` spans, in ms.

The host-to-device copy of each dispatch's batches: the `copy` spans
inside the untraced window, summed, over its rounds. Nothing is read
where the program records no such span.
"""

SPAN = "copy"


def read(ctx):
    w = ctx.window
    inside = [b - a for name, a, b in w.spans
              if name == SPAN and a >= w.t0_s and b <= w.t1_s]
    if not inside:
        return None
    return sum(inside) / w.rounds * 1e3
