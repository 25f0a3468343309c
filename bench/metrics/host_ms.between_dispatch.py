"""Host time per round outside the dispatch spans, in ms.

The window minus its `dispatch` spans, over its rounds: batch sampling
and stacking, the host-to-device copy, eval and Python, all read from
`run_fl`'s own spans.
"""


def read(ctx):
    w = ctx.window
    return (w.seconds - w.dispatch_s) / w.rounds * 1e3
