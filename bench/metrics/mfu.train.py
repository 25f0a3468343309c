"""The whole training step's share of the chips' bf16 peak, in %.

Training FLOPs of a sample (3 x the forward's 2 x multiply-adds, from
the configuration's shapes) times the samples trained in the window,
over the window's seconds, the chips and the peak of their kind.
"""


def read(ctx):
    flops = ctx.train_flops_per_sample * ctx.samples
    return flops / ctx.window.seconds / (ctx.chips *
                                         ctx.peaks["bf16_flops"]) * 100
