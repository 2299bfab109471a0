"""Model FLOPs of the training steps run in the traced window, over the
window times the chip's bf16 peak, in percent. Per sequence: three times
the forward pass (forward, and a backward of twice its matrix products)
over every position, the output head at every position; recomputation is
not counted."""

import numpy as np

from chipbench import work


def read(ctx):
    if not ctx.trace.n_devices or not ctx.work.steps:
        return None
    S, B = ctx.engine["seq"], ctx.engine["batch"]
    flops = 3 * B * work.forward_flops(ctx.arch, ctx.dims, np.arange(S),
                                       S)
    return 100.0 * flops * ctx.work.steps / (
        ctx.trace.window_s * ctx.peaks["bf16_flops"])
