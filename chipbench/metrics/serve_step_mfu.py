"""Model FLOPs of every prompt and output token processed in the traced
window, over the window times the chip's bf16 peak, in percent.

Each prompt token of each prefill chunk and each decoded token counts its
layers' matrix products and the keys each layer attends; the output
head counts once per sampled position (a prompt's last, each decode step).
Positions come from the benchmark's own record of the window."""

import numpy as np

from chipbench import work


def read(ctx):
    w = ctx.work
    if not ctx.trace.n_devices or (not w.prefill_chunks
                                   and not len(w.decode_positions)):
        return None
    prompt = [np.arange(a, b) for a, b in w.prefill_chunks]
    positions = np.concatenate(prompt + [w.decode_positions])
    flops = work.forward_flops(ctx.arch, ctx.dims, positions, w.head_rows)
    return 100.0 * flops / (ctx.trace.window_s * ctx.peaks["bf16_flops"])
