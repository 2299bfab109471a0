"""Share of its roofline that the forward SALO kernel reaches in the
training step, in percent: per call, operations 4 * B * H * hd * pairs
(scores and weighted sum over the attended pairs of a whole causal
sequence) and bytes q, k, v and the output in bf16 with the f32
log-sum-exp per row; times the calls the trace holds (remat runs it again
in the backward pass), least time over the kernel's summed device time.
The calls spread evenly over the architecture's layers, each kind
(``attention_layers``) taking its count's share. On v5e this work is bound
by operations."""

from chipbench import work

KERNEL = "salo_plan_attention"


def operations_and_bytes(layer, S, B):
    """Operations and bytes of one call on a layer kind
    ``(count, H, Hkv, hd, window, sinks)``."""
    _, H, Hkv, hd, window, sinks = layer
    pairs = work.pairs_causal_prefix(S, window, sinks)
    ops = 4.0 * B * H * hd * pairs
    nbytes = B * S * (hd * 2 * (2 * H + 2 * Hkv) + 4 * H)
    return ops, float(nbytes)


def read(ctx):
    calls, seconds = ctx.trace.op_calls(KERNEL), ctx.trace.op_seconds(KERNEL)
    if not calls or not seconds:
        return None
    layers = ctx.arch.attention_layers(ctx.dims)
    n = sum(count for count, *_ in layers)
    share = 0.0
    for layer in layers:
        ops, nbytes = operations_and_bytes(layer, ctx.engine["seq"],
                                           ctx.engine["batch"])
        least = max(ops / ctx.peaks["bf16_flops"],
                    nbytes / ctx.peaks["hbm_bytes_per_s"])
        share += 100.0 * (layer[0] * calls / n) * least / seconds
    return share
