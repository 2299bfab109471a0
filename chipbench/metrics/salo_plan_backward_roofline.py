"""Share of their roofline that the two backward SALO kernels (dq and dk/dv)
reach together, in percent. Per backward pass of one layer: operations
10 * B * H * hd * pairs (the probabilities recomputed once, then dP, dQ, dK
and dV over the attended pairs); bytes q, k, v, o and dO read and dq, dk,
dv written in bf16, with two f32 row statistics. Times the dq calls the
trace holds, spread evenly over the architecture's layers, each kind
(``attention_layers``) taking its count's share; least time over both
kernels' summed device time. On v5e this work is bound by operations."""

from chipbench import work

KERNELS = ("salo_plan_backward_dq", "salo_plan_backward_dkv")


def operations_and_bytes(layer, S, B):
    """Operations and bytes of one call on a layer kind
    ``(count, H, Hkv, hd, window, sinks)``."""
    _, H, Hkv, hd, window, sinks = layer
    pairs = work.pairs_causal_prefix(S, window, sinks)
    ops = 10.0 * B * H * hd * pairs
    nbytes = B * S * (hd * 2 * (4 * H + 4 * Hkv) + 2 * 4 * H)
    return ops, float(nbytes)


def read(ctx):
    calls = ctx.trace.op_calls(KERNELS[0])
    seconds = sum(ctx.trace.op_seconds(k) for k in KERNELS)
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
