"""Share of their roofline that the two backward SALO kernels (dq and dk/dv)
reach together, in percent. Per backward pass of one layer: operations
10 * B * H * hd * pairs (the probabilities recomputed once, then dP, dQ, dK
and dV over the attended pairs); bytes q, k, v, o and dO read and dq, dk,
dv written in bf16, with two f32 row statistics. Times the dq calls the
trace holds, least time over both kernels' summed device time. On v5e
this work is bound by operations."""

from chipbench import work

KERNELS = ("salo_plan_backward_dq", "salo_plan_backward_dkv")


def operations_and_bytes(m, S, B):
    pairs = work.pairs_causal_prefix(S, m["window"], m["sinks"])
    ops = 10.0 * B * m["H"] * m["hd"] * pairs
    nbytes = B * S * (m["hd"] * 2 * (4 * m["H"] + 4 * m["Hkv"])
                      + 2 * 4 * m["H"])
    return ops, float(nbytes)


def read(ctx):
    calls = ctx.trace.op_calls(KERNELS[0])
    seconds = sum(ctx.trace.op_seconds(k) for k in KERNELS)
    if not calls or not seconds:
        return None
    ops, nbytes = operations_and_bytes(ctx.dims, ctx.engine["seq"],
                                       ctx.engine["batch"])
    least = max(ops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * calls * least / seconds
