"""Share of its roofline that the forward SALO kernel reaches in the
training step, in percent: per call, operations 4 * B * H * hd * pairs
(scores and weighted sum over the attended pairs of a whole causal
sequence) and bytes q, k, v and the output in bf16 with the f32
log-sum-exp per row; times the calls the trace holds (remat runs it again
in the backward pass), least time over the kernel's summed device time.
On v5e this work is bound by operations."""

from chipbench import work

KERNEL = "salo_plan_attention"


def operations_and_bytes(m, S, B):
    pairs = work.pairs_causal_prefix(S, m["window"], m["sinks"])
    ops = 4.0 * B * m["H"] * m["hd"] * pairs
    nbytes = B * S * (m["hd"] * 2 * (2 * m["H"] + 2 * m["Hkv"])
                      + 4 * m["H"])
    return ops, float(nbytes)


def read(ctx):
    calls, seconds = ctx.trace.op_calls(KERNEL), ctx.trace.op_seconds(KERNEL)
    if not calls or not seconds:
        return None
    ops, nbytes = operations_and_bytes(ctx.dims, ctx.engine["seq"],
                                       ctx.engine["batch"])
    least = max(ops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * calls * least / seconds
