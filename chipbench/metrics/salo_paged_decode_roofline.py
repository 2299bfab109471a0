"""Share of its roofline that the paged decode kernel reaches, in percent:
the least time the chip needs for the window's decode work, the larger of
operations over peak FLOP/s and bytes over peak bandwidth, divided by the
kernel's summed device time in the trace. On v5e this work is bound by
bytes.

Per layer and decoded token at position t: operations 4 * H * hd * keys(t)
(scores and the weighted sum); bytes the K and V pages that hold an
attended key, at the slab's dtype, with their per-page scales when the slab
is int8, plus the query read and the output written in bf16. Summed over
the architecture's layer kinds (``attention_layers``), each times its
count."""

import numpy as np

from chipbench import work

KERNEL = "salo_paged_decode"


def operations_and_bytes(layers, engine, positions):
    positions = np.asarray(positions, np.int64)
    int8 = engine["kv_dtype"] == "int8"
    ops = nbytes = 0.0
    for count, H, Hkv, hd, window, sinks in layers:
        keys = work.attended(positions, window, sinks)
        pages = work.ring_pages(positions, window, sinks, engine["page"])
        page_bytes = 2 * engine["page"] * Hkv * hd * (1 if int8 else 2)
        page_bytes += 2 * 4 if int8 else 0
        ops += count * (4.0 * H * hd * float(keys.sum()))
        nbytes += count * (float(pages.sum()) * page_bytes
                           + positions.size * 2 * H * hd * 2)
    return ops, nbytes


def read(ctx):
    seconds = ctx.trace.op_seconds(KERNEL)
    if not seconds or not len(ctx.work.decode_positions):
        return None
    ops, nbytes = operations_and_bytes(ctx.arch.attention_layers(ctx.dims),
                                       ctx.engine, ctx.work.decode_positions)
    least = max(ops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
