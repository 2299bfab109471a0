"""Share of its roofline that the paged decode kernel reaches, in percent:
the least time the chip needs for the window's decode work, the larger of
operations over peak FLOP/s and bytes over peak bandwidth, divided by the
kernel's summed device time in the trace. On v5e this work is bound by
bytes.

Per layer and decoded token at position t: operations 4 * H * hd * keys(t)
(scores and the weighted sum); bytes the K and V pages that hold an
attended key, at the slab's dtype, with their per-page scales when the slab
is int8, plus the query read and the output written in bf16."""

import numpy as np

from chipbench import work

KERNEL = "salo_paged_decode"


def operations_and_bytes(m, engine, positions):
    positions = np.asarray(positions, np.int64)
    keys = work.attended(positions, m["window"], m["sinks"])
    pages = work.ring_pages(positions, m["window"], m["sinks"],
                            engine["page"])
    int8 = engine["kv_dtype"] == "int8"
    page_bytes = 2 * engine["page"] * m["Hkv"] * m["hd"] * (1 if int8 else 2)
    page_bytes += 2 * 4 if int8 else 0
    ops = 4.0 * m["H"] * m["hd"] * float(keys.sum())
    nbytes = float(pages.sum()) * page_bytes \
        + positions.size * 2 * m["H"] * m["hd"] * 2
    return m["L"] * ops, m["L"] * nbytes


def read(ctx):
    seconds = ctx.trace.op_seconds(KERNEL)
    if not seconds or not len(ctx.work.decode_positions):
        return None
    ops, nbytes = operations_and_bytes(ctx.dims, ctx.engine,
                                       ctx.work.decode_positions)
    least = max(ops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
