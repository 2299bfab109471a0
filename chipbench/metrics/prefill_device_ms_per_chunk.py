"""Device milliseconds of the chunk-prefill program per chunk launched,
from the ``XLA Modules`` line of the trace (the jit of the engine's
``_chunk_fn``)."""

PROGRAM = "_chunk_fn"


def read(ctx):
    launches, seconds = ctx.trace.module_runs(PROGRAM)
    return seconds / launches * 1e3 if launches else None
