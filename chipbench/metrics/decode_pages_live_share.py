"""Share of the pages the paged decode launches walk that hold a key a
live row attends, in percent: over the ``decode_launch`` spans of the
window, the sum of their ``pages_live`` over the sum of their
``pages_walked``. The engine records both at the launch
(``ContinuousEngine._advance_decode``): ``pages_walked`` from the Pallas
kernel's grid, each live row's live pages rounded up to whole 16-page
blocks (``paged_decode_walk``, per shard), in every layer (under the XLA
twin, every page of every row); ``pages_live`` from each live row's
position. Source: the program's ``obs`` tracer."""


def read(ctx):
    args = [e["args"] for e in ctx.spans
            if e["name"] == "decode_launch" and e["args"]]
    walked = sum(a["pages_walked"] for a in args)
    return 100.0 * sum(a["pages_live"] for a in args) / walked \
        if walked else None
