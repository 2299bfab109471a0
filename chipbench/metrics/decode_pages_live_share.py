"""Share of the pages the paged decode launches walk that hold a key a
live row attends, in percent: over the ``decode_launch`` spans of the
window, the sum of their ``pages_live`` over the sum of their
``pages_walked``. The engine records both at the launch
(``ContinuousEngine._advance_decode``): ``pages_walked`` from the kernel's
grid, every row and page of every layer; ``pages_live`` from each live
row's position. Source: the program's ``obs`` tracer."""


def read(ctx):
    args = [e["args"] for e in ctx.spans
            if e["name"] == "decode_launch" and e["args"]]
    walked = sum(a["pages_walked"] for a in args)
    return 100.0 * sum(a["pages_live"] for a in args) / walked \
        if walked else None
