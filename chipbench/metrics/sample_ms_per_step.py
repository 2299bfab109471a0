"""Host milliseconds per decode step spent in the engine's ``sample`` span
(the argmax over logits already on the host); the span has no children,
so its duration is its self time. Source: the program's ``obs`` tracer."""


def read(ctx):
    spans = [e["dur"] for e in ctx.spans if e["name"] == "sample"]
    return sum(spans) / len(spans) * 1e3 if spans else None
