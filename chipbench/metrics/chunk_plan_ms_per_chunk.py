"""Host milliseconds per prefill chunk spent building its plan, tables,
write targets and page-skip arrays before the chunk program is launched:
the mean duration of the engine's ``chunk_plan`` spans (recorded in
``ContinuousEngine._advance_prefill``; the span has no children, so its
duration is its self time). Source: the program's ``obs`` tracer."""


def read(ctx):
    spans = [e["dur"] for e in ctx.spans if e["name"] == "chunk_plan"]
    return sum(spans) / len(spans) * 1e3 if spans else None
