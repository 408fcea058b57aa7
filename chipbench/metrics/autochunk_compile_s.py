"""Host seconds in the program's AutoChunk compile stages: the sum of its
``compile.*`` spans (trace, estimate, search, select, lower, emit, replay,
autotune, plan_prefill) that are not nested in another ``compile.*``
span, over the whole run."""


def read(data):
    spans = data.get("compile_spans") or []
    total = sum(s for _, s in spans)
    return total if spans else None
