"""Host time a step spends inside the program's own spans named `span`
(`mxnet_tpu/telemetry/trace.py`), in ms: the sum of their durations
inside the traced window over the window's steps. Unlike
`program_span_ms` it is not a mean over spans, so a span that opens a
hundred times a step (`autograd::vjp`) reads as what the step pays for
it. None where the program records no such span."""


def read(run, span):
    spans = run["program_spans_ms"].get(span)
    steps = (run["trace"] or {}).get("steps")
    return sum(spans) / steps if spans and steps else None
