"""Mean duration, in ms, of the program's own spans named `span`
(`mxnet_tpu/telemetry/trace.py`) inside the traced window."""


def read(run, span):
    spans = run["program_spans_ms"].get(span)
    return sum(spans) / len(spans) if spans else None
