"""Mean duration, in ms, of the harness's `jax.profiler.TraceAnnotation`
spans named `span` inside the traced window (read from the trace)."""


def read(run, span):
    spans = (run["trace"] or {}).get("host_ms", {}).get(span)
    return sum(spans) / len(spans) if spans else None
