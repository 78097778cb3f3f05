"""Host time a step loses to a pause the program records as spans named
`span` (`host::gc`, the collector's, `mxnet_tpu/telemetry/trace.py`), in
ms: the sum of their durations inside the traced window over the window's
steps. A pause is rare, so no span in the window reads 0.0, not nothing:
but only where the program keeps the counter family `family`, which says
that it records such pauses at all. None where it has no such family (the
parent of the PR that added it), or the window has no steps."""


def read(run, span, family):
    try:
        from mxnet_tpu.telemetry import metrics
    except ImportError:
        return None
    if family not in {fam.name for fam in metrics.REGISTRY.collect()}:
        return None
    steps = (run["trace"] or {}).get("steps")
    if not steps:
        return None
    return sum(run["program_spans_ms"].get(span, ())) / steps
