"""Seconds or records of the program's compile log
(`mxnet_tpu.compile.build_log()`, fed by JAX's own compile events):
records of the given `kind` (`trace`, `lower`, `build`, or a list of
them) made before the process had completed `before_step` training
steps, without those whose outcome is `outcome_not` and without traces
nested inside another trace. `what` is `seconds` or `count`. None where
the program keeps no such log."""


def read(run, kind, what, before_step, outcome_not=None):
    try:
        from mxnet_tpu.compile import build_log
    except ImportError:
        return None
    kinds = (kind,) if isinstance(kind, str) else tuple(kind)
    records = [r for r in build_log()
               if r.kind in kinds and not r.inner and r.step < before_step
               and (outcome_not is None or r.outcome != outcome_not)]
    if what == "count":
        return float(len(records))
    return sum(r.seconds for r in records)
