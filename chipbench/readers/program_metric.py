"""A counter or gauge of the program's own registry
(`mxnet_tpu.telemetry.metrics.REGISTRY`, collected once, which folds in
what the program keeps on the device): the value of family `name`
(summed over its label sets), over that of `over` where given, times
`scale`. None where the program has no such family or it was never
set."""


def _value(families, name):
    fam = families.get(name)
    if fam is None:
        return None
    children = fam.collect()
    return sum(child.value for _, child in children) if children else None


def read(run, name, over=None, scale=1.0):
    try:
        from mxnet_tpu.telemetry import metrics
    except ImportError:
        return None
    families = {fam.name: fam for fam in metrics.REGISTRY.collect()}
    value = _value(families, name)
    if value is None:
        return None
    if over is not None:
        below = _value(families, over)
        if not below:
            return None
        value = value / below
    return float(value) * scale
