"""Model FLOP/s over device time, as a share of the chips' peak: the
operations one step's forward and backward require (the model's
`flops_per_item`, recomputation not counted) over the device's busy time
per step, over chips times the bf16 peak of peaks.json."""


def read(run):
    t = run["trace"]
    if not t or not t["devices"] or not t["steps"]:
        return None
    flops = run["flops_per_item"] * run["items_per_step"]
    step_s = t["busy_ns"] / t["steps"] / 1e9
    peak = run["peak"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * flops / step_s / peak
