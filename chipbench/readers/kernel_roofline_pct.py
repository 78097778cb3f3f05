"""Share of its roofline that a Pallas kernel reached, in %, per call
site, from the traced window's longest device ops.

`run["trace"]["device_ops"]` holds the eight longest ops only, by HLO
instruction name (a `pallas_call` named `k` runs as `k.<n>`, or as
`jvp_k_.<n>` and the like under a transformation): every call site is
its own name. For each name there that is a call site of one of
`kernels`: the least time the chip could take for that site's calls in
the window (the larger of operations over peak FLOP/s and bytes over
peak bytes/s, from the model module's `kernel_work(cfg, batch, block_q,
block_k)`, one call a step, the blocks named by the metric's `args`)
over the name's device seconds. The mean over the sites
found; None where none is among the eight, or the model counts no such
kernel. A site is read whole or not at all, so an op that fell off the
list never inflates the share."""
import json
import os
import re

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _call_site_of(name, kernel):
    return re.search(r"(?:^|_)%s_*(?:\.\d+)?$" % re.escape(kernel), name)


def read(run, kernels, model, config, block_q, block_k):
    t = run["trace"]
    if not t or not t["devices"] or not t["steps"]:
        return None
    from chipbench import harness

    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        files = {c["name"]: c["file"] for c in json.load(f)["configs"]}
    with open(os.path.join(_ROOT, files[config])) as f:
        cfg = json.load(f)
    module = harness.load_module(_ROOT, "models", model)
    if not hasattr(module, "kernel_work"):
        return None
    batch = run["items_per_step"] // cfg.get("bptt", 1) // run["chips"]
    work = module.kernel_work(cfg, batch, block_q, block_k)
    shares = []
    for name, seconds in t["device_ops"]:
        for kernel in kernels:
            if kernel in work and _call_site_of(name, kernel) and seconds:
                flops, nbytes = work[kernel]
                least = max(flops / run["peak"]["bf16_flops_per_s"],
                            nbytes / run["peak"]["hbm_bytes_per_s"])
                shares.append(100.0 * least * t["steps"] / seconds)
    return sum(shares) / len(shares) if shares else None
