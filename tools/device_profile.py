#!/usr/bin/env python
"""Where a benchmark cell's device time goes: its runner under
``mx.profiler`` for a few steps, then ``mx.profiler.dumps()``.

The cell is built as ``chipbench/run.py`` builds it (same files, seed,
pool and warm-up), steps run as its window runs them (the loss read
every ``read_every``\\ th step, here inside a ``trace.span`` so that the
wait shows by name in every idle gap), and what is printed is the
program's own reduction (``telemetry/device_table.py``): device ms a step
by executable, phase, scope and kernel, the idle gaps split by what the
host did, the step executable's memory, and the Host section. Replaces
the by-hand joins of a kept trace with a sandbox compile's HLO text that
earlier PRs made (PERF.md section 5).

Needs the chip the cell needs; one process, no child.

    python tools/device_profile.py --workload <cell> --seed <n>
        [--steps 30] [--depth 3] [--out DIR] [--cut FILE.json.gz]

``--out`` also writes the table as JSON (and the timings below);
``--cut`` writes a two-step cut of the capture with the ring and the
fused computations' names, the form ``tests/test_device_table.py`` reads.
Step time is read three times over ``--steps`` steps each: before the
capture, inside it, and after it (what a capture costs while it runs).
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import resource
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
READ = "device_profile::read_loss"


def _steps(runner, n, read_every, span):
    """`n` steps as the benchmark's window runs them; ms a step by the
    host's clock, the last loss on the host before the clock stops."""
    start = time.perf_counter()
    for i in range(1, n + 1):
        loss = runner.step()
        if i % read_every == 0 or i == n:
            with span(READ):
                runner.read_loss(loss)
    return (time.perf_counter() - start) / n * 1e3


def _short_text(program_text, keep_fused):
    """The program text with every instruction cut to what
    `device_table.walk_program` reads: its name, the names it refers to,
    its `op_name`. Of the fused computations only `keep_fused` stay."""
    from mxnet_tpu.telemetry import device_table as dt

    out, current = [], None
    for computation, name, reads, calls, op_name in dt.walk_program(
            program_text):
        if computation.startswith("fused_computation") \
                and computation not in keep_fused:
            continue
        if computation != current:
            out += ["}"] * (current is not None)
            out.append("%%%s () -> () {" % computation)
            current = computation
        out.append("  %%%s = op(%s), %s%s" % (
            name, ", ".join("%" + o for o in reads),
            ", ".join("calls=%" + c for c in calls),
            ', metadata={op_name="%s"}' % op_name if op_name else ""))
    return "\n".join(out + ["}"] * (current is not None))


def cut_capture(capture, ring, program_text, steps=2):
    """A capture cut to `steps` launches of the step executable on the
    first device (those around the longest wait between two launches),
    small enough to keep beside a test: the events
    with their names cut to the instruction and the computation it calls,
    the ring's events of that stretch, and the program text cut to names
    (every `op_name` whole; of the fused computations those that mix
    phases)."""
    from mxnet_tpu.telemetry import device_table as dt

    plane = sorted(capture["ops"])[0]
    launches = sorted(m[1:] + m[:1] for m in capture["modules"][plane]
                      if m[0].startswith(dt.STEP_EXECUTABLE))
    # the stretch that holds the longest wait between two launches
    waits = [b[0] - a[0] - a[1] for a, b in zip(launches, launches[1:])]
    first = max(0, min(waits.index(max(waits)), len(launches) - steps))
    launches = launches[first:first + steps]
    lo, hi = launches[0][0], launches[-1][0] + launches[-1][1]
    names, index, ops, called = [], {}, [], set()
    for i, start, dur in capture["ops"][plane]:
        if lo <= start < hi:
            name, _, _, calls = dt._parse_line(capture["names"][i])
            called.add(calls)
            name = "%%%s = op()%s" % (name, ", calls=%" + calls
                                      if calls else "")
            j = index.setdefault(name, len(names))
            if j == len(names):
                names.append(name)
            ops.append([j, start - lo, dur])
    modules = [[m[0], m[1] - lo, m[2]] for m in capture["modules"][plane]
               if lo <= m[1] < hi]
    sync = capture["sync"]
    events = []
    if sync:
        offset = sync[0] - sync[1]
        events = [dict(e, ts=e["ts"] + (offset - lo) / 1e3)
                  for e in ring if e.get("ph") == "X"
                  and e["ts"] * 1e3 + offset < hi
                  and (e["ts"] + e["dur"]) * 1e3 + offset > lo]
        sync = [0, 0]
    mixed = {name for name, phases in dt.program_index(
        program_text or "")[1].items()
        if name in called and len(phases) > 1}
    return {"names": names, "ops": {plane: ops},
            "modules": {plane: modules}, "sync": sync, "ring": events,
            "program_text": _short_text(program_text or "", mixed)}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--depth", type=int, default=3)
    parser.add_argument("--out")
    parser.add_argument("--cut", metavar="FILE.json.gz")
    args = parser.parse_args(argv)

    from chipbench import harness

    bench = harness.load_bench(ROOT)
    cell, wl, cfg = harness.cell_files(ROOT, bench, args.workload)

    import jax
    from mxnet_tpu.compile import build_log, enable_jax_cache

    enable_jax_cache()
    devices = jax.devices()[:cell["chips"]]
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import train_step as ts_mod
    from mxnet_tpu.telemetry import device_table as dt
    from mxnet_tpu.telemetry import metrics, trace

    model = harness.load_module(ROOT, "models", cfg["model"])
    runner = harness.load_module(ROOT, "runners", wl["runner"]).setup(
        cfg, wl, args.seed, devices, model)
    for _ in range(3):
        runner.read_loss(runner.step())
    read_every = wl.get("read_every", 8)
    facts = {"workload": args.workload, "seed": args.seed,
             "device": devices[0].device_kind, "steps": args.steps}
    facts["step_ms_before"] = _steps(runner, args.steps, read_every,
                                     trace.span)

    tmp = tempfile.mkdtemp(prefix="device_profile_")
    try:
        mx.profiler.set_config(filename=tmp)
        trace.clear()
        mx.profiler.set_state("run")
        # starting a capture stalls the first step after it: two steps
        # run, and end, before the counted ones (skip=2 below)
        runner.step()
        runner.read_loss(runner.step())
        facts["step_ms_in_capture"] = _steps(runner, args.steps, read_every,
                                             trace.span)
        mx.profiler.set_state("stop")
        facts["step_ms_after"] = _steps(runner, args.steps, read_every,
                                        trace.span)

        built = len(build_log())
        facts["program"] = [ts.program_stats()
                            for ts in list(ts_mod._live_steps)]
        facts["records_of_the_demand"] = [
            r.kind for r in build_log()[built:]]
        rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        t0 = time.perf_counter()
        table = mx.profiler.device_table(depth=args.depth, skip=2)
        facts["device_table_s"] = time.perf_counter() - t0
        facts["peak_rss_mb_before_and_after"] = [
            rss0 / 1024.0,
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0]
        facts["memory_stats"] = {
            k: v for k, v in (devices[0].memory_stats() or {}).items()
            if "peak" in k or k == "bytes_in_use"}
        families = {f.name: f for f in metrics.REGISTRY.collect()}
        facts["counters"] = {
            name: [[list(k), c.value] for k, c in families[name].collect()]
            for name in ("mx_step_program_recompiled_total",
                         "mx_gc_pause_seconds_total",
                         "mx_train_step_involuntary_switches_total",
                         "mx_train_steps_total")}
        host = dt.host_table(trace.chrome_trace()["traceEvents"])
        print(dt.render(table, rows=40) if table
              else "no device line in the capture (not a TPU?)")
        print(dt.render_host(host))
        print(json.dumps(facts))
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, "device_table.json"),
                      "w") as f:
                json.dump({"facts": facts, "table": table, "host": host},
                          f, indent=1)
        if args.cut and table:
            steps = [ts for ts in list(ts_mod._live_steps)
                     if ts.program_text()]
            cut = cut_capture(mx.profiler._state["capture"],
                              trace.chrome_trace()["traceEvents"],
                              steps[0].program_text() if steps else None)
            with gzip.open(args.cut, "wt") as f:
                json.dump(cut, f)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
