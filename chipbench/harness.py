"""The harness behind chipbench/run.py. It knows no model, cell or metric
by name: a cell is `workloads/<cell>.json`, its configuration the file that
BENCHMARK.json names, a runner `runners/<name>.py`, a model
`models/<name>.py`, a per-layer metric `layer_metrics/<metric>.json` with
its reader `readers/<name>.py` — all found by name under the directory
that holds BENCHMARK.json, and loaded from their files.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time

from chipbench import trace_reduce
from chipbench.trace_reduce import STEP, WINDOW

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
clock = time.perf_counter
READ = "chipbench::read_loss"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchError(Exception):
    """The benchmark's own files are wrong; no result is printed."""


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_bench(root):
    """BENCHMARK.json, with every name and unit held to the driver's
    character rules."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            names = [entry["name"]] + list(entry.get("reduced", ())) \
                + [entry[k] for k in ("config", "traffic") if k in entry]
            for name in names:
                if not NAME.match(name):
                    raise BenchError("bad name %r in %s" % (name, group))
            if "unit" in entry and not UNIT.match(entry["unit"]):
                raise BenchError("bad unit %r of %s"
                                 % (entry["unit"], entry["name"]))
    return bench


def load_module(root, kind, name):
    """`<root>/chipbench/<kind>/<name>.py`, loaded from its file, so that
    a new file is found with no edit anywhere."""
    if not NAME.match(name):
        raise BenchError("bad %s name %r" % (kind, name))
    path = os.path.join(root, "chipbench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_%s_%s" % (kind, name.replace("-", "_").replace(".", "_")),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cell_files(root, bench, name):
    """(cell entry, workload file, configuration file) of one cell."""
    cells = [c for c in bench["workloads"] if c["name"] == name]
    if not cells:
        raise BenchError("no cell %r in BENCHMARK.json" % name)
    cell = cells[0]
    wl = _json(os.path.join(root, "chipbench", "workloads", name + ".json"))
    conf = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    cfg = _json(os.path.join(root, conf["file"]))
    if wl["config"] != cell["config"] or wl["chips"] != cell["chips"]:
        raise BenchError("workloads/%s.json disagrees with BENCHMARK.json"
                         % name)
    return cell, wl, cfg


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


class _Compiles:
    """Programs compiled, or loaded from the persistent cache, since the
    last reset (JAX reports both under the same event)."""

    def __init__(self):
        self.n = 0
        self._on = False

    def reset(self):
        if not self._on:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(self._event)
            self._on = True
        self.n = 0

    def _event(self, name, _secs, **_kw):
        if name == _COMPILE_EVENT:
            self.n += 1


_compiles = _Compiles()


def _compare(checks, name, value, above=None, below=None):
    """Record a compared number beside its limits in `checks` (the
    result line's last key); True where it lies strictly between them
    (a NaN lies nowhere)."""
    entry = {"value": value}
    if above is not None:
        entry["above"] = above
    if below is not None:
        entry["below"] = below
    checks[name] = entry
    return (above is None or value > above) and \
        (below is None or value < below)


def check_losses(reads, pool_size, chk, checks):
    """What is wrong with the losses. `reads`: (call, loss) of every
    step whose loss was read, the first step's first: all finite, the
    first near ln(`chk.classes`). The step trains on pool batch `call %
    pool_size`, so two reads of one batch see the same rows under the
    values of two moments: of the batches read twice, the one read
    furthest apart has to have its loss fallen by over
    `chk.loss_fall_min` between its first and its last read. The same
    rows in the same program, so the fall has no spread from batch to
    batch (the reads of different batches differ by as much as the loss
    falls over a window, and "last read under first" was a coin)."""
    losses = [v for _, v in reads]
    classes = chk["classes"]
    if not all(math.isfinite(v) for v in losses):
        return ["non-finite loss: %r" % (reads,)]
    problems = []
    # An untrained classifier sits at ln(classes) plus the spread of its
    # logits (default init: about 1.3x).
    if not _compare(checks, "first_loss_over_ln_classes",
                    losses[0] / math.log(classes), 0.7, 1.5):
        problems.append("first loss %.4f is not near ln(%d)"
                        % (losses[0], classes))
    by_batch = {}
    for call, v in reads:
        by_batch.setdefault(call % pool_size, []).append((call, v))
    pairs = [(seen[-1][0] - seen[0][0], seen[-1][0], seen[0], seen[-1])
             for seen in by_batch.values() if len(seen) > 1]
    if not pairs:
        problems.append("no pool batch was read twice (calls %r, %d "
                        "batches): the loss's fall is not compared"
                        % ([c for c, _ in reads], pool_size))
        return problems
    _, _, (c0, v0), (c1, v1) = max(pairs)
    if not _compare(checks, "same_batch_loss_fall", v0 - v1,
                    chk["loss_fall_min"]):
        problems.append("the loss on pool batch %d did not fall by over %g "
                        "from call %d to call %d: %.6f -> %.6f"
                        % (c0 % pool_size, chk["loss_fall_min"], c0, c1,
                           v0, v1))
    return problems


SAMPLE = 1024


def sample_masters(runner):
    """name -> host copy (fp32) of an evenly spaced sample of each
    trainable master's elements, up to SAMPLE of them, taken by one
    jitted program, built at each call and not kept (the step donates
    the arrays themselves, so they are copied out)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def spaced(v):
        flat = v.reshape(-1)
        return flat[::max(1, flat.size // SAMPLE)][:SAMPLE].astype(
            jnp.float32)

    sampler = jax.jit(lambda masters: {k: spaced(v)
                                       for k, v in masters.items()})
    got = jax.device_get(sampler(runner.masters()))
    return {k: np.asarray(v, np.float32) for k, v in got.items()}


def check_motion(cfg, before, after, updates, checks):
    """What is wrong with how the masters moved over `updates` steps,
    by the sample `sample_masters` took before and after them: the share
    of sampled elements that changed is over `check.moved_share_min` (a
    skipped update, or masters too coarse to take the stated step, moves
    few); under Adam, whose step is about the learning rate whatever the
    gradient, mean |change| / (the stated learning rate x updates) lies
    inside `check.mean_step_over_lr` (a rate other than the stated one
    moves it by its factor)."""
    import numpy as np

    chk = cfg["check"]
    moved = np.abs(np.concatenate([after[k] - before[k]
                                   for k in sorted(before)]))
    problems = []
    share = float(np.mean(moved > 0))
    if not _compare(checks, "masters_moved_share", share,
                    chk["moved_share_min"]):
        problems.append("%.4f of the sampled master elements moved in %d "
                        "updates" % (share, updates))
    if "mean_step_over_lr" in chk:
        lo, hi = chk["mean_step_over_lr"]
        ratio = float(np.mean(moved)) / (
            cfg["optimizer"]["params"]["learning_rate"] * updates)
        if not _compare(checks, "mean_step_over_lr", ratio, lo, hi):
            problems.append("the masters moved %.4g stated learning rates "
                            "an update, not %g to %g" % (ratio, lo, hi))
    return problems


def _bf16(a):
    """`a` rounded to bf16 and held in fp32."""
    import jax.numpy as jnp
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def check_reference(cfg, wl, seed, runner, model, checks=None):
    """Logits and loss of the system's evaluation forward, with the
    trained values, against the model's plain reference on the check's
    seeded sample; in training mode where `check.batch_statistics` says
    so. Where `check.rms_over_bf16_operands` has the cell's dtype, the
    logits' rms error is compared as a multiple of the one the reference
    itself makes with every product's operands rounded to bf16 (the
    model's `reference_forward` takes `operand`); else their largest
    error over the largest logit, against `check.tolerance`. Returns
    (facts, problems)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    checks = {} if checks is None else checks
    dtype = wl.get("dtype") or "float32"
    tol = cfg["check"]["tolerance"][dtype]
    over = cfg["check"].get("rms_over_bf16_operands", {}).get(dtype)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 32)), 0x5EED)
    x, y = model.make_batch(cfg, key, cfg["check"]["samples"])
    mode = {"train": True} if cfg["check"].get("batch_statistics") else {}
    got, got_loss = runner.eval_forward(x, y, **mode)
    params = runner.params()
    ref = jax.jit(lambda p, a: model.reference_forward(cfg, p, a, **mode))
    want = np.asarray(ref(params, x), np.float32)
    want_loss = float(model.reference_loss(jnp.asarray(want), y))
    err = float(np.abs(got - want).max() / np.abs(want).max())
    rms = float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))
    # Logits within tol * max|logit| of the reference move a softmax
    # cross-entropy by at most twice that; a relative bound on the loss
    # itself fails for no fault where the loss is small.
    loss_tol = 2 * tol * float(np.abs(want).max())
    facts = {"logits_rel_err": err, "logits_rms_rel_err": rms,
             "loss": got_loss,
             "reference_loss": want_loss, "tolerance": tol, "dtype": dtype}
    problems = []
    if not np.isfinite(got).all():
        problems.append("non-finite logits from the evaluation forward")
    if over is not None:
        low = np.asarray(jax.jit(lambda p, a: model.reference_forward(
            cfg, p, a, operand=_bf16, **mode))(params, x), np.float32)
        ratio = float(np.sqrt(np.mean((got - want) ** 2)
                              / np.mean((low - want) ** 2)))
        facts["logits_rms_over_bf16_operands"] = ratio
        if not _compare(checks, "logits_rms_over_bf16_operands", ratio,
                        below=over):
            problems.append("logits' rms error is %.3g times the "
                            "reference's with bf16 operands, limit %g"
                            % (ratio, over))
    elif not _compare(checks, "logits_rel_err", err, below=tol):
        problems.append("logits differ from the reference: rel err %.3g, "
                        "tolerance %.3g (%s)" % (err, tol, dtype))
    if not _compare(checks, "loss_gap_to_reference",
                    abs(got_loss - want_loss), below=loss_tol):
        problems.append("evaluation loss %.6f, reference %.6f, may differ "
                        "by %.3g" % (got_loss, want_loss, loss_tol))
    return facts, problems


def memory_peak_bytes(devices):
    """The larger of `peak_bytes_in_use` and `peak_bytes_reserved`,
    whichever the backend reports, on the fullest chip; and which it was.
    """
    best, which = 0, None
    for d in devices:
        stats = d.memory_stats() or {}
        for key in ("peak_bytes_in_use", "peak_bytes_reserved"):
            if stats.get(key, 0) > best:
                best, which = int(stats[key]), key
    return best, which


def _program_spans(lo_s, hi_s):
    """The program's own spans (telemetry/trace.py, on perf_counter's
    clock) that lie inside [lo_s, hi_s]: (name, start_s, end_s)."""
    from mxnet_tpu.telemetry import trace as ptrace

    out = []
    for ev in ptrace.chrome_trace()["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        s = ev["ts"] / 1e6
        e = s + ev["dur"] / 1e6
        if s >= lo_s and e <= hi_s:
            out.append((ev["name"], s, e))
    return out


def _read(runner, loss):
    """(the call that made `loss`, its value on the host)."""
    return runner.calls - 1, runner.read_loss(loss)


def _plain_window(runner, seconds, read_every, seen):
    """Steps run free; the loss is read on the host every `read_every`th
    step, as a training script's logging does; the window closes on the
    first such read at or after `seconds` by which some pool batch has
    had its loss read twice (`seen`: the batches that set-up read), so
    that `check_losses` has a fall to compare."""
    attempted = failed = 0
    losses = []
    seen, twice = set(seen), False
    raised = None
    start = now = clock()
    while True:
        attempted += 1
        try:
            loss = runner.step()
            if attempted % read_every == 0:
                losses.append(_read(runner, loss))
                now = clock()
                if not math.isfinite(losses[-1][1]):
                    failed += 1
                batch = losses[-1][0] % len(runner.pool)
                twice = twice or batch in seen
                seen.add(batch)
                if now - start >= seconds and twice:
                    break
        except Exception as exc:   # the step is the system under test
            raised, failed, now = exc, failed + 1, clock()
            break
    return {"attempted": attempted, "failed": failed, "reads": losses,
            "window_s": now - start, "raised": raised,
            "done": attempted - (1 if raised else 0)}


def _traced_window(runner, steps, read_every, keep=None):
    """`steps` steps inside jax.profiler.trace, each in a span of the
    harness, the whole in one; the trace goes to a temporary directory
    outside the checkout and is reduced before the directory is removed.
    """
    import jax
    from jax.profiler import TraceAnnotation
    from mxnet_tpu.telemetry import trace as ptrace

    tmp = tempfile.mkdtemp(prefix="chipbench_trace_")
    losses = []
    try:
        ptrace.clear()
        jax.profiler.start_trace(tmp)
        try:
            # Starting the profiler stalls the first step after it: two
            # steps run, and end, before the window opens.
            runner.step()
            before = _read(runner, runner.step())
            lo = clock()
            with TraceAnnotation(WINDOW):
                for i in range(1, steps + 1):
                    with TraceAnnotation(STEP):
                        loss = runner.step()
                    if i % read_every == 0 or i == steps:
                        with TraceAnnotation(READ):
                            losses.append(_read(runner, loss))
            hi = clock()
        finally:
            jax.profiler.stop_trace()
        spans = _program_spans(lo, hi)
        paths = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        rows = trace_reduce.load_xplane(paths[0]) if paths else []
        if keep:
            with gzip.open(keep, "wt") as f:
                json.dump(rows, f)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    # The program's spans move onto the trace's clock through the window
    # span, which both clocks saw open.
    wins = [r for r in rows if r[2] == WINDOW]
    shifted = []
    if wins:
        off = wins[0][3] - lo * 1e9
        shifted = [(n, s * 1e9 + off, e * 1e9 + off) for n, s, e in spans]
    reduced = trace_reduce.reduce_trace(rows, shifted)
    by_name = {}
    for name, s, e in spans:
        by_name.setdefault(name, []).append((e - s) * 1e3)
    failed = sum(1 for _, v in losses if not math.isfinite(v))
    return {"attempted": steps, "failed": failed,
            "reads": [before] + losses,
            "window_s": hi - lo, "raised": None, "done": steps,
            "trace": reduced, "program_spans_ms": by_name}


def _read_layers(root, declared, cell_name, run):
    """name -> value of the cell's per-layer metrics, each by the reader
    its `layer_metrics/<metric>.json` names; a reader that finds nothing
    to read returns None and its metric stays out."""
    values = {}
    for metric in declared:
        if not applies(metric, cell_name):
            continue
        spec = _json(os.path.join(root, "chipbench", "layer_metrics",
                                  metric["name"] + ".json"))
        reader = load_module(root, "readers", spec["reader"])
        value = reader.read(run, **spec.get("args", {}))
        if value is not None:
            values[metric["name"]] = value
    return values


def run_cell(root, bench, name, seed, seconds, trace, devices, t0,
             say=print, keep_trace=None):
    """One run of one cell on `devices`; returns the result object."""
    import jax

    _, wl, cfg = cell_files(root, bench, name)
    model = load_module(root, "models", cfg["model"])
    runner_mod = load_module(root, "runners", wl["runner"])
    peaks = _json(os.path.join(root, "chipbench", "peaks.json"))
    kind = devices[0].device_kind
    if kind not in peaks:
        raise BenchError("no peaks for device kind %r in peaks.json" % kind)

    phases = {}
    jax.block_until_ready(jax.device_put(0.0, devices[0]))
    phases["import_s"] = clock() - t0
    t = clock()
    runner = runner_mod.setup(cfg, wl, seed, devices, model)
    phases["build_s"] = clock() - t
    t = clock()
    reads = [_read(runner, runner.step())]
    phases["first_step_s"] = clock() - t
    t = clock()
    runner.step()
    reads.append(_read(runner, runner.step()))  # the third, ended
    phases["warm_steps_s"] = clock() - t
    setup_s = clock() - t0
    say(json.dumps({"setup_s": setup_s, "phases": phases}))

    # Outside set-up and the window: a sample of the masters, read again
    # after the window.
    masters_before = sample_masters(runner)
    calls_before = runner.calls

    _compiles.reset()
    read_every = wl.get("read_every", 8)
    if trace:
        win = _traced_window(runner, wl.get("trace_steps", 30), read_every,
                             keep_trace)
    else:
        win = _plain_window(runner, seconds, read_every,
                            {c % len(runner.pool) for c, _ in reads})
    compiles = _compiles.n
    peak_bytes, peak_key = memory_peak_bytes(devices)
    updates = runner.calls - calls_before
    masters_after = sample_masters(runner)
    reads += win["reads"]

    checks = {}
    problems = []
    if win["raised"] is not None:
        problems.append("a step raised: %r" % (win["raised"],))
    if not _compare(checks, "compiles_in_window", compiles, below=1):
        problems.append("%d programs compiled inside the window" % compiles)
    problems += check_motion(cfg, masters_before, masters_after, updates,
                             checks)
    facts, ref_problems = check_reference(cfg, wl, seed, runner, model,
                                          checks)
    problems += check_losses(reads, len(runner.pool), cfg["check"], checks)
    problems += ref_problems
    say(json.dumps({
        "steps": win["done"], "window_s": win["window_s"],
        "compiles_in_window": compiles, "updates": updates,
        "pool_batches": len(runner.pool), "reads": reads,
        "reference": facts, "memory_counter": peak_key,
        "problems": problems}))

    if trace:
        declared = bench["per_layer"]
        values = _read_layers(root, declared, name, {
            "phases": phases, "trace": win["trace"],
            "program_spans_ms": win["program_spans_ms"],
            "items_per_step": runner.items_per_step,
            "flops_per_item": model.flops_per_item(cfg),
            "peak": peaks[kind], "chips": len(devices),
            "memory_peak_bytes": peak_bytes})
        say(json.dumps({"program_spans_mean_ms": {
            k: sum(v) / len(v)
            for k, v in sorted(win["program_spans_ms"].items())}}))
    else:
        declared = bench["end_to_end"]
        items = win["done"] * runner.items_per_step
        values = {"train_rate": items / win["window_s"], "setup_s": setup_s}
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in declared
               if applies(m, name) and m["name"] in values}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": jax.device_count(), "memory_peak_bytes": peak_bytes}
    result = {"correct": not problems, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if trace and win["trace"]:
        device["busy_s"] = win["trace"]["busy_ns"] / 1e9
        device["window_s"] = win["trace"]["window_ns"] / 1e9
        result["breakdown"] = {"device_ops": win["trace"]["device_ops"],
                               "idle_gaps": win["trace"]["idle_gaps"]}
    result["checks"] = checks           # the last key: numbers and limits
    return result


def main(argv, t0):
    parser = argparse.ArgumentParser(prog="chipbench/run.py")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--keep-trace", metavar="FILE.json.gz",
                        help="with --trace 1, also write the trace's event "
                             "rows there (the driver never passes this)")
    args = parser.parse_args(argv)
    try:
        bench = load_bench(ROOT)
        chips = cell_files(ROOT, bench, args.workload)[0]["chips"]
    except BenchError as exc:
        print("chipbench: %s" % exc, file=sys.stderr)
        return 2

    import jax
    from mxnet_tpu.compile import enable_jax_cache

    cache_dir = enable_jax_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("chipbench needs a TPU; JAX found platform %r"
              % devices[0].platform, file=sys.stderr)
        return 2
    if len(devices) < chips:
        print("cell %s needs %d chips; JAX reports %d"
              % (args.workload, chips, len(devices)), file=sys.stderr)
        return 2
    print(json.dumps({"jax_cache_dir": cache_dir}), flush=True)
    try:
        result = run_cell(ROOT, bench, args.workload, args.seed,
                          args.seconds, args.trace, devices[:chips], t0,
                          keep_trace=args.keep_trace)
    except BenchError as exc:
        print("chipbench: %s" % exc, file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    for name, entry in result["checks"].items():
        print("check %s %s" % (name, json.dumps(entry)), file=sys.stderr)
    return 0
