"""mxnet_tpu.telemetry.device_table — where a captured window's device
time, idle gaps and host time went, reduced inside the program.

``mx.profiler.set_state('run')`` … ``set_state('stop')`` leaves a
``jax.profiler`` capture (an ``.xplane.pb``). Its device planes
(``/device:TPU:<n>``) carry one event per executed HLO op on the line
``XLA Ops`` — the event's name is the op's HLO line, which on the TPU
names the instruction and its operands but not its ``op_name`` — and one
per launched executable on ``XLA Modules``. The program gave every op a
name worth summing by: ``TrainStep`` traces its step under the scopes
``forward``, ``loss`` and ``optimizer_update`` (autodiff names the
backward ``transpose(jvp(forward))``), every Gluon block traces its
``forward`` under its own name, and the ops keep their own scopes
(``mla_attention``, ``moe_experts``, …) inside those. Those names are in
the step executable's own HLO text, ``TrainStep.program_text()``, which
the program has at no compile: the instruction an event names is looked
up there. No second compile, no join by hand. The names are those of
the build that *compiled* the executable: JAX's persistent cache keys a
program without its metadata, so a step loaded from an entry that an
older build wrote carries that build's names (none of the phases, before
PR 39) until the entry is written anew. The table's ``names`` says so
(``stale: ...``) where the text holds none of the phases, as it says
where more than one live TrainStep shares the executable's name
(``ambiguous: ...``) and where there is no text at all; it is None
where the names are the capture's own.

What else the capture cannot say by itself, and where it comes from:

* which phases a *fusion's inner instructions* came from (XLA fuses a
  weight's gradient product into its Adam update): the fused
  computation's ``op_name``\\ s in the same text;
* what the host did through an idle gap: the trace rings
  (``telemetry.trace``), laid on the capture's clock through the one
  annotation ``trace.mark_capture_clock()`` wrote at the capture's
  start (``perf_counter``'s reading as its argument). Retroactive events
  that no capture mirrors (``train_step::step``, ``xla::*``,
  ``host::gc``) are in the rings like every span.

An op that encloses others on its line (a ``conditional`` or ``while``
and the ops of its body) counts its *self* time, so that the ops' times
add up to what the line was busy with, not to more. Asynchronous copies
still run beside compute, so the sum can pass the busy time.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import statistics

from . import trace as _trace

__all__ = ["PHASES", "find_capture", "load_capture", "walk_program",
           "program_index",
           "classify", "reduce_capture", "host_table", "render",
           "render_host"]

DEVICE_PLANE = "/device:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP_EXECUTABLE = "jit_mx_train_step"
PHASES = ("forward", "loss", "backward", "optimizer_update", "unscoped")
NO_SPAN = "(no span)"
_NAMELESS = frozenset(["unscoped"])
_LONGEST_GAPS = 8          # idle gaps a table lists
_LONGEST_INTERVALS = 5     # intervals each list of the host table holds

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
# `transpose(jvp(forward))` -> wrappers "transpose(jvp(", base "forward"
_COMPONENT = re.compile(r"^((?:[\w\-]+\()*)([^()]*)\)*$")
# `backward` itself is opened only around the deterministic reduction's
# gathers, which no transpose names.
_ANCHORS = ("forward", "loss", "backward", "optimizer_update")


def find_capture(trace_dir):
    """The newest ``*.xplane.pb`` under `trace_dir`, or None."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load_capture(path):
    """The device lines and the clock mark of one capture, as plain
    data: ``names`` (each distinct HLO line once), ``ops`` and ``modules``
    (``{plane: [[name index or name, start_ns, dur_ns], ...]}``) and
    ``sync`` (``[capture_ns, perf_counter_ns]`` of the clock mark, None
    where the capture holds none)."""
    from jax.profiler import ProfileData

    names, index = [], {}
    ops, modules, sync = {}, {}, None
    for plane in ProfileData.from_file(path).planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                if sync is None:
                    sync = _find_sync(line)
            continue
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                rows = ops.setdefault(plane.name, [])
                for ev in line.events:
                    name = ev.name
                    i = index.get(name)
                    if i is None:
                        i = index[name] = len(names)
                        names.append(name)
                    rows.append([i, int(ev.start_ns), int(ev.duration_ns)])
            elif line.name == MODULES_LINE:
                modules.setdefault(plane.name, []).extend(
                    [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                    for ev in line.events)
    return {"names": names, "ops": ops, "modules": modules, "sync": sync}


def _find_sync(line):
    for ev in line.events:
        if ev.name.startswith(_trace.CLOCK_SYNC):
            for key, value in ev.stats:
                if key == "perf_counter_ns":
                    return [int(ev.start_ns), int(value)]
    return None


# -- names --------------------------------------------------------------------

def classify(op_name):
    """``(phase, scope path)`` of one ``op_name``: the phase from the first
    component that is one of the step's scopes (``transpose(...)`` around
    it: the backward), the path from the components after it without the
    primitive's own name at the end. ``("unscoped", path)`` where the step
    gave the op no phase."""
    parts = op_name.split(";", 1)[0].split("/")
    for i, part in enumerate(parts):
        m = _COMPONENT.match(part)
        if m and m.group(2) in _ANCHORS:
            phase = "backward" if "transpose(" in m.group(1) else m.group(2)
            return phase, tuple(parts[i + 1:-1])
    while parts and parts[0].startswith(("jit(", "pjit(")):
        parts = parts[1:]
    return "unscoped", tuple(parts[:-1])


def _phases_of(op_names):
    """The set of phases of some ``op_name``\\ s; what carries no phase
    counts only where nothing else does."""
    found = {classify(part)[0] for name in op_names
             for part in name.split(";")}
    named = found - {"unscoped"}
    return frozenset(named or found)


def walk_program(program_text):
    """``(computation, instruction, operands, called computations,
    op_name or None)`` of every instruction of an executable's HLO text,
    in the text's order."""
    current = None
    for line in program_text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = m.group(1)
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        head, _, tail = line[m.end():].partition("), ")
        # a fusion's computation, a conditional's branches, a loop's body
        tail = tail.split(", metadata={", 1)[0].split(", backend_config=")[0]
        found = _OP_NAME.search(line)
        yield (current, m.group(1), _OPERAND.findall(head),
               _OPERAND.findall(tail), found.group(1) if found else None)


def program_index(program_text):
    """``({instruction: op_name}, {fused computation: phases of its inner
    instructions})`` from an executable's HLO text
    (``TrainStep.program_text()``). An instruction whose own name tells
    no phase — one the compiler made (``copy-done``, ``slice-start``, a
    ``broadcast`` of a constant), or a copy of a parameter — takes the
    ``op_name`` of the nearest instruction that does: of what made its
    operands, else of what uses it, else of what calls its computation.
    A copy of a tensor is counted with what made the tensor, a prefetched
    weight with what reads it, a branch's untouched zeros with the
    conditional."""
    op_names, operands, users, inner = {}, {}, {}, {}
    home, caller = {}, {}    # instruction -> its computation -> who calls it
    for computation, name, reads, calls, op_name in walk_program(
            program_text):
        if op_name:
            op_names[name] = op_name
            inner.setdefault(computation, []).append(op_name)
        operands[name] = reads
        for operand in reads:
            users.setdefault(operand, []).append(name)
        home[name] = computation
        for called in calls:
            caller[called] = name
    fused = {computation: _phases_of(names)
             for computation, names in inner.items()}

    def phased(name):
        return name in op_names and classify(op_names[name])[0] != "unscoped"

    def nearest(name, edges):
        todo, seen = list(edges.get(name, ())), {name}
        while todo and len(seen) < 32:
            here = todo.pop(0)
            if here in seen:
                continue
            seen.add(here)
            if phased(here):
                return op_names[here]
            todo.extend(edges.get(here, ()))
        return None

    resolved, orphans = {}, []
    for name in operands:
        if not phased(name):
            found = nearest(name, operands) or nearest(name, users)
            if found:
                resolved[name] = found
            else:
                orphans.append(name)
    op_names.update(resolved)
    for name in orphans:          # what a branch hands back untouched
        above = caller.get(home[name])
        while above is not None and not phased(above):
            above = caller.get(home[above])
        if above is not None:
            op_names[name] = op_names[above]
    return op_names, fused


def _choose_program(program_texts, instructions):
    """``(op names, fused phases, note)`` of the step executable's text.
    Each text is indexed by itself: two live TrainSteps are both
    ``jit_mx_train_step`` and share ``fusion.N`` names, so one index over
    both would let the later overwrite the earlier. With more than one
    the text that holds most of the capture's `instructions` is read. The
    note says why the names may not be the capture's, None where they
    are."""
    texts = list(dict.fromkeys(t for t in program_texts if t))
    if not texts:
        return {}, {}, ("none: no live TrainStep has run, so no executable's "
                        "text names the ops; every op reads unscoped")
    indexes = [program_index(text) for text in texts]
    note = None
    if len(indexes) > 1:
        indexes.sort(key=lambda index: -len(instructions & index[0].keys()))
        note = ("ambiguous: %d live TrainSteps run executables of one name; "
                "phases and scopes are read from the text that holds most of "
                "the capture's instructions" % len(indexes))
    op_names, fused = indexes[0]
    if not any(classify(name)[0] != "unscoped"
               for name in op_names.values()):
        note = ("stale: the step executable's text names none of the step's "
                "phases: it was compiled by an older build (an entry of the "
                "persistent compile cache); clear the compile cache")
    return op_names, fused, note


def _parse_line(hlo_line):
    """(instruction name, kind, op_name or "", called computation or None)
    of one ``XLA Ops`` event name: the op's HLO line, which carries its
    ``op_name`` on some backends and not on the TPU's."""
    m = _INSTRUCTION.match(hlo_line)
    name = m.group(1) if m else hlo_line.split(" ", 1)[0].lstrip("%")
    kind = re.sub(r"(\.\d+|\.clone\d*)+$", "", name)
    op = _OP_NAME.search(hlo_line)
    calls = _CALLS.search(hlo_line)
    return (name, kind, op.group(1) if op else "",
            calls.group(1) if calls else None)


def _phase_row(phases):
    return "+".join(p for p in PHASES if p in phases)


# -- the reduction ------------------------------------------------------------

def _self_times(rows):
    """[(start, end, name index, self_ns)] of one line's events, sorted by
    start: an event that lies inside another takes its time out of the
    enclosing one's."""
    rows = sorted(rows, key=lambda r: (r[1], -r[2]))
    out, stack = [], []
    for i, start, dur in rows:
        end = start + dur
        while stack and start >= out[stack[-1]][1]:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= min(end, parent[1]) - start
        out.append([start, end, i, dur])
        stack.append(len(out) - 1)
    return out


def _merge(intervals):
    merged = []
    for s, e in intervals:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _host_spans(ring_events, sync):
    """[(start_ns, end_ns, name)] of the rings' complete events on the
    capture's clock, sorted by start."""
    if not sync:
        return []
    offset = sync[0] - sync[1]
    spans = []
    for ev in ring_events:
        if ev.get("ph") == "X":
            start = ev["ts"] * 1e3 + offset
            spans.append((start, start + ev.get("dur", 0) * 1e3,
                          ev["name"]))
    spans.sort()
    return spans


def _split_gap(lo, hi, spans, starts):
    """``{span name: ns}`` over [lo, hi): each instant goes to the span
    open then that started last (the innermost), or to :data:`NO_SPAN`."""
    open_ = [s for s in spans[:bisect.bisect_left(starts, hi)]
             if s[1] > lo]
    cuts = sorted({lo, hi} | {t for s in open_ for t in s[:2]
                              if lo < t < hi})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        over = [s for s in open_ if s[0] <= a and s[1] >= b]
        name = max(over)[2] if over else NO_SPAN
        out[name] = out.get(name, 0) + b - a
    return out


def reduce_capture(capture, ring_events=(), program_texts=(), depth=3,
                   steps=None, skip=0):
    """The device table of one capture (see the module's text).

    `capture` is :func:`load_capture`'s; `ring_events` the trace rings'
    events (``trace.chrome_trace()["traceEvents"]``); `program_texts` the
    HLO text of each live TrainStep's executable, for the ops' names and
    the fusions that mix phases (``names`` of the table says where they
    cannot be the capture's own: no text, an executable that an older
    build compiled, more than one live TrainStep; None where they are). The
    window runs from the start of the step executable's launch number
    `skip` to the end of its last one (starting a capture stalls the first
    step after it), or over every op where the capture holds no such
    launch; `steps` overrides the count of launches the sums are divided
    by. Scope paths are cut to `depth` components; the longest idle gaps
    are split by the host's spans. Times are ms a step, averaged over the
    capture's devices."""
    parsed = [_parse_line(line) for line in capture["names"]]
    op_names, fused, names_note = _choose_program(
        program_texts, {info[0] for info in parsed})
    spans = _host_spans(ring_events, capture.get("sync"))
    span_starts = [s[0] for s in spans]
    planes = sorted(capture["ops"])
    n_dev = max(len(planes), 1)

    by_exe, by_phase, by_scope, by_kernel = {}, {}, {}, {}
    op_ns = busy_ns = window_ns = 0
    n_steps = 0
    gaps = []
    for plane in planes:
        events = _self_times(capture["ops"][plane])
        modules = sorted((m[1], m[1] + m[2], m[0].split("(", 1)[0])
                         for m in capture.get("modules", {}).get(plane, ()))
        launches = [m for m in modules if m[2] == STEP_EXECUTABLE][skip:]
        if launches:
            lo, hi = launches[0][0], launches[-1][1]
        elif events:
            lo, hi = events[0][0], max(e[1] for e in events)
        else:
            continue
        n_steps = max(n_steps, len(launches))
        window_ns += hi - lo
        for start, end, name in modules:
            if lo <= start < hi:
                row = by_exe.setdefault(name, [0, 0])
                row[0] += 1
                row[1] += end - start
        inside = [e for e in events if lo <= e[0] < hi]
        module_starts = [m[0] for m in modules]
        for start, end, i, self_ns in inside:
            name, kind, op_name, calls = parsed[i]
            m = bisect.bisect_right(module_starts, start) - 1
            in_step = m >= 0 and modules[m][2] == STEP_EXECUTABLE
            if in_step and not op_name:
                op_name = op_names.get(name, "")
            phase, path = classify(op_name) if op_name else ("unscoped", ())
            phases = {phase}
            if in_step and fused.get(calls, _NAMELESS) != _NAMELESS:
                phases = fused[calls]
                if not op_name:     # the fusion's own line carries none
                    phase = next(p for p in PHASES if p in phases)
            row = _phase_row(phases)
            by_phase[row] = by_phase.get(row, 0) + self_ns
            scope = "/".join((phase,) + path[:depth])
            by_scope[scope] = by_scope.get(scope, 0) + self_ns
            krow = by_kernel.setdefault(kind, [0, 0])
            krow[0] += 1
            krow[1] += self_ns
            op_ns += self_ns
        merged = _merge((max(s, lo), min(e, hi)) for s, e, _, _ in inside)
        busy_ns += sum(e - s for s, e in merged)
        edges = [lo] + [t for piece in merged for t in piece] + [hi]
        starts = [e[0] for e in inside]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                j = bisect.bisect_left(starts, ge)
                nxt = parsed[inside[j][2]][0] if j < len(inside) else None
                gaps.append((ge - gs, gs, ge, nxt))

    n = steps or n_steps or 1
    per = 1e6 * n * n_dev                     # ns, all devices -> ms a step

    def rows(table, key):
        return [{key: k, "ms": v / per, "pct": 100.0 * v / op_ns}
                for k, v in sorted(table.items(),
                                   key=lambda kv: (-kv[1], kv[0]))]

    gap_rows = []
    for length, gs, ge, nxt in sorted(
            gaps, reverse=True)[:_LONGEST_GAPS]:
        host = _split_gap(gs, ge, spans, span_starts) if spans \
            else {NO_SPAN: length}
        gap_rows.append({
            "ms": length / 1e6, "ended_by": nxt or "window end",
            "host_ms": {k: v / 1e6 for k, v in sorted(
                host.items(), key=lambda kv: -kv[1])}})
    return {
        "steps": n, "devices": len(planes), "names": names_note,
        "window_ms": window_ns / per, "op_ms": op_ns / per,
        "busy_ms": busy_ns / per, "idle_ms": (window_ns - busy_ns) / per,
        "by_executable": [
            {"executable": k, "launches": v[0] / (n * n_dev),
             "ms": v[1] / per}
            for k, v in sorted(by_exe.items(), key=lambda kv: -kv[1][1])],
        "by_phase": rows(by_phase, "phase") if op_ns else [],
        "by_scope": rows(by_scope, "scope") if op_ns else [],
        "by_kernel": [
            {"kernel": k, "calls": v[0] / (n * n_dev), "ms": v[1] / per,
             "pct": 100.0 * v[1] / op_ns}
            for k, v in sorted(by_kernel.items(),
                               key=lambda kv: (-kv[1][1], kv[0]))],
        "idle_gaps": gap_rows,
    }


# -- the host, with or without a capture ---------------------------------------

def host_table(ring_events):
    """What the host did between successive ``train_step::dispatch``
    ends: their median, the longest intervals, and those most over their
    peers (the intervals within a fifth of their own length: a loop that
    reads the loss every eighth step has two kinds of interval, and a
    stall makes one of either kind longer than the rest of its kind),
    each with the collector's ms inside it (``host::gc``), the stepping
    thread's CPU share of it and its involuntary context switches
    (``train_step::step``'s ``cpu_ms`` and ``switches``): wall time
    without CPU time and with switches is a host that was taken away,
    wall time under ``host::gc`` a pause of Python's own. ``gc`` sums the
    collections between the first and the last dispatch. None where the
    rings hold fewer than two dispatches."""
    ends, steps, gcs = [], [], []
    for ev in ring_events:
        if ev.get("ph") != "X":
            continue
        end = ev["ts"] + ev.get("dur", 0)
        if ev["name"] == "train_step::dispatch":
            ends.append(end)
        elif ev["name"] == "train_step::step":
            steps.append((end, ev.get("args") or {}))
        elif ev["name"] == _trace.GC_SPAN:
            gcs.append((ev["ts"], end))
    ends.sort()
    if len(ends) < 2:
        return None
    steps.sort(key=lambda s: s[0])
    step_ends = [s[0] for s in steps]
    intervals = []
    for a, b in zip(ends, ends[1:]):
        # the step whose call returned right after this dispatch did
        j = bisect.bisect_left(step_ends, b)
        args = steps[j][1] if j < len(steps) else {}
        gc_us = sum(min(e, b) - max(s, a) for s, e in gcs
                    if e > a and s < b)
        ms = (b - a) / 1e3
        intervals.append({
            "ms": ms, "step": args.get("step"), "gc_ms": gc_us / 1e3,
            "cpu_pct": 100.0 * args["cpu_ms"] / ms
            if "cpu_ms" in args and ms else None,
            "switches": args.get("switches")})
    median = statistics.median(i["ms"] for i in intervals)
    lengths = sorted(i["ms"] for i in intervals)
    for i in intervals:
        peers = lengths[bisect.bisect_left(lengths, i["ms"] / 1.2):
                        bisect.bisect_right(lengths, i["ms"] * 1.2)]
        i["over_peers_ms"] = i["ms"] - (statistics.median(peers)
                                        if len(peers) >= 3 else median)
    inside = [(s, e) for s, e in gcs if e > ends[0] and s < ends[-1]]
    return {
        "intervals": len(intervals), "median_ms": median,
        "gc": {"collections": len(inside),
               "ms": sum(e - s for s, e in inside) / 1e3,
               "longest_ms": max((e - s for s, e in inside),
                                 default=0.0) / 1e3},
        "longest": sorted(
            intervals, key=lambda i: -i["ms"])[:_LONGEST_INTERVALS],
        "most_over_peers": sorted(
            intervals,
            key=lambda i: -i["over_peers_ms"])[:_LONGEST_INTERVALS],
    }


# -- text ------------------------------------------------------------------------

def render(table, rows=12):
    """The "Device" section of ``mx.profiler.dumps()``."""
    out = ["Device (%d steps on %d device(s); ms a step): busy %.3f, idle "
           "%.3f, sum of op time %.3f"
           % (table["steps"], table["devices"], table["busy_ms"],
              table["idle_ms"], table["op_ms"])]
    if table.get("names"):
        out.append("  names: %s" % table["names"])
    for title, key, label in (("by executable", "by_executable",
                               "executable"),
                              ("by phase", "by_phase", "phase"),
                              ("by scope", "by_scope", "scope"),
                              ("by kernel", "by_kernel", "kernel")):
        out.append("  %s" % title)
        for row in table[key][:rows]:
            extra = "  %7.2f launches" % row["launches"] \
                if "launches" in row else "  %5.1f %%" % row["pct"]
            if "calls" in row:
                extra += "  %8.1f calls" % row["calls"]
            out.append("    %-64s %10.3f%s" % (row[label], row["ms"], extra))
        if len(table[key]) > rows:
            rest = table[key][rows:]
            out.append("    %-64s %10.3f" % ("(%d more)" % len(rest),
                                             sum(r["ms"] for r in rest)))
    out.append("  longest idle gaps (ms; what the host did through each)")
    for gap in table["idle_gaps"]:
        out.append("    %9.3f before %-28s %s" % (
            gap["ms"], gap["ended_by"][:28],
            ", ".join("%s %.3f" % kv for kv in gap["host_ms"].items())))
    return "\n".join(out)


def render_host(host):
    """The "Host" section of ``mx.profiler.dumps()``."""
    out = ["Host (%d intervals between train_step::dispatch ends; median "
           "%.3f ms; %d collections of %.3f ms in all, the longest %.3f)"
           % (host["intervals"], host["median_ms"],
              host["gc"]["collections"], host["gc"]["ms"],
              host["gc"]["longest_ms"])]
    for title, key in (("longest", "longest"),
                       ("most over their peers", "most_over_peers")):
        out.append("  %s" % title)
        for i in host[key]:
            cpu = "n/a" if i["cpu_pct"] is None else "%.0f %%" % i["cpu_pct"]
            out.append("    %10.3f ms (%+9.3f)  step %-8s gc %8.3f ms  cpu "
                       "%-7s involuntary switches %s"
                       % (i["ms"], i["over_peers_ms"], i["step"],
                          i["gc_ms"], cpu, i["switches"]))
    return "\n".join(out)
