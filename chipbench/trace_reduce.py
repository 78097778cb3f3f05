"""xplane -> events -> busy union, per-op sums, idle gaps, programs per
step. The only code that turns a profiler trace into numbers; checked on
a recorded trace by tests/chipbench_tests/test_trace_reduce.py.

An event row is ``[plane, line, name, start_ns, dur_ns]``. Device planes
are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
executed HLO op (the name is the whole HLO line), ``XLA Modules`` one per
launched program. The host plane ``/host:CPU`` has one line per thread and
carries the harness's ``jax.profiler.TraceAnnotation`` spans on the same
clock as the device lines.
"""
from __future__ import annotations

import bisect

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the harness's own spans
WINDOW, STEP = "chipbench::window", "chipbench::step"


def short_name(name):
    """An op event's name is its whole HLO line (kilobytes): keep what
    stands before `` = ``, without the leading ``%``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load_xplane(path, host_prefix="chipbench::"):
    """Rows of the device planes' ops and modules lines, names shortened,
    and of the host spans whose name starts with `host_prefix` (the host
    plane also holds every runtime call; only the harness's own
    annotations are kept)."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if device or ev.name.startswith(host_prefix):
                    rows.append([plane.name, line.name, short_name(ev.name),
                                 int(ev.start_ns), int(ev.duration_ns)])
    return rows


def busy_union(intervals, lo, hi):
    """Merged, sorted [start, end) pieces of `intervals` clipped to
    [lo, hi)."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _gaps(merged, lo, hi):
    edges = [lo] + [t for piece in merged for t in piece] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def _label(at_ns, host_spans, next_op):
    """The innermost host span open at `at_ns` (the one that started
    last), else what the device ran next."""
    open_ = [(s, name) for name, s, e in host_spans if s <= at_ns < e]
    if open_:
        return max(open_)[1]
    return "before " + (next_op or "window end")


def reduce_trace(rows, host_spans=(), window=WINDOW, step=STEP, top=8):
    """Everything the readers take from one traced window.

    `host_spans` are further ``(name, start_ns, end_ns)`` spans on the
    trace's clock (the program's own spans, shifted by the harness) that
    may label an idle gap. Returns None where the trace holds no window
    annotation; `devices` is 0 where no device op lies inside it.
    """
    wins = [r for r in rows if r[0] == HOST_PLANE and r[2] == window]
    if not wins:
        return None
    lo = wins[0][3]
    hi = lo + wins[0][4]
    # the harness's spans inside the window (steps and their parts)
    inner = [(r[2], r[3], r[3] + r[4]) for r in rows
             if r[0] == HOST_PLANE and r[2] != window and lo <= r[3] < hi]
    labels = inner + [s for s in host_spans if s[2] > lo and s[1] < hi]
    host_ms = {}
    for name, s, e in inner:
        host_ms.setdefault(name, []).append((e - s) / 1e6)
    devices = {}
    for plane in sorted({r[0] for r in rows if r[0].startswith(DEVICE_PLANE)}):
        ops = sorted((r[3], r[3] + r[4], r[2]) for r in rows
                     if r[0] == plane and r[1] == OPS_LINE
                     and r[3] + r[4] > lo and r[3] < hi)
        if not ops:
            continue
        merged = busy_union([(s, e) for s, e, _ in ops], lo, hi)
        sums = {}
        for s, e, name in ops:
            sums[name] = sums.get(name, 0) + min(e, hi) - max(s, lo)
        starts = [s for s, _, _ in ops]
        gaps = []       # only the longest get a label: there are many
        for gs, ge in sorted(_gaps(merged, lo, hi),
                             key=lambda g: g[0] - g[1])[:top]:
            i = bisect.bisect_left(starts, ge)
            nxt = ops[i][2] if i < len(ops) and ge < hi else None
            gaps.append((ge - gs, _label(gs, labels, nxt)))
        devices[plane] = {
            "busy_ns": sum(e - s for s, e in merged),
            "programs": sum(1 for r in rows if r[0] == plane
                            and r[1] == MODULES_LINE and lo <= r[3] < hi),
            "op_ns": sums,
            "gaps": gaps,
        }
    n = max(len(devices), 1)
    op_ns = {}
    for d in devices.values():
        for name, ns in d["op_ns"].items():
            op_ns[name] = op_ns.get(name, 0) + ns / n
    gaps = sorted((g for d in devices.values() for g in d["gaps"]),
                  reverse=True)
    return {
        "window_ns": hi - lo,
        "steps": len(host_ms.get(step, ())),
        "host_ms": host_ms,
        "devices": len(devices),
        # averaged over the chips used
        "busy_ns": sum(d["busy_ns"] for d in devices.values()) / n,
        "programs": sum(d["programs"] for d in devices.values()) / n,
        "device_ops": [[name, ns / 1e9] for name, ns in sorted(
            op_ns.items(), key=lambda kv: (-kv[1], kv[0]))[:top]],
        "idle_gaps": [[label, ns / 1e9] for ns, label in gaps[:top]],
    }
