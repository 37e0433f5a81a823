"""Profiler trace -> device busy time, idle share and the breakdown.

The one reduction every run uses, so that every PR computes ``idle_share``
the same way:

* the traced window runs from the start of the first benchmark span
  (``TraceAnnotation`` on the host) to the end of the last;
* busy time is the union of the device's operation intervals inside that
  window, per device, averaged over the devices; on a TPU these are the
  program executions of the device plane's "XLA Modules" line (one
  interval per launch), on the CPU backend the XLA client threads' thunk
  events;
* ``device_ops`` names the device plane's "XLA Ops" (the operations inside
  the programs, the scan's ``%while`` among them) where it has them;
* an idle gap is a stretch of the window in which no operation ran; it is
  named by the benchmark span it falls in and by the innermost host event
  on that span's thread that covers the gap's midpoint (what the host was
  doing while the device waited);
* ``device_ops`` sums the time of each operation name.
"""
from __future__ import annotations

import glob
import os

TPU_BUSY, TPU_OPS = "XLA Modules", "XLA Ops"
CPU_THREAD = "tf_XLAPjRtCpuClient"
TOP = 10


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def short_name(name: str) -> str:
    """An HLO instruction's name (``%while.4 = (...) while(...)`` ->
    ``%while.4``), or the event name, at most 80 characters."""
    return name.split(" = ", 1)[0][:80]


def device_events(planes) -> list:
    """[(busy events, op events), ...] per device, each event a
    (start_ns, end_ns, name)."""
    out = []
    for p in planes:
        if p.name.startswith("/device:") and "CUSTOM" not in p.name:
            lines = {line.name: [(e.start_ns, e.start_ns + e.duration_ns,
                                  e.name) for e in line.events]
                     for line in p.lines if line.name in (TPU_BUSY, TPU_OPS)}
            busy = lines.get(TPU_BUSY, [])
            if busy:
                out.append((busy, lines.get(TPU_OPS) or busy))
    if out:
        return out
    for p in planes:                 # CPU backend: no device plane
        if p.name == "/host:CPU":
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for line in p.lines if line.name.startswith(CPU_THREAD)
                   for e in line.events if e.duration_ns > 0
                   and not e.name.startswith(("ThreadpoolListener",
                                              "end: "))]
            if evs:
                out.append((evs, evs))
    return out


def span_events(planes, names) -> tuple:
    """([(start_ns, end_ns, name)] of the benchmark's host spans,
    [(start_ns, end_ns, name)] of every other event on their threads)."""
    spans, other = [], []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in line.events]
            mine = [e for e in evs if e[2] in names]
            if mine:
                spans += mine
                other += [e for e in evs if e[2] not in names]
    return sorted(spans), other


def reduce(planes, names) -> dict:
    planes = list(planes)
    spans, host = span_events(planes, names)
    devs = device_events(planes)
    if not spans or not devs:
        return None
    lo, hi = spans[0][0], max(e for _, e, _ in spans)
    window_ns = hi - lo
    busy, ops, gaps = [], {}, []
    for evs, op_evs in devs:
        merged = union(clip([(s, e) for s, e, _ in evs], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        for s, e, name in op_evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                key = short_name(name)
                ops[key] = ops.get(key, 0) + d
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s))

    def doing(start, dur):
        t = start + dur / 2
        inside = [sp for sp in spans if sp[0] <= t < sp[1]]
        name = inside[-1][2] if inside else "outside spans"
        busy_host = [h for h in host if h[0] <= t < h[1]]
        if busy_host:
            name += ": " + min(busy_host, key=lambda h: h[1] - h[0])[2][:80]
        return name

    busy_s = sum(busy) / len(busy) * 1e-9
    return {
        "busy_s": busy_s,
        "window_s": window_ns * 1e-9,
        "idle_share": 1.0 - busy_s / (window_ns * 1e-9),
        "device_ops": [[k, v * 1e-9 / len(devs)] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[doing(s, d), d * 1e-9]
                      for d, s in sorted(gaps, reverse=True)[:TOP]],
    }


def reduce_dir(trace_dir: str, names) -> dict:
    """Reduce the one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    import jax
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        return None
    return reduce(jax.profiler.ProfileData.from_file(files[0]).planes, names)
