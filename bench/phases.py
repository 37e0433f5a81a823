"""Profiler trace + the traced program's scope map -> device self time by
scan-step phase, scheme-hook time and the share in mixed fusions.

The scope map is ``repro.netsim.obs.profile.trace_scopes`` of the
executable the traced launch ran: every HLO instruction's phase (the innermost
``netsim.*`` scope on its name stack, ``other`` without one), the scheme
hook whose ``hook.*`` scope is on that stack (if any), and whether it is
a fusion whose fused instructions carry more than one phase.

* The device's operations are each TPU device plane's "XLA Ops" line,
  kept where they run inside an execution of the traced program (the
  "XLA Modules" events named after its HLO module); on the CPU backend,
  the XLA thread events of the host plane. All are clipped to the traced
  window: the benchmark spans, as ``trace_reduce`` takes them.
* An operation's self time is its duration minus the part of it that the
  operations nested in it cover: each instant goes to the innermost
  operation running then (the latest started). The scan's ``%while``
  keeps only the loop's own time, which has no phase.
* An operation's self time goes to the phase of its instruction (``other``
  for a name the map does not have), counts as hook time where a hook
  scope is on its stack, and as mixed where it is a mixed fusion. The
  program's busy time in no operation also goes to ``other``: on the CPU
  backend, its executor between operations. So the phases add up to the
  busy time. Times are averaged over the devices.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os

from bench import trace_reduce

OTHER = "other"
# bench/run.py's spans: the traced window
SPANS = ("bench.setup", "bench.grid")
CPU_SKIP = ("ThreadpoolListener", "ThunkExecutor", "end: ")


def self_times(events) -> dict:
    """{name: self ns} of (start_ns, end_ns, name) events: each instant of
    their union goes to the innermost event running then, the latest
    started (the longer of two that start together encloses the other)."""
    out = {}
    stack = []              # [name, running], in start order
    ends = []               # heap of (end, seq, stack entry)
    t = None

    def credit(until):
        while stack and not stack[-1][1]:
            stack.pop()
        if stack and until > t:
            name = stack[-1][0]
            out[name] = out.get(name, 0) + (until - t)

    for seq, (s, e, name) in enumerate(sorted(events,
                                              key=lambda v: (v[0], -v[1]))):
        while ends and ends[0][0] <= s:
            end, _, entry = heapq.heappop(ends)
            credit(end)
            t = end
            entry[1] = False
        credit(s)
        t = s
        entry = [name, True]
        stack.append(entry)
        heapq.heappush(ends, (e, seq, entry))
    while ends:
        end, _, entry = heapq.heappop(ends)
        credit(end)
        t = end
        entry[1] = False
    return out


def op_name(name: str) -> str:
    """The HLO instruction a trace event names (``%fusion.2 = f32[8] ...``
    and ``fusion.2`` -> ``fusion.2``)."""
    return trace_reduce.short_name(name).lstrip("%")


def overlap(a, b) -> int:
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(hi - lo, 0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def device_ops(planes, modules) -> list:
    """[(busy intervals, [(start_ns, end_ns, instruction)]), ...] per
    device: on a TPU the executions of the HLO modules ``modules`` (all
    executions where none matches) and the operations inside them; on the
    CPU backend ``trace_reduce``'s busy events and the XLA thread
    events."""
    out = []
    for p in planes:
        if not p.name.startswith("/device:") or "CUSTOM" in p.name:
            continue
        lines = {line.name: line for line in p.lines}
        if trace_reduce.TPU_OPS not in lines:
            continue
        runs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in lines[trace_reduce.TPU_BUSY].events]
        runs = sorted((s, e) for s, e, n in runs
                      if n.startswith(modules)) or sorted(
                          (s, e) for s, e, _ in runs)
        starts = [s for s, _ in runs]
        evs = []
        for e in lines[trace_reduce.TPU_OPS].events:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if i >= 0 and e.start_ns < runs[i][1]:
                evs.append((e.start_ns, e.start_ns + e.duration_ns,
                            op_name(e.name)))
        out.append((runs, evs))
    if out:
        return out
    cpu = [p for p in planes if p.name == "/host:CPU"]
    for busy, _ in trace_reduce.device_events(cpu):
        out.append(([(s, e) for s, e, _ in busy],
                    [(e.start_ns, e.start_ns + e.duration_ns,
                      op_name(e.name))
                     for line in cpu[0].lines
                     if line.name.startswith("tf_XLA")
                     for e in line.events
                     if e.duration_ns > 0
                     and not e.name.startswith(CPU_SKIP)]))
    return out


def reduce(planes, scopes: list, names) -> dict:
    """Self seconds by phase (``phase_s``), under hook scopes
    (``hook_s``), in mixed fusions (``mixed_s``) and in all
    (``self_s``), averaged over the devices; None without a scope map,
    benchmark spans or device operations."""
    planes = list(planes)
    if not scopes:
        return None
    ops = {}
    for s in scopes:
        ops.update(s["ops"])
    modules = tuple(sorted({s["module"] for s in scopes}))
    spans, _ = trace_reduce.span_events(planes, names)
    devs = device_ops(planes, modules)
    if not spans or not devs:
        return None
    lo, hi = spans[0][0], max(e for _, e, _ in spans)
    phase, hook, mixed = {OTHER: 0}, 0, 0
    for busy, evs in devs:
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in evs
                  if min(e, hi) > max(s, lo)]
        busy = trace_reduce.union(trace_reduce.clip(busy, lo, hi))
        ran = trace_reduce.union([(s, e) for s, e, _ in inside])
        phase[OTHER] += sum(e - s for s, e in busy) - overlap(busy, ran)
        for name, ns in self_times(inside).items():
            p, h, m = ops.get(name, (OTHER, "", False))
            phase[p] = phase.get(p, 0) + ns
            hook += ns if h else 0
            mixed += ns if m else 0
    k = 1e-9 / len(devs)
    return {"phase_s": {p: v * k for p, v in phase.items()},
            "hook_s": hook * k, "mixed_s": mixed * k,
            "self_s": sum(phase.values()) * k}


def reduce_dir(trace_dir: str, scopes: list, names) -> dict:
    """``reduce`` of the one ``.xplane.pb`` under ``trace_dir``."""
    import jax
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not scopes or len(files) != 1:
        return None
    return reduce(jax.profiler.ProfileData.from_file(files[0]).planes,
                  scopes, names)


def on_chip(launches: list) -> bool:
    """Whether there are launch records and every one ran on an
    accelerator. The readers of the program's spans and scopes read only
    such launches: on the CPU backend the host spans and the executor's
    operations share the same cores, so neither splits the work as on a
    chip (and ``bench/run.py`` itself runs only on a TPU)."""
    return bool(launches) and all(ln.get("backend", "cpu") != "cpu"
                                  for ln in launches)


def observe(obs: dict):
    """The phase reduction of the traced launch, computed once per run and
    kept in ``obs``: ``reduce``'s seconds with the traced ``scheme`` and
    its number of ``launches``. The program names the launches its
    profiler trace recorded (``profile.traced_launches``) and maps their
    executables to its scopes (``profile.trace_scopes``, which may
    compile: the readers run after the window's compile count). None
    where the program has neither, or the launches ran on the CPU."""
    if "phases" not in obs:
        obs["phases"] = _observe(obs)
    return obs["phases"]


def _observe(obs: dict):
    from repro.netsim.obs import profile
    if not hasattr(profile, "traced_launches"):
        return None
    launches = profile.traced_launches()
    if not on_chip(launches):
        return None
    scheme = launches[0]["scheme"]
    red = reduce_dir(launches[0]["trace_dir"], profile.trace_scopes(scheme),
                     SPANS)
    if red is None:
        return None
    return dict(red, scheme=scheme, launches=len(launches))


def per_step_us(obs: dict, seconds: float) -> float:
    """Seconds of the traced launches per scan step of one launch, in us."""
    return seconds / (obs["phases"]["launches"] * obs["steps"]) * 1e6


def phase_us(obs: dict, phase: str):
    """A phase's device self time per scan step of the traced launch."""
    red = observe(obs)
    if not red:
        return None
    return per_step_us(obs, red["phase_s"].get(phase, 0.0))
