"""Launch-plan profiling, host spans and run manifests.

The runner's launch plans (chunk × scheme × device padding) decide how a
sweep actually hits the hardware. This module provides:

  * ``profiled_traced_batch`` — an ahead-of-time (lower → compile →
    execute) drive of the SAME jitted batch program ``simulate_batch``
    uses, with ``jax.block_until_ready`` fencing so compile seconds and
    execute seconds are separately attributable, plus guarded
    ``memory_analysis()`` capture and whether the compile was found in
    JAX's persistent compilation cache. Compiled executables are cached
    per static signature, so repeat launches of a chunked plan report
    ``compile_cached: true`` with ``compile_s ≈ 0``.
  * ``traced_launches`` / ``trace_scopes`` / ``hlo_scopes`` — the launch
    records of the launches a ``jax.profiler`` trace recorded, and the map
    of their executables' instructions to the scan step's ``netsim.*``
    phase scopes and ``hook.*`` scheme-hook scopes (through a twin
    compiled with this program's op metadata where the executable came
    from a persistent cache written by another build), for attributing
    the device trace.
  * ``span`` — a host span on the profiler's clock that also adds its
    seconds to a launch's profile dict.
  * ``git_rev`` / ``memory_figures`` — the canonical helpers the benches
    re-export through ``benchmarks/record.py`` (src never imports
    benchmarks).
  * ``configure_compile_cache`` — JAX's persistent compilation cache at
    a fixed path, for entry points that run on a chip (never at import).
  * ``write_manifest`` / ``read_manifest`` — JSONL run manifests: one
    header record (git rev, plan sha256 fingerprint, backend, grid
    summary) followed by one record per launch (scheme, cell range,
    compile/execute seconds, host span seconds, memory figures).
    ``tools/obs_report.py`` summarizes and diffs them.

Schema: every line is a JSON object with a ``record`` field — ``header``
for the first line, ``launch`` for the rest (see docs/observability.md).
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import time
from contextlib import contextmanager
from functools import wraps
from typing import Optional

MANIFEST_VERSION = 1

# static-signature -> _Program. Module-level on purpose: the jit cache and
# this AOT cache are separate, so every profiled launch must come through
# here to amortize its own compile.
_AOT_CACHE: dict = {}

CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# [count] of JAX's persistent-cache hits, process-wide like JAX's own
# listener registry; None until the listener is registered
_cache_hits: Optional[list] = None
# launch records of the launches under the latest profiler trace
_traced: list = []

PHASE_SCOPE, HOOK_SCOPE = "netsim.", "hook."
OTHER = "other"


class _Program:
    """One AOT-compiled batch program of ``_AOT_CACHE``: the executable,
    the arguments that lowered it (arrays as shapes), and, once
    ``trace_scopes`` asked for it, the map of its HLO to the step's
    scopes."""

    __slots__ = ("compiled", "args", "scopes")

    def __init__(self, compiled, args):
        self.compiled, self.args, self.scopes = compiled, args, None


def _persistent_cache_hits() -> int:
    """Compiles loaded from JAX's persistent compilation cache so far in
    this process, counted by a ``jax.monitoring`` listener registered on
    the first call. (JAX's miss event marks an entry written, so it cannot
    tell a cache without the entry from a cache that is off: a compile
    with no hit is a miss.)"""
    global _cache_hits
    if _cache_hits is None:
        import jax
        _cache_hits = [0]

        def on_event(event, **kwargs):
            if event == CACHE_HIT_EVENT:
                _cache_hits[0] += 1

        jax.monitoring.register_event_listener(on_event)
    return _cache_hits[0]


@contextmanager
def span(name: str, profile: Optional[dict] = None):
    """A host span: a ``jax.profiler.TraceAnnotation`` called ``name``,
    so it shares the device trace's clock when a profiler runs. Given a
    launch's ``profile`` dict, the span's seconds are also added to its
    ``<last part of name>_s`` key (``netsim.stack`` -> ``stack_s``)."""
    import jax
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    if profile is not None:
        key = name.rsplit(".", 1)[-1] + "_s"
        profile[key] = profile.get(key, 0.0) + time.perf_counter() - t0


def git_rev(cwd: Optional[str] = None) -> str:
    """``git describe --always --dirty`` of the repo containing this file
    (or ``cwd``); ``"unknown"`` outside a checkout."""
    try:
        out = subprocess.run(
            ["git", "describe", "--always", "--dirty"],
            cwd=cwd or os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
        rev = out.stdout.strip()
        return rev if out.returncode == 0 and rev else "unknown"
    except Exception:
        return "unknown"


def configure_compile_cache(root: str) -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads that
    variable itself, so no other directory is set here), else
    ``<root>/.jax_cache``. The path is part of what a later run has to find
    again, so it is fixed, never built from a temporary name, a pid or the
    time. Every compile is written (minimum compile time 0): the default
    one-second floor would skip the faster netsim programs. Entry points
    call this in ``main()``; importing ``repro`` never does."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(root), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def memory_figures(compiled) -> dict:
    """Guarded ``memory_analysis()`` capture from a compiled executable.
    The API varies across JAX/XLA versions and backends — absent figures
    are simply omitted, never raised."""
    figs = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                figs[attr] = int(v)
    except Exception:
        pass
    return figs


def _leaf_sig(tree) -> tuple:
    import jax
    return tuple((tuple(l.shape), str(l.dtype), str(getattr(l, "sharding",
                                                            "")))
                 for l in jax.tree_util.tree_leaves(tree))


def _shapes(tree):
    import jax
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=x.sharding), tree)


def profiled_traced_batch(cfg, params, wlp, scheme, steps, period_slots,
                          delay_pad, history_slots, mode, decimate, warm,
                          channel, profile: dict, rounds: bool = False):
    """Run the batched engine through an explicit lower → compile →
    execute pipeline, filling ``profile`` in place with:

    ``compile_s`` / ``compile_cached`` / ``persistent_cache`` (``hit``:
    loaded from JAX's persistent compilation cache; ``miss``: compiled by
    the backend; ``in_process``: found in this module's AOT cache) /
    ``execute_s`` / ``backend`` (the platform the outputs live on, i.e.
    where the launch ran — not the default backend), the
    ``memory_figures`` of the executable and, where a ``jax.profiler``
    trace records the launch, its ``trace_dir`` (``traced_launches``).
    Returns the engine output (same pytree as
    ``fluid._run_traced_batch``)."""
    import jax
    from repro.netsim import fluid

    key = (cfg, scheme, steps, period_slots, delay_pad, history_slots,
           mode, decimate, warm, channel, jax.default_backend(),
           _leaf_sig(params), _leaf_sig(wlp), rounds)
    prog = _AOT_CACHE.get(key)
    cached = prog is not None
    t0 = time.perf_counter()
    if not cached:
        hits = _persistent_cache_hits()
        args = (cfg, _shapes(params), _shapes(wlp), scheme, steps,
                period_slots, delay_pad, history_slots, mode, decimate,
                warm, channel, rounds)
        compiled = fluid._jitted_traced_batch().lower(
            cfg, params, wlp, *args[3:]).compile()
        prog = _AOT_CACHE[key] = _Program(compiled, args)
        profile["persistent_cache"] = ("hit" if _persistent_cache_hits()
                                       > hits else "miss")
    else:
        profile["persistent_cache"] = "in_process"
    profile["compile_s"] = time.perf_counter() - t0 if not cached else 0.0
    profile["compile_cached"] = cached
    profile.update(memory_figures(prog.compiled))
    t0 = time.perf_counter()
    out = prog.compiled(params, wlp)
    out = jax.block_until_ready(out)
    profile["execute_s"] = time.perf_counter() - t0
    leaf = jax.tree_util.tree_leaves(out)[0]
    profile["backend"] = next(iter(leaf.devices())).platform
    _note_traced(profile)
    return out


def _active_trace_dir() -> Optional[str]:
    """The directory a running ``jax.profiler`` trace writes to, or None
    with no trace running (JAX keeps it in its profiler's state)."""
    try:
        from jax._src import profiler
    except ImportError:
        return None
    state = getattr(profiler, "_profile_state", None)
    if state is None or state.profile_session is None:
        return None
    return str(state.log_dir)


def traced_launches() -> list:
    """The launch records (the ``profile`` dicts, filled in place) of the
    launches that ran under this process's latest ``jax.profiler`` trace,
    in order; each names the trace's directory as ``trace_dir``."""
    return list(_traced)


def _note_traced(profile: dict) -> None:
    trace_dir = _active_trace_dir()
    if trace_dir is None:
        return
    if _traced and _traced[-1]["trace_dir"] != trace_dir:
        _traced.clear()
    profile["trace_dir"] = trace_dir
    _traced.append(profile)


def trace_scopes(scheme: str) -> list:
    """The ``hlo_scopes`` maps of the cached programs of the scheme named
    ``scheme``, for attributing a device trace of their launches.

    JAX's persistent-cache key leaves the op metadata out by default, so
    an executable loaded from there carries the op names of the build that
    wrote it. Where a program's HLO holds no ``netsim.*`` scope, it is
    compiled once more, keyed with its metadata and past the in-process
    trace and lowering caches (a new jit of the same function), and each
    instruction takes the scopes of the same-named instruction of that
    twin where the two lines agree but for metadata (``other`` where they
    do not). The launches keep their executable. This may compile: call
    it where no compile is timed or counted."""
    maps = []
    for key, prog in _AOT_CACHE.items():
        if key[1].name != scheme:
            continue
        if prog.scopes is None:
            text = prog.compiled.as_text()
            scopes = hlo_scopes(text)
            if all(p == OTHER for p, _, _ in scopes["ops"].values()):
                scopes = _matched_scopes(scopes, text, _twin(prog.args))
            prog.scopes = scopes
        maps.append(prog.scopes)
    return maps


def _twin(args) -> str:
    """HLO text of the batch program for ``args`` compiled anew with its
    op metadata in the persistent-cache key."""
    import jax
    from repro.netsim import fluid

    @wraps(fluid._run_traced_batch_impl)
    def fresh(*a, **kw):
        return fluid._run_traced_batch_impl(*a, **kw)

    jitted = fluid._jit_traced_batch(fluid._donated_inputs(), fresh)
    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return jitted.lower(*args).compile().as_text()
    finally:
        jax.config.update(flag, was)


def _matched_scopes(scopes: dict, text: str, twin: str) -> dict:
    """``scopes`` of ``text`` with each instruction's scopes taken from
    the same-named instruction of ``twin`` where the two lines agree but
    for metadata."""
    mine, theirs = _instruction_lines(text), _instruction_lines(twin)
    twin_ops = hlo_scopes(twin)["ops"]
    ops = {name: (twin_ops[name] if name in twin_ops
                  and mine.get(name) == theirs.get(name)
                  else [OTHER, "", False])
           for name in scopes["ops"]}
    return {"module": scopes["module"], "ops": ops}


_HLO_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_HLO_INST = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_METADATA = re.compile(r",? metadata=\{[^}]*\}")


def _instruction_lines(hlo_text: str) -> dict:
    """{instruction: its line without metadata} of an HLO module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_INST.match(line)
        if m:
            out[m.group(1)] = _HLO_METADATA.sub("", line.strip())
    return out


def _scope_of(op_name: str) -> tuple:
    """(innermost ``netsim.*`` phase without its prefix, or ``other``;
    innermost ``hook.*`` method, or ``""``) of an ``op_name`` name stack."""
    parts = op_name.split("/")
    phases = [p[len(PHASE_SCOPE):] for p in parts
              if p.startswith(PHASE_SCOPE)]
    hooks = [p[len(HOOK_SCOPE):] for p in parts if p.startswith(HOOK_SCOPE)]
    return (phases[-1] if phases else OTHER, hooks[-1] if hooks else "")


def hlo_scopes(hlo_text: str) -> dict:
    """Map an optimized HLO module's text (``compiled.as_text()``) to the
    scan step's scopes: ``{"module": name, "ops": {instruction: [phase,
    hook, mixed]}}`` for every instruction outside fused computations (the
    ones a device trace names). ``phase`` is the innermost ``netsim.*``
    scope of the instruction's ``op_name`` (``other`` without one),
    ``hook`` the innermost ``hook.*`` scope's method (``""`` without one),
    and ``mixed`` whether a fusion's fused instructions carry more than
    one phase (instructions without a phase do not count)."""
    module, comps, insts, current = "", {}, [], None
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        m = _HLO_COMP.match(line)
        if m and not line.startswith(" "):
            current = comps.setdefault(m.group(1), [])
            continue
        m = _HLO_INST.match(line)
        if m is None or current is None:
            continue
        op = _HLO_OP_NAME.search(line)
        calls = _HLO_CALLS.search(line)
        rec = (m.group(1), op.group(1) if op else "",
               calls.group(1) if calls else None, " fusion(" in line)
        current.append(rec)
        insts.append(rec)
    fused = {c for _, _, c, is_fusion in insts if is_fusion and c}

    def phases_in(comp, seen=()):
        out = set()
        for _, op_name, calls, is_fusion in comps.get(comp, ()):
            phase = _scope_of(op_name)[0]
            if phase != OTHER:
                out.add(phase)
            if is_fusion and calls and calls not in seen:
                out |= phases_in(calls, seen + (comp,))
        return out

    ops = {}
    for comp, recs in comps.items():
        if comp in fused:
            continue
        for name, op_name, calls, is_fusion in recs:
            phase, hook = _scope_of(op_name)
            mixed = bool(is_fusion and calls
                         and len(phases_in(calls)) > 1)
            ops[name] = [phase, hook, mixed]
    return {"module": module, "ops": ops}


def _json_safe(obj):
    """Round-trippable JSON: non-finite floats become strings, numpy
    scalars collapse to Python numbers."""
    if isinstance(obj, dict):
        return {str(k): _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, bool) or obj is None or isinstance(obj, (int, str)):
        return obj
    if isinstance(obj, float):
        return obj if obj == obj and abs(obj) != float("inf") else str(obj)
    if hasattr(obj, "item"):
        return _json_safe(obj.item())
    return str(obj)


def write_manifest(path: str, header: dict, launches: list) -> str:
    """Write a JSONL run manifest: one ``record: "header"`` line, then one
    ``record: "launch"`` line per launch. Returns ``path``."""
    head = dict(header)
    head.setdefault("record", "header")
    head.setdefault("manifest_version", MANIFEST_VERSION)
    head.setdefault("git_rev", git_rev())
    head.setdefault("timestamp", time.strftime("%Y-%m-%dT%H:%M:%S"))
    lines = [head] + [dict(l, record="launch") for l in launches]
    with open(path, "w") as f:
        for rec in lines:
            f.write(json.dumps(_json_safe(rec), sort_keys=True) + "\n")
    return path


def read_manifest(path: str):
    """Read a JSONL manifest -> ``(header, launches)``. Tolerates a
    missing header (returns ``{}``) so partial files still summarize."""
    header, launches = {}, []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            if rec.get("record") == "header":
                header = rec
            else:
                launches.append(rec)
    return header, launches
