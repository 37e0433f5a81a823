"""Launch-plan profiling and run manifests.

The runner's launch plans (chunk × scheme × device padding) decide how a
sweep actually hits the hardware, but until now the only way to see the
compile-vs-execute split or the XLA memory footprint was ad-hoc prints.
This module provides:

  * ``profiled_traced_batch`` — an ahead-of-time (lower → compile →
    execute) drive of the SAME jitted batch program ``simulate_batch``
    uses, with ``jax.block_until_ready`` fencing so compile seconds and
    execute seconds are separately attributable, plus guarded
    ``memory_analysis()`` / ``cost_analysis()`` capture. Compiled
    executables are cached per static signature, so repeat launches of a
    chunked plan report ``compile_cached: true`` with ``compile_s ≈ 0``.
  * ``git_rev`` / ``memory_figures`` — the canonical helpers the benches
    re-export through ``benchmarks/record.py`` (src never imports
    benchmarks).
  * ``configure_compile_cache`` — JAX's persistent compilation cache at
    a fixed path, for entry points that run on a chip (never at import).
  * ``write_manifest`` / ``read_manifest`` — JSONL run manifests: one
    header record (git rev, plan sha256 fingerprint, backend, grid
    summary) followed by one record per launch (scheme, cell range,
    compile/execute seconds, memory figures). ``tools/obs_report.py``
    summarizes and diffs them.

Schema: every line is a JSON object with a ``record`` field — ``header``
for the first line, ``launch`` for the rest (see docs/observability.md).
"""
from __future__ import annotations

import json
import os
import subprocess
import time
from typing import Optional

MANIFEST_VERSION = 1

# static-signature -> compiled executable. Module-level on purpose: the jit
# cache and this AOT cache are separate, so every profiled launch must come
# through here to amortize its own compile.
_AOT_CACHE: dict = {}


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
    """Guarded ``memory_analysis()``/``cost_analysis()`` capture from a
    compiled executable. Both APIs vary across JAX/XLA versions and
    backends — absent figures are simply omitted, never raised."""
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
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            for key in ("flops", "bytes accessed"):
                if key in ca:
                    figs[key.replace(" ", "_")] = float(ca[key])
    except Exception:
        pass
    return figs


def _leaf_sig(tree) -> tuple:
    import jax
    return tuple((tuple(l.shape), str(l.dtype), str(getattr(l, "sharding",
                                                            "")))
                 for l in jax.tree_util.tree_leaves(tree))


def profiled_traced_batch(cfg, params, wlp, scheme, steps, period_slots,
                          delay_pad, history_slots, mode, decimate, warm,
                          channel, profile: dict):
    """Run the batched engine through an explicit lower → compile →
    execute pipeline, filling ``profile`` in place with:

    ``compile_s`` / ``compile_cached`` / ``execute_s`` / ``backend`` (the
    platform the outputs live on, i.e. where the launch ran — not the
    default backend) and the ``memory_figures`` of the executable. Returns
    the engine output (same pytree as ``fluid._run_traced_batch``)."""
    import jax
    from repro.netsim import fluid

    jitted = fluid._jitted_traced_batch()
    key = (cfg, scheme, steps, period_slots, delay_pad, history_slots,
           mode, decimate, warm, channel, jax.default_backend(),
           _leaf_sig(params), _leaf_sig(wlp))
    compiled = _AOT_CACHE.get(key)
    cached = compiled is not None
    t0 = time.perf_counter()
    if not cached:
        lowered = jitted.lower(cfg, params, wlp, scheme, steps,
                               period_slots, delay_pad, history_slots,
                               mode, decimate, warm, channel)
        compiled = lowered.compile()
        _AOT_CACHE[key] = compiled
    profile["compile_s"] = time.perf_counter() - t0 if not cached else 0.0
    profile["compile_cached"] = cached
    profile.update(memory_figures(compiled))
    t0 = time.perf_counter()
    out = compiled(params, wlp)
    out = jax.block_until_ready(out)
    profile["execute_s"] = time.perf_counter() - t0
    leaf = jax.tree_util.tree_leaves(out)[0]
    profile["backend"] = next(iter(leaf.devices())).platform
    return out


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
