#!/usr/bin/env python3
"""Summarize and diff netsim JSONL run manifests (docs/observability.md).

A manifest is written by ``run_experiment_batch``/``sweep_grid``
(``manifest_path=...``): one ``record: "header"`` line (git rev, plan
sha256 fingerprint, backend, grid summary) followed by one
``record: "launch"`` line per device launch (scheme, cell range,
compile/execute wall-clock split, host span seconds, XLA memory figures).

Usage:
    python tools/obs_report.py summarize MANIFEST.jsonl
    python tools/obs_report.py diff OLD.jsonl NEW.jsonl

Pure stdlib on purpose — the CLI must work on a machine without the
simulator's dependencies (e.g. to inspect a manifest copied off a
cluster).
"""
from __future__ import annotations

import argparse
import json
import sys

_HEADER_KEYS = ("git_rev", "fingerprint", "backend", "n_devices",
                "trace_mode", "horizon_us", "steps", "n_cells", "schemes",
                "n_launches", "n_resumed", "timestamp")
_MEM_KEYS = ("temp_size_in_bytes", "argument_size_in_bytes",
             "output_size_in_bytes", "generated_code_size_in_bytes")


def load_manifest(path: str):
    """JSONL manifest -> (header dict, launch record list). Tolerates a
    missing header so partial files still summarize."""
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


def _fmt_bytes(n) -> str:
    try:
        n = float(n)
    except (TypeError, ValueError):
        return "-"
    for unit in ("B", "KB", "MB", "GB"):
        if abs(n) < 1024.0 or unit == "GB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}GB"


def _fmt_s(v) -> str:
    try:
        return f"{float(v):8.3f}"
    except (TypeError, ValueError):
        return "       -"


def _launch_key(rec: dict):
    return (rec.get("scheme"), rec.get("lo"), rec.get("hi"))


def summarize(path: str, out=sys.stdout) -> None:
    header, launches = load_manifest(path)
    print(f"manifest: {path}", file=out)
    for k in _HEADER_KEYS:
        if k in header:
            print(f"  {k:14s} {header[k]}", file=out)
    executed = [r for r in launches if not r.get("resumed")]
    resumed = len(launches) - len(executed)
    print(f"\nlaunches ({len(launches)} total, {resumed} resumed):",
          file=out)
    print(f"  {'scheme':12s} {'cells':>12s} {'compile_s':>9s} "
          f"{'execute_s':>9s} {'cached':>6s} {'temp':>9s} {'args':>9s}",
          file=out)
    for rec in launches:
        cells = f"[{rec.get('lo')}, {rec.get('hi')})"
        if rec.get("resumed"):
            print(f"  {rec.get('scheme', '?'):12s} {cells:>12s} "
                  f"{'(resumed from checkpoint)':>26s}", file=out)
            continue
        print(f"  {rec.get('scheme', '?'):12s} {cells:>12s} "
              f"{_fmt_s(rec.get('compile_s')):>9s} "
              f"{_fmt_s(rec.get('execute_s')):>9s} "
              f"{str(bool(rec.get('compile_cached'))).lower():>6s} "
              f"{_fmt_bytes(rec.get('temp_size_in_bytes')):>9s} "
              f"{_fmt_bytes(rec.get('argument_size_in_bytes')):>9s}",
              file=out)
    tot_c = sum(r.get("compile_s", 0.0) for r in executed)
    tot_e = sum(r.get("execute_s", 0.0) for r in executed)
    print(f"\ntotals: compile {tot_c:.3f}s  execute {tot_e:.3f}s  "
          f"(compile share "
          f"{tot_c / (tot_c + tot_e) * 100 if tot_c + tot_e else 0:.0f}%)",
          file=out)


def diff(path_a: str, path_b: str, out=sys.stdout) -> None:
    """Match launches across two manifests by (scheme, lo, hi) and print
    execute-time and memory deltas — the regression view for 'did this
    change make launches slower or fatter'."""
    ha, la = load_manifest(path_a)
    hb, lb = load_manifest(path_b)
    print(f"diff: {path_a} ({ha.get('git_rev', '?')}) -> "
          f"{path_b} ({hb.get('git_rev', '?')})", file=out)
    for k in ("backend", "n_devices", "trace_mode", "steps", "n_cells",
              "fingerprint"):
        va, vb = ha.get(k), hb.get(k)
        if va != vb:
            print(f"  {k}: {va} -> {vb}", file=out)
    a_by = {_launch_key(r): r for r in la if not r.get("resumed")}
    b_by = {_launch_key(r): r for r in lb if not r.get("resumed")}
    common = [k for k in a_by if k in b_by]
    print(f"\nmatched launches: {len(common)} "
          f"(only-old: {len(a_by) - len(common)}, "
          f"only-new: {len(b_by) - len(common)})", file=out)
    print(f"  {'scheme':12s} {'cells':>12s} {'exec_old':>9s} "
          f"{'exec_new':>9s} {'ratio':>6s} {'temp_old':>9s} "
          f"{'temp_new':>9s}", file=out)
    for key in common:
        ra, rb = a_by[key], b_by[key]
        ea, eb = ra.get("execute_s"), rb.get("execute_s")
        try:
            ratio = f"{float(eb) / float(ea):5.2f}x"
        except (TypeError, ValueError, ZeroDivisionError):
            ratio = "    -"
        cells = f"[{key[1]}, {key[2]})"
        print(f"  {key[0] or '?':12s} {cells:>12s} "
              f"{_fmt_s(ea):>9s} {_fmt_s(eb):>9s} {ratio:>6s} "
              f"{_fmt_bytes(ra.get('temp_size_in_bytes')):>9s} "
              f"{_fmt_bytes(rb.get('temp_size_in_bytes')):>9s}", file=out)
    for label, records in (("old", [a_by[k] for k in common]),
                           ("new", [b_by[k] for k in common])):
        tot_e = sum(r.get("execute_s", 0.0) for r in records)
        tot_c = sum(r.get("compile_s", 0.0) for r in records)
        print(f"totals[{label}]: compile {tot_c:.3f}s  "
              f"execute {tot_e:.3f}s", file=out)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Summarize / diff netsim JSONL run manifests")
    sub = p.add_subparsers(dest="cmd", required=True)
    ps = sub.add_parser("summarize", help="print one manifest's header, "
                                          "per-launch table and totals")
    ps.add_argument("manifest")
    pd = sub.add_parser("diff", help="match two manifests' launches and "
                                     "print execute/memory deltas")
    pd.add_argument("old")
    pd.add_argument("new")
    args = p.parse_args(argv)
    if args.cmd == "summarize":
        summarize(args.manifest)
    else:
        diff(args.old, args.new)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
