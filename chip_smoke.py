"""Chip smoke test: the paper's Fig. 3 sweep on a TPU, end to end.

Drives the sweep path users run (``repro.netsim.sweep_grid`` over all
seven schemes x 1-1000 km on the congestion workload, 220 ms = 44,000
steps, streaming metrics) on one TPU chip and checks what comes out:

  1. device check: a TPU or a non-zero exit (there is no CPU fallback);
  2. the Fig. 3 sweep twice, cold then warm: per-scheme compile/execute
     seconds, warm scenario-steps/s, no compile in the warm pass, no
     device-OOM split, complete finite rows, strict conservation, warm
     rows identical to cold rows, and the paper's direction (matchrdma
     above dcqcn in throughput at 1000 km; below it in peak buffer and
     pause ratio where the two carry a comparable load);
  3. one ``trace_mode="full"`` launch of matchrdma on the same grid (the
     [B, T] readback and the donated inputs), agreeing with its streamed
     rows;
  4. a CPU cross-check: 1/100/1000 km x all schemes at 30 ms on the host's
     CPU backend in this process, against the same subset on the chip.

``--chips 4`` runs only the sharded path: the Fig. 3 grid split across
four chips (7 cells padded to 8) against the same grid on one chip, row
for row.

    python chip_smoke.py               # one TPU chip
    python chip_smoke.py --chips 4     # four TPU chips, sharded sweep only

The last line of stdout is one JSON object naming the device; a failed
phase exits non-zero before it is printed. The run manifests go to
``chip_smoke_out/``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
# the cross-check needs the host's CPU backend beside the chip's
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

PLATFORM = "tpu"
OUT_DIR = os.path.join(ROOT, "chip_smoke_out")
DISTANCES_KM = (1.0, 10.0, 50.0, 100.0, 300.0, 500.0, 1000.0)
CROSS_KM = (1.0, 100.0, 1000.0)
CROSS_HORIZON_US = 30_000.0
COMPARED = ("throughput_gbps", "peak_buffer_mb", "mean_buffer_mb",
            "pause_ratio")
# largest relative difference |a - b| / max(|a|, |b|, 1) admitted between
# rows of two different compiled programs: TPU vs CPU, full-trace vs
# streamed, sharded vs one chip. The dynamics amplify roundoff: a 1e-6
# relative nudge to two capacities moves a row of this subset by 1.1e-2
# on the CPU alone, so a bitwise bound would reject a correct chip. A
# TPU v5e showed 8.6e-4 against the CPU.
TOL = 0.02


class PhaseError(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseError(msg)


def rel_diff(a: float, b: float) -> float:
    return abs(a - b) / max(abs(a), abs(b), 1.0)


def same_rows(rows_a, rows_b) -> bool:
    """Bit-identical rows (NaN sentinels such as ``avg_fct_us`` compare
    equal, which ``==`` on floats would not)."""
    return (json.dumps(rows_a, sort_keys=True)
            == json.dumps(rows_b, sort_keys=True))


def max_rel_diff(rows_a, rows_b, cols=COMPARED):
    """(largest relative difference, where) over matching rows."""
    check(len(rows_a) == len(rows_b),
          f"row counts differ: {len(rows_a)} vs {len(rows_b)}")
    worst = (0.0, None)
    for a, b in zip(rows_a, rows_b):
        check((a["scheme"], a["distance_km"]) == (b["scheme"],
                                                  b["distance_km"]),
              f"row order differs: {a['scheme']}@{a['distance_km']} vs "
              f"{b['scheme']}@{b['distance_km']}")
        for c in cols:
            d = rel_diff(a[c], b[c])
            if d > worst[0] or worst[1] is None:
                worst = (d, f"{a['scheme']}@{a['distance_km']:g}km {c}: "
                            f"{a[c]!r} vs {b[c]!r}")
    return worst


def device_check(chips: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    if d0.platform != PLATFORM:
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{d0.platform!r}); this check has no CPU fallback")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"JAX sees {len(devs)}")
    return devs


class CompileCounter:
    """Counts XLA backend compiles (persistent-cache hits included) while
    armed, through JAX's own monitoring event."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.armed, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == self.event:
            self.n += 1


def fig3_grid(distances=DISTANCES_KM, horizon_us=None):
    """(configs, workload, horizon) of ``benchmarks.scheme_compare.run``."""
    from benchmarks.scheme_compare import _workload
    from repro.config.base import NetConfig
    from repro.netsim.runner import convergence_horizon_us
    cfgs = [NetConfig(distance_km=d) for d in distances]
    if horizon_us is None:
        horizon_us = max(convergence_horizon_us(cfgs), 30_000.0)
    return cfgs, _workload(horizon_us), horizon_us


def run_sweep(tag, cfgs, wl, horizon_us, schemes, devices,
              trace_mode="metrics"):
    """One ``sweep_grid`` call with the runner's guards armed; returns
    (rows, manifest header, launch records)."""
    from repro.netsim import sweep_grid
    from repro.netsim.obs.profile import read_manifest
    path = os.path.join(OUT_DIR, f"{tag}.jsonl")
    rows = sweep_grid(cfgs, wl, schemes, horizon_us, trace_mode=trace_mode,
                      devices=devices, strict_conservation=True,
                      on_nonfinite="raise", manifest_path=path)
    header, launches = read_manifest(path)
    for ln in launches:
        check(not ln.get("oom_split"),
              f"{tag}: launch {ln['scheme']} took the device-OOM split")
    return rows, header, launches


def print_launches(tag, launches):
    for ln in launches:
        print(f"  {tag} {ln['scheme']:<10} backend={ln['backend']} "
              f"compile_s={ln['compile_s']!r} "
              f"cached={ln['compile_cached']} "
              f"execute_s={ln['execute_s']!r} cells={ln['n_real']}"
              f"/{ln['pad_to']} devices={ln['n_devices']}", flush=True)


def check_rows(tag, rows, cfgs, schemes, streamed=True):
    """Every cell has a row with finite Fig. 3 columns and, for streamed
    (metrics-mode) rows, its scheme's streamed columns."""
    from benchmarks.scheme_compare import STREAMED_COLS
    check(len(rows) == len(cfgs) * len(schemes),
          f"{tag}: {len(rows)} rows for {len(cfgs)} x {len(schemes)} cells")
    for r in rows:
        extra = STREAMED_COLS[r["scheme"]] if streamed else ()
        for c in COMPARED + extra:
            check(c in r and math.isfinite(r[c]),
                  f"{tag}: {r['scheme']}@{r['distance_km']:g}km column "
                  f"{c} missing or non-finite: {r.get(c)!r}")


def check_ordering(rows):
    """The paper's direction on the Fig. 3 grid. At 1000 km matchrdma
    carries more than dcqcn. The buffer and pause claims compare the two at
    a comparable load: at 1000 km dcqcn carries a fifth of matchrdma's
    traffic and so queues less, so they are checked at every distance
    where the two throughputs are within 10 % (the short haul)."""
    by = {(r["scheme"], r["distance_km"]): r for r in rows}
    m, d = by[("matchrdma", 1000.0)], by[("dcqcn", 1000.0)]
    print(f"[sweep] 1000 km: matchrdma thr={m['throughput_gbps']!r} Gbps; "
          f"dcqcn thr={d['throughput_gbps']!r} Gbps", flush=True)
    check(m["throughput_gbps"] > d["throughput_gbps"],
          "1000 km: matchrdma throughput not above dcqcn")
    matched = [km for km in DISTANCES_KM
               if rel_diff(by[("matchrdma", km)]["throughput_gbps"],
                           by[("dcqcn", km)]["throughput_gbps"]) <= 0.1]
    check(bool(matched), "no distance where matchrdma and dcqcn carry a "
                         "comparable load")
    for km in matched:
        m, d = by[("matchrdma", km)], by[("dcqcn", km)]
        print(f"[sweep] {km:g} km: peak buffer matchrdma "
              f"{m['peak_buffer_mb']!r} MB vs dcqcn {d['peak_buffer_mb']!r}"
              f" MB; pause ratio {m['pause_ratio']!r} vs "
              f"{d['pause_ratio']!r}", flush=True)
        check(m["peak_buffer_mb"] < d["peak_buffer_mb"],
              f"{km:g} km: matchrdma peak buffer not below dcqcn")
        check(m["pause_ratio"] < d["pause_ratio"],
              f"{km:g} km: matchrdma pause ratio not below dcqcn")


def phase_sweep(tpu, counter):
    from repro.netsim.schemes import ALL_SCHEMES
    cfgs, wl, horizon = fig3_grid()
    print(f"[sweep] Fig. 3 grid: {len(ALL_SCHEMES)} schemes x "
          f"{len(cfgs)} distances, horizon {horizon:g} us", flush=True)
    t0 = time.perf_counter()
    cold, header, cold_l = run_sweep("fig3_cold", cfgs, wl, horizon,
                                     ALL_SCHEMES, [tpu])
    cold_wall = time.perf_counter() - t0
    print_launches("cold", cold_l)
    counter.armed, counter.n = True, 0
    t0 = time.perf_counter()
    warm, _, warm_l = run_sweep("fig3_warm", cfgs, wl, horizon,
                                ALL_SCHEMES, [tpu])
    warm_wall = time.perf_counter() - t0
    counter.armed = False
    print_launches("warm", warm_l)
    steps = header["steps"]
    for tag, launches in (("cold", cold_l), ("warm", warm_l)):
        for ln in launches:
            check(ln["backend"] == PLATFORM,
                  f"{tag}: {ln['scheme']} ran on {ln['backend']}")
    check(all(ln["compile_cached"] for ln in warm_l),
          "warm pass: a launch was not served by the compiled cache")
    check(counter.n == 0, f"warm pass: {counter.n} XLA compiles")
    check_rows("cold", cold, cfgs, ALL_SCHEMES)
    check(same_rows(warm, cold), "warm rows differ from cold rows")
    cells = len(cold)
    warm_exec = sum(ln["execute_s"] for ln in warm_l)
    print(f"[sweep] steps={steps} cells={cells} "
          f"cold: compile_s={sum(ln['compile_s'] for ln in cold_l)!r} "
          f"execute_s={sum(ln['execute_s'] for ln in cold_l)!r} "
          f"wall_s={cold_wall!r}", flush=True)
    print(f"[sweep] warm: execute_s={warm_exec!r} wall_s={warm_wall!r} "
          f"compiles=0 scenario_steps_per_s={cells * steps / warm_exec!r}",
          flush=True)
    check_ordering(cold)
    return cfgs, wl, horizon, cold


def phase_full(tpu, cfgs, wl, horizon, metric_rows):
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        full, _, launches = run_sweep("fig3_full", cfgs, wl, horizon,
                                      ("matchrdma",), [tpu],
                                      trace_mode="full")
    print_launches("full", launches)
    donation = [str(w.message) for w in caught
                if "donated" in str(w.message).lower()]
    ln = launches[0]
    print(f"[full] output_bytes={ln.get('output_size_in_bytes')} "
          f"alias_bytes={ln.get('alias_size_in_bytes')} donation_warnings="
          f"{donation or 'none'}", flush=True)
    check(ln["backend"] == PLATFORM, f"full: ran on {ln['backend']}")
    check_rows("full", full, cfgs, ("matchrdma",), streamed=False)
    streamed = [r for r in metric_rows if r["scheme"] == "matchrdma"]
    worst, where = max_rel_diff(full, streamed)
    print(f"[full] full vs streamed rows: max rel diff {worst!r} ({where})",
          flush=True)
    check(worst <= TOL,
          f"full vs streamed rows differ by {worst!r} > "
          f"{TOL} ({where})")


@contextlib.contextmanager
def no_persistent_cache():
    """Keep the CPU programs out of the persistent cache: one compiled on
    another host type could be read back on this one."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()


def phase_cross(tpu):
    import jax
    from repro.netsim.schemes import ALL_SCHEMES
    cfgs, wl, horizon = fig3_grid(CROSS_KM, CROSS_HORIZON_US)
    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    with no_persistent_cache(), jax.default_device(cpu):
        cpu_rows, _, cpu_l = run_sweep("cross_cpu", cfgs, wl, horizon,
                                       ALL_SCHEMES, [cpu])
    cpu_wall = time.perf_counter() - t0
    tpu_rows, _, tpu_l = run_sweep("cross_tpu", cfgs, wl, horizon,
                                   ALL_SCHEMES, [tpu])
    check(all(ln["backend"] == "cpu" for ln in cpu_l),
          "cross-check: the CPU pass did not run on the CPU")
    check(all(ln["backend"] == PLATFORM for ln in tpu_l),
          "cross-check: the chip pass did not run on the chip")
    check_rows("cross_cpu", cpu_rows, cfgs, ALL_SCHEMES)
    check_rows("cross_tpu", tpu_rows, cfgs, ALL_SCHEMES)
    worst, where = max_rel_diff(tpu_rows, cpu_rows)
    print(f"[cross] {len(tpu_rows)} cells, CPU wall_s={cpu_wall!r}; "
          f"max rel diff TPU vs CPU {worst!r} ({where}); tol {TOL}",
          flush=True)
    for c in COMPARED:
        w, at = max_rel_diff(tpu_rows, cpu_rows, (c,))
        print(f"  {c}: {w!r} ({at})", flush=True)
    check(worst <= TOL,
          f"TPU vs CPU rows differ by {worst!r} > {TOL} ({where})")


def phase_sharded(devs):
    from repro.netsim.schemes import ALL_SCHEMES
    cfgs, wl, horizon = fig3_grid()
    print(f"[sharded] Fig. 3 grid on {len(devs)} chips vs one", flush=True)
    rows4, _, l4 = run_sweep("sharded_4", cfgs, wl, horizon, ALL_SCHEMES,
                             devs)
    print_launches("4chip", l4)
    rows1, _, l1 = run_sweep("sharded_1", cfgs, wl, horizon, ALL_SCHEMES,
                             devs[:1])
    print_launches("1chip", l1)
    for ln in l4:
        check(ln["n_devices"] == len(devs) and ln["pad_to"] % len(devs) == 0,
              f"sharded: {ln['scheme']} ran on {ln['n_devices']} devices, "
              f"padded to {ln['pad_to']}")
    check(all(ln["backend"] == PLATFORM for ln in l4 + l1),
          "sharded: a launch did not run on the chip")
    check_rows("sharded_4", rows4, cfgs, ALL_SCHEMES)
    identical = same_rows(rows4, rows1)
    worst, where = max_rel_diff(rows4, rows1)
    print(f"[sharded] bit-identical={identical} max rel diff {worst!r} "
          f"({where}); tol {TOL}", flush=True)
    check(identical or worst <= TOL,
          f"sharded vs one-chip rows differ by {worst!r} ({where})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded four-chip sweep against "
                         "its one-chip twin")
    args = ap.parse_args(argv)
    devs = device_check(args.chips)
    import jax
    from repro.netsim.obs.profile import configure_compile_cache
    print(f"compile cache: {configure_compile_cache(ROOT)}", flush=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_sharded(devs[:4])
        else:
            counter = CompileCounter()
            cfgs, wl, horizon, rows = phase_sweep(devs[0], counter)
            phase_full(devs[0], cfgs, wl, horizon, rows)
            phase_cross(devs[0])
    except PhaseError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s",
          flush=True)
    d0 = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
