"""On-chip benchmark of scenario sweeps: one cell of BENCHMARK.json.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

A run builds the cell's grid from its configuration and traffic files and
the seed, warms up (compile, from the persistent cache after the first run,
plus one whole grid: that is ``setup_s``), then sweeps whole grids back to
back through ``repro.netsim.sweep_grid(..., trace_mode="metrics")`` until
the first grid that ends after ``--seconds``. ``scenario_steps_per_s`` is
cells x scan steps of every grid in the window over the window's wall
time. After the window the rows of a seeded sample of cells are compared
with the plain reference the configuration names (``bench/check.py``);
``correct`` says whether every row is present, finite and within the
limit. The configuration's ``"channel"`` is the channel every cell runs
on (``ideal``: the program's default, passed as nothing).

Each window grid's record (the ``grids`` key of the result line) holds its
wall, the process's CPU seconds and involuntary context switches over it,
and the CPU quota's throttled microseconds over it (cgroup ``cpu.stat``;
``null`` where the host has none): a grid that stalled while the process
was throttled waited on the quota, not on the program.

``--trace 1`` routes the same grids through launch manifests and the JAX
profiler and reports the per-layer metrics instead, each read by its own
module under ``bench/metrics``.

The last stdout line is one JSON object; the numbers compared, each with
its limit, are the last lines of stderr and the ``checks`` key of that
object. Without a TPU, or where JAX sees another number of chips than the
cell names, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse   # noqa: E402
import json   # noqa: E402
import os   # noqa: E402
import resource   # noqa: E402
import shutil   # noqa: E402
import sys   # noqa: E402
import tempfile   # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import check, grid   # noqa: E402
from repro.netsim.obs.profile import configure_compile_cache   # noqa: E402

PLATFORM = "tpu"
SPAN_SETUP, SPAN_GRID = "bench.setup", "bench.grid"
# the cgroup's CPU statistics (v2): time its CPU quota held it back
CPU_STAT, THROTTLED = "/sys/fs/cgroup/cpu.stat", "throttled_usec"


def device_check(chips: int, platform: str = PLATFORM):
    """The devices the cell runs on. Exits without a TPU, and unless JAX
    sees exactly the cell's number of chips: ``sweep_grid`` shards over
    every visible device, so a run on more would not be a run on
    ``chips``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise SystemExit(f"bench: no TPU (JAX platform is {devs[0].platform!r});"
                         f" this benchmark has no CPU fallback")
    if len(devs) != chips:
        raise SystemExit(f"bench: the cell runs on {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs


class CompileCounter:
    """Counts XLA backend compiles (persistent-cache loads included) while
    armed, through JAX's own monitoring event. The window must count none."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.armed, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == self.event:
            self.n += 1


def load_reader(root: str, name: str):
    """The per-layer metric module ``bench/metrics/<name>.py``."""
    return grid.load_module(os.path.join(root, "bench", "metrics",
                                         name + ".py"),
                            "bench_metric_" + name.replace(".", "_"))


def metric_entries(root: str, section: str, workload: str) -> list:
    """The metrics of ``section`` that the cell ``workload`` reports."""
    bench = grid.load_json(os.path.join(root, "BENCHMARK.json"))
    return [m for m in bench[section]
            if workload in m.get("workloads", [workload])]


class Sweep:
    """The timed entry: one whole grid per call, as a user sweeps it."""

    def __init__(self, cell: grid.Cell, seed: int):
        from repro.netsim import get_channel_model
        self.cell = cell
        self.cells = cell.cells(seed)
        self.scenarios = grid.to_program(self.cells)
        self.n_rows = len(self.cells) * len(cell.schemes)
        name = cell.config.get("channel", "ideal")
        self.channel = None if name == "ideal" else get_channel_model(name)

    def __call__(self, manifest_path=None, schemes=None):
        from repro.netsim import sweep_grid
        return sweep_grid(self.scenarios, schemes or self.cell.schemes,
                          horizon_us=self.cell.horizon_us,
                          trace_mode="metrics", channel=self.channel,
                          manifest_path=manifest_path)


def throttled_us():
    """Microseconds the CPU quota has throttled this process's cgroup, or
    None where the host keeps no such count."""
    try:
        with open(CPU_STAT) as f:
            for ln in f:
                k, _, v = ln.partition(" ")
                if k == THROTTLED:
                    return int(v)
    except OSError:
        pass
    return None


def host_usage() -> tuple:
    """(process CPU seconds, involuntary context switches, throttled us)."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime + ru.ru_stime, ru.ru_nivcsw, throttled_us()


def grid_record(wall_s: float, before: tuple, after: tuple) -> dict:
    """One window grid's wall and what the host did meanwhile."""
    thr = (None if before[2] is None or after[2] is None
           else after[2] - before[2])
    return {"wall_s": wall_s, "cpu_s": after[0] - before[0],
            "nivcsw": after[1] - before[1], "throttled_us": thr}


def run_window(sweep: Sweep, seconds: float, manifests: str = None):
    """Whole grids back to back until one ends after ``seconds``.
    Returns (rows of each grid, each grid's record, window wall, manifest
    paths)."""
    import jax
    grids, records, paths = [], [], []
    start = time.perf_counter()
    while True:
        path = (os.path.join(manifests, f"grid{len(grids)}.jsonl")
                if manifests else None)
        before = host_usage()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_GRID):
            rows = sweep(path)
        t1 = time.perf_counter()
        grids.append(rows)
        records.append(grid_record(t1 - t0, before, host_usage()))
        paths.append(path)
        if t1 - start >= seconds:
            return grids, records, t1 - start, paths


def peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def device_info(devices, peak: int) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def heaviest_scheme(launches: list) -> str:
    """The scheme whose launches held the device longest (summed
    ``execute_s`` of the set-up grid's manifest)."""
    held = {}
    for ln in launches:
        held[ln["scheme"]] = held.get(ln["scheme"], 0.0) + ln["execute_s"]
    return max(held, key=held.get)


def traced_observations(sweep: Sweep, seconds: float, setup_manifest: str,
                        tmp: str) -> tuple:
    """The per-layer run: manifests for the whole window, then the trace's
    window: one ``sweep_grid`` call of the cell's grid for the scheme that
    held the device longest in the set-up grid, under the profiler. One
    scheme, not the grid: the device plane records every operation of
    every scan step (75 to 150 a step), and collecting a whole long grid's
    would take many minutes. It goes through the already-compiled manifest
    path, so nothing compiles while traced."""
    import jax
    from repro.netsim.obs.profile import read_manifest
    from bench import trace_reduce
    grids, records, window_s, paths = run_window(sweep, seconds, tmp)
    setup_launches = read_manifest(setup_manifest)[1]
    traced = heaviest_scheme(setup_launches)
    obs = {"steps": sweep.cell.steps(), "setup_launches": setup_launches,
           "grids": [dict(r, launches=read_manifest(p)[1])
                     for r, p in zip(records, paths)]}
    tdir = os.path.join(tmp, "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    t0 = time.perf_counter()
    with jax.profiler.trace(tdir, profiler_options=opts):
        with jax.profiler.TraceAnnotation(SPAN_GRID):
            sweep(os.path.join(tmp, "traced.jsonl"), (traced,))
    obs["trace"] = trace_reduce.reduce_dir(tdir, (SPAN_SETUP, SPAN_GRID))
    print(f"bench: traced the {traced} sweep; trace and its reduction took "
          f"{time.perf_counter() - t0!r} s", file=sys.stderr, flush=True)
    return grids, records, window_s, obs


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = grid.Cell(root, args.workload)
    devices = device_check(cell.chips)
    configure_compile_cache(root)
    counter = CompileCounter()
    sweep = Sweep(cell, args.seed)
    tmp = tempfile.mkdtemp(prefix="bench_")
    try:
        line = measure(args, root, cell, sweep, devices, counter, tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(line))
    return 0


def measure(args, root, cell, sweep, devices, counter, tmp) -> dict:
    """Set-up, window, the comparison and the metrics: the result line."""
    import jax

    setup_manifest = os.path.join(tmp, "setup.jsonl") if args.trace else None
    ready_s = time.perf_counter() - T0
    with jax.profiler.TraceAnnotation(SPAN_SETUP):
        warm_rows = sweep(setup_manifest)
    setup_s = time.perf_counter() - T0

    counter.armed = True
    if args.trace:
        grids, records, window_s, obs = traced_observations(
            sweep, args.seconds, setup_manifest, tmp)
    else:
        grids, records, window_s, _ = run_window(sweep, args.seconds)
    counter.armed = False
    peak = peak_bytes(devices)
    print(f"bench: {len(grids)} grids of {sweep.n_rows} rows in "
          f"{window_s!r} s; set-up {setup_s!r} s ({ready_s!r} s to the "
          f"set-up grid); grid walls "
          f"{[round(r['wall_s'], 3) for r in records]}; CPU s "
          f"{[round(r['cpu_s'], 3) for r in records]}; involuntary switches "
          f"{[r['nivcsw'] for r in records]}; throttled us "
          f"{[r['throttled_us'] for r in records]}", file=sys.stderr,
          flush=True)

    verdict = check.judge(cell, sweep.cells, [warm_rows] + grids, args.seed,
                          devices[0], compiles=counter.n)
    device = device_info(devices, peak)
    if args.trace:
        metrics = {}
        for m in metric_entries(root, "per_layer", cell.name):
            value = load_reader(root, m["name"]).read(obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        tr = obs["trace"]
        if tr is None:
            raise RuntimeError("bench: the profiler trace held no device "
                               "program inside the benchmark spans")
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}
    else:
        steps = sweep.n_rows * cell.steps() * len(grids)
        values = {"scenario_steps_per_s": steps / window_s,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in metric_entries(root, "end_to_end", cell.name)}
        breakdown = None
    for c in verdict["checks"]:
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    line = {"correct": verdict["correct"], "attempted": verdict["attempted"],
            "failed": verdict["failed"], "metrics": metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["grids"] = records
    line["checks"] = verdict["checks"]
    return line


if __name__ == "__main__":
    sys.exit(main())
