"""Readings that set a cell's ``row_gap`` limit, on the chip.

    python3 bench/control.py --workload NAME --seeds 101,102,... \
        [--control-seeds 3] [--out PATH]

For each seed, in one process: one grid of the program, then the
reference in float32 on the seed's sample of cells (the lower reading:
the program's ``row_gap``), then, for the first ``--control-seeds`` seeds,
the control: the same reference computed in bfloat16, one precision below
the configuration's float32, put in the program's place and judged against
the float32 reference (the upper reading). The reference is the one the
cell's configuration names, resolved as a benchmark run resolves it
(``grid.Cell``, ``check.reference_rows``). Prints one JSON line per seed
and writes them all to ``--out``. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import check, grid   # noqa: E402
from bench import run   # noqa: E402


def readings(cell, seed: int, device, control: bool) -> dict:
    import jax.numpy as jnp
    sweep = run.Sweep(cell, seed)
    t0 = time.perf_counter()
    rows = sweep()
    t1 = time.perf_counter()
    picks = check.sample(len(sweep.cells), cell.schemes, seed)
    refs = check.reference_rows(cell, sweep.cells, picks, device=device)
    t2 = time.perf_counter()
    g = check.gaps(rows, refs, cell.schemes)
    out = {"seed": seed, "reference": cell.config["reference"],
           "program_gap": max(g)[0],
           "program_worst": list(max(g)[1:]),
           "bad_rows": check.bad_rows(rows, sweep.cells, cell.schemes),
           "grid_s": t1 - t0, "reference_s": t2 - t1}
    if control:
        low = check.reference_rows(cell, sweep.cells, picks, device=device,
                                   dtype=jnp.bfloat16)
        n = len(cell.schemes)
        as_rows = [None] * (len(sweep.cells) * n)
        for si, s in enumerate(cell.schemes):
            for i, r in low[s].items():
                as_rows[i * n + si] = r
        c = check.gaps(as_rows, refs, cell.schemes)
        out.update(control_gap=max(c)[0], control_worst=list(max(c)[1:]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = grid.Cell(ROOT, args.workload)
    device = run.device_check(1)[0]
    run.configure_compile_cache(ROOT)
    seeds = [int(s) for s in args.seeds.split(",")]
    lines = []
    for k, seed in enumerate(seeds):
        r = readings(cell, seed, device, k < args.control_seeds)
        print(json.dumps(r), flush=True)
        lines.append(r)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write("\n".join(json.dumps(r) for r in lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
