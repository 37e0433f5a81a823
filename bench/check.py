"""What decides ``correct``: the rows the timed grids produced, against the
plain reference that the cell's configuration names under ``"reference"``
(a Python file, loaded by its path), run after the window closed.

Every reference module provides two functions:

* ``simulate_rows(scheme, cells, grid_nets, horizon_us, dtype, device)``:
  the rows of ``cells`` (``bench/grid.py`` cell dicts) under ``scheme``,
  one per cell in order, each with the program's row columns.
  ``grid_nets`` are the nets of the whole grid (statics shared across
  it), ``dtype`` the float type of every quantity (the configuration's
  precision, or the control's one below it), ``device`` where to run
  (``None``: JAX's default).
* ``refuse_unmodelled(config, cells)``: raises ``ValueError``, naming the
  field, where the configuration or a cell sets anything the module does
  not model. ``grid.Cell`` calls it as it loads, before any chip work.

* Every grid of the window must return every row (one per cell and
  scheme, in grid order), with every column finite (``avg_fct_us`` may be
  NaN or inf: those are its in-band "no finite flow" and "none finished").
* Every grid must return the rows the set-up grid returned, bit for bit:
  the sweep is deterministic.
* For a sample of cells drawn from the seed (``SAMPLE_CELLS`` per scheme,
  so every scheme is covered), the reference recomputes the row, and each
  column's relative gap ``|a - b| / max(|a|, |b|, GAP_FLOOR)`` must stay
  within the configuration's ``limits.row_gap``.

* Nothing may compile, or load from the persistent cache, inside the
  window: every program the window runs was built in set-up.

``attempted`` counts the rows of the window's grids; ``failed`` counts those
missing, non-finite, changed from the set-up grid, or outside the limit.
"""
from __future__ import annotations

import json
import math

import numpy as np

SAMPLE_CELLS = 8
GAP_FLOOR = 1e-3          # columns are MB, Gbps, us or fractions
EXEMPT = ("avg_fct_us",)  # NaN / inf are its documented sentinels
KEYS = ("scheme", "distance_km")


def row_gap(a: dict, b: dict) -> float:
    """Widest relative gap between two rows over the columns of ``b``
    (2.0, the largest a relative gap can be, where one side is missing or
    non-finite and the other is not)."""
    worst = 0.0
    for k, y in b.items():
        if k in KEYS:
            if a.get(k) != y:
                return 2.0
            continue
        x = a.get(k)
        if x is None:
            return 2.0
        if not (math.isfinite(x) and math.isfinite(y)):
            same = (x == y) or (math.isnan(x) and math.isnan(y))
            worst = max(worst, 0.0 if same else 2.0)
            continue
        worst = max(worst, abs(x - y) / max(abs(x), abs(y), GAP_FLOOR))
    return worst


def sample(n_cells: int, schemes, seed: int) -> dict:
    """scheme -> sorted cell indices to recompute, drawn from the seed."""
    rng = np.random.default_rng([int(seed) % 2 ** 64, 0x5EED])
    k = min(SAMPLE_CELLS, n_cells)
    return {s: sorted(int(i) for i in rng.choice(n_cells, k, replace=False))
            for s in schemes}


def bad_rows(rows: list, cells: list, schemes) -> int:
    """Rows missing, out of order or non-finite in one grid's output."""
    expect = [(s, float(c["net"]["distance_km"])) for c in cells
              for s in schemes]
    bad = max(len(expect) - len(rows), 0)
    for r, key in zip(rows, expect):
        if (r.get("scheme"), r.get("distance_km")) != key or any(
                not math.isfinite(v) for k, v in r.items()
                if k not in KEYS + EXEMPT):
            bad += 1
    return bad


def changed_rows(rows: list, baseline: list) -> int:
    return sum(json.dumps(a, sort_keys=True) != json.dumps(b, sort_keys=True)
               for a, b in zip(rows, baseline))


def reference_rows(cell, cells: list, picks: dict, device=None,
                   dtype=None) -> dict:
    """scheme -> {cell index: reference row}, from the cell's own
    reference module (``cell.reference``)."""
    import jax.numpy as jnp
    nets = [c["net"] for c in cells]
    out = {}
    for s, idx in picks.items():
        rows = cell.reference.simulate_rows(
            s, [cells[i] for i in idx], nets, cell.horizon_us,
            dtype=dtype or jnp.float32, device=device)
        out[s] = dict(zip(idx, rows))
    return out


def gaps(rows: list, refs: dict, schemes) -> list:
    """(gap, scheme, cell index) of every sampled row."""
    n = len(schemes)
    out = []
    for si, s in enumerate(schemes):
        for i, ref in refs[s].items():
            j = i * n + si
            out.append((row_gap(rows[j], ref) if j < len(rows) else 2.0,
                        s, i))
    return out


def judge(cell, cells: list, grids: list, seed: int, device=None,
          compiles: int = 0) -> dict:
    """``grids``: the set-up grid's rows, then each window grid's rows;
    ``compiles``: the programs compiled or loaded inside the window."""
    limit = float(cell.config["limits"]["row_gap"])
    schemes = cell.schemes
    baseline, window = grids[0], grids[1:]
    bad = sum(bad_rows(g, cells, schemes) for g in window)
    changed = sum(changed_rows(g, baseline) for g in window)
    refs = reference_rows(cell, cells, sample(len(cells), schemes, seed),
                          device=device)
    g = gaps(window[-1], refs, schemes)
    worst = max(x[0] for x in g)
    outside = sum(x[0] > limit for x in g)
    checks = [
        {"name": "row_gap", "value": worst, "limit": limit},
        {"name": "rows_outside", "value": outside, "limit": 0},
        {"name": "rows_missing_or_nonfinite", "value": bad, "limit": 0},
        {"name": "rows_changed_from_setup", "value": changed, "limit": 0},
        {"name": "compiles_in_window", "value": compiles, "limit": 0},
    ]
    failed = bad + changed + outside
    return {"correct": failed == 0 and worst <= limit and compiles == 0,
            "attempted": len(cells) * len(schemes) * len(window),
            "failed": failed, "checks": checks}
