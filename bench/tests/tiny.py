"""A checkout of the benchmark cut to a size a CPU test run can hold: the
same configurations and traffic mixes with a short horizon and two or
three cells per grid.

It adds cells that BENCHMARK.json does not have: ``multipath3_skew``,
the Fig. 3 configuration on three parallel paths of unequal delay and
capacity, so that the tests hold the reference's link axis (spray,
per-link PFC, rdmacell's reorder buffer) to the program's; and the cells
the benchmark left out whose traffic mixes it keeps (``PARKED``), so
that their grids stay pinned until they come back."""
import json
import os
import shutil

# the cell the harness tests drive end to end
DRIVE_CELL = "fig3cd_congestion"
HORIZON_US = {"fig3cd_congestion": 4000.0, "fig3b_msgsize": 4000.0,
              "multipath3_skew": 3000.0}
AXES = {"fig3cd_congestion": [[1.0, 300.0]],
        "fig3b_msgsize": [[1.0, 100.0], [16384, 1048576]],
        "multipath3_skew": [[[1.0, 1.0, 1.0], [1.0, 2.0, 4.0]],
                            [[0.5, 0.3, 0.2]]]}


# left out of BENCHMARK.json while the program reads wrong rows on the chip
# (PERF.md, Open questions); its mix stays under bench/traffic
PARKED = [{"name": "fig3b_msgsize", "config": "dual_dc_16x100g",
           "traffic": "fig3b_msgsize", "chips": 1,
           "why": "42 cells per launch, per-cell work dominates"}]


def _json(path: str, obj=None):
    if obj is None:
        with open(path) as f:
            return json.load(f)
    with open(path, "w") as f:
        json.dump(obj, f)


def _add_three_path_cell(dest: str) -> None:
    """The fig3cd flows on the Fig. 3 configuration with three paths."""
    bench = _json(os.path.join(dest, "BENCHMARK.json"))
    base = {c["name"]: c for c in bench["configs"]}["dual_dc_16x100g"]
    cfg = _json(os.path.join(dest, base["file"]))
    cfg["net"] = dict(cfg["net"], num_paths=3)
    cfg_file = "bench/configs/three_path_100km.json"
    _json(os.path.join(dest, cfg_file), cfg)
    mix = _json(os.path.join(dest, "bench/traffic/fig3cd_congestion.json"))
    mix["axes"] = [{"net": "path_delay_scale"}, {"net": "path_cap_frac"}]
    _json(os.path.join(dest, "bench/traffic/multipath3_skew.json"), mix)
    bench["configs"].append(dict(base, name="three_path_100km",
                                 file=cfg_file))
    bench["workloads"].append({"name": "multipath3_skew",
                               "config": "three_path_100km",
                               "traffic": "multipath3_skew", "chips": 1,
                               "why": "the reference's link axis"})
    _json(os.path.join(dest, "BENCHMARK.json"), bench)


def full(root: str, dest: str) -> str:
    """A copy of the benchmark at its own sizes, with the ``PARKED`` cells
    back in its BENCHMARK.json."""
    shutil.copytree(os.path.join(root, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(root, "BENCHMARK.json"), dest)
    bench = _json(os.path.join(dest, "BENCHMARK.json"))
    have = {w["name"] for w in bench["workloads"]}
    bench["workloads"] += [w for w in PARKED if w["name"] not in have]
    _json(os.path.join(dest, "BENCHMARK.json"), bench)
    return dest


def make(root: str, dest: str) -> str:
    full(root, dest)
    _add_three_path_cell(dest)
    for name, horizon in HORIZON_US.items():
        path = os.path.join(dest, "bench", "traffic", name + ".json")
        mix = _json(path)
        mix["horizon_us"] = horizon
        for axis, values in zip(mix["axes"], AXES[name]):
            axis["values"] = values
        _json(path, mix)
    return dest
