"""BENCHMARK.json resolves by name, and each grid is its source's grid."""
import json
import os
import shutil

import pytest

from bench import grid
from bench.tests import tiny
import bench.run as run

ROOT = run.ROOT
BENCH = grid.load_json(os.path.join(ROOT, "BENCHMARK.json"))
# the benchmark's cells, and those it left out but keeps the mixes of
CELLS = sorted({w["name"] for w in BENCH["workloads"] + tiny.PARKED})
ALL = ("dcqcn", "pseudo_ack", "themis", "matchrdma", "geopipe", "sdr_rdma",
       "rdmacell")
KM = [1.0, 10.0, 50.0, 100.0, 300.0, 500.0, 1000.0]

# cell -> (cells in the grid, distances in grid order, scan steps, schemes,
# links)
SOURCE = {
    "fig3cd_congestion": (7, KM, 44_000, ALL, 1),
    "fig3b_msgsize": (42, [d for d in KM for _ in range(6)], 44_000,
                      ALL[:4], 1),
}


@pytest.fixture(scope="module")
def full_root(tmp_path_factory):
    """The benchmark at its own sizes, its left-out cells back in."""
    return tiny.full(ROOT, str(tmp_path_factory.mktemp("full")))


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(full_root, name):
    cell = grid.Cell(full_root, name)
    assert cell.chips in (1, 4)
    assert cell.schemes and cell.horizon_us > 0
    assert "row_gap" in cell.config["limits"]
    assert os.path.isfile(os.path.join(ROOT, cell.config["reference"]))


@pytest.mark.parametrize("name", sorted(SOURCE))
def test_grid_matches_source(full_root, name):
    n, km, steps, schemes, links = SOURCE[name]
    cell = grid.Cell(full_root, name)
    cells = cell.cells(0)
    assert len(cells) == n
    assert [c["net"]["distance_km"] for c in cells] == km
    assert cell.steps() == steps
    assert cell.schemes == schemes
    assert {c["net"]["num_paths"] for c in cells} == {links}


def test_fig3cd_seed0_is_the_congestion_workload():
    from repro.netsim.workload import congestion_workload
    cell = grid.Cell(ROOT, "fig3cd_congestion")
    h = cell.horizon_us
    want = congestion_workload(num_inter=4, num_intra=4,
                               burst_start_us=h / 3.0, burst_len_us=h / 3.0,
                               horizon_us=h)
    got = grid.to_program(cell.cells(0))
    assert all(s.workload == want for s in got)


def test_fig3b_seed0_is_the_throughput_workload(full_root):
    from repro.netsim.workload import throughput_workload
    cell = grid.Cell(full_root, "fig3b_msgsize")
    msgs = (1 << 10, 16 << 10, 64 << 10, 256 << 10, 1 << 20, 8 << 20)
    got = grid.to_program(cell.cells(0))
    want = [throughput_workload(m, 1, 4) for _ in KM for m in msgs]
    assert [s.workload for s in got] == want


@pytest.mark.parametrize("name", sorted(SOURCE))
def test_seed_moves_only_start_times(full_root, name):
    cell = grid.Cell(full_root, name)
    base, seeded = cell.cells(0), cell.cells(2 ** 31 + 12345)
    assert seeded == cell.cells(2 ** 31 + 12345)
    assert len(base) == len(seeded)
    moved = 0
    for a, b in zip(base, seeded):
        assert a["net"] == b["net"]
        assert len(a["flows"]) == len(b["flows"])
        for fa, fb in zip(a["flows"], b["flows"]):
            moved += fa["start_us"] != fb["start_us"]
            assert {k: v for k, v in fa.items() if k != "start_us"} == \
                {k: v for k, v in fb.items() if k != "start_us"}
            assert 0.0 <= fb["start_us"] < cell.horizon_us
    assert moved > 0


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_declares_its_entry(metric):
    mod = run.load_reader(ROOT, metric["name"])
    assert mod.LAYER == metric["layer"]
    assert mod.UNIT == metric["unit"]
    assert mod.MOVES == metric["moves"]
    assert mod.read({}) is None


def test_new_entries_need_no_code_edit(tmp_path):
    """A config, a traffic mix and a per-layer metric added as new files
    plus new BENCHMARK.json entries resolve with no edit elsewhere."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCH))
    cfg = grid.load_json(os.path.join(ROOT, "bench/configs/"
                                      "dual_dc_16x100g.json"))
    cfg.update(name="dual_dc_8x100g")
    cfg["net"] = dict(cfg["net"], num_otn_links=8)
    (root / "bench/configs/dual_dc_8x100g.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/short_1km.json").write_text(json.dumps({
        "horizon_us": 1000.0, "schemes": ["dcqcn"],
        "axes": [{"net": "distance_km", "values": [1.0]}],
        "flows": [{"count": 2, "is_inter": True, "msg_size": 4096,
                   "concurrency": 2}]}))
    (root / "bench/metrics/grids_read.py").write_text(
        'LAYER = "sweep API and row extraction"\nUNIT = "grids"\n'
        'MOVES = "scenario_steps_per_s"\n\n\n'
        'def read(obs):\n    return float(len(obs.get("grids") or []))'
        ' or None\n')
    bench["configs"].append({"name": "dual_dc_8x100g", "source": "x",
                             "file": "bench/configs/dual_dc_8x100g.json",
                             "reduced": ["num_otn_links"], "why": "x"})
    bench["workloads"].append({"name": "throwaway", "config":
                               "dual_dc_8x100g", "traffic": "short_1km",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({
        "name": "grids_read", "unit": "grids", "better": "higher",
        "source": "host_clock", "layer": "sweep API and row extraction",
        "moves": "scenario_steps_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = grid.Cell(str(root), "throwaway")
    cells = cell.cells(7)
    assert cells[0]["net"]["num_otn_links"] == 8
    assert len(cells[0]["flows"]) == 2 and cell.steps() == 200
    names = [m["name"] for m in run.metric_entries(str(root), "per_layer",
                                                   "throwaway")]
    assert "grids_read" in names and "step_us.matchrdma" not in names
    mod = run.load_reader(str(root), "grids_read")
    assert mod.read({"grids": [1, 2]}) == 2.0
