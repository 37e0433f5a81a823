"""The pipeline-boundary cell ``pp2dc_dsv3_rounds``: its grid is the one
its sources give, its reference (``bench/reference/rounds.py``) judges the
program's round gate, and a program without the gate is not correct."""
import hashlib
import json
import os
from fractions import Fraction

import jax
import numpy as np
import pytest

import bench.run as run
from bench import check, grid
from bench.tests import tiny

ROOT = run.ROOT
CELL = "pp2dc_dsv3_rounds"
ALL = ("dcqcn", "pseudo_ack", "themis", "matchrdma", "geopipe", "sdr_rdma",
       "rdmacell")
KM = [1.0, 10.0, 50.0, 100.0, 300.0, 500.0, 1000.0]

# DeepSeek-V3 (arXiv:2412.19437; its config.json): the width that crosses
# a stage boundary, the sequence, 16-way PP on 2,048 GPUs, the pre-training
# totals
HIDDEN, SEQ, BF16 = 7168, 4096, 2
GPUS, STAGES = 2048, 16
TOKENS, GPU_HOURS = Fraction(148 * 10 ** 11), Fraction(2664 * 10 ** 3)

# sha256 of the cells (json, sorted keys) and of the stacked workload
# leaves of the program's scenarios (round fields included)
PINNED = {
    0: ("fca3788f499e762750017d6f80edfe61cebc2008d384687d6f67fd0648af6f75",
        "7a198264030b70e5ad5242a3288898001022e0ae81e4f4643fb34d38855b1f64"),
    3000000017: (
        "e99e1cdf94781e2e8dce8bad4d9c63a5567e6fefd76a56e3da37d20895ceecab",
        "2064b0573fa02342c45e1983fc13b5874dbaee96a1d07a06df1eec071a21ecba"),
}


def _hashes(cells):
    from repro.netsim.workload import stack_workload_params
    wlp = stack_workload_params([s.workload for s in grid.to_program(cells)])
    h = hashlib.sha256()
    for leaf in wlp:
        h.update(np.ascontiguousarray(leaf).tobytes())
    return (hashlib.sha256(json.dumps(cells, sort_keys=True).encode())
            .hexdigest(), h.hexdigest())


# ------------------------------------------------ the grid, from its sources

def test_the_grid_is_the_deployments():
    cell = grid.Cell(ROOT, CELL)
    cells = cell.cells(0)
    assert len(cells) == 7 and cell.steps() == 100_000
    assert [c["net"]["distance_km"] for c in cells] == KM
    assert cell.schemes == ALL and cell.chips == 1
    rate = TOKENS / (GPU_HOURS * 3600 / GPUS)           # tokens/s
    period_us = (GPUS // STAGES) * SEQ / rate * 10 ** 6
    for c in cells:
        assert len(c["flows"]) == GPUS // STAGES == 128
        for f in c["flows"]:
            assert f["is_inter"] == 1.0 and f["start_us"] == 0.0
            assert f["round_bytes"] == SEQ * HIDDEN * BF16 == 58_720_256
            assert Fraction(f["round_period_us"]) == period_us == 165_888
    assert cell.config["net"] == grid.load_json(os.path.join(
        ROOT, "bench/configs/dual_dc_16x100g.json"))["net"]
    assert cell.config["reference"] == "bench/reference/rounds.py"
    assert cell.config["limits"]["row_gap"] == 0.02


def test_the_seed_moves_only_start_times_within_the_jitter():
    cell = grid.Cell(ROOT, CELL)
    base, seeded = cell.cells(0), cell.cells(2 ** 31 + 12345)
    starts = []
    for a, b in zip(base, seeded):
        assert a["net"] == b["net"]
        for fa, fb in zip(a["flows"], b["flows"]):
            starts.append(fb["start_us"])
            assert {k: v for k, v in fa.items() if k != "start_us"} == \
                {k: v for k, v in fb.items() if k != "start_us"}
    assert 0.0 <= min(starts) and max(starts) < 200.0
    assert len(set(starts)) == len(starts)


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_cells_and_workloads_are_the_ones_pinned(seed):
    assert _hashes(grid.Cell(ROOT, CELL).cells(seed)) == PINNED[seed]


# ------------------------------------------------ judged by its reference

def _tiny(dest: str, km=(1.0, 100.0), round_bytes=1.5e6,
          round_period_us=300.0) -> str:
    """The benchmark with this cell cut to two distances, four round flows
    (by default some rounds bound by their period, some by delivery)
    beside two intra-DC flows, and a 4 ms horizon."""
    root = tiny.make(ROOT, dest)
    path = os.path.join(root, "bench", "traffic", CELL + ".json")
    mix = grid.load_json(path)
    mix["horizon_us"] = 4000.0
    mix["axes"][0]["values"] = list(km)
    mix["flows"] = [
        dict(mix["flows"][0], count=4, concurrency=4,
             round_bytes=round_bytes, round_period_us=round_period_us),
        {"count": 2, "is_inter": False, "msg_size": 262144,
         "concurrency": 8}]
    with open(path, "w") as f:
        json.dump(mix, f)
    return root


@pytest.fixture(scope="module")
def rounds_root(tmp_path_factory):
    return _tiny(str(tmp_path_factory.mktemp("rounds")))


@pytest.fixture
def drive_rounds(rounds_root, monkeypatch, capsys):
    """bench/run.py's main on the CPU over a cut-down cell (by default
    ``rounds_root``'s)."""
    monkeypatch.setattr(run, "device_check",
                        lambda chips: jax.devices("cpu")[:chips])
    monkeypatch.setattr(run, "configure_compile_cache", lambda root: None)

    def go(root=rounds_root):
        args = ["--workload", CELL, "--seed", str(2 ** 31 + 41),
                "--seconds", "0.1"]
        jax.clear_caches()
        try:
            assert run.main(args, root=root) == 0
        finally:
            jax.clear_caches()
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return go


def test_the_program_is_judged_against_the_round_reference(rounds_root,
                                                           drive_rounds):
    line = drive_rounds()
    gap = {c["name"]: c["value"] for c in line["checks"]}["row_gap"]
    assert line["correct"] is True and line["failed"] == 0
    assert gap < 1e-4
    # the round columns are among those compared
    cell = grid.Cell(rounds_root, CELL)
    cells = cell.cells(2 ** 31 + 41)
    refs = check.reference_rows(cell, cells, {"dcqcn": [0, 1]})
    for row in refs["dcqcn"].values():
        assert row["round_progress"] > 1.0
        assert 0.0 <= row["round_stall_frac"] < 1.0
    assert refs["dcqcn"][1]["round_stall_frac"] > 0.0


def test_over_a_hundred_rounds_the_program_keeps_to_the_reference(
        tmp_path, drive_rounds):
    # 64 KiB rounds due every 20 us at 1 and 10 km: well over 100 rounds a
    # flow in 4 ms, where a gate whose slack grows with the rounds would
    # release round R+1 before round R is in
    root = _tiny(str(tmp_path), km=(1.0, 10.0), round_bytes=65536.0,
                 round_period_us=20.0)
    line = drive_rounds(root)
    gap = {c["name"]: c["value"] for c in line["checks"]}["row_gap"]
    assert line["correct"] is True and gap < 1e-4
    cell = grid.Cell(root, CELL)
    refs = check.reference_rows(cell, cell.cells(2 ** 31 + 41),
                                {"dcqcn": [0]})
    assert refs["dcqcn"][0]["round_progress"] > 100.0


def test_a_program_that_ignores_the_gate_is_not_correct(drive_rounds,
                                                        monkeypatch):
    from repro.netsim import fluid
    make = fluid.make_step_fn

    def ungated(*a, **kw):
        step = make(*a, **dict(kw, rounds=False))

        def go(state, t):
            new, out = step(state, t)
            return new._replace(
                round_r=state.round_r, round_due_us=state.round_due_us,
                stall_us=state.stall_us, round_late=state.round_late), out
        go.ctx = step.ctx
        return go
    monkeypatch.setattr(fluid, "make_step_fn", ungated)
    line = drive_rounds()
    assert line["correct"] is False and line["failed"] > 0


def test_the_reference_refuses_a_negative_round_field():
    ref = grid.load_module(os.path.join(ROOT, "bench/reference/rounds.py"),
                           "rounds_refusal")
    cell = grid.Cell(ROOT, CELL)
    cells = cell.cells(0)[:1]
    cells[0]["flows"][0]["round_bytes"] = -1.0
    with pytest.raises(ValueError, match="round_bytes"):
        ref.refuse_unmodelled(cell.config, cells)
    cells[0]["flows"][0].update(round_bytes=1.0, route=[1.0, 0.0])
    with pytest.raises(ValueError, match="route"):
        ref.refuse_unmodelled(cell.config, cells)
