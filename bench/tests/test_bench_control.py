"""Sound runs, traced and not, come out correct; the control (the
reference one precision below the configuration's float32, put in the
program's place) does not."""
import math

import jax.numpy as jnp

import bench.run as run
from bench import check, grid
from bench.tests.tiny import DRIVE_CELL


def test_sound_run_is_correct(drive):
    line = drive()
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 14
    assert list(line)[-1] == "checks"
    assert {c["name"] for c in line["checks"]} >= {"row_gap"}
    assert set(line["metrics"]) == {"scenario_steps_per_s", "setup_s"}


def test_sound_traced_run_reports_every_layer(drive):
    line = drive("--trace", "1")
    assert line["correct"] is True
    assert set(line["metrics"]) == {"host_ms_per_grid", "compile_s",
                                    "device_step_us", "step_us.matchrdma",
                                    "idle_share"}
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["breakdown"]["device_ops"]


def test_control_bfloat16_reference_in_the_programs_place(drive, tiny_root,
                                                          monkeypatch):
    def control(self, manifest_path=None):
        picks = {s: list(range(len(self.cells))) for s in self.cell.schemes}
        refs = check.reference_rows(self.cell, self.cells, picks,
                                    dtype=jnp.bfloat16)
        return [refs[s][i] for i in range(len(self.cells))
                for s in self.cell.schemes]
    monkeypatch.setattr(run.Sweep, "__call__", control)
    line = drive()
    assert line["correct"] is False
    gap = {c["name"]: c["value"] for c in line["checks"]}["row_gap"]
    limit = grid.Cell(tiny_root, DRIVE_CELL).config["limits"]["row_gap"]
    assert math.isfinite(gap) and gap > 3 * limit
