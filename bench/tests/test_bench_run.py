"""bench/run.py refuses to run without a TPU, on another number of chips
than the cell names, and in a directory that holds only the benchmark's
own files."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import bench.run as run

ARGS = ["--workload", "fig3cd_congestion", "--seed", "3", "--seconds", "1"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        try:
            assert not isinstance(json.loads(line), dict)
        except ValueError:
            pass


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _run(run.ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    _no_result(proc)


def test_bare_benchmark_directory_exits_nonzero(tmp_path):
    shutil.copytree(os.path.join(run.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    _no_result(proc)


@pytest.mark.parametrize("chips,ok", [(4, True), (1, False), (8, False)])
def test_device_check_wants_exactly_the_cells_chips(chips, ok):
    """sweep_grid shards over every visible device, so a one-chip cell on a
    host that shows four must not run (here: four forced CPU devices)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import bench.run as r; "
            f"print(len(r.device_check({chips}, platform='cpu')))")
    proc = subprocess.run([sys.executable, "-c", code], cwd=run.ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    if ok:
        assert proc.returncode == 0 and proc.stdout.split()[-1] == "4"
    else:
        assert proc.returncode != 0
        assert f"runs on {chips} chips, JAX sees 4" in proc.stderr
