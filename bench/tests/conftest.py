import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.tests.tiny import DRIVE_CELL  # noqa: E402


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A cut-down checkout of the benchmark (``tiny.make``)."""
    from bench.tests import tiny
    return tiny.make(ROOT, str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture
def drive(tiny_root, monkeypatch, capsys):
    """Run bench/run.py's main on the CPU over the cut-down checkout (or
    ``root=``), the chip check and the persistent cache skipped; returns
    the result line."""
    import jax
    import bench.run as run
    monkeypatch.setattr(run, "device_check",
                        lambda chips: jax.devices("cpu")[:chips])
    monkeypatch.setattr(run, "configure_compile_cache", lambda root: None)

    def go(*extra, root=None):
        args = ["--workload", DRIVE_CELL, "--seed", str(2 ** 31 + 77),
                "--seconds", "0.1"] + list(extra)
        jax.clear_caches()
        try:
            assert run.main(args, root=root or tiny_root) == 0
        finally:
            jax.clear_caches()
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return go
