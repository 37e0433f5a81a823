"""Each per-layer metric reader on synthetic launch-manifest records."""
import pytest

import bench.run as run

STEPS = 1000


def launch(scheme, execute_s, compile_s=0.0):
    return {"scheme": scheme, "execute_s": execute_s, "compile_s": compile_s}


OBS = {
    "steps": STEPS,
    "setup_launches": [launch("dcqcn", 0.5, 2.0),
                       launch("matchrdma", 0.9, 3.0)],
    "grids": [
        {"wall_s": 1.5, "launches": [launch("dcqcn", 0.2),
                                     launch("matchrdma", 0.8)]},
        {"wall_s": 1.7, "launches": [launch("dcqcn", 0.3),
                                     launch("matchrdma", 1.0)]},
    ],
    "trace": {"busy_s": 0.75, "window_s": 1.0, "idle_share": 0.25,
              "device_ops": [], "idle_gaps": []},
}

EXPECT = {
    # host: (1.5 - 1.0 + 1.7 - 1.3) / 2 grids
    "host_ms_per_grid": 450.0,
    "compile_s": 5.0,
    # 2.3 s over 4 launches of 1000 steps
    "device_step_us": 575.0,
    # 1.8 s over 2 matchrdma launches
    "step_us.matchrdma": 900.0,
    "idle_share": 0.25,
}


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_on_synthetic_manifests(name):
    value = run.load_reader(run.ROOT, name).read(OBS)
    assert value == pytest.approx(EXPECT[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_finds_nothing_returns_nothing(name):
    empty = {"steps": STEPS, "setup_launches": [], "grids": [],
             "trace": None}
    assert run.load_reader(run.ROOT, name).read(empty) is None


def test_matchrdma_reader_silent_without_matchrdma():
    obs = dict(OBS, grids=[{"wall_s": 1.0,
                            "launches": [launch("dcqcn", 0.2)]}])
    assert run.load_reader(run.ROOT, "step_us.matchrdma").read(obs) is None


def test_trace_follows_the_scheme_that_held_the_device_longest():
    launches = OBS["setup_launches"] + [launch("dcqcn", 0.3)]
    assert run.heaviest_scheme(launches) == "matchrdma"
    assert run.heaviest_scheme(launches + [launch("dcqcn", 0.2)]) == "dcqcn"
