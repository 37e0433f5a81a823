"""A cell takes everything from its files: the reference its configuration
names, every flow field its traffic mix sets, and its channel. What the
reference does not model is refused as the cell loads, before any chip
work."""
import hashlib
import time
import json
import os

import pytest

import bench.run as run
from bench import check, control, grid
from bench.tests import tiny
from bench.tests.tiny import DRIVE_CELL

ROOT = run.ROOT
FLUID = os.path.join(ROOT, "bench", "reference", "fluid.py")
CONFIG = "bench/configs/dual_dc_16x100g.json"

# the Fig. 3 reference, every column of every row scaled
SCALED = '''
import importlib.util
_spec = importlib.util.spec_from_file_location("planted_inner", {fluid!r})
_inner = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_inner)
refuse_unmodelled = _inner.refuse_unmodelled


def simulate_rows(scheme, cells, grid_nets, horizon_us, dtype, device):
    with open({mark!r}, "a") as f:
        f.write(scheme + "\\n")
    rows = _inner.simulate_rows(scheme, cells, grid_nets, horizon_us,
                                dtype=dtype, device=device)
    return [{{k: v if k in ("scheme", "distance_km") else v * {scale!r}
             for k, v in r.items()}} for r in rows]
'''

# models everything, records what it is handed, answers one column
RECORDER = '''
import json

import jax.numpy as jnp


def refuse_unmodelled(config, cells):
    pass


def simulate_rows(scheme, cells, grid_nets, horizon_us, dtype, device):
    with open({mark!r}, "a") as f:
        f.write(json.dumps({{"scheme": scheme,
                            "dtype": jnp.dtype(dtype).name,
                            "flows": [c["flows"] for c in cells]}}) + "\\n")
    return [{{"scheme": scheme, "distance_km": float(c["net"]["distance_km"]),
             "throughput_gbps": 1.0}} for c in cells]
'''

# sha256 of the cells (json, sorted keys) and of repr(to_program(cells)),
# as the generator made them at the commit before flow groups carried
# every FlowSpec field
PINNED = {
    ("fig3cd_congestion", 0): (
        "488a4d7ad8c39d23135fd94822fbbae4adfc61bbf6767207bdfbc485bee65255",
        "e5bb6baf86fbf924bbdf86ce2f5be618786cc06dcfbb18ed011b382c345fce93"),
    ("fig3cd_congestion", 3000000017): (
        "3bfc7879f0417b4186c23ee0fc71d0f85c903a645a901ef4f344bcb71467eda7",
        "18e9b9c15e7bf767b0b21fed3dcc80d51346363f63323b0398de4143b29637f4"),
    ("fig3b_msgsize", 0): (
        "b1e5c475df014e5238758b70fe2a39559d125f83ced8a0cdf3686fef002e11e1",
        "4040e0ca19bb5d1f6c5a1001b2c2c769d59b2e02f630ff305469a49db369f7eb"),
    ("fig3b_msgsize", 3000000017): (
        "0270e5a0ffa799d3868c1968f1ff502a2d94669c350b846a1d83b6853b6c050d",
        "0ac83988816986b2565c1f0ba63349e97010c2fef94561b4d6b192ccdf2813fe"),
}


def _edit(path: str, fn) -> None:
    obj = grid.load_json(path)
    fn(obj)
    with open(path, "w") as f:
        json.dump(obj, f)


def checkout(tmp_path, config=None, mix=None, planted=None, **fmt):
    """A cut-down checkout whose drive cell runs two schemes on one
    distance, with its configuration and traffic mix edited by ``config``
    and ``mix`` and, given ``planted``, its reference replaced by that
    module source (formatted with ``mark``, the file it writes to, and
    ``fmt``). Returns (root, mark)."""
    root = tiny.make(ROOT, str(tmp_path / "checkout"))
    mark = str(tmp_path / "mark.txt")

    def small(m):
        m["schemes"] = ["dcqcn", "matchrdma"]
        m["axes"][0]["values"] = [300.0]
        if mix:
            mix(m)
    _edit(os.path.join(root, "bench", "traffic", DRIVE_CELL + ".json"),
          small)
    edits = [config] if config else []
    if planted is not None:
        path = str(tmp_path / "planted_reference.py")
        with open(path, "w") as f:
            f.write(planted.format(mark=mark, **fmt))
        edits.append(lambda c: c.update(reference=path))
    for edit in edits:
        _edit(os.path.join(root, CONFIG), edit)
    return root, mark


def fig3cd(seed=0):
    """The real Fig. 3 configuration and the fig3cd cells at ``seed``."""
    cfg = grid.load_json(os.path.join(ROOT, CONFIG))
    mix = grid.load_json(os.path.join(ROOT, "bench", "traffic",
                                      "fig3cd_congestion.json"))
    return cfg, grid.build(cfg, mix, seed)


# ------------------------------------------------ the reference it names

@pytest.mark.parametrize("scale,correct", [(1.0, True), (1.05, False)])
def test_the_configurations_reference_judges_the_run(drive, tmp_path, scale,
                                                     correct):
    root, mark = checkout(tmp_path, planted=SCALED, fluid=FLUID, scale=scale)
    line = drive(root=root)
    with open(mark) as f:
        assert sorted(set(f.read().split())) == ["dcqcn", "matchrdma"]
    assert line["correct"] is correct
    gap = {c["name"]: c["value"] for c in line["checks"]}["row_gap"]
    if correct:
        assert line["failed"] == 0 and gap < 1e-5
    else:
        assert gap == pytest.approx(0.05 / 1.05, rel=1e-3)


@pytest.mark.parametrize("edit,error", [
    (lambda c: c.update(reference="bench/reference/nowhere.py"),
     FileNotFoundError),
    (lambda c: c.pop("reference"), ValueError),
    (lambda c: c.update(channel="bernoulli_loss"), ValueError),
    (lambda c: c["net"].update(failure_schedule=[[[10.0, 20.0]]]),
     ValueError),
], ids=["missing_path", "no_reference", "channel", "failure_schedule"])
def test_a_cell_is_refused_as_it_loads(tmp_path, monkeypatch, edit, error):
    root, _ = checkout(tmp_path, config=edit)
    with pytest.raises(error):
        grid.Cell(root, DRIVE_CELL)

    def chip(chips):
        raise AssertionError("the refused cell reached the chip check")
    monkeypatch.setattr(run, "device_check", chip)
    with pytest.raises(error):
        run.main(["--workload", DRIVE_CELL, "--seed", "5", "--seconds",
                  "0.1"], root=root)


def test_control_runs_the_cells_own_reference(tmp_path):
    root, mark = checkout(tmp_path, planted=RECORDER)
    cell = grid.Cell(root, DRIVE_CELL)
    out = control.readings(cell, 2 ** 31 + 9, None, True)
    assert out["reference"].endswith("planted_reference.py")
    with open(mark) as f:
        seen = [json.loads(ln) for ln in f]
    assert sorted({(r["scheme"], r["dtype"]) for r in seen}) == [
        ("dcqcn", "bfloat16"), ("dcqcn", "float32"),
        ("matchrdma", "bfloat16"), ("matchrdma", "float32")]


# ------------------------------------------------ flow fields, as data

THREE_SITES = dict(num_paths=3, num_sites=3,
                   site_edges=[[0, 1], [0, 2], [1, 2]])


def _route_by_group(m):
    m["flows"][0].update(route=[0.5, 0.3, 0.2], src_site=0, dst_site=2)


def _route_by_axes(m):
    m["axes"] += [{"flows": "route", "values": [[0.5, 0.3, 0.2]]},
                  {"flows": "src_site", "values": [0]},
                  {"flows": "dst_site", "values": [2]}]


@pytest.mark.parametrize("mix", [_route_by_group, _route_by_axes],
                         ids=["group", "axes"])
def test_flow_fields_reach_the_program_and_the_reference(tmp_path, mix):
    root, mark = checkout(tmp_path, mix=mix, planted=RECORDER,
                          config=lambda c: c["net"].update(THREE_SITES))
    cell = grid.Cell(root, DRIVE_CELL)
    cells = cell.cells(2 ** 31 + 3)
    first = cells[0]["flows"][0]
    assert (first["route"], first["src_site"], first["dst_site"]) == (
        [0.5, 0.3, 0.2], 0, 2)
    spec = grid.to_program(cells)[0].workload.flows[0]
    assert (spec.route, spec.src_site, spec.dst_site) == (
        (0.5, 0.3, 0.2), 0, 2)
    check.reference_rows(cell, cells, {"dcqcn": [0]})
    with open(mark) as f:
        (seen,) = [json.loads(ln) for ln in f]
    assert seen["flows"] == [cells[0]["flows"]]


def test_an_unknown_group_key_fails_to_program_naming_it():
    cfg, _ = fig3cd()
    mix = grid.load_json(os.path.join(ROOT, "bench", "traffic",
                                      "fig3cd_congestion.json"))
    mix["flows"][0]["rounds"] = 4
    cells = grid.build(cfg, mix, 0)
    assert cells[0]["flows"][0]["rounds"] == 4
    with pytest.raises(ValueError, match="'rounds'"):
        grid.to_program(cells)


def test_an_unknown_group_key_fails_the_cell_naming_it(tmp_path):
    root, _ = checkout(tmp_path,
                       mix=lambda m: m["flows"][1].update(rounds=4))
    with pytest.raises(ValueError, match="'rounds'"):
        grid.Cell(root, DRIVE_CELL)


@pytest.mark.parametrize("key", ["window", "duty"])
def test_a_group_key_the_generator_derives_fails(key):
    cfg, _ = fig3cd()
    mix = grid.load_json(os.path.join(ROOT, "bench", "traffic",
                                      "fig3cd_congestion.json"))
    mix["flows"][0][key] = 1.0
    with pytest.raises(ValueError, match=f"'{key}'"):
        grid.build(cfg, mix, 0)


# ------------------------------------------- what the reference refuses

def _set_flow(key, value):
    def edit(cfg, cells):
        cells[0]["flows"][0][key] = value
    return edit


def _set_net(key, value):
    def edit(cfg, cells):
        cells[-1]["net"][key] = value
    return edit


def _set_channel(cfg, cells):
    cfg["channel"] = "bernoulli_loss"


REFUSED = {
    "route": (_set_flow("route", [1.0]), "'route'"),
    "src_site": (_set_flow("src_site", 1), "'src_site'"),
    "dst_site": (_set_flow("dst_site", 2), "'dst_site'"),
    "unknown_flow_key": (_set_flow("rounds", 4), "'rounds'"),
    "failure_schedule": (_set_net("failure_schedule", [[[10.0, 20.0]]]),
                         "'failure_schedule'"),
    "site_edges": (_set_net("site_edges", [[0, 1]]), "'site_edges'"),
    "channel_schedule": (_set_net("channel_schedule", [[[0.1, 0.0, 1.0]]]),
                         "'channel_schedule'"),
    "path_thresh_kb": (_set_net("path_thresh_kb", [512.0]),
                       "'path_thresh_kb'"),
    "unknown_net_key": (_set_net("horizon_us", 1000.0), "'horizon_us'"),
    "channel": (_set_channel, "'bernoulli_loss'"),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_the_reference_refuses_what_it_does_not_model(name):
    fluid = grid.load_module(FLUID, "fluid_under_test")
    cfg, cells = fig3cd()
    fluid.refuse_unmodelled(cfg, cells)
    edit, named = REFUSED[name]
    edit(cfg, cells)
    with pytest.raises(ValueError, match=named):
        fluid.refuse_unmodelled(cfg, cells)


@pytest.mark.parametrize("key,value", [("route", []), ("route", ()),
                                       ("src_site", 0), ("dst_site", 1)])
def test_the_reference_takes_defaults_spelled_out(key, value):
    fluid = grid.load_module(FLUID, "fluid_under_test")
    cfg, cells = fig3cd()
    for c in cells:
        for f in c["flows"]:
            f[key] = value
    fluid.refuse_unmodelled(cfg, cells)


# ----------------------------------------------------------- the channel

@pytest.mark.parametrize("name", ["ideal", "bernoulli_loss"])
def test_the_channel_comes_from_the_configuration(tmp_path, monkeypatch,
                                                  name):
    import repro.netsim as netsim
    root, _ = checkout(tmp_path, planted=RECORDER,
                       config=lambda c: c.update(channel=name))
    seen = {}
    monkeypatch.setattr(netsim, "sweep_grid",
                        lambda *a, **kw: seen.update(kw) or [])
    sweep = run.Sweep(grid.Cell(root, DRIVE_CELL), 0)
    sweep()
    want = None if name == "ideal" else netsim.get_channel_model(name)
    assert sweep.channel is want and seen["channel"] is want


# ------------------------------------------------------ the cells pinned

@pytest.fixture(scope="module")
def full_root(tmp_path_factory):
    """The benchmark at its own sizes, its left-out cells back in."""
    return tiny.full(ROOT, str(tmp_path_factory.mktemp("full")))


@pytest.mark.parametrize("name,seed", sorted(PINNED))
def test_cells_and_scenarios_are_the_ones_pinned(full_root, name, seed):
    cells = grid.Cell(full_root, name).cells(seed)
    got = (hashlib.sha256(json.dumps(cells, sort_keys=True).encode())
           .hexdigest(),
           hashlib.sha256(repr(grid.to_program(cells)).encode()).hexdigest())
    assert got == PINNED[name, seed]


# ---------------------------------------------------- each grid's record

def test_each_window_grid_records_what_the_host_did():
    def busy(path):
        t = time.process_time()
        while time.process_time() - t < 0.05:
            pass
        return []
    grids, records, window_s, _ = run.run_window(busy, 0.1)
    assert len(grids) == len(records) >= 2
    for r in records:
        assert set(r) == {"wall_s", "cpu_s", "nivcsw", "throttled_us"}
        assert r["cpu_s"] >= 0.04 and r["nivcsw"] >= 0
        assert r["throttled_us"] is None or r["throttled_us"] >= 0
    assert sum(r["wall_s"] for r in records) <= window_s


# ------------------------------------------------------- the widest gap

class _Flat:
    """A reference whose every row reads 100 Gbps."""

    @staticmethod
    def simulate_rows(scheme, cells, grid_nets, horizon_us, dtype, device):
        return [{"scheme": scheme, "distance_km": c["net"]["distance_km"],
                 "throughput_gbps": 100.0} for c in cells]


@pytest.mark.parametrize("off,correct", [
    ({("dcqcn", 3): 0.01}, True),                            # under the limit
    ({("dcqcn", 3): 0.05}, False),                           # one row
    ({("matchrdma", i): 0.05 for i in range(8)}, False),     # one scheme
], ids=["one_row_1pct", "one_row_5pct", "one_scheme_5pct"])
def test_the_widest_row_gap(off, correct):
    from types import SimpleNamespace
    config = grid.load_json(os.path.join(ROOT, CONFIG))
    assert config["limits"]["row_gap"] == 0.02
    schemes = ("dcqcn", "matchrdma")
    cell = SimpleNamespace(config=config, schemes=schemes, horizon_us=1.0,
                           reference=_Flat)
    cells = [{"net": {"distance_km": float(i)}} for i in range(8)]
    rows = [{"scheme": s, "distance_km": float(i),
             "throughput_gbps": 100.0 * (1.0 + off.get((s, i), 0.0))}
            for i in range(8) for s in schemes]
    verdict = check.judge(cell, cells, [rows, rows], seed=7)
    assert verdict["correct"] is correct
    assert verdict["failed"] == (0 if correct else len(off))
