"""The one traffic generator: a configuration file and a traffic-mix file,
both data, plus a seed, give the grid of scenario cells a run sweeps.

A cell is plain data, ``{"net": {...}, "flows": [...]}``: the network
fields of the configuration with this cell's axis values applied, and one
dict per flow. ``to_program`` turns cells into the program's ``Scenario``
objects; the reference reads the same dicts directly.

Mix file keys:

``horizon_us``  simulated time of every cell.
``schemes``     the control schemes swept over every cell.
``axes``        ordered list, outermost first; each is ``{"net": field,
                "values": [...]}`` (a network field per cell) or
                ``{"flows": field, "values": [...]}`` (a field of every
                flow group). The grid is their cartesian product.
``flows``       flow groups: ``count``, ``is_inter``, ``msg_size``,
                ``concurrency``, optional ``total_bytes`` (omitted =
                unbounded), and the on/off phase either in microseconds
                (``start_us``, ``period_us``, ``on_us``) or as fractions
                of the horizon (``start_of_horizon``, ... as ``[num,
                den]``).
``seed``        what the seed may draw, per group: ``shift_of_horizon:
                [num, den]`` moves the group's start by a uniform draw in
                +-(num/den) of the horizon, once per cell;
                ``offset_us: x`` delays each flow's start by a uniform
                draw in [0, x). Seed 0 draws nothing: the source's grid.

The seed never changes the grid's shape, sizes, schemes or horizon, so
every seed gives the same work.
"""
from __future__ import annotations

import itertools
import json
import os

import numpy as np

UNBOUNDED = 1e18


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _of(group: dict, key: str, horizon: float, default=None):
    if key + "_us" in group:
        return float(group[key + "_us"])
    if key + "_of_horizon" in group:
        num, den = group[key + "_of_horizon"]
        return horizon * num / den
    return default


def _flows(groups: list, horizon: float, override: dict, rng) -> list:
    flows = []
    for g in groups:
        g = dict(g, **override)
        start = _of(g, "start", horizon, 0.0)
        period = _of(g, "period", horizon, 0.0)
        on = _of(g, "on", horizon, period)
        draw = g.get("seed", {})
        if "shift_of_horizon" in draw and rng is not None:
            num, den = draw["shift_of_horizon"]
            start += rng.uniform(-1.0, 1.0) * horizon * num / den
        for _ in range(int(g["count"])):
            s = start
            if "offset_us" in draw and rng is not None:
                s += rng.uniform(0.0, float(draw["offset_us"]))
            msg, conc = float(g["msg_size"]), int(g["concurrency"])
            flows.append({
                "is_inter": 1.0 if g["is_inter"] else 0.0,
                "msg_size": msg, "concurrency": conc,
                "window": msg * conc,
                "total_bytes": float(g.get("total_bytes", UNBOUNDED)),
                "start_us": s, "period_us": period,
                "duty": on / period if period > 0 else 1.0,
            })
    return flows


def build(config: dict, mix: dict, seed: int) -> list:
    """Cells of the grid, in grid order (first axis outermost)."""
    horizon = float(mix["horizon_us"])
    axes = mix.get("axes", [])
    rng = (np.random.default_rng(int(seed) % 2 ** 64) if int(seed) != 0
           else None)
    cells = []
    for combo in itertools.product(*(a["values"] for a in axes)):
        net = dict(config["net"])
        override = {}
        for a, v in zip(axes, combo):
            if "net" in a:
                net[a["net"]] = v
            else:
                override[a["flows"]] = v
        cells.append({"net": net,
                      "flows": _flows(mix["flows"], horizon, override, rng)})
    return cells


def to_program(cells: list):
    """The program's ``Scenario`` objects for ``cells``."""
    from repro.config.base import NetConfig
    from repro.netsim import Scenario
    from repro.netsim.workload import FlowSpec, Workload
    out = []
    for c in cells:
        net = {k: tuple(tuple(x) if isinstance(x, list) else x for x in v)
               if isinstance(v, list) else v for k, v in c["net"].items()}
        flows = tuple(FlowSpec(
            is_inter=bool(f["is_inter"]), msg_size=f["msg_size"],
            concurrency=f["concurrency"], total_bytes=f["total_bytes"],
            start_us=f["start_us"], period_us=f["period_us"],
            duty=f["duty"]) for f in c["flows"])
        out.append(Scenario(NetConfig(**net), Workload(flows)))
    return out


class Cell:
    """One ``workloads`` entry of BENCHMARK.json, resolved by name: its
    configuration file, its traffic-mix file and the grid they make."""

    def __init__(self, root: str, name: str):
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        entry = {w["name"]: w for w in bench["workloads"]}[name]
        cfg = {c["name"]: c for c in bench["configs"]}[entry["config"]]
        self.root, self.name, self.entry = root, name, entry
        self.chips = int(entry["chips"])
        self.config = load_json(os.path.join(root, cfg["file"]))
        self.mix = load_json(os.path.join(
            root, os.path.dirname(cfg["file"]), "..", "traffic",
            entry["traffic"] + ".json"))
        self.horizon_us = float(self.mix["horizon_us"])
        self.schemes = tuple(self.mix["schemes"])

    def cells(self, seed: int) -> list:
        return build(self.config, self.mix, seed)

    def steps(self) -> int:
        return int(round(self.horizon_us / self.config["net"]["dt_us"]))
