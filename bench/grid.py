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
                den]``). Any other key (``route``, ``src_site``, a field
                a later program adds) is copied into each flow dict as it
                stands; ``to_program`` hands it to ``FlowSpec`` and
                refuses a key that names no ``FlowSpec`` field.
``seed``        what the seed may draw, per group: ``shift_of_horizon:
                [num, den]`` moves the group's start by a uniform draw in
                +-(num/den) of the horizon, once per cell;
                ``offset_us: x`` delays each flow's start by a uniform
                draw in [0, x). Seed 0 draws nothing: the source's grid.

The seed never changes the grid's shape, sizes, schemes or horizon, so
every seed gives the same work.
"""
from __future__ import annotations

import copy
import dataclasses
import importlib.util
import itertools
import json
import os

import numpy as np

UNBOUNDED = 1e18
# the generator's own group keys; every other group key is a flow field
GROUP_KEYS = frozenset(
    ["count", "is_inter", "msg_size", "concurrency", "total_bytes", "seed"]
    + [k + u for k in ("start", "period", "on")
       for u in ("_us", "_of_horizon")])


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """The Python file at ``path``, loaded as a module called ``name``."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"bench: no module file {path!r}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(root: str, config: dict):
    """The plain reference module the configuration names under
    ``"reference"`` (a path from the checkout's root)."""
    if "reference" not in config:
        raise ValueError(f"bench: configuration {config.get('name')!r} "
                         f"names no reference")
    path = os.path.join(root, config["reference"])
    return load_module(path, "bench_reference_" + "".join(
        c if c.isalnum() else "_" for c in config["reference"]))


def _tupled(v):
    """Lists, at any depth, as tuples (the program's config values)."""
    return tuple(_tupled(x) for x in v) if isinstance(v, list) else v


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
        extra = {k: v for k, v in g.items() if k not in GROUP_KEYS}
        if "shift_of_horizon" in draw and rng is not None:
            num, den = draw["shift_of_horizon"]
            start += rng.uniform(-1.0, 1.0) * horizon * num / den
        for _ in range(int(g["count"])):
            s = start
            if "offset_us" in draw and rng is not None:
                s += rng.uniform(0.0, float(draw["offset_us"]))
            msg, conc = float(g["msg_size"]), int(g["concurrency"])
            flow = {
                "is_inter": 1.0 if g["is_inter"] else 0.0,
                "msg_size": msg, "concurrency": conc,
                "window": msg * conc,
                "total_bytes": float(g.get("total_bytes", UNBOUNDED)),
                "start_us": s, "period_us": period,
                "duty": on / period if period > 0 else 1.0,
            }
            for k, v in extra.items():
                if k in flow:
                    raise ValueError(f"bench: flow group key {k!r} is "
                                     f"derived by the generator")
                flow[k] = copy.deepcopy(v)
            flows.append(flow)
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


def flow_spec(flow: dict):
    """The program's ``FlowSpec`` for one flow dict: every key that is a
    ``FlowSpec`` field, lists as tuples; ``window``, which the generator
    derives for the reference, is left out; any other key raises."""
    from repro.netsim.workload import FlowSpec
    fields = {f.name: f for f in dataclasses.fields(FlowSpec)}
    kw = {}
    for k, v in flow.items():
        if k == "window":
            continue
        if k not in fields:
            raise ValueError(f"bench: flow key {k!r} is not a field of the "
                             f"program's FlowSpec")
        kw[k] = bool(v) if fields[k].type in (bool, "bool") else _tupled(v)
    return FlowSpec(**kw)


def to_program(cells: list):
    """The program's ``Scenario`` objects for ``cells``."""
    from repro.config.base import NetConfig
    from repro.netsim import Scenario
    from repro.netsim.workload import Workload
    return [Scenario(NetConfig(**{k: _tupled(v) for k, v in c["net"].items()}),
                     Workload(tuple(flow_spec(f) for f in c["flows"])))
            for c in cells]


class Cell:
    """One ``workloads`` entry of BENCHMARK.json, resolved by name: its
    configuration file, its traffic-mix file, the grid they make and the
    plain reference the configuration names. Loading refuses, before any
    chip work, a cell whose reference is missing or does not model what
    the cell sets (the reference's ``refuse_unmodelled``)."""

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
        self.reference = load_reference(root, self.config)
        # the seed moves only start times, so seed 0 stands for every seed
        self.reference.refuse_unmodelled(self.config, self.cells(0))

    def cells(self, seed: int) -> list:
        return build(self.config, self.mix, seed)

    def steps(self) -> int:
        return int(round(self.horizon_us / self.config["net"]["dt_us"]))
