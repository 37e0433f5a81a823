"""Launches of the set-up grid whose compile missed JAX's persistent
compilation cache (the manifest's ``persistent_cache`` is ``miss``: the
backend compiled them). Layer: the launch
(``netsim/obs/profile.py``). Nothing to read off the chip
(``phases.on_chip``)."""
from bench import phases

LAYER = "launch"
UNIT = "count"
MOVES = "setup_s"


def read(obs):
    launches = obs.get("setup_launches") or []
    if not phases.on_chip(launches) or any("persistent_cache" not in ln
                                           for ln in launches):
        return None
    return sum(ln["persistent_cache"] == "miss" for ln in launches)
