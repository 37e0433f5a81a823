"""Seconds the set-up grid's launches spent compiling (from the persistent
cache after a checkout's first run): the manifest's summed ``compile_s``.
Layer: the launch (``netsim/obs/profile.py``)."""
LAYER = "launch"
UNIT = "s"
MOVES = "setup_s"


def read(obs):
    launches = obs.get("setup_launches") or []
    if not launches:
        return None
    return sum(ln.get("compile_s", 0.0) for ln in launches)
