"""Host time of a sweep outside its device launches, per grid.

The host clock around each window grid's ``sweep_grid`` call, minus the
launch manifest's summed ``execute_s`` (each launch fenced by
``block_until_ready``): stacking, transfer, row extraction and the
manifest itself. Layer: the sweep API (``netsim/runner.py``)."""
LAYER = "sweep API and row extraction"
UNIT = "ms"
MOVES = "scenario_steps_per_s"


def read(obs):
    grids = obs.get("grids") or []
    if not grids:
        return None
    host = [g["wall_s"] - sum(ln["execute_s"] for ln in g["launches"])
            for g in grids]
    return sum(host) / len(host) * 1e3
