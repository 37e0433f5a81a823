"""Host seconds of the program's ``netsim.rows`` span, reading the launch's
outputs back and building its rows (``np.asarray``,
``_metrics_streaming``, ``_guard_nonfinite``): the launch manifests'
``rows_s`` summed over a window grid's launches, as the mean per grid,
in ms. Layer: the sweep API (``netsim/runner.py``, ``netsim/fluid.py``
``simulate_batch``). Nothing to read off the chip (``phases.on_chip``)."""
from bench import phases

LAYER = "sweep API and row extraction"
UNIT = "ms"
MOVES = "scenario_steps_per_s"
KEY = "rows_s"


def read(obs):
    grids = obs.get("grids") or []
    launches = [ln for g in grids for ln in g["launches"]]
    if not phases.on_chip(launches) or any(KEY not in ln
                                           for ln in launches):
        return None
    return sum(ln[KEY] for ln in launches) / len(grids) * 1e3
