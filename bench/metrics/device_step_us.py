"""Device time per scan step of one launch, over every launch of the
window's grids: summed ``execute_s`` / (launches x scan steps). Layer: the
batched program (``netsim/fluid.py`` ``_run_traced_batch_impl``)."""
LAYER = "batched program"
UNIT = "us"
MOVES = "scenario_steps_per_s"


def read(obs):
    launches = [ln for g in obs.get("grids") or [] for ln in g["launches"]]
    if not launches:
        return None
    return (sum(ln["execute_s"] for ln in launches)
            / (len(launches) * obs["steps"]) * 1e6)
