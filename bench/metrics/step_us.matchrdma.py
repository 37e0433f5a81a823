"""Device time per scan step of the matchrdma launches: their summed
``execute_s`` / (their count x scan steps). Layer: the scheme hooks
(``netsim/schemes/matchrdma.py``, ``core/``). Nothing to read where the
cell does not sweep matchrdma."""
LAYER = "scheme hooks"
UNIT = "us"
MOVES = "scenario_steps_per_s"
SCHEME = "matchrdma"


def read(obs):
    launches = [ln for g in obs.get("grids") or [] for ln in g["launches"]
                if ln["scheme"] == SCHEME]
    if not launches:
        return None
    return (sum(ln["execute_s"] for ln in launches)
            / (len(launches) * obs["steps"]) * 1e6)
