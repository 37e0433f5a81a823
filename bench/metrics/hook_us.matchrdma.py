"""Device self time per scan step of the traced launch under the scheme
hooks' ``hook.*`` scopes, where the traced scheme is matchrdma (nothing
to read otherwise). From the profiler trace and the scope map of the
launch's HLO (``bench/phases.py``). Layer: the scheme hooks
(``netsim/schemes/matchrdma.py``, ``core/``)."""
from bench import phases

LAYER = "scheme hooks"
UNIT = "us"
MOVES = "scenario_steps_per_s"
SCHEME = "matchrdma"


def read(obs):
    red = phases.observe(obs)
    if not red or red["scheme"] != SCHEME:
        return None
    return phases.per_step_us(obs, red["hook_s"])
