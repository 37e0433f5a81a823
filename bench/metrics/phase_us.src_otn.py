"""Device self time per scan step of the traced launch in the
``netsim.src_otn`` scope: the source line's capacity, source-OTN release
and spray, with the src_otn_release and route_weights hooks (5). From
the profiler trace and the scope map of the launch's HLO
(``bench/phases.py``). Layer: the scan step's phases
(``netsim/fluid.py`` ``make_step_fn``)."""
from bench import phases

LAYER = "scan step phases"
UNIT = "us"
MOVES = "scenario_steps_per_s"


def read(obs):
    return phases.phase_us(obs, "src_otn")
