"""Device self time per scan step of the traced launch in the
``netsim.accumulators`` scope: the step's outputs, the conservation
residual and the streaming metric accumulators, with the
accumulate_metrics and extra_traces hooks. From the profiler trace and
the scope map of the launch's HLO (``bench/phases.py``). Layer: the scan
step's phases (``netsim/fluid.py`` ``make_step_fn``)."""
from bench import phases

LAYER = "scan step phases"
UNIT = "us"
MOVES = "scenario_steps_per_s"


def read(obs):
    return phases.phase_us(obs, "accumulators")
