"""Share of the traced launch's device self time spent in fusions whose
fused instructions carry more than one scan-step phase: the part of the
``phase_us.*`` attribution that names one phase for work of several.
From the profiler trace and the scope map of the launch's HLO
(``bench/phases.py``). Layer: the scan step's phases."""
from bench import phases

LAYER = "scan step phases"
UNIT = "share"
MOVES = "scenario_steps_per_s"


def read(obs):
    red = phases.observe(obs)
    if not red or red["self_s"] <= 0:
        return None
    return red["mixed_s"] / red["self_s"]
