"""Device self time per scan step of the traced launch in the
``netsim.rounds`` scope: the round gate of round-gated flows, its release
latch and the posted-bytes cap on the sender (docs/rounds.md). From the
profiler trace and the scope map of the launch's HLO
(``bench/phases.py``). Layer: the scan step's phases (``netsim/fluid.py``
``make_step_fn``). Nothing to read where the traced program has no round
gate: a workload without rounds, or a program without the gate."""
from bench import phases

LAYER = "scan step phases"
UNIT = "us"
MOVES = "scenario_steps_per_s"
PHASE = "rounds"


def read(obs):
    red = phases.observe(obs)
    if not red or PHASE not in red["phase_s"]:
        return None
    return phases.per_step_us(obs, red["phase_s"][PHASE])
