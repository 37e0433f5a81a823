"""Share of the traced window in which no program ran on the device:
1 - (union of device execution intervals / window), from the profiler
trace (``bench/trace_reduce.py``). The traced window is one ``sweep_grid``
call of the cell's grid for the scheme whose launches held the device
longest in the set-up grid (``bench/run.py``). Layer: the device."""
LAYER = "device"
UNIT = "share"
MOVES = "scenario_steps_per_s"


def read(obs):
    trace = obs.get("trace")
    if not trace:
        return None
    return trace["idle_share"]
