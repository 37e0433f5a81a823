"""Plain reference of lock-step rounds on the dual-DC fluid model.

It extends ``fluid.py`` (loaded from beside this file, and left as it is)
by the round gate a pipeline-parallel training job puts on its boundary
flows, written out from its definition (docs/rounds.md), and imports
nothing of the program under test. A flow with ``round_bytes`` b > 0 and
``round_period_us`` P:

  1. has R = 1 round released at its start, the next due at start + P;
  2. at each step, before it sends, releases round R+1 (R += 1, due = t +
     P) when t >= due and round R has landed: by last step's state, what
     it has yet to send of R x b and what it has sent that is still in
     the network (its queues and the long haul) come to at most one MTU;
  3. counts dt of stall on every step where t >= due and round R has not
     landed;
  4. sends no more than it has posted: R x b less what it has sent caps
     the rate its window allows. Each of the seven rate laws here stays at
     or below that rate, so this is also a cap on what it sends.

The bytes in the long haul are a running sum of what enters and leaves
the delay line, kept beside the model's state.

A flow with b = 0 is the Fig. 3 model's flow. Rows are ``fluid.py``'s
columns and, where any of the cells has rounds, ``round_progress`` (the
mean over inter-DC round flows of delivered / b) and ``round_stall_frac``
(the mean of their stall over the simulated time).
"""
from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_reference_rounds_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


fluid = _sibling("fluid")

ROUND_KEYS = ("round_bytes", "round_period_us")
_WHO = "reference " + os.path.basename(__file__)


def refuse_unmodelled(config: dict, cells: list) -> None:
    """``fluid.py``'s refusals, with the two round keys modelled at any
    value of at least 0."""
    bare = []
    for c in cells:
        flows = []
        for f in c["flows"]:
            for k in ROUND_KEYS:
                if k in f and not float(f[k]) >= 0.0:
                    raise ValueError(f"{_WHO}: flow {k!r} = {f[k]!r} is "
                                     f"not modelled (only >= 0)")
            flows.append({k: v for k, v in f.items() if k not in ROUND_KEYS})
        bare.append(dict(c, flows=flows))
    fluid.refuse_unmodelled(config, bare)


def cell_params(net: dict, flows: list) -> dict:
    p = fluid.cell_params(net, flows)
    for k in ROUND_KEYS:
        p[k] = [float(f.get(k, 0.0)) for f in flows]
    return p


class RoundModel(fluid.Model):
    """``fluid.Model`` with each flow's round gate in front of its
    sender."""

    def init(self, p, d):
        st = super().init(p, d)
        z = jnp.zeros((p["is_inter"].shape[0],), self.dt)
        st.update(round_r=z + 1.0,
                  round_due=p["start_us"] + p["round_period_us"],
                  stall=z, in_haul=z)
        return st

    def _haul_row(self, pipe, t, d):
        """[F] bytes the delay line holds at step ``t``'s slot."""
        if self.L > 1:
            return jnp.sum(pipe[jnp.mod(t, d["ld"]), jnp.arange(self.L)],
                           axis=0)
        return pipe[jnp.mod(t, d["d"])]

    def step(self, st, acc, t, p, d):
        b = p["round_bytes"]
        is_round = b > 0
        t_us = t.astype(self.dt) * self.dt_us
        q_dst = jnp.sum(st["q_dst"], axis=0) if self.L > 1 else st["q_dst"]
        left = (st["round_r"] * b - st["sent"] + st["q_src"] + q_dst
                + st["q_leaf"] + st["in_haul"])
        due = is_round & (t_us >= st["round_due"])
        landed = left <= fluid.MTU
        release = due & landed
        round_r = st["round_r"] + release.astype(self.dt)
        round_due = jnp.where(release, t_us + p["round_period_us"],
                              st["round_due"])
        stall = st["stall"] + jnp.where(due & ~landed, self.dt_us, 0.0)
        posted = jnp.maximum(round_r * b - st["sent"], 0.0)
        # the sender's rate is capped by its NIC and its window; the bytes
        # posted so far are a third cap on the same rate
        nic = jnp.where(is_round, jnp.minimum(d["nic"], posted / self.dt_s),
                        d["nic"])
        new, a = super().step(st, acc, t, p, dict(d, nic=nic))
        in_haul = (st["in_haul"] + self._haul_row(new["pipe"], t, d)
                   - self._haul_row(st["pipe"], t, d))
        new.update(round_r=round_r, round_due=round_due,
                   stall=stall.astype(self.dt), in_haul=in_haul)
        return new, a


def round_cols(P: dict, st: dict, sim_us: float) -> dict:
    f64 = lambda x: np.asarray(jax.device_get(x), np.float64)  # noqa: E731
    b = f64(P["round_bytes"])
    mask = (f64(P["is_inter"]) > 0) & (b > 0)
    n = np.maximum(mask.sum(1), 1)
    got = f64(st["delivered"]) / np.maximum(b, 1.0)
    stall = f64(st["stall"]) / sim_us
    return {"round_progress": np.where(mask, got, 0.0).sum(1) / n,
            "round_stall_frac": np.where(mask, stall, 0.0).sum(1) / n}


def simulate_rows(scheme: str, cells: list, grid_nets: list,
                  horizon_us: float, dtype=jnp.float32, device=None) -> list:
    """Reference rows of ``cells`` under one scheme (``fluid.py``'s
    contract), with the round columns where any cell has rounds."""
    statics = fluid.grid_statics(grid_nets, horizon_us)
    nets = [c["net"] for c in cells]
    per = [cell_params(c["net"], c["flows"]) for c in cells]
    P = {k: np.asarray([q[k] for q in per], np.float64) for k in per[0]}
    rounds = bool((P["round_bytes"] > 0).any())
    P = {k: jnp.asarray(v, dtype) for k, v in P.items()}
    if device is not None:
        P = jax.device_put(P, device)
    model = (RoundModel if rounds else fluid.Model)(
        scheme, nets[0], statics, dtype)
    st, acc = jax.jit(model.run)(P)
    rows = fluid.rows_from(scheme, nets, P, st, acc, statics)
    if rounds:
        sim_us = statics["steps"] * float(nets[0]["dt_us"])
        cols = round_cols(P, st, sim_us)
        for i, row in enumerate(rows):
            row.update({k: float(v[i]) for k, v in cols.items()})
    return rows
