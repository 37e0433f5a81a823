"""Gradient-based controller autotuning over the differentiable engine.

Replaces the zeroth-order population search in ``benchmarks.hillclimb
.netsim_tune`` with ``jax.value_and_grad`` straight through the soft-step
engine (``NetConfig.soft_step`` — docs/differentiable.md): one Adam step
costs TWO simulator evaluations per cell (forward + backward) against the
hillclimb's five-candidate population per iteration, and it follows the
actual objective slope instead of shrinking a bracket.

Pieces
------
``KNOB_BOUNDS``          the tunable controller knobs and their boxes —
                         the same boxes ``netsim_tune`` brackets over.
``ADVERSARIAL_BOUNDS``   impairment-knob boxes for the adversarial mode:
                         ``tune(..., adversarial=True)`` gradient-ASCENDS
                         the channel knobs (under the ``impaired`` model)
                         to find the worst-case impairment mix for a
                         scheme — the tuner turned attacker.
``tune``                 clamp-reparameterized Adam over the chosen knob
                         vector, shared across the distance grid; the
                         final knob is then scored on the HARD engine
                         with the true hillclimb objective (a soft-mode
                         surrogate may not be trusted as a result).

Objectives
----------
The descent objective is a *smooth surrogate* built from the streamed
sums (no p99: the histogram inversion is piecewise constant, its gradient
is zero almost everywhere):

    surrogate = thr_mean_gbps - 0.5 * mean_buffer_mb - pause_ratio

The reported ``objective`` is the true hillclimb score
``throughput_gbps - 0.5 * p99_buffer_mb`` from a hard-engine
(``soft_step=False``) evaluation at the tuned knob — comparable
number-for-number with ``netsim_tune``'s printed scores.

Accounting is honest: ``sim_evals`` counts 2 per Adam step (forward +
backward sweep of the scan) plus 1 for the final hard-engine scoring,
per cell. ``benchmarks.grad_tune_bench`` pins this against the
hillclimb's ``iters * population`` evals-to-target.

Temperature vs horizon: the backward sweep accumulates float32 tangents
over the whole scan, and cold temperatures sharpen per-step gate
Jacobians — at ``temp=0.3`` the tangents stay FD-faithful out to a
~20 ms horizon (~18k steps) but turn to noise by 40 ms, while
``temp>=0.6`` stays clean there (``temp=1.0`` matches FD at 80 ms).
``tune`` therefore defaults ``temp=None`` → ``max(0.3, 1.5e-5 ·
horizon_us)``, the measured clean frontier, and clips gradients at
±1e6 so a blown tangent can at worst waste a step, never silently
freeze Adam (an overflowing ``g²`` second moment zeroes the update);
see docs/differentiable.md.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import NetConfig
from repro.netsim.fluid import (
    WARMUP_FRAC, _run_traced_batch_impl, as_workload_batch, batch_padding,
    batch_template, stack_net_params,
)
from repro.netsim.workload import has_rounds

__all__ = [
    "KNOB_BOUNDS", "ADVERSARIAL_BOUNDS", "TuneResult", "tune",
    "surrogate_from_sums", "true_objective",
]

# controller knobs the gradient tuner may move, and their boxes — the same
# brackets benchmarks.hillclimb.netsim_tune searches. Both are traced
# NetParams leaves, so every Adam step reuses one compiled program.
KNOB_BOUNDS: Dict[str, Tuple[float, float]] = {
    "budget_headroom": (0.85, 1.0),
    "slot_us": (50.0, 400.0),
}

# impairment knobs for the adversarial mode (channel model ``impaired``):
# the tuner gradient-ascends these to MINIMIZE the scheme's objective.
ADVERSARIAL_BOUNDS: Dict[str, Tuple[float, float]] = {
    "loss_rate": (0.0, 0.05),
    "jitter_us": (0.0, 200.0),
    "flap_depth": (0.0, 1.0),
}


class TuneResult(NamedTuple):
    knobs: Dict[str, float]       # tuned knob values (clamped, final)
    objective: float              # TRUE objective, hard engine, final knobs
    surrogate: float              # last soft-surrogate value seen
    sim_evals: int                # per-cell simulator evaluations spent
    history: List[Dict[str, float]]   # per-step {knob..., "surrogate"}


def surrogate_from_sums(sum_s: dict, n_warm: int) -> jax.Array:
    """Smooth scalar objective from the streamed per-cell sums ([B] each):
    mean over the batch of throughput minus buffer/pause penalties."""
    thr = sum_s["thr_inter"] / n_warm * 8.0 / 1e9          # Gbps
    qdst = sum_s["q_dst"] / n_warm / 1e6                   # mean MB
    pause = sum_s["pause_dst"] / n_warm                    # ratio
    return jnp.mean(thr - 0.5 * qdst - pause)


def true_objective(rows: Sequence[dict]) -> float:
    """The hillclimb score over a batch of hard-engine metric rows."""
    thr = sum(r["throughput_gbps"] for r in rows) / len(rows)
    buf = sum(r["p99_buffer_mb"] for r in rows) / len(rows)
    return float(thr - 0.5 * buf)


def _adam_step(theta, m, v, g, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    mhat = m / (1.0 - b1 ** t)
    vhat = v / (1.0 - b2 ** t)
    return theta - lr * mhat / (jnp.sqrt(vhat) + eps), m, v


def tune(knobs: Sequence[str] = ("budget_headroom",),
         scheme="matchrdma",
         dists: Sequence[float] = (100.0, 1000.0),
         horizon_us: float = 80_000.0,
         workload=None,
         channel: Optional[str] = None,
         steps: int = 8,
         lr_frac: float = 0.08,
         temp: Optional[float] = None,
         adversarial: bool = False,
         base_cfg: Optional[NetConfig] = None,
         init: Optional[Dict[str, float]] = None,
         verbose: bool = False) -> TuneResult:
    """Adam-tune ``knobs`` (shared across the ``dists`` grid) by gradient
    descent through the soft-step engine; score the result hard.

    ``lr_frac`` is the Adam step as a fraction of each knob's box width
    (Adam's invariance to gradient scale makes this the natural unit).
    ``temp=None`` picks the horizon-scaled default (module docstring,
    "Temperature vs horizon"). ``adversarial=True`` flips the sign (the tuner MINIMIZES the scheme's
    surrogate by moving impairment knobs) and defaults the channel to
    ``"impaired"``; knobs must then come from ``ADVERSARIAL_BOUNDS``.
    """
    from repro.netsim import get_scheme, run_experiment_batch
    from repro.netsim.workload import congestion_workload

    scheme = get_scheme(scheme)
    bounds = ADVERSARIAL_BOUNDS if adversarial else KNOB_BOUNDS
    if adversarial and channel is None:
        channel = "impaired"
    for k in knobs:
        if k not in bounds:
            raise ValueError(f"grad_tune: unknown knob {k!r} "
                             f"(have {sorted(bounds)})")
    lo = jnp.asarray([bounds[k][0] for k in knobs], jnp.float32)
    hi = jnp.asarray([bounds[k][1] for k in knobs], jnp.float32)
    if init is None:
        theta = (lo + hi) / 2.0
    else:
        theta = jnp.asarray([init[k] for k in knobs], jnp.float32)
    wl = congestion_workload() if workload is None else workload
    if temp is None:
        # the measured float32-tangent clean frontier (module docstring)
        temp = max(0.3, 1.5e-5 * horizon_us)

    if base_cfg is None:
        base_cfg = NetConfig()
    soft_base = dataclasses.replace(base_cfg, soft_step=True, soft_temp=temp,
                                    horizon_us=horizon_us)
    cfgs = [dataclasses.replace(soft_base, distance_km=d) for d in dists]
    b = len(cfgs)
    tmpl = batch_template(cfgs)
    n_steps = tmpl.horizon_steps(None)
    delay_pad, hist_slots = batch_padding(cfgs)
    wlp = as_workload_batch(wl, b)
    rounds = has_rounds(wlp)
    params0 = stack_net_params(cfgs)
    warm = int(n_steps * WARMUP_FRAC)
    n_warm = max(n_steps - warm, 1)
    sign = -1.0 if adversarial else 1.0

    def loss(th):
        # clamp reparameterization: the simulator always sees an in-box
        # knob; clip's zero gradient outside the box pins saturated knobs
        # at the wall (Adam momentum walks them back in when the slope
        # reverses).
        vals = jnp.clip(th, lo, hi)
        p = params0._replace(
            **{k: jnp.full((b,), vals[i], jnp.float32)
               for i, k in enumerate(knobs)})
        _, acc = _run_traced_batch_impl(
            tmpl, p, wlp, scheme, n_steps, 0, delay_pad, hist_slots,
            mode="metrics", warm=warm, channel=channel, rounds=rounds)
        return -sign * surrogate_from_sums(acc.sum_s, n_warm)

    vg = jax.jit(jax.value_and_grad(loss))
    lr = lr_frac * (hi - lo)
    m = jnp.zeros_like(theta)
    v = jnp.zeros_like(theta)
    history: List[Dict[str, float]] = []
    surr = float("nan")
    for t in range(1, steps + 1):
        val, g = vg(theta)
        # a blown float32 tangent must at worst waste a step: unclipped,
        # g*g overflows Adam's second moment to inf and the update
        # silently becomes zero for the rest of the run
        g = jnp.clip(g, -1e6, 1e6)
        surr = sign * -float(val)
        rec = {k: float(jnp.clip(theta, lo, hi)[i])
               for i, k in enumerate(knobs)}
        rec["surrogate"] = surr
        history.append(rec)
        if verbose:
            print(f"  adam {t}: surrogate={surr:.3f} "
                  + " ".join(f"{k}={rec[k]:.4g}" for k in knobs))
        theta, m, v = _adam_step(theta, m, v, g, t, lr)

    final = {k: float(jnp.clip(theta, lo, hi)[i])
             for i, k in enumerate(knobs)}
    # hard-engine scoring at the tuned knob: the reported objective is the
    # same number netsim_tune prints, never the soft surrogate
    hard = [dataclasses.replace(base_cfg, distance_km=d,
                                horizon_us=horizon_us, **final)
            for d in dists]
    rows = run_experiment_batch(hard, wl, scheme, horizon_us,
                                trace_mode="metrics", channel=channel)
    obj = true_objective(rows)
    return TuneResult(knobs=final, objective=obj, surrogate=surr,
                      sim_evals=2 * steps + 1, history=history)
