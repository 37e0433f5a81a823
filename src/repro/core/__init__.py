"""The paper's contribution: MatchRDMA segmented, rate-matched control.

  reservoir.py  — Eq. (1) buffer-requirement model
  slots.py      — destination-OTN slot-level observations
  estimator.py  — communication-aware slot-weighted rate estimation
  budget.py     — rate-budget generation + inter-OTN control subchannel
  pseudo_ack.py — source-OTN budget-gated pseudo-ACK
  cc_proxy.py   — DCQCN machine (sender / proxy / THEMIS variants)
  matchrdma.py  — the composed three-segment controller
"""
# The modules here import ``repro.netsim.soft``, which runs the netsim
# package first, and netsim imports these modules back: entering through
# netsim is the order in which every module is complete when it is read.
import repro.netsim  # noqa: F401
from repro.core.budget import BudgetState, fair_share, init_budget, update_budget
from repro.core.cc_proxy import DcqcnState, init_dcqcn, step_dcqcn, themis_rtt_scale
from repro.core.estimator import (
    RateEstimate, periodic_estimate, slot_weighted_estimate,
)
from repro.core.matchrdma import (
    MatchRdmaState, accumulate_step, init_matchrdma, maybe_slot_update,
    slot_update, step_channel,
)
from repro.core.pseudo_ack import PseudoAckState, init_pseudo_ack, step_pseudo_ack
from repro.core.reservoir import (
    buffer_bound_e2e_vs_segmented, control_uncertainty_window_us,
    queue_trajectory, rate_mismatch_integral, required_buffer,
)
from repro.core.slots import SlotObs, SlotRing, classify_slot, init_ring, push_slot

__all__ = [
    "BudgetState", "fair_share", "init_budget", "update_budget",
    "DcqcnState", "init_dcqcn", "step_dcqcn", "themis_rtt_scale",
    "RateEstimate", "periodic_estimate", "slot_weighted_estimate",
    "MatchRdmaState", "accumulate_step", "init_matchrdma", "maybe_slot_update",
    "slot_update", "step_channel",
    "PseudoAckState", "init_pseudo_ack", "step_pseudo_ack",
    "buffer_bound_e2e_vs_segmented", "control_uncertainty_window_us",
    "queue_trajectory", "rate_mismatch_integral", "required_buffer",
    "SlotObs", "SlotRing", "classify_slot", "init_ring", "push_slot",
]
