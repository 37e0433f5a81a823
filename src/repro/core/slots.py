"""Destination-OTN slot-level observation construction (Fig. 2(e), left half).

Each slot (duration ``slot_us``) aggregates three local observables:
  * egress_bytes — bytes the destination OTN forwarded into the AI-DC;
  * ack_delay_us — mean intra-DC ACK return time seen in the slot (the
    destination-side loop: OTN -> receiver NIC -> ACK back to OTN);
  * cnp_count    — CNPs generated by the receiver in the slot.

``classify_slot`` compares ACK return time and CNP frequency against preset
thresholds to assess the slot congestion level; ``SlotRing`` keeps the
recent history the estimator aggregates into windows, oldest-first as a
shift register (a push shifts every slot down one and writes the newest
last), so reading it in order needs no index arithmetic and, under
``vmap``, no per-cell gather or scatter.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.config.base import NetConfig
# submodule import (not the package __init__), so no core<->netsim cycle
from repro.netsim.soft import soft_gt, soft_pos


class SlotObs(NamedTuple):
    """One slot's observation (scalars or [*] batched)."""
    egress_rate: jax.Array    # bytes/s realized in the slot
    ack_delay_us: jax.Array   # mean intra-DC ACK return delay
    cnp_count: jax.Array      # CNPs in the slot
    local_queue: jax.Array    # mean local (dst-OTN) queue occupancy, bytes


class SlotRing(NamedTuple):
    """Oldest-first shift register of the last R slots (leading axis R;
    position R-1 is the newest)."""
    rates: jax.Array          # [R] egress rates
    congested: jax.Array      # [R] bool-ish f32 congestion flags
    busy: jax.Array           # [R] backlog present => egress == capability
    count: jax.Array          # scalar int32 — total slots ever written


def init_ring(num_slots: int) -> SlotRing:
    return SlotRing(
        rates=jnp.zeros((num_slots,), jnp.float32),
        congested=jnp.zeros((num_slots,), jnp.float32),
        busy=jnp.zeros((num_slots,), jnp.float32),
        count=jnp.int32(0),
    )


def classify_slot(obs: SlotObs, cfg: NetConfig,
                  queue_thresh_bytes=None, soft=None) -> jax.Array:
    """Congestion level in [0, 3]: ACK-delay, CNP-frequency and local-egress
    (own queue backlog) indicators — Fig. 2(e) 'ACK/CNP feedback and local
    egress observations'. ``queue_thresh_bytes`` may be a traced per-scenario
    override of the static ``cfg.queue_thresh_kb``. ``soft`` (a traced
    temperature, docs/differentiable.md) relaxes the three indicator steps
    to sigmoids; None keeps the hard classifier."""
    if queue_thresh_bytes is None:
        queue_thresh_bytes = cfg.queue_thresh_kb * 1024.0
    if soft is None:
        a = (obs.ack_delay_us > cfg.ack_delay_thresh_us).astype(jnp.float32)
        c = (obs.cnp_count > cfg.cnp_freq_thresh).astype(jnp.float32)
        q = (obs.local_queue > queue_thresh_bytes).astype(jnp.float32)
    else:
        a = soft_gt(obs.ack_delay_us, cfg.ack_delay_thresh_us, soft,
                    0.1 * cfg.ack_delay_thresh_us + 1.0)
        c = soft_gt(obs.cnp_count, cfg.cnp_freq_thresh, soft, 0.25)
        q = soft_gt(obs.local_queue, queue_thresh_bytes, soft,
                    0.05 * queue_thresh_bytes + 1.0)
    return a + c + q


def push_slot(ring: SlotRing, obs: SlotObs, cfg: NetConfig,
              busy: jax.Array = None,
              queue_thresh_bytes=None, soft=None) -> SlotRing:
    level = classify_slot(obs, cfg, queue_thresh_bytes=queue_thresh_bytes,
                          soft=soft)
    if queue_thresh_bytes is None:
        queue_thresh_bytes = cfg.queue_thresh_kb * 1024.0
    if busy is None:
        if soft is None:
            busy = (obs.local_queue > queue_thresh_bytes).astype(jnp.float32)
        else:
            busy = soft_gt(obs.local_queue, queue_thresh_bytes, soft,
                           0.05 * queue_thresh_bytes + 1.0)
    if soft is None:
        congested = (level > 0).astype(jnp.float32)
    else:
        congested = soft_pos(level, soft, 0.5)
    return SlotRing(
        rates=_shift_in(ring.rates, obs.egress_rate.astype(jnp.float32)),
        congested=_shift_in(ring.congested, congested),
        busy=_shift_in(ring.busy, busy),
        count=ring.count + 1,
    )


def _shift_in(hist: jax.Array, new: jax.Array) -> jax.Array:
    """Drop the oldest slot and append ``new`` as the newest."""
    return jnp.concatenate([hist[1:], jnp.asarray(new, hist.dtype)[None]])


def ordered_history(ring: SlotRing) -> tuple:
    """(rates, congested, busy, valid) with oldest-first ordering — the
    stored order."""
    r = ring.rates.shape[0]
    valid_n = jnp.minimum(ring.count, r)
    # positions [r - valid_n, r) hold slots written so far
    valid = jnp.arange(r) >= (r - valid_n)
    return ring.rates, ring.congested, ring.busy, valid.astype(jnp.float32)
