"""In-scan event telemetry: a bounded per-scenario event ring in the scan
carry (``trace_mode="window"`` + ``NetConfig.event_ring_slots > 0``).

The engine cannot afford a [B, T] trace buffer on long horizons, yet the
paper's claims hinge on *when* things happen — PFC pause onsets, brake
firings, retransmit bursts, failover dips. The event ring keeps the LAST
``event_ring_slots`` discrete events per scenario in O(E) device memory:
each scan step evaluates a STATIC list of candidate events (one slot per
possible event source), computes a traced ``fired`` predicate for each via
where()-selects of quantities the step already produced, and scatters the
fired candidates into a circular buffer. The ``count`` field is the
MONOTONE total of events ever fired, so overflow is observable (oldest
events are evicted, never silently miscounted).

Taxonomy (``EVENT_KINDS``):

  * ``pfc_xoff`` / ``pfc_xon``       — destination-OTN PFC pause asserted /
                                       released (per link at L > 1; ``obj``
                                       is the link index)
  * ``otn_xoff_cross``               — total destination-OTN backlog crossed
                                       the xoff threshold upward
  * ``retx_onset``                   — retransmit backlog became non-empty
                                       (loss-repair path active runs only)
  * ``fail_enter`` / ``fail_exit``   — a failure-schedule outage window
                                       opened / closed on link ``obj``
  * ``scheme_brake``                 — the scheme's proxy brake fired
                                       (matchrdma: budget-summary / loss cut)
  * ``scheme_budget_on`` / ``_off``  — the scheme's repair-budget reservation
                                       engaged / released (sdr_rdma: the
                                       congestion EWMA crossed 0.5)
  * ``round_release``                — flows released their next round
                                       (``value``: how many; rounds-on
                                       workloads only, docs/rounds.md)
  * ``round_late``                   — of those, releases made after their
                                       due step (the round had not landed)

Schemes add their own candidates through ``Scheme.emit_events`` (see
``docs/observability.md`` + ``docs/scheme-api.md``). Ring-off runs
(``event_ring_slots == 0`` — the default) never build any of this, so the
default jaxpr and the goldens stay bit-identical.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

# name -> i32 kind code stored in the ring. Third-party schemes may register
# additional kinds (pick codes >= 100 to stay clear of future engine kinds).
EVENT_KINDS = {
    "pfc_xoff": 0,
    "pfc_xon": 1,
    "otn_xoff_cross": 2,
    "retx_onset": 3,
    "fail_enter": 4,
    "fail_exit": 5,
    "scheme_brake": 6,
    "scheme_budget_on": 7,
    "scheme_budget_off": 8,
    "round_release": 9,
    "round_late": 10,
}


def kind_name(code: int) -> str:
    """Kind-code -> taxonomy name (``kind_12`` for unknown codes)."""
    for name, c in EVENT_KINDS.items():
        if c == int(code):
            return name
    return f"kind_{int(code)}"


class EventRing(NamedTuple):
    """Circular event buffer carried through the scan. Arrays are sized
    ``[E + 1]``: slot ``E`` is the DISCARD slot every non-fired candidate
    scatters into, so the per-step write is a fixed-shape scatter with no
    data-dependent control flow. Under the batched engine every leaf gains
    a leading [B] axis."""
    t_us: jax.Array    # [E+1] f32 event timestamps (simulated µs); -1 empty
    kind: jax.Array    # [E+1] i32 EVENT_KINDS code; -1 empty
    obj: jax.Array     # [E+1] i32 object index (link id, 0 when N/A)
    value: jax.Array   # [E+1] f32 payload (backlog bytes, brake level, ...)
    count: jax.Array   # scalar i32 — MONOTONE total of events ever fired


def init_event_ring(slots: int) -> EventRing:
    e = int(slots) + 1
    return EventRing(
        t_us=jnp.full((e,), -1.0, jnp.float32),
        kind=jnp.full((e,), -1, jnp.int32),
        obj=jnp.zeros((e,), jnp.int32),
        value=jnp.zeros((e,), jnp.float32),
        count=jnp.int32(0),
    )


def push_events(ring: EventRing, slots: int, t_us, candidates) -> EventRing:
    """Scatter this step's fired candidates into the ring.

    ``candidates``: sequence of ``(kind_name, obj, value, fired)`` with
    STATIC ``kind_name``/``obj`` and traced scalar ``value``/``fired``.
    Fired candidates take consecutive ring positions ``(count + rank) mod
    slots`` (rank = exclusive prefix sum of the fired mask, so positions
    within one step never collide as long as ``slots >= len(candidates)``
    — checked at trace time by the engine); non-fired candidates write to
    the discard slot. Oldest events are evicted on wraparound; ``count``
    only ever grows."""
    names = [c[0] for c in candidates]
    unknown = [n for n in names if n not in EVENT_KINDS]
    if unknown:
        raise ValueError(
            f"push_events: unknown event kind(s) {unknown!r} — register "
            f"them in repro.netsim.obs.EVENT_KINDS (docs/observability.md)")
    kinds = jnp.asarray([EVENT_KINDS[n] for n in names], jnp.int32)
    objs = jnp.asarray([int(c[1]) for c in candidates], jnp.int32)
    vals = jnp.stack([jnp.asarray(c[2], jnp.float32).reshape(())
                      for c in candidates])
    fired = jnp.stack([jnp.asarray(c[3]).reshape(()).astype(jnp.bool_)
                       for c in candidates])
    fired_i = fired.astype(jnp.int32)
    rank = jnp.cumsum(fired_i) - fired_i           # exclusive prefix sum
    pos = jnp.where(fired, jnp.mod(ring.count + rank, slots), slots)
    ts = jnp.broadcast_to(jnp.asarray(t_us, jnp.float32), pos.shape)
    return EventRing(
        t_us=ring.t_us.at[pos].set(ts),
        kind=ring.kind.at[pos].set(kinds),
        obj=ring.obj.at[pos].set(objs),
        value=ring.value.at[pos].set(vals),
        count=ring.count + jnp.sum(fired_i),
    )


def engine_event_candidates(ctx, prev_state, state, t):
    """The engine-owned candidate list of one step — a pure function of the
    (pre, post) state pair and the traced step index, evaluated AROUND the
    step transition (never inside it, so ring-off runs keep the exact
    step jaxpr). Candidate COUNT is static: it depends only on compile-time
    structure (link count, repair path, failure schedule, rounds)."""
    multi = ctx.num_links > 1
    L = ctx.num_links
    t_f = jnp.asarray(t, jnp.float32)
    cands = []

    # PFC pause edges on the destination-OTN pause state (per link at L>1)
    pd0, pd1 = prev_state.pause_dst, state.pause_dst
    if multi:
        q_link = jnp.sum(state.q_dst, axis=1)                     # [L]
        for li in range(L):
            cands.append(("pfc_xoff", li, q_link[li],
                          (pd0[li] < 0.5) & (pd1[li] >= 0.5)))
            cands.append(("pfc_xon", li, q_link[li],
                          (pd0[li] >= 0.5) & (pd1[li] < 0.5)))
    else:
        q_tot = jnp.sum(state.q_dst)
        cands.append(("pfc_xoff", 0, q_tot, (pd0 < 0.5) & (pd1 >= 0.5)))
        cands.append(("pfc_xon", 0, q_tot, (pd0 >= 0.5) & (pd1 < 0.5)))

    # total dst-OTN backlog crossing the (single-pipe) xoff threshold upward
    prev_tot = jnp.sum(prev_state.q_dst)
    new_tot = jnp.sum(state.q_dst)
    th = jnp.asarray(ctx.xoff_otn, jnp.float32).reshape(())
    cands.append(("otn_xoff_cross", 0, new_tot,
                  (prev_tot < th) & (new_tot >= th)))

    # retransmit-backlog onset (loss-repair path active runs only — the
    # slot is absent otherwise, keeping the candidate count static per
    # compiled program)
    if state.retx_backlog is not None:
        pb = jnp.sum(prev_state.retx_backlog)
        nb = jnp.sum(state.retx_backlog)
        cands.append(("retx_onset", 0, nb, (pb <= 0.0) & (nb > 0.0)))

    # failure-window entry/exit, recomputed from the traced window table
    # (a pure function of t — no extra carry)
    fw = getattr(ctx.params, "fail_windows", None)
    if fw is not None and int(np.shape(fw)[-2]) > 0:
        fw = jnp.asarray(fw)                                      # [L, W, 2]
        t_us_now = t_f * ctx.dt_us
        t_us_prev = (t_f - 1.0) * ctx.dt_us
        down_now = jnp.any((t_us_now >= fw[..., 0])
                           & (t_us_now < fw[..., 1]), axis=-1)     # [L]
        down_prev = jnp.any((t_us_prev >= fw[..., 0])
                            & (t_us_prev < fw[..., 1]), axis=-1) & (t > 0)
        for li in range(fw.shape[0]):
            cands.append(("fail_enter", li, jnp.float32(0.0),
                          down_now[li] & ~down_prev[li]))
            cands.append(("fail_exit", li, jnp.float32(1.0),
                          down_prev[li] & ~down_now[li]))

    # round releases, all flows of the step in one event each (rounds-on
    # workloads only — the slots exist only when the round gate does)
    if state.round_r is not None:
        n_rel = jnp.sum(state.round_r - prev_state.round_r)
        n_late = jnp.sum(state.round_late - prev_state.round_late)
        cands.append(("round_release", 0, n_rel, n_rel > 0))
        cands.append(("round_late", 0, n_late, n_late > 0))
    return cands


def decode_events(ring: EventRing, slots: int,
                  cell: Optional[int] = None) -> list:
    """Host-side: ring -> chronologically ordered event dicts
    (``{"t_us", "kind", "obj", "value"}``). For a batched ring (leading
    [B] axis) pass the ``cell`` index. Returns the last ``min(count,
    slots)`` events, oldest first."""
    t = np.asarray(ring.t_us)
    k = np.asarray(ring.kind)
    o = np.asarray(ring.obj)
    v = np.asarray(ring.value)
    c = np.asarray(ring.count)
    if t.ndim == 2:
        if cell is None:
            raise ValueError(
                "decode_events: batched ring — pass the cell index")
        t, k, o, v, c = t[cell], k[cell], o[cell], v[cell], c[cell]
    count = int(c)
    n = min(count, slots)
    if count <= slots:
        idx = np.arange(n)
    else:
        start = count % slots
        idx = (start + np.arange(slots)) % slots
    return [{"t_us": float(t[i]), "kind": kind_name(k[i]),
             "obj": int(o[i]), "value": float(v[i])} for i in idx]


def event_count(ring: EventRing) -> np.ndarray:
    """Host-side monotone event totals (scalar, or [B] for a batch)."""
    return np.asarray(ring.count)


def unroll_window(window: dict, steps: int, window_steps: int,
                  cell: Optional[int] = None):
    """Host-side: the [W, ...]-ring trace dict of ``trace_mode="window"``
    -> ``(step_idx, traces)`` in chronological order. ``step_idx`` is the
    [min(steps, W)] array of engine step indices each row corresponds to;
    ``traces`` maps each key to its time-ordered samples. For a batched
    window (leading [B] axis on every leaf) pass ``cell``."""
    w = int(window_steps)
    n = min(int(steps), w)
    if int(steps) <= w:
        idx = np.arange(n)
        step_idx = np.arange(n)
    else:
        start = int(steps) % w
        idx = (start + np.arange(w)) % w
        step_idx = np.arange(int(steps) - w, int(steps))
    out = {}
    for key, arr in window.items():
        a = np.asarray(arr)
        if cell is not None:
            a = a[cell]
        out[key] = a[idx]
    return step_idx, out
