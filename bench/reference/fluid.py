"""Plain reference of the dual-DC / multi-link OTN fluid model.

A second, independent implementation of what a sweep row means: the same
fluid queues, PFC and ECN, DCQCN, and the seven control schemes, written
out step by step from the model's equations as this benchmark's yardstick.
It imports nothing of the program under test and takes nothing the program
made: every parameter comes from the benchmark's configuration and traffic
files.

One cell is one scenario: a ``jax.lax.scan`` over ``dt_us`` steps of a
per-cell state; a block of cells of one scheme is ``vmap``-ped. ``dtype``
is the float type of every quantity (float32 as the configuration states;
the correctness control runs it in bfloat16).

Model, per step (sender NIC -> source OTN -> long haul (delay D, L links)
-> destination OTN -> destination leaf -> receiver):

  1. a flow is active when started, inside its on-period and not done;
  2. the ACK, CNP and pause lines are read D steps late;
  3. the sender's window (msg_size x concurrency) and its scheme's rate law
     set what it sends; the source OTN's PFC pauses inter-DC senders;
  4. the source OTN releases by its scheme's law, capped by the long-haul
     line (zero while the destination OTN pauses it); at L > 1 the release
     is sprayed over the links by the flows' weights and clipped per link;
  5. the destination OTN and leaf drain proportionally to backlog; the leaf
     marks ECN RED-style and pauses the OTN (PFC); the OTN pauses the long
     haul with hysteresis at its BDP-scaled threshold;
  6. the receiver emits CNPs from marked bytes (one per MTU, rate-limited);
  7. the scheme routes feedback, DCQCN updates, completions latch.

Rows are the Fig. 3 columns (warm means over the last 90 % of the
horizon, all-step peak, a 512-bin log-histogram p99) and each scheme's own
columns.
"""
from __future__ import annotations

import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

MTU = 1500.0
INF = 1e30
WARM_FRAC = 0.1
HIST_BINS = 512
HIST_MAX = 1e12
MAX_BURST_S = 2e-3          # pseudo-ACK credit cap, seconds of budget
PROXY_CUT, PROXY_FLOOR, PROXY_RECOVER = 0.7, 0.25, 5e-4
GEOPIPE = dict(stages=4, slice_us=200.0, boost=4.0)
SDR_MAX_RETX = 0.9
FAST_RECOVERY = 5

# schemes whose sender-side DCQCN does not limit inter-DC flows
WINDOW_ONLY = ("matchrdma", "geopipe")
BUDGET_BLOCK = ("dcqcn", "themis", "pseudo_ack", "matchrdma", "rdmacell")


# ------------------------------------------------------ what it models

# flow keys it reads, at any value
FLOW_KEYS = ("is_inter", "msg_size", "concurrency", "window", "total_bytes",
             "start_us", "period_us", "duty")
# flow keys it accepts only at the program's default: every flow sprays
# over all links by equal weight, from site 0 to site 1
FLOW_AT_DEFAULT = {"route": [], "src_site": 0, "dst_site": 1}
# network fields it reads, at any value
NET_KEYS = (
    "num_otn_links", "link_gbps", "intra_dc_delay_us", "distance_km",
    "dst_dc_gbps", "nic_gbps", "num_paths", "path_delay_scale",
    "path_cap_frac", "dt_us", "ecn_kmin_kb", "ecn_kmax_kb", "ecn_pmax",
    "dcqcn_g", "dcqcn_rai_mbps", "dcqcn_hai_mbps", "dcqcn_alpha_timer_us",
    "dcqcn_rate_timer_us", "dcqcn_bytes_counter_mb", "cnp_interval_us",
    "min_rate_mbps", "pfc_xoff_kb", "pfc_xon_kb", "otn_buffer_bdp_frac",
    "slot_us", "slots_per_window", "ack_delay_thresh_us", "cnp_freq_thresh",
    "queue_thresh_kb", "stable_cv_thresh", "stable_weight", "jitter_weight",
    "budget_headroom", "budget_probe", "budget_floor_mbps",
    "control_proc_slots", "geopipe_credit_bdp_frac", "sdr_window_bdp_frac",
    "sdr_ack_coalesce_us", "sdr_retx_budget_frac",
    "rdmacell_token_bucket_us", "rdmacell_rob_limit_mb")
# network fields it accepts only at the program's default: one site pair,
# per-link PFC at pfc_xoff_kb, no schedule, no failure, no impairment,
# the hard step
NET_AT_DEFAULT = {
    "path_thresh_kb": [], "num_sites": 2, "site_edges": [],
    "channel_schedule": [], "channel_schedule_dt_us": 0.0,
    "failure_schedule": [], "loss_rate": 0.0, "loss_burst_len": 1.0,
    "jitter_us": 0.0, "flap_period_us": 0.0, "flap_depth": 0.0,
    "channel_seed": 0, "soft_step": False, "remat_steps": 0}
CHANNELS = ("ideal",)
_WHO = "reference " + os.path.basename(__file__)


def _plain(v):
    return [_plain(x) for x in v] if isinstance(v, (list, tuple)) else v


def _refuse(where: str, items: dict, keys, at_default: dict) -> None:
    for k, v in items.items():
        if k in keys:
            continue
        if k not in at_default:
            raise ValueError(f"{_WHO}: {where} key {k!r} is "
                             f"not modelled")
        if _plain(v) != at_default[k]:
            raise ValueError(f"{_WHO}: {where} {k!r} = {v!r} "
                             f"is not modelled (only {at_default[k]!r})")


def refuse_unmodelled(config: dict, cells: list) -> None:
    """Raise ``ValueError``, naming the field, where the configuration or a
    cell sets anything this reference does not model: a channel other
    than ``ideal``, a network field outside ``NET_KEYS`` (or one of
    ``NET_AT_DEFAULT`` off its default), a flow key outside ``FLOW_KEYS``
    (or one of ``FLOW_AT_DEFAULT`` off its default)."""
    channel = config.get("channel", "ideal")
    if channel not in CHANNELS:
        raise ValueError(f"{_WHO}: channel {channel!r} is not "
                         f"modelled (only {', '.join(CHANNELS)})")
    for c in cells:
        _refuse("net", c["net"], NET_KEYS, NET_AT_DEFAULT)
        for f in c["flows"]:
            _refuse("flow", f, FLOW_KEYS, FLOW_AT_DEFAULT)


# ---------------------------------------------------------------- statics

def delay_steps(delay_us: float, dt_us: float) -> int:
    return max(int(np.round(np.float32(delay_us) / np.float32(dt_us))), 1)


def cell_delays_us(net: dict) -> list:
    owd = 5.0 * net["distance_km"]
    scales = net.get("path_delay_scale") or [1.0] * net["num_paths"]
    return [owd * s for s in scales]


def history_slots(net: dict) -> int:
    """Slot-history length: two control windows (2D + a slot), at least
    64 slots, in whole estimator windows."""
    spw = net["slots_per_window"]
    ctrl = max(int(math.ceil(2.0 * 5.0 * net["distance_km"]
                             / net["slot_us"])) + 1, 4)
    want = max(64, 2 * ctrl)
    return ((want + spw - 1) // spw) * spw


def grid_statics(grid_nets: list, horizon_us: float) -> dict:
    """What the whole grid shares: scan length, warm cut-off, the slot
    history length (the longest any cell needs) and the ring padding."""
    dt = grid_nets[0]["dt_us"]
    steps = int(round(horizon_us / dt))
    return dict(steps=steps, warm=int(steps * WARM_FRAC),
                hist=max(history_slots(n) for n in grid_nets),
                dpad=max(delay_steps(d, dt) for n in grid_nets
                         for d in cell_delays_us(n)))


def cell_params(net: dict, flows: list) -> dict:
    """Per-cell traced values (float64 here, cast to the run's dtype)."""
    L = net["num_paths"]
    otn = net["num_otn_links"] * net["link_gbps"]
    fracs = net.get("path_cap_frac") or [1.0 / L] * L
    p = {k: net[k] for k in (
        "dst_dc_gbps", "nic_gbps", "pfc_xoff_kb", "pfc_xon_kb",
        "otn_buffer_bdp_frac", "ecn_kmin_kb", "ecn_kmax_kb",
        "queue_thresh_kb", "budget_floor_mbps", "budget_headroom",
        "geopipe_credit_bdp_frac", "sdr_window_bdp_frac",
        "sdr_ack_coalesce_us", "sdr_retx_budget_frac",
        "rdmacell_token_bucket_us", "rdmacell_rob_limit_mb", "slot_us")}
    p["owd_us"] = 5.0 * net["distance_km"]
    p["otn_gbps"] = otn
    p["link_delay_us"] = cell_delays_us(net)
    p["link_cap_gbps"] = [otn * f for f in fracs]
    p["link_thresh_kb"] = [net["pfc_xoff_kb"]] * L
    fl = {k: [f[k] for f in flows] for k in (
        "is_inter", "window", "total_bytes", "start_us", "period_us",
        "duty")}
    p.update(fl)
    return p


# ------------------------------------------------------------- the model

def _drain(q, arrivals, cap):
    """Fluid FIFO drain of up to ``cap`` bytes, shared by backlog."""
    avail = q + arrivals
    tot = jnp.sum(avail)
    out = jnp.minimum(tot, cap)
    share = jnp.where(tot > 0, avail / jnp.maximum(tot, 1e-12), 0.0)
    drained = share * out
    return avail - drained, drained


def _fair(budget, mask):
    return budget / jnp.maximum(mask.sum(), 1.0) * mask


def _hist_bin(x):
    span = float(np.log(HIST_MAX))
    frac = jnp.log(jnp.maximum(x, 1.0)) / span
    idx = 1 + jnp.floor(frac * (HIST_BINS - 1)).astype(jnp.int32)
    return jnp.where(x < 1.0, 0, jnp.clip(idx, 1, HIST_BINS - 1))


def _rob(tx_cum, arr_cum):
    """Reorder-buffer bytes per flow: arrivals beyond the slowest link's
    frontier (its arrivals scaled by its share of the flow's bytes)."""
    tx_tot = jnp.sum(tx_cum, axis=0)
    arr_tot = jnp.sum(arr_cum, axis=0)
    share = tx_cum / jnp.maximum(tx_tot[None, :], 1.0)
    est = jnp.where(share > 1e-6, arr_cum / jnp.maximum(share, 1e-6),
                    jnp.inf)
    front = jnp.min(est, axis=0)
    front = jnp.where(jnp.isfinite(front), front, arr_tot)
    return jnp.maximum(arr_tot - jnp.minimum(front, arr_tot), 0.0)


class Model:
    """One scheme on one grid's statics; ``run`` drives a block of cells."""

    def __init__(self, scheme: str, net: dict, statics: dict, dtype):
        self.s, self.net, self.st, self.dt = scheme, net, statics, dtype
        self.L = net["num_paths"]
        self.dt_us = float(net["dt_us"])
        self.dt_s = self.dt_us * 1e-6
        self.proc = int(net["control_proc_slots"] * net["slot_us"]
                        / self.dt_us)
        self.sps = max(int(round(net["slot_us"] / self.dt_us)), 1)
        self.cpad = statics["dpad"] + self.proc
        self.spray = self.L > 1 and scheme == "rdmacell"
        self.budget_block = scheme in BUDGET_BLOCK

    # -- per-cell derived quantities (traced)
    def derive(self, p):
        n, f = self.net, self.dt
        d = {}
        d["d"] = jnp.clip(jnp.maximum(jnp.round(p["owd_us"] / self.dt_us)
                                      .astype(jnp.int32), 1),
                          1, self.st["dpad"])
        d["nic"] = p["nic_gbps"] * 1e9 / 8.0
        d["c_otn"] = p["otn_gbps"] * 1e9 / 8.0
        d["c_leaf"] = p["dst_dc_gbps"] * 1e9 / 8.0
        d["xoff"] = p["pfc_xoff_kb"] * 1024.0
        bdp = d["c_otn"] * 2.0 * p["owd_us"] * 1e-6
        d["bdp"] = bdp
        d["xoff_otn"] = jnp.maximum(d["xoff"], p["otn_buffer_bdp_frac"] * bdp)
        d["xon_otn"] = d["xoff_otn"] / 2.0
        if self.L > 1:
            caps = p["link_cap_gbps"] * 1e9 / 8.0
            d["caps"] = caps
            d["ld"] = jnp.clip(jnp.round(p["link_delay_us"] / self.dt_us)
                               .astype(jnp.int32), 1, self.st["dpad"])
            lbdp = caps * 2.0 * p["link_delay_us"] * 1e-6
            d["xoff_l"] = jnp.maximum(p["link_thresh_kb"] * 1024.0,
                                      p["otn_buffer_bdp_frac"] * lbdp)
            d["xon_l"] = d["xoff_l"] / 2.0
        d["rtt"] = jnp.where(p["is_inter"] > 0,
                             2.0 * d["d"] * self.dt_us + 4.0, 4.0)
        if self.s == "themis":
            d["rtt_scale"] = jnp.clip(jnp.sqrt(d["rtt"] / 10.0), 1.0, 4.0)
        else:
            d["rtt_scale"] = jnp.ones_like(p["is_inter"])
        d["cdelay"] = jnp.clip(d["d"] + jnp.floor(
            n["control_proc_slots"] * p["slot_us"] / self.dt_us)
            .astype(jnp.int32), 1, self.cpad)
        return {k: v if jnp.issubdtype(v.dtype, jnp.integer)
                else v.astype(f) for k, v in d.items()}

    def init(self, p, d):
        f, dt, L = p["is_inter"].shape[0], self.dt, self.L
        z = jnp.zeros((f,), dt)
        dp = self.st["dpad"]
        st = dict(
            sent=z, acked=z, delivered=z, done=jnp.full((f,), INF, dt),
            rc=jnp.full((f,), 1.0, dt) * d["nic"], rt=z + d["nic"],
            alpha=jnp.ones((f,), dt), t_alpha=z, t_rate=z, bytes_ctr=z,
            stage_t=z, stage_b=z,
            cnp_timer=jnp.full((f,), 1e9, dt), marked=z,
            proxy_timer=jnp.full((f,), 1e9, dt), proxy_mod=jnp.ones((f,), dt),
            q_src=z, q_leaf=z,
            q_dst=jnp.zeros((L, f), dt) if L > 1 else z,
            pipe=jnp.zeros((dp, L, f) if L > 1 else (dp, f), dt),
            ack_line=jnp.zeros((dp, f), dt), cnp_line=jnp.zeros((dp, f), dt),
            pause_line=jnp.zeros((dp, L) if L > 1 else (dp,), dt),
            pause_dst=jnp.zeros((L,), dt) if L > 1 else jnp.zeros((), dt),
        )
        if self.budget_block:
            b0 = p["dst_dc_gbps"] * 1e9 / 8.0 * 0.25
            R = self.st["hist"]
            st.update(
                budget=b0, tighten=jnp.ones((), dt),
                slots_clear=jnp.zeros((), dt), cap_ewma=jnp.zeros((), dt),
                have_cap=jnp.zeros((), dt),
                line_b=jnp.full((self.cpad,), 1.0, dt) * b0,
                line_s=jnp.zeros((self.cpad,), dt), cidx=jnp.int32(0),
                b_src=b0, s_src=jnp.zeros((), dt),
                packed=z, credits=z,
                a_egress=jnp.zeros((), dt), a_cnp=jnp.zeros((), dt),
                a_delay=jnp.zeros((), dt), a_n=jnp.zeros((), dt),
                a_queue=jnp.zeros((), dt), a_paused=jnp.zeros((), dt),
                r_rates=jnp.zeros((R,), dt), r_cong=jnp.zeros((R,), dt),
                r_busy=jnp.zeros((R,), dt), r_idx=jnp.int32(0),
                r_count=jnp.int32(0))
        if self.s == "geopipe":
            st.update(line_b=jnp.zeros((self.cpad,), dt), cidx=jnp.int32(0),
                      granted=jnp.zeros((), dt), egress_cum=jnp.zeros((), dt),
                      phase=jnp.int32(0))
        if self.s == "sdr_rdma":
            st.update(ack_cum=z, ack_held=z,
                      co_timer=jnp.full((), 1e9, dt),
                      cong=jnp.zeros((), dt))
        if self.spray:
            st.update(tokens=p["rdmacell_token_bucket_us"] * 1e-6 * d["caps"],
                      tx_cum=jnp.zeros((L, f), dt),
                      arr_cum=jnp.zeros((L, f), dt))
        return st

    def init_acc(self):
        dt = self.dt
        z = jnp.zeros((), dt)
        acc = {"s_" + k: z for k in ("q_dst", "pause", "thr_i", "thr_x")}
        acc.update({"c_" + k: z for k in ("q_dst", "pause", "thr_i",
                                          "thr_x")})
        acc["peak"] = z
        acc["hist"] = jnp.zeros((HIST_BINS,), jnp.int32)
        if self.budget_block:
            acc["budget"] = z
        if self.s in ("dcqcn", "themis"):
            acc["cc_rate"] = z
        if self.s == "pseudo_ack":
            acc["lead"] = z
        if self.s == "matchrdma":
            acc["budget_src"] = z
        if self.s == "geopipe":
            acc["credit"], acc["stall"] = z, z
        if self.s == "sdr_rdma":
            acc["lag"], acc["reserve"] = z, z
        if self.spray:
            acc["rob"] = z
            acc["tx_link"] = jnp.zeros((self.L,), dt)
        return acc

    # -- scheme pieces
    def _geo_credit(self, p, d, st):
        window = p["geopipe_credit_bdp_frac"] * d["bdp"]
        released = jnp.sum(st["sent"] * p["is_inter"]) - jnp.sum(st["q_src"])
        return jnp.maximum(window - (released - st["granted"]), 0.0)

    def _sdr_reserve(self, p, st):
        return jnp.clip(p["sdr_retx_budget_frac"], 0.0, SDR_MAX_RETX) \
            * st["cong"]

    def _slot_update(self, p, st):
        n, dt = self.net, self.dt
        sps = jnp.maximum(jnp.round(p["slot_us"] / self.dt_us), 1.0)
        slot_s = p["slot_us"] * 1e-6
        paused = st["a_paused"] / sps
        unpaused_s = slot_s * jnp.maximum(1.0 - paused, 1e-3)
        mean_q = st["a_queue"] / sps
        rate = st["a_egress"] / unpaused_s
        delay = st["a_delay"] / jnp.maximum(st["a_n"], 1.0)
        qt = p["queue_thresh_kb"] * 1024.0
        busy = ((mean_q > qt) & (paused < 0.9)).astype(dt)
        level = ((delay > n["ack_delay_thresh_us"]).astype(dt)
                 + (st["a_cnp"] > n["cnp_freq_thresh"]).astype(dt)
                 + (mean_q > qt).astype(dt))
        cong = (level > 0).astype(dt)
        R = st["r_rates"].shape[0]
        i = st["r_idx"]
        rates = st["r_rates"].at[i].set(rate)
        congs = st["r_cong"].at[i].set(cong)
        busys = st["r_busy"].at[i].set(busy)
        idx = jnp.mod(i + 1, R)
        count = st["r_count"] + 1
        # oldest-first history, valid where filled
        order = jnp.mod(idx + jnp.arange(R), R)
        valid = (jnp.arange(R) >= R - jnp.minimum(count, R)).astype(dt)
        rates_o, cong_o, busy_o = rates[order], congs[order], busys[order]
        spw = n["slots_per_window"]
        nw = R // spw
        rw = rates_o[:nw * spw].reshape(nw, spw)
        cw = cong_o[:nw * spw].reshape(nw, spw)
        bw = busy_o[:nw * spw].reshape(nw, spw)
        vw = valid[:nw * spw].reshape(nw, spw)
        w_valid = vw.min(axis=1)
        mean = rw.mean(axis=1)
        cv = rw.std(axis=1) / jnp.maximum(mean, 1e-9)
        stable = (cv < n["stable_cv_thresh"]) & (cw.max(axis=1) < 0.5)
        w = jnp.where(stable, n["stable_weight"], n["jitter_weight"]) \
            * w_valid
        w = w * (0.5 + 0.5 * (jnp.arange(nw) + 1) / nw).astype(dt)
        est = jnp.sum(w * mean) / jnp.maximum(jnp.sum(w), 1e-9)
        wcap = w * bw.mean(axis=1)
        have = (jnp.sum(wcap) > 1e-9).astype(dt)
        capab = jnp.sum(wcap * mean) / jnp.maximum(jnp.sum(wcap), 1e-9)
        ctrl = jnp.maximum(jnp.ceil(2.0 * p["owd_us"] / p["slot_us"]) + 1.0,
                           4.0)
        n_recent = jnp.clip(jnp.maximum(ctrl, 4 * spw), 1, R)
        recent = valid * (jnp.arange(R) >= R - n_recent).astype(dt)
        cong_recent = jnp.sum(cong_o * recent) / jnp.maximum(
            jnp.sum(recent), 1.0)
        # budget: match the demonstrated capability when the destination
        # was congested within a control window, else probe upwards once
        # per control window
        cap = p["otn_gbps"] * 1e9 / 8.0
        floor = p["budget_floor_mbps"] * 1e6 / 8.0
        tighten = jnp.where(st["a_cnp"] > n["cnp_freq_thresh"],
                            jnp.maximum(st["tighten"] * 0.95, 0.7),
                            jnp.minimum(st["tighten"] * 1.02, 1.0))
        cap_ewma = jnp.where(
            have > 0, jnp.where(st["have_cap"] > 0,
                                0.8 * st["cap_ewma"] + 0.2 * capab, capab),
            st["cap_ewma"])
        have_cap = jnp.maximum(st["have_cap"], have)
        matched = p["budget_headroom"] * jnp.where(
            have_cap > 0, cap_ewma, est) * tighten
        declared = p["dst_dc_gbps"] * 1e9 / 8.0
        constrained = cong_recent > 0.02
        clear = jnp.where(constrained, 0.0, st["slots_clear"] + 1.0)
        raise_now = clear >= ctrl
        cap_ewma = jnp.where(raise_now & (have_cap > 0),
                             jnp.maximum(cap_ewma, est), cap_ewma)
        ceiling = jnp.minimum(
            1.1 * jnp.where(have_cap > 0, cap_ewma, declared), cap)
        factor = jnp.where(have_cap > 0, n["budget_probe"], 2.0)
        open_up = jnp.where(raise_now,
                            jnp.minimum(st["budget"] * factor, ceiling),
                            st["budget"])
        clear = jnp.where(raise_now, 0.0, clear)
        budget = jnp.clip(jnp.where(constrained, matched, open_up),
                          floor, cap)
        z = jnp.zeros((), dt)
        return dict(st, r_rates=rates, r_cong=congs, r_busy=busys,
                    r_idx=idx, r_count=count, budget=budget,
                    tighten=tighten, slots_clear=clear, cap_ewma=cap_ewma,
                    have_cap=have_cap, a_egress=z, a_cnp=z, a_delay=z,
                    a_n=z, a_queue=z, a_paused=z)

    # -- one step of one cell
    def step(self, st, acc, t, p, d):
        n, dt, s, L = self.net, self.dt, self.s, self.L
        dt_us, dt_s = self.dt_us, self.dt_s
        inter, intra = p["is_inter"], 1.0 - p["is_inter"]
        t_us = t.astype(dt) * dt_us
        ridx = jnp.mod(t, d["d"])
        new = dict(st)

        started = (t_us >= p["start_us"]).astype(dt)
        in_period = jnp.where(
            p["period_us"] > 0,
            (jnp.mod(jnp.maximum(t_us - p["start_us"], 0.0),
                     jnp.maximum(p["period_us"], 1.0))
             < p["duty"] * p["period_us"]).astype(dt), 1.0)
        not_done = (st["delivered"] < p["total_bytes"]).astype(dt)
        active = started * in_period * not_done

        ack_arr, cnp_arr = st["ack_line"][ridx], st["cnp_line"][ridx]
        if L > 1:
            lidx = jnp.mod(t, d["ld"])
            links = jnp.arange(L)
            pause_sig = st["pause_line"][lidx, links]
            pipe_out = st["pipe"][lidx, links]
            cap_link = jnp.where(pause_sig > 0.5, 0.0, d["caps"] * dt_s)
            cap_src = jnp.sum(cap_link)
        else:
            pause_sig = st["pause_line"][ridx]
            pipe_out = st["pipe"][ridx]
            cap_src = jnp.where(pause_sig > 0.5, 0.0, d["c_otn"] * dt_s)

        # ---- ACK view and sender rate
        if s in ("pseudo_ack", "matchrdma"):
            seen = st["packed"]
        elif s == "sdr_rdma":
            seen = st["ack_held"]
        else:
            seen = st["acked"] + ack_arr
        acked = jnp.minimum(jnp.where(inter > 0, seen, st["delivered"]),
                            st["sent"])
        base = jnp.minimum(
            jnp.maximum(p["window"] - (st["sent"] - acked), 0.0) / dt_s,
            d["nic"])
        dcqcn_rate = jnp.minimum(st["rc"], base)
        if s in WINDOW_ONLY:
            rate = jnp.where(inter > 0, base, dcqcn_rate)
        elif s == "sdr_rdma":
            swnd = p["sdr_window_bdp_frac"] * d["bdp"]
            unacked = st["sent"] - jnp.minimum(st["ack_held"], st["sent"])
            room = jnp.maximum(swnd - unacked, 0.0)
            eff = (jnp.minimum(dcqcn_rate, room / dt_s)
                   * (1.0 - self._sdr_reserve(p, st)))
            rate = jnp.where(inter > 0, eff, dcqcn_rate)
        elif self.spray:
            rob = jnp.sum(_rob(st["tx_cum"], st["arr_cum"]) * inter)
            limit = p["rdmacell_rob_limit_mb"] * 1e6
            gate = jnp.where(rob > limit, limit / jnp.maximum(rob, 1.0), 1.0)
            rate = jnp.where(inter > 0, dcqcn_rate * gate, dcqcn_rate)
        else:
            rate = dcqcn_rate
        nic_pause = (jnp.sum(st["q_src"]) > d["xoff_otn"]).astype(dt)
        rate = rate * jnp.where(inter > 0, 1.0 - nic_pause, 1.0)
        send = rate * active * dt_s
        sent = st["sent"] + send

        # ---- source OTN release
        arr_src = send * inter
        if s == "matchrdma":
            share = _fair(st["b_src"], active * inter)
            avail = st["q_src"] + arr_src
            want = jnp.minimum(avail, share * st["proxy_mod"] * dt_s * inter)
            drained = want * jnp.minimum(
                1.0, cap_src / jnp.maximum(jnp.sum(want), 1e-9))
            q_src = avail - drained
        elif s == "geopipe":
            cap = jnp.minimum(cap_src, self._geo_credit(p, d, st))
            avail = st["q_src"] + arr_src
            f = avail.shape[0]
            boost = jnp.where(jnp.mod(jnp.arange(f), GEOPIPE["stages"])
                              == st["phase"], GEOPIPE["boost"], 1.0)
            w = avail * boost
            tot_w = jnp.sum(w)
            out = jnp.minimum(jnp.sum(avail), cap)
            drained = jnp.minimum(
                jnp.where(tot_w > 0, w / jnp.maximum(tot_w, 1e-12), 0.0)
                * out, avail)
            left = out - jnp.sum(drained)
            rem = avail - drained
            rem_tot = jnp.sum(rem)
            drained = drained + jnp.where(
                rem_tot > 0, rem / jnp.maximum(rem_tot, 1e-12), 0.0) * left
            q_src = avail - drained
        else:
            q_src, drained = _drain(st["q_src"], arr_src, cap_src)
        if L > 1:
            w = jnp.ones((drained.shape[0], L), dt)
            if self.spray:
                tok = jnp.maximum(st["tokens"], 0.0)
                tok = jnp.where(jnp.sum(tok) <= 0.0, jnp.ones_like(tok), tok)
                w = w * tok[None, :]
            w = jnp.maximum(w, 0.0) * (cap_link > 0.0)[None, :]
            share = w / jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-9)
            want = drained[:, None] * share
            link_want = jnp.sum(want, axis=0)
            scale = jnp.minimum(1.0, cap_link
                                / jnp.maximum(link_want, 1e-9))
            sent_link = (want * scale[None, :]).T
            q_src = q_src + (drained - jnp.sum(sent_link, axis=0))
            new["pipe"] = st["pipe"].at[lidx, links].set(sent_link)
        else:
            new["pipe"] = st["pipe"].at[ridx].set(drained)

        # ---- destination OTN
        leaf_pfc = (jnp.sum(st["q_leaf"]) > d["xoff"]).astype(dt)
        q_dst, dr_dst = _drain(st["q_dst"], pipe_out,
                               d["c_leaf"] * dt_s * (1.0 - leaf_pfc))
        egress = jnp.sum(dr_dst)
        q_dst_tot = jnp.sum(q_dst)

        def hyst(paused, q, xoff, xon):
            return jnp.where(q > xoff, 1.0, jnp.where(q < xon, 0.0, paused))
        if L > 1:
            pause_dst = hyst(st["pause_dst"], jnp.sum(q_dst, axis=1),
                             d["xoff_l"], d["xon_l"])
            new["pause_line"] = st["pause_line"].at[lidx, links].set(
                pause_dst)
            dr_dst_f = jnp.sum(dr_dst, axis=0)
        else:
            pause_dst = hyst(st["pause_dst"], q_dst_tot, d["xoff_otn"],
                             d["xon_otn"])
            new["pause_line"] = st["pause_line"].at[ridx].set(pause_dst)
            dr_dst_f = dr_dst

        # ---- destination leaf, ECN marking, CNPs
        q_leaf_tot = jnp.sum(st["q_leaf"])
        kmin, kmax = p["ecn_kmin_kb"] * 1024.0, p["ecn_kmax_kb"] * 1024.0
        mark_p = (jnp.clip((q_leaf_tot - kmin) / jnp.maximum(kmax - kmin, 1.0),
                           0.0, 1.0) * n["ecn_pmax"]
                  + (q_leaf_tot > kmax).astype(dt) * (1.0 - n["ecn_pmax"]))
        q_leaf, dr_leaf = _drain(st["q_leaf"], dr_dst_f + send * intra,
                                 d["c_leaf"] * dt_s)
        delivered = st["delivered"] + dr_leaf
        marked = st["marked"] + dr_leaf * mark_p
        cnp_timer = st["cnp_timer"] + dt_us
        emit = (marked >= MTU) & (cnp_timer >= n["cnp_interval_us"])
        cnp_out = emit.astype(dt)
        cnp_timer = jnp.where(emit, 0.0, cnp_timer)
        marked = jnp.where(emit, 0.0, marked)

        # ---- scheme feedback
        e2e_cnp = jnp.where(inter > 0, cnp_arr, cnp_out * intra)
        cnp_wire, cnp_in = cnp_out * inter, e2e_cnp
        if s == "pseudo_ack":
            backlog = jnp.maximum(sent * inter - st["packed"], 0.0)
            new["packed"] = st["packed"] + backlog
        if s == "matchrdma":
            share = _fair(st["b_src"], active * inter)
            credits = jnp.minimum(st["credits"] + share * dt_s,
                                  share * MAX_BURST_S)
            rel = jnp.minimum(jnp.maximum(sent * inter - st["packed"], 0.0),
                              credits)
            new["credits"] = credits - rel
            new["packed"] = st["packed"] + rel
            timer = st["proxy_timer"] + dt_us
            fire = (st["s_src"] > 0.5) & (timer >= n["cnp_interval_us"])
            new["proxy_mod"] = jnp.where(
                fire, jnp.maximum(st["proxy_mod"] * PROXY_CUT, PROXY_FLOOR),
                jnp.minimum(st["proxy_mod"] * (1.0 + PROXY_RECOVER * dt_us),
                            1.0))
            new["proxy_timer"] = jnp.where(fire, 0.0, timer)
            delay_us = (jnp.sum(q_leaf) / d["c_leaf"] * 1e6
                        + n["intra_dc_delay_us"])
            new.update(a_egress=st["a_egress"] + egress,
                       a_cnp=st["a_cnp"] + jnp.sum(cnp_out * inter),
                       a_delay=st["a_delay"] + delay_us,
                       a_n=st["a_n"] + 1.0,
                       a_queue=st["a_queue"] + q_dst_tot,
                       a_paused=st["a_paused"] + leaf_pfc)
            new = jax.lax.cond(jnp.mod(t + 1, self.sps) == 0,
                               partial(self._slot_update, p),
                               lambda x: x, new)
            overrun = (q_dst_tot > 0.5 * d["xoff_otn"]).astype(dt)
            ci = st["cidx"]
            new["b_src"], new["s_src"] = st["line_b"][ci], st["line_s"][ci]
            new["line_b"] = st["line_b"].at[ci].set(new["budget"])
            new["line_s"] = st["line_s"].at[ci].set(overrun)
            new["cidx"] = jnp.mod(ci + 1, d["cdelay"])
            cnp_wire, cnp_in = jnp.zeros_like(cnp_out), cnp_out * intra
        if s == "geopipe":
            egress_cum = st["egress_cum"] + egress
            grant = egress_cum + jnp.maximum(d["xoff_otn"] - q_dst_tot, 0.0)
            ci = st["cidx"]
            new["granted"] = st["line_b"][ci]
            new["line_b"] = st["line_b"].at[ci].set(grant)
            new["cidx"] = jnp.mod(ci + 1, d["cdelay"])
            new["egress_cum"] = egress_cum
            new["phase"] = jnp.mod(jnp.floor(
                (t.astype(dt) + 1.0) * dt_us / GEOPIPE["slice_us"])
                .astype(jnp.int32), GEOPIPE["stages"])
            cnp_wire, cnp_in = jnp.zeros_like(cnp_out), cnp_out * intra
        if s == "sdr_rdma":
            ack_cum = st["ack_cum"] + ack_arr * inter
            timer = st["co_timer"] + dt_us
            fire = timer >= p["sdr_ack_coalesce_us"]
            new["ack_held"] = jnp.where(fire, ack_cum, st["ack_held"])
            new["co_timer"] = jnp.where(fire, 0.0, timer)
            new["ack_cum"] = ack_cum
            hit = (jnp.sum(cnp_arr * inter) > 0).astype(dt)
            g = min(dt_us / 1000.0, 1.0)
            new["cong"] = (1.0 - g) * st["cong"] + g * hit
        if self.spray:
            bucket = p["rdmacell_token_bucket_us"] * 1e-6 * d["caps"]
            new["tokens"] = jnp.clip(st["tokens"] + cap_link - link_want,
                                     0.0, bucket)
            new["tx_cum"] = st["tx_cum"] + sent_link
            new["arr_cum"] = st["arr_cum"] + pipe_out

        new["ack_line"] = st["ack_line"].at[ridx].set(dr_leaf * inter)
        new["cnp_line"] = st["cnp_line"].at[ridx].set(cnp_wire)

        # ---- DCQCN at the sender (cut on CNP, timer/byte-counter raises)
        g = n["dcqcn_g"]
        rai = n["dcqcn_rai_mbps"] * 1e6 / 8.0
        rhai = n["dcqcn_hai_mbps"] * 1e6 / 8.0
        rmin = n["min_rate_mbps"] * 1e6 / 8.0
        rs = d["rtt_scale"]
        cut = cnp_in > 0
        t_alpha = st["t_alpha"] + dt_us
        t_rate = st["t_rate"] + dt_us
        bytes_ctr = st["bytes_ctr"] + send
        a_dec = t_alpha >= n["dcqcn_alpha_timer_us"]
        alpha_no = jnp.where(a_dec, (1.0 - g) * st["alpha"], st["alpha"])
        t_fire = t_rate >= n["dcqcn_rate_timer_us"]
        b_fire = bytes_ctr >= n["dcqcn_bytes_counter_mb"] * 1e6
        fire = t_fire | b_fire
        stage_t = jnp.where(t_fire, st["stage_t"] + 1, st["stage_t"])
        stage_b = jnp.where(b_fire, st["stage_b"] + 1, st["stage_b"])
        hyper = (stage_t > FAST_RECOVERY) & (stage_b > FAST_RECOVERY)
        additive = (jnp.maximum(stage_t, stage_b) > FAST_RECOVERY) & ~hyper
        inc = jnp.where(hyper, rhai, jnp.where(additive, rai, 0.0)) * rs
        rt_inc = jnp.where(fire, st["rt"] + inc, st["rt"])
        rc_inc = jnp.where(fire, 0.5 * (st["rc"] + rt_inc), st["rc"])
        rc_cut = jnp.maximum(st["rc"] * (1.0 - st["alpha"] / rs / 2.0), rmin)
        new["rc"] = jnp.clip(jnp.where(cut, rc_cut, rc_inc), rmin, None)
        new["rt"] = jnp.where(cut, st["rc"], rt_inc)
        new["alpha"] = jnp.clip(jnp.where(cut, (1.0 - g) * st["alpha"] + g,
                                          alpha_no), 0.0, 1.0)
        new["t_alpha"] = jnp.where(cut | a_dec, 0.0, t_alpha)
        new["t_rate"] = jnp.where(cut | fire, 0.0, t_rate)
        new["bytes_ctr"] = jnp.where(cut | b_fire, 0.0, bytes_ctr)
        new["stage_t"] = jnp.where(cut, 0.0, stage_t)
        new["stage_b"] = jnp.where(cut, 0.0, stage_b)

        done = (delivered >= p["total_bytes"]) & (st["done"] >= INF / 2)
        new.update(sent=sent, acked=acked, delivered=delivered,
                   done=jnp.where(done, t_us, st["done"]),
                   cnp_timer=cnp_timer, marked=marked, q_src=q_src,
                   q_dst=q_dst, q_leaf=q_leaf, pause_dst=pause_dst)

        # ---- row accumulators (warm means, peak, histogram, scheme's own)
        inc = (t >= self.st["warm"]).astype(dt)
        if L > 1:
            cap_w = d["caps"] / jnp.maximum(jnp.sum(d["caps"]), 1e-9)
            pause_v = jnp.sum(pause_dst * cap_w)
        else:
            pause_v = pause_dst
        vals = {"q_dst": q_dst_tot, "pause": pause_v,
                "thr_i": jnp.sum(dr_leaf * inter) / dt_s,
                "thr_x": jnp.sum(dr_leaf * intra) / dt_s}
        a = dict(acc)
        for k, v in vals.items():     # compensated (Kahan) warm sums
            y = v * inc - acc["c_" + k]
            tot = acc["s_" + k] + y
            a["c_" + k] = (tot - acc["s_" + k]) - y
            a["s_" + k] = tot
        a["peak"] = jnp.maximum(acc["peak"], q_dst_tot)
        a["hist"] = acc["hist"].at[_hist_bin(q_dst_tot)].add(
            inc.astype(jnp.int32))
        if self.budget_block:
            a["budget"] = acc["budget"] + new["budget"] * inc
        if s in ("dcqcn", "themis"):
            n_inter = jnp.maximum(jnp.sum(inter), 1.0)
            a["cc_rate"] = acc["cc_rate"] + jnp.sum(new["rc"] * inter) \
                / n_inter * inc
        if s == "pseudo_ack":
            a["lead"] = acc["lead"] + jnp.sum(jnp.maximum(
                new["packed"] - delivered, 0.0) * inter) * inc
        if s == "matchrdma":
            a["budget_src"] = acc["budget_src"] + new["b_src"] * inc
        if s == "geopipe":       # from the state before the step
            credit = self._geo_credit(p, d, st)
            stall = ((credit <= 1.0)
                     & (jnp.sum(st["q_src"]) > 1.0)).astype(dt)
            a["credit"] = acc["credit"] + credit * inc
            a["stall"] = acc["stall"] + stall * inc
        if s == "sdr_rdma":      # from the state before the step
            lag = jnp.sum(jnp.maximum(st["ack_cum"] - st["ack_held"], 0.0)
                          * inter)
            a["lag"] = acc["lag"] + lag * inc
            a["reserve"] = acc["reserve"] + self._sdr_reserve(p, st) * inc
        if self.spray:
            a["rob"] = acc["rob"] + jnp.sum(
                _rob(new["tx_cum"], new["arr_cum"]) * inter) * inc
            a["tx_link"] = jnp.sum(new["tx_cum"], axis=1)
        return new, a

    def run(self, P: dict):
        """P: per-cell params with a leading block axis. Returns the final
        (state, accumulators) of every cell."""
        D = jax.vmap(self.derive)(P)
        st0 = jax.vmap(self.init)(P, D)
        acc0 = jax.vmap(lambda _: self.init_acc())(P["owd_us"])

        def body(carry, t):
            st, acc = jax.vmap(
                lambda s_, a_, p_, d_: self.step(s_, a_, t, p_, d_)
            )(*carry, P, D)
            return (st, acc), None

        (st, acc), _ = jax.lax.scan(
            body, (st0, acc0), jnp.arange(self.st["steps"], dtype=jnp.int32))
        return st, acc


# ------------------------------------------------------------- rows

def _hist_centers():
    edges = np.exp(np.linspace(0.0, np.log(HIST_MAX), HIST_BINS))
    return np.concatenate([[0.0], np.sqrt(edges[:-1] * edges[1:])])


def rows_from(scheme: str, nets: list, P: dict, st: dict, acc: dict,
              statics: dict) -> list:
    """Host-side finalisation of one block into sweep rows."""
    f64 = lambda x: np.asarray(jax.device_get(x), np.float64)  # noqa: E731
    n_warm = max(statics["steps"] - statics["warm"], 1)
    inter = f64(P["is_inter"]) > 0
    total, start = f64(P["total_bytes"]), f64(P["start_us"])
    delivered, done = f64(st["delivered"]), f64(st["done"])
    finite = inter & (total < 1e18 / 2)
    completed = finite & (done < INF / 2)
    n_fin, n_done = finite.sum(1), completed.sum(1)
    fct = np.where(completed, done - start, 0.0).sum(1)
    avg_fct = np.where(n_done > 0, fct / np.maximum(n_done, 1), np.inf)
    avg_fct = np.where(n_fin > 0, avg_fct, np.nan)
    hist = f64(acc["hist"])
    rank = 0.99 * hist.sum(axis=-1, keepdims=True)
    p99 = _hist_centers()[np.clip((np.cumsum(hist, -1) < rank).sum(-1), 0,
                                  HIST_BINS - 1)]
    cols = {
        "throughput_gbps": f64(acc["s_thr_i"]) / n_warm * 8.0 / 1e9,
        "goodput_bytes": np.where(inter, delivered, 0.0).sum(1),
        "peak_buffer_mb": f64(acc["peak"]) / 1e6,
        "mean_buffer_mb": f64(acc["s_q_dst"]) / n_warm / 1e6,
        "p99_buffer_mb": p99 / 1e6,
        "pause_ratio": f64(acc["s_pause"]) / n_warm,
        "avg_fct_us": avg_fct,
        "completion_frac": np.where(n_fin > 0, n_done / np.maximum(n_fin, 1),
                                    1.0),
        "intra_thr_gbps": f64(acc["s_thr_x"]) / n_warm * 8.0 / 1e9,
    }
    mean = lambda k: f64(acc[k]) / n_warm   # noqa: E731
    if "budget" in acc:
        cols["mean_budget_gbps"] = mean("budget") * 8.0 / 1e9
    if "cc_rate" in acc:
        cols["mean_cc_rate_gbps"] = mean("cc_rate") * 8.0 / 1e9
    if "lead" in acc:
        cols["mean_pseudo_lead_mb"] = mean("lead") / 1e6
    if "budget_src" in acc:
        cols["mean_budget_at_src_gbps"] = mean("budget_src") * 8.0 / 1e9
    if "credit" in acc:
        cols["mean_credit_mb"] = mean("credit") / 1e6
        cols["credit_stall_frac"] = mean("stall")
    if "lag" in acc:
        cols["mean_ack_lag_mb"] = mean("lag") / 1e6
        cols["mean_retx_reserve_frac"] = mean("reserve")
    if "rob" in acc:
        cols["mean_reorder_buf_mb"] = mean("rob") / 1e6
        tx = f64(acc["tx_link"])
        p = tx / np.maximum(tx.sum(1, keepdims=True), 1.0)
        with np.errstate(divide="ignore", invalid="ignore"):
            h = -np.where(p > 0.0, p * np.log(np.maximum(p, 1e-30)),
                          0.0).sum(1)
        ent = h / np.log(tx.shape[1])
        cols["spray_entropy"] = np.where(tx.sum(1) > 0.0, ent, 0.0)
    rows = []
    for i, net in enumerate(nets):
        row = {"scheme": scheme, "distance_km": float(net["distance_km"])}
        row.update({k: float(v[i]) for k, v in cols.items()})
        rows.append(row)
    return rows


def simulate_rows(scheme: str, cells: list, grid_nets: list,
                  horizon_us: float, dtype=jnp.float32, device=None) -> list:
    """Reference rows of ``cells`` (dicts with ``net`` and ``flows``) under
    one scheme. ``grid_nets`` are the nets of the whole grid the cells
    belong to (they fix the shared slot-history length and scan length)."""
    statics = grid_statics(grid_nets, horizon_us)
    nets = [c["net"] for c in cells]
    per = [cell_params(c["net"], c["flows"]) for c in cells]
    P = {k: np.asarray([q[k] for q in per], np.float64) for k in per[0]}
    P = {k: jnp.asarray(v, dtype) for k, v in P.items()}
    if device is not None:
        P = jax.device_put(P, device)
    model = Model(scheme, nets[0], statics, dtype)
    st, acc = jax.jit(model.run)(P)
    return rows_from(scheme, nets, P, st, acc, statics)
