"""Fluid-flow discrete-time simulator of the dual AI-DC leaf-spine-OTN path.

One ``jax.lax.scan`` step = ``dt_us`` of simulated time. Per-flow byte rates
are integrated through the congestion-relevant queues of Fig. 3(a):

    sender NIC --> [Q_src] source OTN --(pipe: delay D, cap C_otn)-->
    [Q_dst] destination OTN --> [Q_leaf] destination leaf (shared with
    intra-DC flows, ECN marking here) --> receiver

Feedback paths:
  * ACKs:  receiver -> sender, delay D (conventional) / source-OTN pseudo-ACK
           (NTT baseline, ungated) / budget-gated pseudo-ACK (MatchRDMA).
  * CNPs:  receiver -> sender, delay D (baselines) / consumed at destination
           OTN + congestion summary on the control subchannel (MatchRDMA).
  * PFC:   destination-leaf -> destination OTN (1 step);
           destination OTN -> source OTN (delay D, the long-haul pause the
           paper's pause-time-ratio measures);
           source OTN -> sender NIC (1 step).

Schemes (pluggable — ``repro.netsim.schemes``):
  ``make_step_fn`` is a scheme-agnostic skeleton; everything a control
  scheme decides (ACK view, sender rate law, source-OTN release, CNP
  routing, extra-state updates) enters through the ``Scheme`` hooks. Six
  schemes ship registered — the paper's four (``dcqcn``, ``pseudo_ack``,
  ``themis``, ``matchrdma``) plus the related-work pack (``geopipe``,
  ``sdr_rdma``); third-party schemes register with
  ``@register_scheme("name")`` and are usable from every entrypoint.
  Scheme arguments accept a registered name or a ``Scheme`` instance;
  the hook contract is documented in ``docs/scheme-api.md``.

Channel models (pluggable — ``repro.netsim.channel``):
  The long haul itself is a plugin: every entrypoint takes ``channel=``
  (a registered ``ChannelModel`` name or instance; default ``"ideal"`` —
  structurally bit-identical to the pre-channel engine). Non-ideal models
  (``bernoulli_loss``, ``jitter``, ``otn_flap``, ``impaired``) get ONE
  hook point between the pipe exit and the destination OTN (plus a
  capacity tap on the source line), and the engine's loss-repair path
  activates: lost bytes ride a notification ring back (delay D), queue in
  a per-flow retransmit backlog, and re-enter the source OTN at the rate
  the scheme's ``retx_rate`` hook grants. Impairment knobs are traced
  ``NetParams`` leaves (grids compile once per scheme); all randomness is
  counter-based (``fold_in(scenario_key(channel_seed, knobs), t)``) so runs are
  deterministic and resume-safe. See ``docs/channel-models.md``.

Static vs traced scenario split (the batched scenario engine):
  ``NetConfig`` stays the hashable compile-time side — it fixes ``dt_us``,
  slot layout, DCQCN constants and every array SIZE. The per-scenario
  scalars a sweep varies enter as traced ``NetParams`` leaves, and the
  per-scenario workload enters as traced ``WorkloadParams`` leaves (flow
  arrays padded to the batch-max flow count with an ``active_mask``), so
  ``simulate_batch`` vmaps over (NetParams × WorkloadParams) jointly:
  heterogeneous distances AND heterogeneous flow sets share ONE compiled
  ``lax.scan`` and run the whole scenario grid in a single device launch.
  Delay lines are allocated at a static padded length (``delay_pad``) while
  the ring index wraps at the traced actual ``delay_steps``.

Execution modes (``trace_mode``):
  ``full``      every per-step trace key materializes as a [T] (or [B, T])
                array — figures, goldens, debugging.
  ``decimate``  every ``decimate``-th step is kept: [T/k] traces, O(B·T/k)
                memory — long-horizon figures.
  ``metrics``   NO per-step arrays exist anywhere: the ``lax.scan`` carry
                accumulates the Fig. 3 reductions online (Kahan-compensated
                warm-step sums, running maxes, a fixed-bin log-histogram of
                ``q_dst`` for p99) in a ``MetricAcc``, so device memory is
                O(B) per trace key instead of O(B·T) and nothing but final
                states + accumulators ever transfers to host. Schemes
                stream their own reductions through the
                ``Scheme.init_metric_acc``/``accumulate_metrics``/
                ``finalize_metrics`` hooks (mirroring ``extra_traces``).
  ``window``    ``metrics`` plus the LAST ``cfg.trace_window_steps`` steps
                of every trace key kept in a ring carried through the scan
                (O(B·W) memory, still no [B, T] array) and — when
                ``cfg.event_ring_slots > 0`` — a bounded per-scenario ring
                of timestamped discrete events (PFC edges, threshold
                crossings, retx onset, failure entry/exit, and whatever a
                scheme's ``emit_events`` hook contributes). Returns
                ``(final, WindowAux)``; ``repro.netsim.obs`` decodes rings
                and exports Perfetto timelines (docs/observability.md).

Lock-step rounds (docs/rounds.md):
  A flow with ``round_bytes > 0`` sends round by round: round R+1 is
  released ``round_period_us`` after round R at the earliest and not
  before round R is delivered, and it never sends more than is posted.
  The gate is built only when the grid has such a flow (the STATIC
  ``rounds`` switch), under the ``netsim.rounds`` scope.

Device sharding: ``shard_scenario_axis`` splits the stacked [B] scenario
leaves across ``jax.devices()`` (jax.sharding over the vmapped axis), and
``simulate_batch`` applies it automatically whenever the device count
evenly splits the batch — one SPMD launch sweeps the grid on every
accelerator. The runner's launch plans pad chunks to a device multiple so
the split always holds.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import (
    NetConfig, NetParams, batch_template, stack_net_params,
)
from repro.core.cc_proxy import DcqcnState, init_dcqcn, step_dcqcn
from repro.core.matchrdma import default_history_slots
from repro.netsim.channel import (
    ChannelInputs, ChannelModel, get_channel_model, scenario_key,
)
from repro.netsim.obs.profile import span
from repro.netsim.queues import drain_proportional, ecn_mark_prob, pfc_hysteresis
from repro.netsim.soft import lerp, reset_gate, soft_gt, soft_pos, ste
from repro.netsim.schemes import SCHEMES, get_scheme  # noqa: F401 (re-export)
from repro.netsim.schemes.base import Scheme, SchemeCtx, SchemeSignals
from repro.netsim.streaming import (
    HIST_BINS, hist_bin_centers, hist_bin_index, hist_quantile, kahan_add,
)
from repro.netsim.workload import (
    WorkloadParams, as_workload_batch, has_rounds,
)

MTU = 1500.0
# np (not jnp): a module-level jax array would initialize the backend at
# import time; as an f32 numpy scalar it traces identically
INF = np.float32(1e30)


def is_unfinished(done_at_us):
    """True where ``done_at_us`` still carries the INF 'not done' sentinel.

    The one definition both the engine's completion latch and the runner's
    metric extractors compare against (works on numpy and jax arrays).
    f32-safe: any sentinel at or above INF/2 counts, so a round-tripped or
    arithmetically-perturbed sentinel can never masquerade as a real
    completion time (real times are bounded by the horizon, µs-scale)."""
    return done_at_us >= INF / 2

WARMUP_FRAC = 0.1   # fraction of the horizon discarded as startup transient

TRACE_MODES = ("full", "decimate", "metrics", "window")

# engine-owned streaming reductions over the per-step trace dict: warm-step
# sums (-> means) and all-step running maxes
STREAM_SUM_KEYS = ("q_src", "q_dst", "q_leaf", "pause_dst",
                   "thr_inter", "thr_intra")
STREAM_MAX_KEYS = ("q_src", "q_dst", "q_leaf", "cons_err")

# Trace keys that are per-step byte COUNTS (not levels): under
# ``trace_mode="decimate"`` each kept row carries the SUM over its
# decimate-block rather than the last step's sample, so time-normalized
# columns (goodput/wire/retx rates) stay exact at any decimation — the
# parity fix that keeps ``runner._channel_cols_from_traces`` in agreement
# with the streamed ``ChannelModel.finalize_metrics`` path.
DECIMATE_SUM_KEYS = ("chan_wire", "chan_lost", "chan_retx")

# The fixed-bin log histogram backing the streaming p99 (q_dst bytes here;
# the channel subsystem reuses it for repair-wait µs) lives in
# repro.netsim.streaming; the historical names stay importable from here.
HIST_MIN_BYTES = 1.0
HIST_MAX_BYTES = 1e12
_hist_bin_index = hist_bin_index


class MetricAcc(NamedTuple):
    """O(1)-per-scenario scan carry of the Fig. 3 reductions
    (``trace_mode="metrics"``). Under the batched engine every leaf gains a
    leading [B] axis; nothing here scales with the step count."""
    sum_s: dict       # STREAM_SUM_KEYS -> Kahan running sum over warm steps
    sum_c: dict       # STREAM_SUM_KEYS -> Kahan compensation term
    maxes: dict       # STREAM_MAX_KEYS -> running max over ALL steps
    hist: jax.Array   # [HIST_BINS] i32 warm-step log-histogram of q_dst
                      # (integer counts: f32 would silently saturate past
                      # 2^24 increments per bin on long horizons)
    scheme: object    # scheme-private accumulator (Scheme.init_metric_acc)
    chan: object      # channel-private accumulator
                      # (ChannelModel.init_metric_acc; None when ideal)


class WindowAux(NamedTuple):
    """Aux output of ``trace_mode="window"`` (docs/observability.md).

    Everything ``metrics`` mode streams, PLUS the last
    ``cfg.trace_window_steps`` steps of every trace key and (optionally)
    the event ring — all O(W + E) per scenario, never O(T). Under the
    batched engine every leaf gains a leading [B] axis."""
    acc: MetricAcc    # the same streamed Fig. 3 reductions as "metrics"
    window: dict      # trace key -> [W, ...] ring; step t lives in row
                      # t mod W (repro.netsim.obs.unroll_window reorders)
    events: object    # obs.EventRing when cfg.event_ring_slots > 0,
                      # else None


def _failure_len(cfg, params) -> int:
    """STATIC outage-window count W of a compiled program. Prefer the
    ``fail_windows`` leaf SHAPE over ``cfg.failure_len``: inside a batched
    program ``cfg`` is the ``batch_template`` (every traced field reset to
    its default, so ``cfg.failure_len`` reads 0 there) and only the
    stacked leaf still carries W — the same shape-from-params idiom
    ``trace_replay`` uses for its schedule length."""
    fw = getattr(params, "fail_windows", None) if params is not None else None
    if fw is None:
        return cfg.failure_len
    return int(fw.shape[-2])


def _track_chan(channel, cfg, params=None) -> bool:
    """Whether the chan_* trace keys / streamed channel columns exist for
    this run: any non-ideal channel, OR a failure schedule (an outage
    activates the loss-repair path even under the ideal channel — the
    base ``ChannelModel`` streaming hooks then reduce the engine-owned
    chan_* keys)."""
    return (not channel.is_ideal) or _failure_len(cfg, params) > 0


def _init_metric_acc(scheme, channel, ctx, state0) -> MetricAcc:
    z = jnp.float32(0.0)
    return MetricAcc(
        sum_s={k: z for k in STREAM_SUM_KEYS},
        sum_c={k: z for k in STREAM_SUM_KEYS},
        maxes={k: z for k in STREAM_MAX_KEYS},
        hist=jnp.zeros((HIST_BINS,), jnp.int32),
        scheme=scheme.init_metric_acc(ctx, state0),
        chan=(channel.init_metric_acc(ctx, state0)
              if _track_chan(channel, ctx.cfg, ctx.params) else None),
    )


def _accumulate_engine(acc: MetricAcc, out: dict, inc: jax.Array) -> MetricAcc:
    sum_s, sum_c = {}, {}
    for k in STREAM_SUM_KEYS:
        # Kahan-compensated so the streaming mean matches the numpy trace
        # mean to ~ulp — "metrics" mode is a drop-in for figure numbers
        sum_s[k], sum_c[k] = kahan_add(acc.sum_s[k], acc.sum_c[k],
                                       out[k] * inc)
    maxes = {k: jnp.maximum(acc.maxes[k], out[k]) for k in STREAM_MAX_KEYS}
    hist = acc.hist.at[_hist_bin_index(out["q_dst"])].add(
        inc.astype(jnp.int32))
    return acc._replace(sum_s=sum_s, sum_c=sum_c, maxes=maxes, hist=hist)


class SimState(NamedTuple):
    sent: jax.Array          # [F] cumulative bytes leaving the sender NIC
    acked: jax.Array         # [F] cumulative bytes ACKed at the sender
    delivered: jax.Array     # [F] cumulative bytes delivered to the receiver
    done_at_us: jax.Array    # [F] completion time (INF = not done)
    cc: DcqcnState           # [F] DCQCN machine (sender or proxy)
    cnp_timer: jax.Array     # [F] µs since last CNP emission (receiver side)
    marked_acc: jax.Array    # [F] marked-byte accumulator (per-packet model)
    proxy_timer: jax.Array   # [F] µs since last proxy cut (MatchRDMA)
    proxy_mod: jax.Array     # [F] multiplicative proxy modulation in [0.25, 1]
    q_src: jax.Array         # [F] source-OTN queue bytes
    q_dst: jax.Array         # [F] destination-OTN queue bytes
                             # ([L, F] when cfg.num_paths > 1)
    q_leaf: jax.Array        # [F] destination-leaf queue bytes
    pipe: jax.Array          # [Dp, F] in-flight long-haul bytes
                             # ([Dp, L, F] when cfg.num_paths > 1)
    inflight: jax.Array      # [F] running sum of pipe (incremental: O(F)/step)
    ack_line: jax.Array      # [Dp, F] ACK return path
    cnp_line: jax.Array      # [Dp, F] CNP return path
    pause_line: jax.Array    # [Dp] PFC signal dst-OTN -> src-OTN
                             # ([Dp, L]: per-link pause at L > 1)
    pause_dst: jax.Array     # scalar: dst OTN asserting long-haul pause
                             # ([L] per-link at L > 1)
    extra: object            # scheme-private pytree (Scheme.init_extra_state)
    # channel subsystem (ALL None under the ideal channel — the engine
    # structurally skips the machinery, keeping the default path
    # bit-identical to the pre-channel engine):
    chan: object             # channel-private pytree (init_channel_state)
    retx_backlog: object     # [F] lost bytes awaiting retransmission at src
    retx_line: object        # [Dp, F] loss notifications dst -> src
    retx_inflight: object    # [F] running sum of retx_line (incremental)
    # lock-step rounds (docs/rounds.md; ALL None unless the workload has
    # rounds — the round gate is then structurally absent):
    round_r: object = None       # [F] rounds released so far
    round_due_us: object = None  # [F] earliest time of the next release
    stall_us: object = None      # [F] time spent due but undelivered
    round_late: object = None    # [F] releases made after their due step


def _delay_steps(cfg: NetConfig) -> int:
    """STATIC delay-step count — sizes the delay-line padding (the shared
    f32-aware definition lives on ``NetConfig.static_delay_steps``)."""
    return cfg.static_delay_steps


def _proc_steps(cfg: NetConfig) -> int:
    return cfg.control_proc_steps


def init_state(cfg: NetConfig, num_flows: int, params: NetParams = None,
               delay_pad: int = 0, history_slots: int = 0,
               scheme: Scheme = None, channel: ChannelModel = None,
               round_wl: WorkloadParams = None) -> SimState:
    """``delay_pad``/``history_slots`` are static ring sizes (0 = size for
    ``cfg`` itself); ``params`` carries the traced per-scenario scalars;
    ``scheme`` owns the ``extra`` slot (None = the default MatchRDMA
    block); ``channel`` owns the ``chan``/``retx_*`` slots (None = the
    ideal channel — the slots stay empty); ``round_wl`` is the workload
    whose rounds the ``round_*`` slots track (None = no rounds — the slots
    stay empty): the first round is released at each flow's start."""
    f = num_flows
    if delay_pad <= 0:
        delay_pad = _delay_steps(cfg)
    if params is None:
        params = NetParams.of(cfg)
    if scheme is None:
        scheme = Scheme()
    channel = get_channel_model(channel)
    multi = cfg.num_paths > 1
    L = cfg.num_paths
    z = jnp.zeros((f,), jnp.float32)
    nic = params.nic_gbps * 1e9 / 8.0
    # the loss-repair slots exist whenever anything can LOSE bytes: a
    # non-ideal channel, or a failure schedule (a dead link dumps its
    # in-flight bytes into the retransmit path — docs/failures.md)
    repair = (not channel.is_ideal) or _failure_len(cfg, params) > 0
    if repair:
        backlog, retx_inflight = z, z
        retx_line = jnp.zeros((delay_pad, f), jnp.float32)
    else:
        backlog = retx_line = retx_inflight = None
    if channel.is_ideal:
        chan = None
    else:
        if cfg.soft_step:
            # soft mode pins one noise stream per seed (no knob-bit
            # folding): differentiable + common random numbers across
            # knob perturbations — mirrors make_step_fn
            base_key = jax.random.PRNGKey(cfg.channel_seed)
        else:
            base_key = scenario_key(
                jax.random.PRNGKey(cfg.channel_seed), params)
        # models whose init accepts a ``link`` index (the base-class
        # signature since the trace_replay model landed) are told which
        # link-axis entry they serve; legacy third-party signatures
        # without it keep working unchanged
        import inspect
        try:
            takes_link = "link" in inspect.signature(
                channel.init_channel_state).parameters
        except (TypeError, ValueError):  # builtins/partials without sigs
            takes_link = False
        if multi:
            # one independent impairment process per link: fold the link
            # index into the scenario key so parallel paths draw
            # decorrelated noise
            keys = jax.vmap(lambda l: jax.random.fold_in(base_key, l))(
                jnp.arange(L))
            if takes_link:
                chan = jax.vmap(
                    lambda k, l: channel.init_channel_state(
                        cfg, params, f, key=k, link=l)
                )(keys, jnp.arange(L))
            else:
                chan = jax.vmap(
                    lambda k: channel.init_channel_state(cfg, params, f,
                                                         key=k)
                )(keys)
        else:
            chan = channel.init_channel_state(cfg, params, f, key=base_key)
    return SimState(
        sent=z, acked=z, delivered=z,
        done_at_us=jnp.full((f,), INF),
        cc=init_dcqcn(f, nic),
        cnp_timer=jnp.full((f,), 1e9, jnp.float32),
        marked_acc=z,
        proxy_timer=jnp.full((f,), 1e9, jnp.float32),
        proxy_mod=jnp.ones((f,), jnp.float32),
        q_src=z,
        q_dst=jnp.zeros((L, f), jnp.float32) if multi else z,
        q_leaf=z,
        pipe=(jnp.zeros((delay_pad, L, f), jnp.float32) if multi
              else jnp.zeros((delay_pad, f), jnp.float32)),
        inflight=z,
        ack_line=jnp.zeros((delay_pad, f), jnp.float32),
        cnp_line=jnp.zeros((delay_pad, f), jnp.float32),
        pause_line=(jnp.zeros((delay_pad, L), jnp.float32) if multi
                    else jnp.zeros((delay_pad,), jnp.float32)),
        pause_dst=(jnp.zeros((L,), jnp.float32) if multi
                   else jnp.float32(0.0)),
        extra=scheme.init_extra_state(
            cfg, params, f, history_slots=history_slots,
            chan_delay_pad=delay_pad + _proc_steps(cfg)),
        chan=chan, retx_backlog=backlog, retx_line=retx_line,
        retx_inflight=retx_inflight,
        **({} if round_wl is None else dict(
            round_r=jnp.ones((f,), jnp.float32),
            round_due_us=(jnp.asarray(round_wl.start_us)
                          + jnp.asarray(round_wl.round_period_us)),
            stall_us=z, round_late=z)),
    )


def make_step_fn(cfg: NetConfig, wl: WorkloadParams, scheme,
                 period_slots: int = 0, params: NetParams = None,
                 delay_pad: int = 0, channel=None, rounds: bool = False):
    """Build the per-step transition — the scheme-agnostic skeleton.

    ``wl``: the traced per-flow workload leaves. All per-scenario scalars
    are read from ``params`` (traced), so the same compiled step serves
    every cell of a vmapped scenario batch; ``cfg`` only contributes static
    structure (dt, slot layout, DCQCN constants). ``scheme`` is a
    registered name or a ``Scheme`` instance; everything scheme-specific
    happens inside its hooks. ``channel`` is a registered channel-model
    name or ``ChannelModel`` instance (None = ``"ideal"``): non-ideal
    models get the single channel hook point between the pipe exit and the
    destination OTN, and the engine's loss-repair path (notification ring,
    retransmit backlog served at ``Scheme.retx_rate``) activates.
    ``rounds`` (STATIC, ``workload.has_rounds`` of the grid) builds the
    round gate of flows with ``round_bytes > 0`` (docs/rounds.md); the
    state must then carry the ``round_*`` slots (``init_state(round_wl=)``).
    """
    scheme = get_scheme(scheme)
    channel = get_channel_model(channel)
    impaired = not channel.is_ideal
    # hard-failure schedule (docs/failures.md; STATIC window count keys
    # the compile). ``repair`` gates the loss-repair machinery: a dead
    # link dumps its in-flight bytes into the retransmit path, so the
    # backlog/notification-ring plumbing must exist even under the ideal
    # channel whenever failures can fire.
    if params is None:
        params = NetParams.of(cfg)
    has_fail = _failure_len(cfg, params) > 0
    repair = impaired or has_fail
    if delay_pad <= 0:
        delay_pad = _delay_steps(cfg)
    dt_us = cfg.dt_us
    dt_s = dt_us * 1e-6
    # soft-step relaxation (docs/differentiable.md): with cfg.soft_step the
    # traced temperature replaces every knob-dependent hard select below by
    # a tempered blend; None (the default) leaves the hard jaxpr untouched.
    soft = params.soft_temp if cfg.soft_step else None
    if rounds and cfg.soft_step:
        raise ValueError(
            "make_step_fn: round-gated flows (FlowSpec.round_bytes, "
            "FlowSpec.round_period_us) have no soft relaxation — run them "
            "with soft_step=False (docs/rounds.md)")
    # traced actual delay, clamped to the static ring allocation (mirrors
    # budget.init_channel) — an out-of-range wrap would silently alias
    # ring rows through JAX's index clamping instead of erroring
    d_steps = jnp.clip(params.delay_steps(dt_us), 1, delay_pad)
    nic = params.nic_gbps * 1e9 / 8.0
    c_otn = params.otn_capacity_gbps * 1e9 / 8.0
    c_leaf = params.dst_dc_gbps * 1e9 / 8.0
    xoff = params.pfc_xoff_kb * 1024.0
    xon = params.pfc_xon_kb * 1024.0
    # OTN nodes are provisioned with BDP-scaled buffers (long-haul headroom)
    bdp = c_otn * 2.0 * params.one_way_delay_us * 1e-6
    xoff_otn = jnp.maximum(xoff, params.otn_buffer_bdp_frac * bdp)
    xon_otn = xoff_otn / 2.0

    # -- multi-link topology (cfg.num_paths > 1; STATIC — keys the compile).
    # At L = 1 none of these exist and the single-pipe code path below is
    # untouched, so the L=1 jaxpr (and the goldens pinning it) stays
    # bit-identical to the pre-topology engine.
    L = cfg.num_paths
    multi = L > 1
    if cfg.is_multisite and not multi:
        raise ValueError(
            f"make_step_fn: multi-site config (num_sites={cfg.num_sites}, "
            f"site_edges={cfg.site_edges!r}) requires num_paths > 1 — a "
            f"site graph compiles onto the link axis (one edge per link; "
            f"see docs/sites.md)")
    if multi:
        link_ids = jnp.arange(L)
        link_caps = params.link_cap_gbps * 1e9 / 8.0              # [L] B/s
        link_d_steps = jnp.clip(
            jnp.round(params.link_delay_us / dt_us).astype(jnp.int32),
            1, delay_pad)                                          # [L]
        # per-link dst-OTN PFC thresholds: the explicit per-path floor or
        # the link's own BDP-scaled headroom, whichever is larger
        link_bdp = link_caps * 2.0 * params.link_delay_us * 1e-6
        xoff_link = jnp.maximum(params.link_thresh_kb * 1024.0,
                                params.otn_buffer_bdp_frac * link_bdp)
        xon_link = xoff_link / 2.0
        route = jnp.asarray(wl.route)                              # [F, W]
        if route.shape[-1] == 1:
            route = jnp.broadcast_to(route, route.shape[:-1] + (L,))
        elif route.shape[-1] != L:
            raise ValueError(
                f"WorkloadParams.route has {route.shape[-1]} link columns "
                f"but cfg.num_paths = {L} — give each flow a length-{L} "
                f"route (or () for the symmetric default)")
        if cfg.is_multisite:
            # the endpoint matrix, compiled: mask each flow's spray row
            # down to the edges serving its (src_site, dst_site) pair
            # (docs/sites.md). The edge table is static; the flow
            # endpoints are traced workload leaves, so heterogeneous
            # meshes share one program. Gated on is_multisite so legacy
            # single-pair configs keep the exact pre-sites jaxpr.
            pairs = np.asarray(cfg.edge_pairs(), np.float32)       # [L, 2]
            f_src = jnp.asarray(wl.src_site)                       # [F]
            f_dst = jnp.asarray(wl.dst_site)                       # [F]
            pair_mask = ((f_src[:, None] == pairs[None, :, 0]) &
                         (f_dst[:, None] == pairs[None, :, 1]))
            route = route * pair_mask.astype(jnp.float32)          # [F, L]

    is_inter = jnp.asarray(wl.is_inter)
    is_intra = 1.0 - is_inter
    window = jnp.asarray(wl.window)
    total_bytes = jnp.asarray(wl.total_bytes)
    start_us = jnp.asarray(wl.start_us)
    period_us = jnp.asarray(wl.period_us)
    duty = jnp.asarray(wl.duty)
    active_mask = jnp.asarray(wl.active_mask)
    if rounds:
        round_b = jnp.asarray(wl.round_bytes)
        round_p = jnp.asarray(wl.round_period_us)
        is_round = round_b > 0
    rtt_us = jnp.where(is_inter > 0, 2.0 * d_steps * dt_us + 4.0, 4.0)

    ctx = SchemeCtx(
        cfg=cfg, params=params, period_slots=period_slots,
        dt_us=dt_us, dt_s=dt_s, nic=nic, c_otn=c_otn, c_leaf=c_leaf,
        xoff=xoff, xon=xon, xoff_otn=xoff_otn, xon_otn=xon_otn,
        is_inter=is_inter, is_intra=is_intra, rtt_us=rtt_us,
        d_steps=d_steps,
        num_links=L,
        link_caps=link_caps if multi else None,
        link_d_steps=link_d_steps if multi else None,
        num_sites=cfg.num_sites,
        edge_sites=(jnp.asarray(cfg.edge_pairs(), jnp.int32)
                    if cfg.is_multisite else None),
        flow_src_site=(jnp.asarray(wl.src_site)
                       if cfg.is_multisite else None),
        flow_dst_site=(jnp.asarray(wl.dst_site)
                       if cfg.is_multisite else None),
        soft=soft,
    )
    rtt_scale = scheme.rtt_scale(ctx)
    if impaired:
        # counter-based randomness: the per-step key is a pure function of
        # (static seed, per-scenario salt, step index) — deterministic,
        # resume-safe inside lax.scan, shared across schemes (common
        # random numbers for paired comparisons)
        if cfg.soft_step:
            # knob-bit folding is a non-differentiable bitcast AND would
            # redraw the noise at every knob perturbation — soft mode pins
            # one stream per seed (common random numbers across gradient
            # steps, the CRN contract grad_tune relies on)
            chan_key0 = jax.random.PRNGKey(cfg.channel_seed)
        else:
            chan_key0 = scenario_key(
                jax.random.PRNGKey(cfg.channel_seed), params)
    zero_f = jnp.zeros((is_inter.shape[0],), jnp.float32)
    if has_fail:
        fw = jnp.asarray(params.fail_windows)          # [L, W, 2]
        fail_lo, fail_hi = fw[..., 0], fw[..., 1]      # [L, W]

    def step(state: SimState, t: jax.Array):
        # Each phase below runs under a ``netsim.<phase>`` named scope and
        # each scheme hook under ``hook.<method>`` (docs/observability.md):
        # scopes only name the HLO ops' metadata, so a device trace can be
        # attributed per phase; the values and the jaxpr are unchanged.
        t_us = t.astype(jnp.float32) * dt_us
        ridx = jnp.mod(t, d_steps)

        with jax.named_scope("netsim.flow"):
            # ---------------------------------------- 0. failure live-mask
            # A link is DOWN inside any of its (down_at, up_at) windows
            # (strict upper bound, so padding (0, 0) windows never fire).
            # Schemes see the mask through ``SchemeCtx.link_live`` and
            # re-spray their routing weights over the survivors; at an
            # all-up step every where() below selects the ORIGINAL tensor,
            # keeping the program bit-identical to a schedule-free run.
            if has_fail:
                link_down = jnp.any((t_us >= fail_lo) & (t_us < fail_hi),
                                    axis=-1)                       # [L]
                link_live = 1.0 - link_down.astype(jnp.float32)    # [L]
                hctx = ctx._replace(link_live=link_live)
            else:
                hctx = ctx

            # -------------------------------------------- 1. flow phase
            if soft is None:
                started = (t_us >= start_us).astype(jnp.float32)
                in_period = jnp.where(
                    period_us > 0,
                    (jnp.mod(jnp.maximum(t_us - start_us, 0.0),
                             jnp.maximum(period_us, 1.0))
                     < duty * period_us).astype(jnp.float32),
                    1.0)
                not_done = (state.delivered
                            < total_bytes).astype(jnp.float32)
            else:
                started = soft_gt(t_us, start_us, soft, dt_us)
                phase = jnp.mod(jnp.maximum(t_us - start_us, 0.0),
                                jnp.maximum(period_us, 1.0))
                gate = soft_gt(duty * period_us, phase, soft, dt_us)
                in_period = lerp(soft_pos(period_us, soft, dt_us), gate, 1.0)
                # STE on the activity gate: the forward pass keeps the
                # exact live-mask (consistent with the hard completion
                # latch below); the backward pass sees the tempered gate
                not_done = ste(
                    (state.delivered < total_bytes).astype(jnp.float32),
                    soft_gt(total_bytes, state.delivered, soft,
                            jnp.maximum(1e-3 * total_bytes, MTU)))
            active = started * in_period * not_done * active_mask

            # ------------------------------------------ 1b. round gate
            # Release round R+1 once it is due AND round R has landed: by
            # last step's state, what the flow has yet to send of R * b
            # and what it sent that is still in the network come to at
            # most one MTU. They are read off the queues and the long
            # haul, not off the cumulative delivered counter, whose
            # roundoff grows with R (docs/rounds.md). The bytes posted,
            # R * b - sent, cap what the sender sends below.
            if rounds:
                with jax.named_scope("netsim.rounds"):
                    in_net = (state.q_src + state.q_leaf + state.inflight
                              + (jnp.sum(state.q_dst, axis=0) if multi
                                 else state.q_dst))
                    if repair:
                        in_net = (in_net + state.retx_backlog
                                  + state.retx_inflight)
                    if impaired:
                        in_net = in_net + (
                            jnp.sum(jax.vmap(channel.held_bytes)(state.chan),
                                    axis=0)
                            if multi else channel.held_bytes(state.chan))
                    unsent = state.round_r * round_b - state.sent
                    due = is_round & (t_us >= state.round_due_us)
                    landed = unsent + in_net <= MTU
                    release = due & landed
                    late = release & (t_us - dt_us >= state.round_due_us)
                    round_r = state.round_r + release.astype(jnp.float32)
                    round_due = jnp.where(release, t_us + round_p,
                                          state.round_due_us)
                    stall_us = state.stall_us + jnp.where(
                        due & ~landed, dt_us, 0.0)
                    round_late = state.round_late + late.astype(jnp.float32)
                    posted = jnp.maximum(round_r * round_b - state.sent, 0.0)

        # ------------------------------------------------ 2. delayed inputs
        with jax.named_scope("netsim.rings"):
            ack_arr = state.ack_line[ridx]
            cnp_arr = state.cnp_line[ridx]
            if multi:
                # each link's ring row wraps at ITS OWN traced delay: row l
                # of the padded ring holds what link l launched d_l steps ago
                lidx = jnp.mod(t, link_d_steps)            # [L]
                pause_sig = state.pause_line[lidx, link_ids]        # [L]
                pipe_out = state.pipe[lidx, link_ids]               # [L, F]
            else:
                pause_sig = state.pause_line[ridx]
                pipe_out = state.pipe[ridx]

        # The source line's capacity this step, from the delayed dst PFC
        # (and the failure mask): what the source OTN may release.
        with jax.named_scope("netsim.src_otn"):
            if soft is None:
                paused_src = pause_sig > 0.5               # delayed dst PFC
                if multi:
                    cap_link = jnp.where(paused_src, 0.0,
                                         link_caps * dt_s)       # [L]
                    if has_fail:
                        cap_link = jnp.where(link_down, 0.0, cap_link)
                    cap_src = jnp.sum(cap_link)
                else:
                    cap_src = jnp.where(paused_src, 0.0, c_otn * dt_s)
                    if has_fail:
                        cap_src = jnp.where(link_down[0], 0.0, cap_src)
            else:
                # the delayed pause signal already lives in [0, 1] in soft
                # mode (soft_hysteresis); re-temper it around the midpoint
                # and scale the capacity instead of zeroing it. The failure
                # mask is schedule-structure (knob-independent), so the
                # hard 0/1 multiplier stays.
                w_pause = soft_gt(pause_sig, 0.5, soft, 0.25)
                if multi:
                    cap_link = (1.0 - w_pause) * link_caps * dt_s  # [L]
                    if has_fail:
                        cap_link = cap_link * link_live
                    cap_src = jnp.sum(cap_link)
                else:
                    cap_src = (1.0 - w_pause) * c_otn * dt_s
                    if has_fail:
                        cap_src = cap_src * link_live[0]
        with jax.named_scope("netsim.rings"):
            retx_arr = state.retx_line[ridx] if repair else zero_f

        with jax.named_scope("netsim.channel"):
            # ---------------------------------------- 2b. channel hook
            # The single hook point of the channel subsystem: what leaves
            # the pipe is impaired BEFORE the destination OTN sees it, and
            # the source-OTN line capacity may be dimmed (OTN flap). Lost
            # bytes ride the loss-notification ring back to the source
            # (delay D). At L > 1 the model is vmapped over the link axis —
            # each parallel path carries its own impairment process
            # (independent keys, own flap phase / loss chain / jitter
            # buffer).
            if impaired:
                if multi:
                    step_key = jax.random.fold_in(chan_key0, t)
                    keys = jax.vmap(
                        lambda l: jax.random.fold_in(step_key, l))(link_ids)
                    eff = jax.vmap(
                        lambda c, k, po, cs: channel.apply_impairments(
                            ctx, c, ChannelInputs(t=t, key=k, pipe_out=po,
                                                  cap_src=cs)))(
                        state.chan, keys, pipe_out, cap_link)
                    pipe_arrivals, chan_new = eff.arrivals, eff.chan
                    lost = jnp.sum(eff.lost, axis=0)                  # [F]
                    cap_link = eff.cap_src                            # [L]
                    cap_src = jnp.sum(cap_link)
                else:
                    eff = channel.apply_impairments(
                        ctx, state.chan, ChannelInputs(
                            t=t, key=jax.random.fold_in(chan_key0, t),
                            pipe_out=pipe_out, cap_src=cap_src))
                    pipe_arrivals, lost = eff.arrivals, eff.lost
                    cap_src, chan_new = eff.cap_src, eff.chan
            else:
                pipe_arrivals, lost, chan_new = pipe_out, zero_f, None
            # ---------------------------------------- 2c. outage dump
            # Bytes reaching the far end of a DEAD link are lost there and
            # ride the loss-notification ring back: conservation holds
            # through the outage and the data re-enters the source queue
            # to be re-sprayed over the surviving links. (Bytes in flight
            # when a link dies keep transiting the ring; they are dumped at
            # exit time while the link stays down, delivered if it came
            # back.)
            if has_fail:
                if multi:
                    deadc = link_down[:, None]                   # [L, 1]
                    fail_lost = jnp.sum(
                        jnp.where(deadc, pipe_arrivals, 0.0), axis=0)  # [F]
                    pipe_arrivals = jnp.where(deadc, 0.0, pipe_arrivals)
                else:
                    fail_lost = jnp.where(link_down[0], pipe_arrivals,
                                          zero_f)
                    pipe_arrivals = jnp.where(link_down[0], zero_f,
                                              pipe_arrivals)
                lost = jnp.where(fail_lost > 0.0, lost + fail_lost, lost)

        with jax.named_scope("netsim.ack_rate"):
            # -------------------------------------------- 3. ACK accounting
            with jax.named_scope("hook.ack_view"):
                acked_inter = scheme.ack_view(hctx, state, ack_arr)
            acked = jnp.where(is_inter > 0, acked_inter,
                              state.delivered)         # intra: ~µs loop
            acked = jnp.minimum(acked, state.sent)

            # -------------------------------------------- 4. sender rates
            win_avail = jnp.maximum(window - (state.sent - acked), 0.0)
            base_rate = jnp.minimum(win_avail / dt_s, nic)
            if rounds:
                with jax.named_scope("netsim.rounds"):
                    # the transport sees only its work: the bytes posted
                    # and, with loss repair, those awaiting retransmission.
                    # The rate law starts from a window that holds no more
                    # (the cap on new data below holds whatever it does)
                    work = (posted + state.retx_backlog + retx_arr if repair
                            else posted)
                    base_rate = jnp.where(
                        is_round, jnp.minimum(base_rate, work / dt_s),
                        base_rate)
            with jax.named_scope("hook.sender_rate"):
                rate = scheme.sender_rate(hctx, state, base_rate)
            # src-OTN -> sender PFC (1 step, from last-step queue)
            if soft is None:
                src_nic_pause = (jnp.sum(state.q_src)
                                 > xoff_otn).astype(jnp.float32)
            else:
                src_nic_pause = soft_gt(jnp.sum(state.q_src), xoff_otn,
                                        soft, 0.05 * xoff_otn + 1.0)
            rate = rate * jnp.where(is_inter > 0, 1.0 - src_nic_pause, 1.0)
        # -------------------------------------------- 4b. loss repair
        # Lost bytes whose notification has arrived are retransmitted with
        # priority: the scheme grants a repair rate (retx_rate) and the
        # skeleton deducts what repair uses from the new-data rate, so
        # total per-flow emission never exceeds max(rate, granted) * dt.
        # The where() keeps the no-repair branch the UNTOUCHED rate tensor
        # AND leaves the send/sent expressions below structurally
        # identical to the ideal path — at zero impairments the compiled
        # arithmetic (XLA fusion/FMA contraction included) is the
        # pre-channel program's, which the zero-impairment identity test
        # pins bit-for-bit against the goldens.
        if repair:
            with jax.named_scope("netsim.channel"):
                backlog_avail = state.retx_backlog + retx_arr
                with jax.named_scope("hook.retx_rate"):
                    retx_bps = jnp.maximum(
                        scheme.retx_rate(hctx, state, rate), 0.0)
                retx_send = (jnp.minimum(jnp.minimum(backlog_avail,
                                                     retx_bps * dt_s),
                                         nic * dt_s)
                             * is_inter * (1.0 - src_nic_pause))
                rate = jnp.where(retx_send > 0.0,
                                 jnp.maximum(rate - retx_send / dt_s, 0.0),
                                 rate)
                retx_backlog = backlog_avail - retx_send
        else:
            retx_send, retx_backlog = zero_f, zero_f
        with jax.named_scope("netsim.ack_rate"):
            if rounds:
                with jax.named_scope("netsim.rounds"):
                    # whatever the rate law returned: no more new data
                    # sent than posted (a cap on the rate, not on the
                    # bytes, keeps the send / sent arithmetic of other
                    # flows as is)
                    rate = jnp.where(is_round,
                                     jnp.minimum(rate, posted / dt_s), rate)
            send = rate * active * dt_s                # bytes this step
            sent = state.sent + send

        # ------------------------------------------------ 5. source OTN
        with jax.named_scope("netsim.src_otn"):
            arrivals_src = send * is_inter
            if repair:
                # where(): at retx_send == 0 the select returns the
                # original arrivals tensor (see the send select above)
                arrivals_src = jnp.where(retx_send > 0.0,
                                         arrivals_src + retx_send,
                                         arrivals_src)
            with jax.named_scope("hook.src_otn_release"):
                q_src, drained_src = scheme.src_otn_release(
                    hctx, state, arrivals_src, cap_src, active)
            if multi:
                # spray the scheme's aggregate release across the parallel
                # links: per-flow weights (workload routing matrix,
                # reweighted by the scheme's route_weights hook), masked by
                # links with capacity this step, then clipped per link.
                # Bytes a saturated link cannot take spill back into the
                # source-OTN queue — an equal-weight spray over unequal
                # paths therefore bottlenecks on its slowest link, which is
                # exactly the imbalance token-gated spraying (rdmacell)
                # adapts away.
                with jax.named_scope("hook.route_weights"):
                    w = jnp.maximum(
                        scheme.route_weights(hctx, state, route), 0.0)
                if soft is None:
                    w = w * (cap_link > 0.0)[None, :]             # [F, L]
                else:
                    # soft zero-cap mask: exactly 0 at cap 0 (soft_pos), so
                    # a fully paused/flapped link still attracts no spray
                    w = w * soft_pos(cap_link, soft, MTU)[None, :]
                row = jnp.sum(w, axis=1, keepdims=True)
                share = w / jnp.maximum(row, 1e-9)                # [F, L]
                want = drained_src[:, None] * share               # [F, L]
                link_want = jnp.sum(want, axis=0)                 # [L]
                scale = jnp.minimum(
                    1.0, cap_link / jnp.maximum(link_want, 1e-9))
                sent_link = (want * scale[None, :]).T             # [L, F]
                spilled = drained_src - jnp.sum(sent_link, axis=0)
                q_src = q_src + spilled
                with jax.named_scope("netsim.rings"):
                    pipe = state.pipe.at[lidx, link_ids].set(sent_link)
                inflight = (state.inflight + jnp.sum(sent_link, axis=0)
                            - jnp.sum(pipe_out, axis=0))
                link_tx = jnp.sum(sent_link, axis=1)              # [L]
            else:
                with jax.named_scope("netsim.rings"):
                    # arrives at t + D
                    pipe = state.pipe.at[ridx].set(drained_src)
                inflight = state.inflight + drained_src - pipe_out

        with jax.named_scope("netsim.dst_queues"):
            # -------------------------------------------- 6. destination OTN
            if soft is None:
                leaf_pfc = (jnp.sum(state.q_leaf)
                            > xoff).astype(jnp.float32)
            else:
                leaf_pfc = soft_gt(jnp.sum(state.q_leaf), xoff, soft,
                                   0.05 * xoff + 1.0)
            cap_dst = c_leaf * dt_s * (1.0 - leaf_pfc)
            q_dst, drained_dst = drain_proportional(state.q_dst,
                                                    pipe_arrivals, cap_dst)
            egress_bytes = jnp.sum(drained_dst)
            q_dst_tot = jnp.sum(q_dst)
            if multi:
                # per-link backlog -> per-link PFC toward that link's source
                # line; each pause rides back at the LINK's own delay
                q_dst_link = jnp.sum(q_dst, axis=1)               # [L]
                pause_dst = pfc_hysteresis(state.pause_dst, q_dst_link,
                                           xoff_link, xon_link,
                                           soft=soft)             # [L]
                with jax.named_scope("netsim.rings"):
                    pause_line = state.pause_line.at[lidx, link_ids].set(
                        pause_dst)
                drained_dst_f = jnp.sum(drained_dst, axis=0)      # [F]
            else:
                pause_dst = pfc_hysteresis(state.pause_dst, q_dst_tot,
                                           xoff_otn, xon_otn, soft=soft)
                with jax.named_scope("netsim.rings"):
                    pause_line = state.pause_line.at[ridx].set(pause_dst)
                drained_dst_f = drained_dst

            # -------------------------------------------- 7. destination leaf
            arrivals_leaf = drained_dst_f + send * is_intra
            mark_p = ecn_mark_prob(jnp.sum(state.q_leaf), cfg, params=params,
                                   soft=soft)
            q_leaf, drained_leaf = drain_proportional(state.q_leaf,
                                                      arrivals_leaf,
                                                      c_leaf * dt_s)
            delivered = state.delivered + drained_leaf
            marked_acc = state.marked_acc + drained_leaf * mark_p

            # -------------------------------------------- 8. CNP generation
            cnp_timer = state.cnp_timer + dt_us
            if soft is None:
                want = marked_acc >= MTU
                emit = want & (cnp_timer >= cfg.cnp_interval_us)
                cnp_out = emit.astype(jnp.float32)
                cnp_timer = jnp.where(emit, 0.0, cnp_timer)
                marked_acc = jnp.where(emit, 0.0, marked_acc)
            else:
                # fractional CNPs: downstream consumers (slot classifier,
                # CC cut gate) already read them through tempered gates at
                # the 0.5 midpoint
                cnp_out = (soft_gt(marked_acc, MTU, soft, 0.1 * MTU)
                           * soft_gt(cnp_timer, cfg.cnp_interval_us, soft,
                                     dt_us))
                # self-referential resets take the DETACHED gate
                # (soft.reset_gate docstring); cnp_out itself keeps full
                # gradients downstream
                cnp_timer = lerp(reset_gate(cnp_out), 0.0, cnp_timer)
                marked_acc = lerp(reset_gate(cnp_out), 0.0, marked_acc)

        # ------------------------------------------------ 9. scheme feedback
        # (CNP routing, pseudo-ACK ledger, proxy brake, slot/budget/channel)
        with jax.named_scope("netsim.feedback"), \
                jax.named_scope("hook.feedback"):
            fb = scheme.feedback(hctx, state, SchemeSignals(
                t=t, active=active, sent=sent, cnp_out=cnp_out,
                cnp_arr=cnp_arr, egress_bytes=egress_bytes,
                q_dst_tot=q_dst_tot, q_leaf=q_leaf, leaf_pfc=leaf_pfc,
                retx_arr=retx_arr, retx_backlog=retx_backlog,
                link_sent=sent_link if multi else None,
                link_arrivals=pipe_arrivals if multi else None,
                link_want=link_want if multi else None,
                link_cap=cap_link if multi else None))

        # ------------------------------------------------ 10. return paths
        with jax.named_scope("netsim.rings"):
            ack_line = state.ack_line.at[ridx].set(drained_leaf * is_inter)
            cnp_line = state.cnp_line.at[ridx].set(fb.cnp_wire)

        # ------------------------------------------------ 11. CC update
        with jax.named_scope("netsim.cc"):
            cc = step_dcqcn(state.cc, fb.cnp_in, send, cfg,
                            rtt_scale=rtt_scale, soft=soft)

        # ------------------------------------------------ 12. FCT
        # the completion latch stays HARD even in soft mode: the INF
        # sentinel makes any blend meaningless (forward exactness is the
        # contract; FCT gradients flow through the byte counters instead)
        with jax.named_scope("netsim.flow"):
            newly_done = (delivered >= total_bytes) & is_unfinished(
                state.done_at_us)
            done_at = jnp.where(newly_done, t_us, state.done_at_us)

        if repair:
            with jax.named_scope("netsim.rings"):
                retx_line = state.retx_line.at[ridx].set(lost)
            with jax.named_scope("netsim.channel"):
                retx_inflight = state.retx_inflight + lost - retx_arr
        else:
            retx_line, retx_inflight = None, None

        new_state = SimState(
            sent=sent, acked=acked, delivered=delivered, done_at_us=done_at,
            cc=cc, cnp_timer=cnp_timer, marked_acc=marked_acc,
            proxy_timer=fb.proxy_timer, proxy_mod=fb.proxy_mod,
            q_src=q_src, q_dst=q_dst, q_leaf=q_leaf,
            pipe=pipe, inflight=inflight,
            ack_line=ack_line, cnp_line=cnp_line,
            pause_line=pause_line, pause_dst=pause_dst, extra=fb.extra,
            chan=chan_new, retx_backlog=(retx_backlog if repair else None),
            retx_line=retx_line, retx_inflight=retx_inflight,
            **({} if not rounds else dict(
                round_r=round_r, round_due_us=round_due, stall_us=stall_us,
                round_late=round_late)),
        )
        # per-flow byte conservation residual: everything the sender emitted
        # is either delivered or sitting in exactly one queue / the pipe —
        # with a channel, also the loss-notification transit, the
        # retransmit backlog, or a jitter deferral buffer
        with jax.named_scope("netsim.accumulators"):
            q_dst_f = jnp.sum(q_dst, axis=0) if multi else q_dst
            residual = sent - delivered - q_src - q_dst_f - q_leaf - inflight
            if repair:
                residual = residual - retx_inflight - retx_backlog
            if impaired:
                held = (jnp.sum(jax.vmap(channel.held_bytes)(chan_new), axis=0)
                        if multi else channel.held_bytes(chan_new))
                residual = residual - held
            cons_err = jnp.max(jnp.abs(residual) / jnp.maximum(sent, 1.0))
            if multi:
                # capacity-weighted pause means keep the scalar trace keys (and
                # the Fig. 3 pause-ratio column) shape-stable across L
                cap_w = link_caps / jnp.maximum(jnp.sum(link_caps), 1e-9)
                pause_trace = jnp.sum(pause_dst * cap_w)
                src_paused_trace = jnp.sum(pause_sig * cap_w)
            else:
                pause_trace, src_paused_trace = pause_dst, pause_sig
            out = {
                "q_src": jnp.sum(q_src),
                "q_dst": q_dst_tot,
                "q_leaf": jnp.sum(q_leaf),
                "pause_dst": pause_trace,
                "src_paused": src_paused_trace,
                "thr_inter": jnp.sum(drained_leaf * is_inter) / dt_s,
                "thr_intra": jnp.sum(drained_leaf * is_intra) / dt_s,
                "cons_err": cons_err,
            }
            if multi:
                out.update({
                    "q_dst_link": q_dst_link,     # [L] per-link dst backlog
                    "link_tx": link_tx,           # [L] bytes launched per link
                    "link_pause": pause_dst,      # [L] per-link PFC state
                })
            if repair:
                # engine-owned channel trace keys (goodput = wire - lost: with
                # selective repair nothing delivered is ever a duplicate)
                backlog_tot = jnp.sum(retx_backlog)
                # granted repair capacity, floored at 1 MB/s: a transport
                # whose window is momentarily exhausted still times out and
                # retransmits eventually — without the floor a zero-rate step
                # inflates the wait estimate to the histogram clamp
                serv_cap = jnp.maximum(
                    jnp.sum(jnp.minimum(retx_bps, nic) * is_inter), 1e6)
                d_us = d_steps.astype(jnp.float32) * dt_us
                # fluid repair-latency estimate for the currently pending
                # backlog: notification transit D + virtual drain time at the
                # granted repair rate + retransmit transit D
                wait_us = jnp.where(
                    backlog_tot > 0,
                    2.0 * d_us + backlog_tot / serv_cap * 1e6,
                    0.0)
                out.update({
                    "chan_wire": jnp.sum(pipe_out),
                    "chan_lost": jnp.sum(lost),
                    "chan_retx": jnp.sum(retx_send),
                    "chan_backlog": backlog_tot,
                    "chan_repair_wait_us": wait_us,
                })
            if has_fail:
                # the live mask as a trace key ([L] at multi; scalar at L=1)
                out["fail_live"] = link_live if multi else link_live[0]
            with jax.named_scope("hook.extra_traces"):
                out.update(scheme.extra_traces(hctx, state))
        return new_state, out

    step.ctx = ctx      # shared per-run quantities for the metric machinery
    return step


def _scan_with_mode(step, scheme, channel, state0, steps: int, mode: str,
                    decimate: int, warm: int):
    """Drive the per-step transition under one of the execution modes.

    Returns ``(final_state, aux)`` where ``aux`` is the [T]-stacked trace
    dict (``full``), the [T//decimate]-stacked trace dict of every
    ``decimate``-th step (``decimate``), a ``MetricAcc`` (``metrics`` —
    no per-step array is ever allocated), or a ``WindowAux`` (``window``
    — the metrics accumulator plus the last-W-steps trace ring and the
    optional event ring, still no [T]-sized array).
    """
    ts = jnp.arange(steps, dtype=jnp.int32)
    if mode == "window":
        # Observability path (docs/observability.md): the event/window
        # machinery wraps AROUND ``step`` — the transition itself is the
        # byte-identical function every other mode runs, so ring-off
        # modes never see any of this code in their jaxpr.
        from repro.netsim.obs.events import (
            engine_event_candidates, init_event_ring, push_events,
        )
        ctx = step.ctx
        w = max(int(ctx.cfg.trace_window_steps), 1)
        slots = int(ctx.cfg.event_ring_slots)
        acc0 = _init_metric_acc(scheme, channel, ctx, state0)
        track_chan = _track_chan(channel, ctx.cfg, ctx.params)
        out_spec = jax.eval_shape(lambda s, t: step(s, t)[1], state0,
                                  jnp.int32(0))
        ring0 = {k: jnp.zeros((w,) + tuple(v.shape), v.dtype)
                 for k, v in out_spec.items()}
        ering0 = init_event_ring(slots) if slots > 0 else None

        def wstep(carry, t):
            state, acc, ring, ev = carry
            new_state, out = step(state, t)
            with jax.named_scope("netsim.accumulators"):
                inc = (t >= warm).astype(jnp.float32)
                acc = _accumulate_engine(acc, out, inc)
                with jax.named_scope("hook.accumulate_metrics"):
                    acc = acc._replace(scheme=scheme.accumulate_metrics(
                        ctx, acc.scheme, new_state, out, inc))
                if track_chan:
                    acc = acc._replace(chan=channel.accumulate_metrics(
                        ctx, acc.chan, new_state, out, inc))
                ring = {k: ring[k].at[jnp.mod(t, w)].set(out[k])
                        for k in ring}
                if ev is not None:
                    cands = list(engine_event_candidates(ctx, state,
                                                         new_state, t))
                    cands += list(scheme.emit_events(ctx, state, new_state,
                                                     out))
                    if len(cands) > slots:
                        raise ValueError(
                            f"event_ring_slots={slots} is smaller than the "
                            f"{len(cands)} per-step event candidates of "
                            f"this run — raise NetConfig.event_ring_slots "
                            f"so one step can never overflow the ring "
                            f"(docs/observability.md)")
                    t_us = t.astype(jnp.float32) * ctx.dt_us
                    ev = push_events(ev, slots, t_us, cands)
            return (new_state, acc, ring, ev), None

        (final, acc, ring, ering), _ = jax.lax.scan(
            wstep, (state0, acc0, ring0, ering0), ts)
        return final, WindowAux(acc=acc, window=ring, events=ering)
    if mode == "metrics":
        acc0 = _init_metric_acc(scheme, channel, step.ctx, state0)
        track_chan = _track_chan(channel, step.ctx.cfg, step.ctx.params)

        def mstep(carry, t):
            state, acc = carry
            state, out = step(state, t)
            with jax.named_scope("netsim.accumulators"):
                inc = (t >= warm).astype(jnp.float32)
                acc = _accumulate_engine(acc, out, inc)
                with jax.named_scope("hook.accumulate_metrics"):
                    acc = acc._replace(scheme=scheme.accumulate_metrics(
                        step.ctx, acc.scheme, state, out, inc))
                if track_chan:
                    acc = acc._replace(chan=channel.accumulate_metrics(
                        step.ctx, acc.chan, state, out, inc))
            return (state, acc), None

        k = step.ctx.cfg.remat_steps
        if k > 1 and steps > k:
            # gradient checkpointing (cfg.remat_steps): rematerialize each
            # k-step block in the backward pass, so jax.grad through the
            # whole scan holds O(T/k + k) residuals instead of O(T) —
            # the memory knob behind long-horizon grad_tune runs. k = 0
            # (the default) keeps the single flat scan below untouched.
            nblocks = steps // k

            @jax.checkpoint
            def block(carry, b):
                carry, _ = jax.lax.scan(
                    mstep, carry, b * k + jnp.arange(k, dtype=jnp.int32))
                return carry, None

            carry, _ = jax.lax.scan(block, (state0, acc0),
                                    jnp.arange(nblocks, dtype=jnp.int32))
            rem = steps - nblocks * k
            if rem:
                carry, _ = jax.lax.scan(
                    mstep, carry,
                    nblocks * k + jnp.arange(rem, dtype=jnp.int32))
            return carry
        (final, acc), _ = jax.lax.scan(mstep, (state0, acc0), ts)
        return final, acc
    if mode == "decimate" and decimate > 1:
        k = decimate
        nblocks = steps // k

        def block(state, b):
            # the inner [k]-stacked traces are transient per outer step:
            # live memory is O(T/k + k), never O(T). Level-like keys keep
            # the block's LAST sample; per-step byte counts
            # (DECIMATE_SUM_KEYS) keep the block SUM so time-normalized
            # rate columns stay exact at any decimation.
            state, outs = jax.lax.scan(step, state,
                                       b * k + jnp.arange(k, dtype=jnp.int32))
            with jax.named_scope("netsim.accumulators"):
                return state, {key: (jnp.sum(v, axis=0)
                                     if key in DECIMATE_SUM_KEYS else v[-1])
                               for key, v in outs.items()}

        final, traces = jax.lax.scan(block, state0,
                                     jnp.arange(nblocks, dtype=jnp.int32))
        rem = steps - nblocks * k
        if rem:
            final, _ = jax.lax.scan(
                step, final, nblocks * k + jnp.arange(rem, dtype=jnp.int32))
        return final, traces
    return jax.lax.scan(step, state0, ts)


def _check_trace_mode(trace_mode: str, decimate: int) -> None:
    if trace_mode not in TRACE_MODES:
        raise ValueError(f"unknown trace_mode {trace_mode!r}; "
                         f"expected one of {TRACE_MODES}")
    if decimate < 1:
        raise ValueError(f"decimate must be >= 1, got {decimate}")


def simulate(cfg: NetConfig, workload, scheme,
             horizon_us: Optional[float] = None, period_slots: int = 0,
             delay_pad: int = 0, history_slots: int = 0,
             trace_mode: str = "full", decimate: int = 1, channel=None):
    """Run one simulation; returns (final_state, traces dict of [T] arrays)
    — or ``(final_state, MetricAcc)`` under ``trace_mode="metrics"``.

    ``workload``: a ``Workload`` (or prebuilt ``WorkloadParams``);
    ``scheme``: a registered name or ``Scheme`` instance; ``channel``: a
    registered channel-model name or ``ChannelModel`` instance (None =
    ``"ideal"`` — names stay first-class here, mirroring the grid APIs).
    ``delay_pad``/``history_slots`` override the static ring sizes (0 = size
    for ``cfg``) — pass the batch padding to reproduce a ``simulate_batch``
    cell bit-for-bit. ``trace_mode``/``decimate``: see the module docstring.
    """
    if isinstance(scheme, str):
        import warnings
        warnings.warn(
            "passing a scheme name string to simulate() is deprecated; "
            "resolve it with repro.netsim.schemes.get_scheme(name) (names "
            "remain first-class in the batched sweep APIs)",
            DeprecationWarning, stacklevel=2)
    scheme = get_scheme(scheme)
    channel = get_channel_model(channel)
    _check_trace_mode(trace_mode, decimate)
    steps = cfg.horizon_steps(horizon_us)
    wlp = workload if isinstance(workload, WorkloadParams) \
        else workload.params()
    wlp = WorkloadParams(*(jnp.asarray(v) for v in wlp))
    if cfg.is_multisite:
        from repro.netsim.topology import validate_site_endpoints
        validate_site_endpoints(cfg, wlp)   # host-side: stalls fail early
    return _run_traced(cfg, wlp, scheme, steps, period_slots,
                       delay_pad, history_slots, trace_mode, decimate,
                       int(steps * WARMUP_FRAC), channel,
                       rounds=has_rounds(wlp))


@partial(jax.jit, static_argnames=("scheme", "steps", "period_slots", "cfg",
                                   "delay_pad", "history_slots", "mode",
                                   "decimate", "warm", "channel", "rounds"))
def _run_traced(cfg, wlp, scheme, steps, period_slots,
                delay_pad=0, history_slots=0, mode="full", decimate=1,
                warm=0, channel=None, rounds=False):
    channel = get_channel_model(channel)
    f = wlp.is_inter.shape[0]
    state0 = init_state(cfg, f, delay_pad=delay_pad,
                        history_slots=history_slots, scheme=scheme,
                        channel=channel, round_wl=wlp if rounds else None)
    step = make_step_fn(cfg, wlp, scheme, period_slots,
                        delay_pad=delay_pad, channel=channel, rounds=rounds)
    return _scan_with_mode(step, scheme, channel, state0, steps, mode,
                           decimate, warm)


# ---------------------------------------------------------------------------
# Batched scenario engine
# ---------------------------------------------------------------------------


def batch_padding(cfgs: Sequence[NetConfig]):
    """(delay_pad, history_slots) covering every scenario in the grid —
    the static ring sizes shared by all cells of a batch.

    Control-channel rings are sized ``delay_pad + proc_steps(template)``
    inside the batched program, and a cell's OWN processing delay derives
    from its (traced) ``slot_us`` — so the pad absorbs any excess of a
    cell's proc steps over the template's, and the history covers the
    longest control window any cell needs. At a uniform default
    ``slot_us`` both reduce to the historical sizes."""
    tmpl = batch_template(cfgs)
    delay_pad = (max(_delay_steps(c) for c in cfgs)
                 + max(0, max(_proc_steps(c) for c in cfgs)
                       - _proc_steps(tmpl)))
    return delay_pad, max(default_history_slots(c) for c in cfgs)


def shard_scenario_axis(params: NetParams, wlp: WorkloadParams,
                        devices: Optional[Sequence] = None):
    """Place stacked [B]-leading scenario leaves so the batch axis is split
    across ``devices`` (default: all of ``jax.devices()``). The computation
    is embarrassingly parallel along [B], so a jit over the sharded inputs
    partitions the whole vmapped scan with zero cross-device traffic.
    Requires the device count to divide B (even split); no-op on a single
    device."""
    devices = list(devices) if devices is not None else jax.devices()
    if len(devices) <= 1:
        return params, wlp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    b = int(np.shape(params.one_way_delay_us)[0])
    if b % len(devices):
        raise ValueError(
            f"shard_scenario_axis: {len(devices)} devices do not evenly "
            f"split a batch of {b} scenarios — pad the batch to a device "
            f"multiple (runner launch plans do this automatically)")
    mesh = Mesh(np.array(devices), ("scenario",))

    def put(x):
        x = jnp.asarray(x)
        spec = PartitionSpec("scenario", *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(put, params), jax.tree.map(put, wlp)


def simulate_batch(cfgs: Sequence[NetConfig], workload, scheme,
                   horizon_us: Optional[float] = None, period_slots: int = 0,
                   trace_mode: str = "full", decimate: int = 1,
                   delay_pad: int = 0, history_slots: int = 0,
                   devices: Optional[Sequence] = None,
                   warm_steps: Optional[int] = None, channel=None,
                   profile: Optional[dict] = None,
                   rounds: Optional[bool] = None):
    """Run a whole scenario grid as ONE vmapped computation.

    ``cfgs``: the per-scenario configs (distance / capacity / buffer grids);
    every structural field (dt, slot layout) must match — the per-scenario
    scalars are extracted into a stacked ``NetParams`` pytree and traced.
    ``workload``: one shared ``Workload``, a per-scenario sequence of
    ``Workload``s (padded to the batch-max flow count, see
    ``WorkloadParams``), or a prebuilt [B, F] ``WorkloadParams`` — the
    workload axis is vmapped jointly with the config axis.
    One compile per (scheme, grid-shape); every cell runs in a single
    device launch (sharded across devices whenever the device count
    evenly splits B). Returns (final_states, traces) with a leading [B]
    axis on every leaf — or ``(final_states, MetricAcc)`` under
    ``trace_mode="metrics"`` (O(B) device memory, no [B, T] arrays).
    ``delay_pad``/``history_slots`` set MINIMUM static ring sizes (so
    chunked launches of one big grid share a compiled program);
    ``warm_steps`` overrides the warm-up cutoff of the streaming
    reductions (default ``WARMUP_FRAC`` of the horizon); ``channel`` is a
    registered channel-model name or instance (None = ``"ideal"``) —
    impairment KNOBS are traced ``NetParams`` leaves, so a loss x jitter
    grid still compiles once per scheme. ``profile``: pass a dict to route
    the launch through the AOT profiling path
    (``repro.netsim.obs.profiled_traced_batch``) — it is filled in place
    with the compile/execute wall-clock split and XLA memory figures
    (docs/observability.md). ``rounds``: the static round-gate switch
    (docs/rounds.md); None derives it from this batch's workload — a
    chunked grid passes the whole grid's, so its launches share a program.
    """
    cfgs = list(cfgs)
    if not cfgs:
        raise ValueError("simulate_batch: empty config batch")
    scheme = get_scheme(scheme)
    channel = get_channel_model(channel)
    _check_trace_mode(trace_mode, decimate)
    tmpl = batch_template(cfgs)
    steps = tmpl.horizon_steps(
        horizon_us if horizon_us is not None
        else max(c.horizon_us for c in cfgs))
    warm = int(steps * WARMUP_FRAC) if warm_steps is None else int(warm_steps)
    dp, hs = batch_padding(cfgs)
    delay_pad, history_slots = max(delay_pad, dp), max(history_slots, hs)
    with span("netsim.stack", profile):
        params = stack_net_params(cfgs)
        wlp = as_workload_batch(workload, len(cfgs))
        if rounds is None:
            rounds = has_rounds(wlp)
    if tmpl.is_multisite:
        from repro.netsim.topology import validate_site_endpoints
        validate_site_endpoints(tmpl, wlp)  # host-side: stalls fail early
    # fresh host-backed buffers: the jitted runner donates its batch inputs
    # (harmless on CPU where donation is skipped), so caller-held device
    # arrays must never be passed through as-is. One explicit device is
    # honoured by committing the inputs to it (the launch runs there).
    devs = list(devices) if devices is not None else jax.devices()
    put = (partial(jax.device_put, device=devs[0])
           if devices is not None and len(devs) == 1 else jnp.asarray)
    b = len(cfgs)
    pad = (-b) % len(devs) if len(devs) > 1 else 0
    with span("netsim.transfer", profile):
        params = NetParams(*(put(np.asarray(v)) for v in params))
        wlp = WorkloadParams(*(put(np.asarray(v)) for v in wlp))
        if pad:
            # pad-and-shard: replicate the last scenario until the device
            # count divides the batch, run sharded, then strip the padded
            # rows from every output leaf — a ragged batch no longer falls
            # back silently to a single-device launch
            def rep(x):
                return jnp.concatenate([x, jnp.repeat(x[-1:], pad, axis=0)],
                                       axis=0)
            params = jax.tree.map(rep, params)
            wlp = jax.tree.map(rep, wlp)
        if len(devs) > 1:
            params, wlp = shard_scenario_axis(params, wlp, devs)
    with span("netsim.launch"):
        if profile is not None:
            from repro.netsim.obs.profile import profiled_traced_batch
            profile.update(n_cells=b, pad=pad, n_devices=len(devs),
                           steps=steps, trace_mode=trace_mode)
            out = profiled_traced_batch(tmpl, params, wlp, scheme, steps,
                                        period_slots, delay_pad,
                                        history_slots, trace_mode, decimate,
                                        warm, channel, profile,
                                        rounds=rounds)
        else:
            out = _run_traced_batch(tmpl, params, wlp, scheme, steps,
                                    period_slots, delay_pad, history_slots,
                                    trace_mode, decimate, warm, channel,
                                    rounds=rounds)
    if pad:
        with span("netsim.rows", profile):
            out = jax.tree.map(lambda x: x[:b], out)
    return out


def _run_traced_batch_impl(cfg, params, wlp, scheme, steps, period_slots,
                           delay_pad, history_slots, mode="full",
                           decimate=1, warm=0, channel=None, rounds=False):
    channel = get_channel_model(channel)
    f = wlp.is_inter.shape[-1]

    def one_scenario(p, w):
        state0 = init_state(cfg, f, params=p, delay_pad=delay_pad,
                            history_slots=history_slots, scheme=scheme,
                            channel=channel,
                            round_wl=w if rounds else None)
        step = make_step_fn(cfg, w, scheme, period_slots,
                            params=p, delay_pad=delay_pad, channel=channel,
                            rounds=rounds)
        return _scan_with_mode(step, scheme, channel, state0, steps, mode,
                               decimate, warm)

    return jax.vmap(one_scenario)(params, wlp)


def _jit_traced_batch(donate_argnums=(), fun=_run_traced_batch_impl):
    """The batch runner jitted with its static arguments; ``donate_argnums``
    ``(1, 2)`` donates the stacked (params, workload) inputs. ``fun`` is
    the runner or a wrapper with its signature (a new wrapper shares none
    of JAX's in-process trace and lowering caches)."""
    return partial(jax.jit,
                   static_argnames=("cfg", "scheme", "steps", "period_slots",
                                    "delay_pad", "history_slots", "mode",
                                    "decimate", "warm", "channel",
                                    "rounds"),
                   donate_argnums=donate_argnums)(fun)


def _donated_inputs() -> tuple:
    """The stacked batch inputs are donated so giant-grid chunk launches
    reuse their buffers in place (XLA ignores donation on CPU and would
    warn about it, hence none there). Initializes the backend."""
    return () if jax.default_backend() == "cpu" else (1, 2)


@lru_cache(maxsize=1)
def _jitted_traced_batch():
    """Build the jitted batch runner on FIRST use, not at import: the
    donation decision needs ``jax.default_backend()``, which initializes
    the backend — importing ``repro.netsim`` must never do that."""
    return _jit_traced_batch(_donated_inputs())


def _run_traced_batch(*args, **kwargs):
    return _jitted_traced_batch()(*args, **kwargs)


_run_traced_batch._cache_size = lambda: _jitted_traced_batch()._cache_size()
