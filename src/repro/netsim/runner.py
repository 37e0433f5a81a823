"""Experiment runner: simulate + extract the paper's Fig. 3 metrics.

The batched path is canonical: every grid — heterogeneous configs AND
workloads (``Scenario``) — executes through a *launch plan*: the scenario
axis is stacked once, split into equal-size chunks (auto-sized so a launch's
trace block stays in bounded memory), and each (scheme, chunk) pair becomes
one vmapped device launch. All chunks of a grid share one compiled program
(the last chunk is padded by repeating its final cell) and shard across
devices whenever the chunk divides the device count.

Execution modes (``trace_mode`` — see ``fluid.py``):
  * ``full``     [B, T] traces materialize; metrics come from one vectorized
                 numpy pass (``_metrics_batch``).
  * ``decimate`` every k-th step materializes; same extractor, approximate
                 means/percentiles.
  * ``metrics``  nothing per-step ever exists: the scan carry streams the
                 Fig. 3 reductions (``MetricAcc``) and only O(B) accumulators
                 + final states transfer to host (``_metrics_streaming``).
                 Schemes append their own columns via
                 ``Scheme.finalize_metrics``.

``run_experiment`` is a thin B=1 delegation onto the same batch-wide
extractors — there is exactly one copy of the Fig. 3 metric definitions.
Passing a scheme NAME to the single-cell entrypoints is deprecated (resolve
through ``repro.netsim.schemes.get_scheme``); names remain first-class for
the grid APIs, where ``schemes=("dcqcn", "matchrdma")`` is the natural
spelling.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import warnings
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.config.base import NetConfig, batch_template
from repro.netsim.channel import get_channel_model
from repro.netsim.obs.profile import span, write_manifest
from repro.netsim.fluid import (
    WARMUP_FRAC, MetricAcc, batch_padding, hist_quantile, is_unfinished,
    simulate_batch,
)
from repro.netsim.schemes import get_scheme
from repro.netsim.workload import (
    Workload, WorkloadParams, as_workload_batch, has_rounds, is_unbounded,
)

# Auto-chunk targets of the launch plan: a full-trace launch keeps its
# materialized [B_chunk, T] block under ~256 MB of f32; a streaming launch
# is O(B) anyway and only caps per-launch compile/host-row cost.
MAX_TRACE_FLOATS = 64 * 1024 * 1024
METRICS_CHUNK_CELLS = 4096
_TRACE_KEYS_EST = 12        # 8 engine trace keys + scheme extras (estimate)


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One cell of the unified scenario axis: a network config AND the
    workload that runs over it. ``sweep_grid`` accepts heterogeneous
    ``Scenario`` grids and executes them in one launch plan per scheme."""
    net: NetConfig
    workload: Workload


def _warn_string_scheme(fn_name: str) -> None:
    warnings.warn(
        f"passing a scheme name string to {fn_name}() is deprecated; "
        f"resolve it with repro.netsim.schemes.get_scheme(name) (or use "
        f"the batched sweep_grid API, where names remain first-class)",
        DeprecationWarning, stacklevel=3)


# ---------------------------------------------------------------------------
# Metric extraction (batch-wide; the ONLY copies of the Fig. 3 metric set)
# ---------------------------------------------------------------------------


def _flow_metrics(wl: WorkloadParams, final_np: dict):
    """[B] goodput / avg-FCT / completion from final state + workload
    leaves — per-flow quantities that never needed per-step traces. Padded
    flows carry ``is_inter == 0`` and ``total_bytes == 0`` and drop out of
    every mask."""
    is_inter = np.asarray(wl.is_inter) > 0                         # [B, F]
    delivered = final_np["delivered"]
    goodput = np.where(is_inter, delivered, 0.0).sum(axis=1)

    total = np.asarray(wl.total_bytes)
    start = np.asarray(wl.start_us)
    done_at = final_np["done_at_us"]
    # the shared sentinel helpers — NOT re-derived magic literals, so both
    # metric paths (and the engine) can never drift apart on what counts
    # as a finite flow / a completed flow
    finite = is_inter & ~is_unbounded(total)                       # [B, F]
    fct = done_at - start
    completed = finite & ~is_unfinished(done_at)
    n_finite = finite.sum(axis=1)
    n_completed = completed.sum(axis=1)
    sum_fct = np.where(completed, fct, 0.0).sum(axis=1)
    avg_fct = np.where(n_completed > 0,
                       sum_fct / np.maximum(n_completed, 1), np.inf)
    avg_fct = np.where(n_finite > 0, avg_fct, np.nan)
    completion = np.where(n_finite > 0,
                          n_completed / np.maximum(n_finite, 1), 1.0)
    return goodput, avg_fct, completion


def _round_cols(wl: WorkloadParams, final_np: dict, sim_us: float) -> dict:
    """[B] round columns of a rounds-on grid (docs/rounds.md), from the
    final state: over the inter-DC flows that run in rounds, the mean
    rounds' worth delivered (``round_progress``) and the mean share of the
    simulated time spent due but undelivered (``round_stall_frac``). Both
    move continuously with time, so a release one step earlier or later
    moves them by one step's worth at most."""
    b = np.asarray(wl.round_bytes, np.float64)
    mask = (np.asarray(wl.is_inter) > 0) & (b > 0)             # [B, F]
    n = np.maximum(mask.sum(axis=1), 1)
    got = final_np["delivered"] / np.maximum(b, 1.0)
    stall = final_np["stall_us"] / sim_us
    return {"round_progress": np.where(mask, got, 0.0).sum(axis=1) / n,
            "round_stall_frac": np.where(mask, stall, 0.0).sum(axis=1) / n}


def _assemble_rows(cfgs: Sequence[NetConfig], scheme_name: str,
                   cols: dict, extra: Optional[dict] = None
                   ) -> List[Dict[str, float]]:
    """[B]-column dicts -> the per-cell row list of a sweep."""
    rows = []
    for i, cfg in enumerate(cfgs):
        row = {"scheme": scheme_name, "distance_km": cfg.distance_km}
        row.update({k: float(v[i]) for k, v in cols.items()})
        if extra:
            row.update({k: float(np.asarray(v)[i]) for k, v in extra.items()})
        rows.append(row)
    return rows


def _channel_cols_from_traces(traces_np: dict, warm: int, dt_s: float,
                              decimate: int = 1) -> dict:
    """The channel metric columns from materialized ``chan_*`` traces —
    the full/decimate-mode twin of ``ChannelModel.finalize_metrics`` (same
    column set, so impairment sweeps agree across trace modes).

    Rate columns normalize by SIMULATED time, not sample count: a
    decimated trace holds ``steps/decimate`` samples, each a block SUM of
    ``decimate`` steps' bytes (``fluid.DECIMATE_SUM_KEYS``), so
    ``n_samples * decimate * dt_s`` is the window the bytes accumulated
    over and the Gbps columns agree exactly with the streamed path at any
    decimation."""
    wire = traces_np["chan_wire"][:, warm:].astype(np.float64)
    lost = traces_np["chan_lost"][:, warm:].astype(np.float64)
    retx = traces_np["chan_retx"][:, warm:].astype(np.float64)
    wait = traces_np["chan_repair_wait_us"][:, warm:]
    per_s = 1.0 / (max(wire.shape[1], 1) * max(decimate, 1) * dt_s)
    # p99 over steps with a repair actually pending (matches the streamed
    # histogram, which only counts wait > 0 samples)
    p99 = np.zeros(wire.shape[0])
    for i in range(wire.shape[0]):
        pending = wait[i][wait[i] > 0]
        p99[i] = np.percentile(pending, 99) if pending.size else 0.0
    return {
        "goodput_gbps": (wire.sum(axis=1) - lost.sum(axis=1))
        * per_s * 8.0 / 1e9,
        "wire_gbps": wire.sum(axis=1) * per_s * 8.0 / 1e9,
        "retx_frac": retx.sum(axis=1) / np.maximum(wire.sum(axis=1), 1.0),
        "p99_repair_latency_us": p99,
    }


def _failover_cols_from_traces(cfgs: Sequence[NetConfig], traces_np: dict,
                               decimate: int = 1) -> dict:
    """Failover scoring columns from the ``thr_inter`` time series of a
    grid whose cells carry a failure schedule (``cfg.failure_len > 0``):

      ``failover_collapse_frac``  1 - (mean inter-DC throughput DURING the
                                  cell's outage span) / (mean before the
                                  first down edge), clipped to [0, 1] —
                                  0 = the scheme rode through the outage,
                                  1 = goodput fully collapsed.
      ``failover_recovery_us``    time from the LAST up edge until the
                                  throughput first regains 90 % of its
                                  pre-outage mean (clamped to the end of
                                  the trace when it never does).

    The outage span of a cell is [min down_at, max up_at] over its REAL
    windows (``up > down``; padding (0, 0) windows are ignored). Cells with
    no real window — the all-up control rows of a failover grid — report 0
    for both columns. Sample j of a decimated trace is the engine value at
    step ``(j+1)*decimate - 1``, so recovery times stay decimation-exact.
    Full/decimate modes only (``trace_mode="metrics"`` streams no per-step
    series to recover a timeline from)."""
    thr = np.asarray(traces_np["thr_inter"], np.float64)       # [B, S]
    n_cells, n_samples = thr.shape
    t_us = (np.arange(n_samples, dtype=np.float64) + 1.0) \
        * max(decimate, 1) * cfgs[0].dt_us
    collapse = np.zeros(n_cells)
    recovery = np.zeros(n_cells)
    for i, cfg in enumerate(cfgs[:n_cells]):
        fa = np.asarray(cfg.failure_array(), np.float64)       # [L, W, 2]
        real = fa[..., 1] > fa[..., 0]
        if not real.any():
            continue
        down = fa[..., 0][real].min()
        up = fa[..., 1][real].max()
        pre = thr[i][t_us < down]
        base = pre.mean() if pre.size else 0.0
        if base <= 0.0:
            continue
        span = thr[i][(t_us >= down) & (t_us < up)]
        during = span.mean() if span.size else 0.0
        collapse[i] = min(max(1.0 - during / base, 0.0), 1.0)
        post = t_us >= up
        rec = post & (thr[i] >= 0.9 * base)
        if rec.any():
            recovery[i] = t_us[rec].min() - up
        elif post.any():
            recovery[i] = max(t_us[-1] - up, 0.0)
    return {"failover_collapse_frac": collapse,
            "failover_recovery_us": recovery}


def _metrics_batch(cfgs: Sequence[NetConfig], wl: WorkloadParams,
                   scheme_name: str, final_np: dict, traces_np: dict,
                   decimate: int = 1, sim_us: Optional[float] = None
                   ) -> List[Dict[str, float]]:
    """Fig. 3 metric set from materialized [B, T] traces in ONE vectorized
    pass (``trace_mode="full"``/``"decimate"``). ``sim_us``: the simulated
    time, for the round columns of a rounds-on grid (``final_np`` then
    carries ``stall_us``)."""
    steps = traces_np["q_dst"].shape[1]
    warm = int(steps * WARMUP_FRAC)

    q_dst = traces_np["q_dst"]
    goodput, avg_fct, completion = _flow_metrics(wl, final_np)
    cols = {
        "throughput_gbps":
            traces_np["thr_inter"][:, warm:].mean(axis=1) * 8.0 / 1e9,
        "goodput_bytes": goodput,
        "peak_buffer_mb": q_dst.max(axis=1) / 1e6,
        "mean_buffer_mb": q_dst[:, warm:].mean(axis=1) / 1e6,
        "p99_buffer_mb": np.percentile(q_dst[:, warm:], 99, axis=1) / 1e6,
        "pause_ratio": traces_np["pause_dst"][:, warm:].mean(axis=1),
        "avg_fct_us": avg_fct,
        "completion_frac": completion,
        "intra_thr_gbps":
            traces_np["thr_intra"][:, warm:].mean(axis=1) * 8.0 / 1e9,
    }
    if "chan_wire" in traces_np:
        cols.update(_channel_cols_from_traces(
            traces_np, warm, cfgs[0].dt_us * 1e-6, decimate))
    if cfgs[0].failure_len > 0:
        cols.update(_failover_cols_from_traces(cfgs, traces_np, decimate))
    if "stall_us" in final_np:
        cols.update(_round_cols(wl, final_np, sim_us))
    return _assemble_rows(cfgs, scheme_name, cols)


def _metrics_streaming(cfgs: Sequence[NetConfig], wl: WorkloadParams,
                       scheme, channel, final_np: dict, acc: MetricAcc,
                       steps: int, warm: int) -> List[Dict[str, float]]:
    """The same Fig. 3 metric set from the O(B) streamed accumulators
    (``trace_mode="metrics"`` — no [B, T] array ever existed). p99 comes
    from inverting the fixed-bin log-histogram (bounded relative error);
    everything else is exact up to summation order."""
    n_warm = max(steps - warm, 1)
    sums = {k: np.asarray(v, np.float64) for k, v in acc.sum_s.items()}
    goodput, avg_fct, completion = _flow_metrics(wl, final_np)
    cols = {
        "throughput_gbps": sums["thr_inter"] / n_warm * 8.0 / 1e9,
        "goodput_bytes": goodput,
        "peak_buffer_mb": np.asarray(acc.maxes["q_dst"]) / 1e6,
        "mean_buffer_mb": sums["q_dst"] / n_warm / 1e6,
        "p99_buffer_mb": hist_quantile(acc.hist, 0.99) / 1e6,
        "pause_ratio": sums["pause_dst"] / n_warm,
        "avg_fct_us": avg_fct,
        "completion_frac": completion,
        "intra_thr_gbps": sums["thr_intra"] / n_warm * 8.0 / 1e9,
    }
    if "stall_us" in final_np:
        cols.update(_round_cols(wl, final_np, steps * cfgs[0].dt_us))
    extra = scheme.finalize_metrics(
        jax.tree.map(np.asarray, acc.scheme), steps, n_warm)
    # the channel accumulator also streams under the IDEAL channel when a
    # failure schedule is active (outage losses ride the chan_* keys —
    # fluid._track_chan), so finalize under the same condition
    if not channel.is_ideal or cfgs[0].failure_len > 0:
        extra = dict(extra or {})
        extra.update(channel.finalize_metrics(
            jax.tree.map(np.asarray, acc.chan), steps, n_warm,
            cfgs[0].dt_us * 1e-6))
    return _assemble_rows(cfgs, scheme.name, cols, extra)


# ---------------------------------------------------------------------------
# The launch plan: (scheme x chunk) device launches over a stacked grid
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Launch:
    """One device launch of a sweep's plan: ``scheme`` over grid cells
    [lo, hi), padded up to ``pad_to`` cells so every chunk of a grid shares
    one compiled program (padding rows are dropped from the output)."""
    scheme: object
    lo: int
    hi: int
    pad_to: int


def chunk_cells(steps: int, trace_mode: str = "full", decimate: int = 1,
                chunk_cells: Optional[int] = None,
                n_devices: int = 1, num_links: int = 1,
                schedule_floats: int = 0) -> int:
    """Scenario cells per device launch of a sweep's plan.

    Returns the explicit ``chunk_cells`` override when given, else the
    bounded-memory auto size: in ``full``/``decimate`` modes the chunk is
    sized so one launch's materialized trace block stays under
    ``MAX_TRACE_FLOATS`` f32 values (~256 MB) — multi-link grids
    (``num_links > 1``) add per-link [L] trace keys, so their per-step
    float estimate grows with L and the chunk shrinks accordingly; in
    ``metrics`` (and ``window`` — O(B·W) with a small fixed W) mode the
    launch is O(B) anyway and the flat ``METRICS_CHUNK_CELLS`` ceiling
    only caps per-launch compile/host-row cost. ``schedule_floats`` is the per-cell resident footprint of a
    ``trace_replay`` schedule table (``num_paths * schedule_len * 3``
    f32 values — the stacked ``chan_schedule`` leaf rides along with
    every launch), folded into the per-cell budget in every mode so a
    long recorded trace shrinks the chunk instead of blowing the launch
    past the memory target. The result is rounded up to a multiple of
    ``n_devices`` so chunked grids still shard the scenario axis evenly.
    (Not clamped to the grid size — ``_plan_launches`` caps the final
    chunk at the cell count and pads the trailing chunk so every launch
    shares one compiled program.)
    """
    if chunk_cells is None:
        if trace_mode in ("metrics", "window"):
            chunk_cells = METRICS_CHUNK_CELLS
            if schedule_floats > 0:
                chunk_cells = min(
                    chunk_cells,
                    max(MAX_TRACE_FLOATS // schedule_floats, 1))
        else:
            t = max(steps // max(decimate, 1), 1)
            # q_dst_link / link_tx / link_pause are [L] per step at L>1
            keys = _TRACE_KEYS_EST + (3 * num_links if num_links > 1 else 0)
            chunk_cells = max(
                MAX_TRACE_FLOATS // (t * keys + max(schedule_floats, 0)), 1)
    chunk_cells = max(int(chunk_cells), 1)
    if n_devices > 1:
        chunk_cells = -(-chunk_cells // n_devices) * n_devices
    return chunk_cells


# non-deprecated private alias: inside run_experiment_batch / sweep_grid the
# ``chunk_cells`` KEYWORD shadows the module-level function
_auto_chunk_cells = chunk_cells


def _sched_floats(cfg: NetConfig) -> int:
    """Per-cell f32 footprint of the cfg's resident schedule tables: the
    ``trace_replay`` channel schedule ([L, W, 3]) plus the failure-window
    table ([L, W', 2]) — both stacked leaves ride along with every launch,
    so long schedules shrink the auto chunk instead of blowing the memory
    target."""
    return (cfg.num_paths * cfg.schedule_len * 3
            + cfg.num_paths * cfg.failure_len * 2)


def __getattr__(name: str):
    if name == "_chunk_cells":
        warnings.warn(
            "repro.netsim.runner._chunk_cells is deprecated (it was a "
            "pre-PR 4 private alias) and will be removed in a future PR; "
            "use runner.chunk_cells instead",
            DeprecationWarning, stacklevel=2)
        return chunk_cells
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")


def _plan_launches(n_cells: int, schemes: Sequence, chunk: int,
                   n_devices: int = 1) -> List[_Launch]:
    """Flatten (scheme x chunk) into the launch list — the per-scheme
    Python loop of the old sweep path, folded into explicit plan entries.
    EVERY launch — including the single-launch case of a grid smaller than
    one chunk — pads to a device multiple, so the scenario axis always
    splits evenly across devices and ``shard_scenario_axis`` never sees an
    odd batch (padding rows are dropped)."""
    pad_to = min(chunk, n_cells)
    if n_devices > 1:
        pad_to = -(-pad_to // n_devices) * n_devices
    return [_Launch(s, lo, min(lo + chunk, n_cells), pad_to)
            for s in schemes for lo in range(0, n_cells, chunk)]


def _pad_chunk(cfgs, wlp: WorkloadParams, n: int):
    """Pad a trailing chunk to ``n`` cells by repeating its last cell (the
    duplicate rows are dropped after the launch)."""
    pad = n - len(cfgs)
    if pad <= 0:
        return cfgs, wlp
    leaves = [np.asarray(v) for v in wlp]
    wlp = WorkloadParams(*(np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
                           for v in leaves))
    return list(cfgs) + [cfgs[-1]] * pad, wlp


def _grid_static(cfgs, horizon_us, delay_pad: int, history_slots: int,
                 wlp: WorkloadParams):
    """The grid-wide static quantities every launch of a plan shares —
    resolved horizon, scan length, warm cutoff, ring paddings, the round
    gate's switch (docs/rounds.md) — computed ONCE over the WHOLE grid.
    Chunks must never re-derive them from their own sub-grid, or chunked
    launches would stop sharing one compiled program (and streaming
    normalizers would drift from the scan length)."""
    dp, hs = batch_padding(cfgs)
    horizon = (horizon_us if horizon_us is not None
               else max(c.horizon_us for c in cfgs))
    steps = batch_template(cfgs).horizon_steps(horizon)
    return (horizon, steps, int(steps * WARMUP_FRAC),
            max(delay_pad, dp), max(history_slots, hs), has_rounds(wlp))


# ---------------------------------------------------------------------------
# Runner hardening: conservation guard, finite guard, checkpoint/resume, OOM
# backoff (docs/failures.md)
# ---------------------------------------------------------------------------


class ConservationError(RuntimeError):
    """``strict_conservation``: a cell's byte-conservation residual
    (``cons_err`` — max over flows of |residual| / max(sent, 1)) exceeded
    the tolerance. Carries the GRID-ORDER ``cell`` index and the engine
    ``step`` of the first violation (``None`` under ``trace_mode="metrics"``,
    where only the running max streams)."""

    def __init__(self, scheme_name: str, cell: int, step: Optional[int],
                 err: float, tol: float):
        self.scheme_name, self.cell, self.step = scheme_name, cell, step
        self.err, self.tol = err, tol
        where = (f"step {step}" if step is not None
                 else "step unknown (trace_mode='metrics' streams only the "
                      "running max — rerun with trace_mode='full' to "
                      "localize)")
        super().__init__(
            f"strict_conservation: scheme {scheme_name!r} violated byte "
            f"conservation at cell {cell}, {where}: "
            f"|residual|/sent = {err:.3e} > tol {tol:.1e}")


def _check_conservation(scheme_name: str, aux, lo: int, n_real: int,
                        trace_mode: str, decimate: int, tol: float) -> None:
    """First ``cons_err > tol`` violation -> ``ConservationError`` with
    grid-order (cell, step) coordinates. Sample j of a decimated trace is
    the engine value AT step ``(j+1)*decimate - 1``, so reported steps are
    exact at any decimation; metrics mode only streams the per-cell running
    max, so its step is ``None``."""
    if trace_mode in ("metrics", "window"):
        maxes = aux.maxes if trace_mode == "metrics" else aux.acc.maxes
        m = np.asarray(maxes["cons_err"])[:n_real]
        bad = m > tol
        if bad.any():
            i = int(np.argmax(bad))
            raise ConservationError(scheme_name, lo + i, None,
                                    float(m[i]), tol)
        return
    k = decimate if trace_mode == "decimate" else 1
    cons = np.asarray(aux["cons_err"])[:n_real]
    bad = cons > tol
    if bad.any():
        i, j = np.argwhere(bad)[0]
        raise ConservationError(scheme_name, lo + int(i),
                                (int(j) + 1) * k - 1,
                                float(cons[i, j]), tol)


# ``avg_fct_us`` is exempt from the finite guard: inf (no flow finished)
# and nan (no finite flow in the cell) are its documented in-band sentinels.
_NONFINITE_EXEMPT = ("avg_fct_us",)


def _guard_nonfinite(rows: List[dict], lo: int,
                     on_nonfinite: str) -> List[dict]:
    """Per-cell finite guard. ``"keep"`` passes rows through untouched;
    ``"quarantine"`` replaces a diverged cell's row with a structured
    failure record (``failed=True`` + the offending column names + the
    grid-order cell index) so one NaN cell cannot poison a sweep's
    aggregation; ``"raise"`` aborts naming the cell and columns."""
    if on_nonfinite == "keep":
        return rows
    out = []
    for i, row in enumerate(rows):
        bad = sorted(k for k, v in row.items()
                     if k not in _NONFINITE_EXEMPT
                     and isinstance(v, float) and not np.isfinite(v))
        if not bad:
            out.append(row)
            continue
        cell = lo + i
        if on_nonfinite == "raise":
            raise RuntimeError(
                f"non-finite metrics at cell {cell} "
                f"(scheme {row.get('scheme')!r}): columns {bad} — rerun "
                f"with on_nonfinite='quarantine' to skip diverged cells")
        out.append({"scheme": row.get("scheme"),
                    "distance_km": row.get("distance_km", float("nan")),
                    "cell_index": cell, "failed": True,
                    "nonfinite_cols": bad})
    return out


def _plan_fingerprint(plan, cfgs, wlp_np, grid_static, period_slots,
                      trace_mode, decimate, channel) -> str:
    """Digest of everything that determines a plan's rows — configs,
    workload leaves, grid statics, modes, channel, scheme set. A resume
    against a checkpoint directory written under a DIFFERENT fingerprint
    refuses loudly instead of silently mixing two sweeps' rows."""
    h = hashlib.sha256()
    for c in cfgs:
        h.update(repr(c).encode())
    for leaf in wlp_np:
        a = np.asarray(leaf)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    names = tuple(sorted({launch.scheme.name for launch in plan}))
    h.update(repr((tuple(grid_static), int(period_slots), trace_mode,
                   int(decimate), getattr(channel, "name", None),
                   names)).encode())
    return h.hexdigest()


def _checkpoint_path(checkpoint_dir: str, launch: _Launch) -> str:
    return os.path.join(
        checkpoint_dir,
        f"{launch.scheme.name}_{launch.lo}_{launch.hi}.json")


def _load_checkpoint(path: str, fingerprint: str) -> Optional[list]:
    """Finished-launch rows from a checkpoint file, or None to (re)run the
    launch. A torn file — the process died mid-write before the atomic
    rename — parses as garbage and is treated as absent; a VALID file from
    a different plan raises."""
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            data = json.load(f)
    except (json.JSONDecodeError, OSError, UnicodeDecodeError):
        return None
    if data.get("fingerprint") != fingerprint:
        raise ValueError(
            f"--resume: checkpoint {path} was written by a DIFFERENT "
            f"launch plan (grid, workload, horizon, trace mode, channel "
            f"or scheme set changed); delete the checkpoint directory to "
            f"start this sweep from scratch")
    return data["rows"]


def _write_checkpoint(path: str, fingerprint: str, launch: _Launch,
                      rows: list) -> None:
    """Atomic per-launch checkpoint: rows round-trip through JSON
    bit-identically (repr-based float serialization; NaN/Infinity use the
    JSON-extension literals), and the tmp-file + rename means a kill at
    ANY point leaves either the complete file or none."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"fingerprint": fingerprint, "scheme": launch.scheme.name,
                   "lo": launch.lo, "hi": launch.hi, "rows": rows}, f)
    os.replace(tmp, path)


def _is_oom_error(e: Exception) -> bool:
    s = str(e)
    return "RESOURCE_EXHAUSTED" in s or "out of memory" in s.lower()


def _run_launch(launch: _Launch, cfgs, wlp_np, grid_static, period_slots,
                trace_mode, decimate, devices, channel, n_dev: int,
                strict_conservation: bool, conservation_tol: float,
                profile: Optional[dict] = None) -> List[dict]:
    """One launch -> its REAL cells' rows (grid order), with
    retry-with-smaller-chunk backoff: a device-OOM failure splits the
    launch into two half-size launches and recurses (each half still pads
    to a device multiple), down to single-cell launches before giving up.
    The conservation guard runs per launch so the raised coordinates are
    the first violation of the first offending chunk. ``profile``: a dict
    routed to the AOT profiling path (filled in place with the launch's
    compile/execute split and memory figures — docs/observability.md)."""
    horizon, steps, warm, delay_pad, history_slots, rounds = grid_static
    with span("netsim.stack", profile):
        sub_cfgs = cfgs[launch.lo:launch.hi]
        sub_wlp = WorkloadParams(*(v[launch.lo:launch.hi] for v in wlp_np))
        n_real = len(sub_cfgs)
        sub_cfgs, sub_wlp = _pad_chunk(sub_cfgs, sub_wlp, launch.pad_to)
    try:
        final, aux = simulate_batch(
            sub_cfgs, sub_wlp, launch.scheme, horizon, period_slots,
            trace_mode=trace_mode, decimate=decimate,
            delay_pad=delay_pad, history_slots=history_slots,
            devices=devices, warm_steps=warm, channel=channel,
            profile=profile, rounds=rounds)
    except Exception as e:  # noqa: BLE001 — filtered to OOM right below
        if not _is_oom_error(e) or n_real <= 1:
            raise
        mid = launch.lo + (n_real + 1) // 2
        warnings.warn(
            f"launch ({launch.scheme.name}, cells [{launch.lo}, "
            f"{launch.hi})) hit device OOM; retrying as two half-size "
            f"launches", RuntimeWarning, stacklevel=2)
        if profile is not None:
            profile["oom_split"] = True
        rows = []
        for lo, hi in ((launch.lo, mid), (mid, launch.hi)):
            pad = hi - lo
            if n_dev > 1:
                pad = -(-pad // n_dev) * n_dev
            rows.extend(_run_launch(
                _Launch(launch.scheme, lo, hi, pad), cfgs, wlp_np,
                grid_static, period_slots, trace_mode, decimate, devices,
                channel, n_dev, strict_conservation, conservation_tol))
        return rows
    with span("netsim.rows", profile):
        if strict_conservation:
            _check_conservation(launch.scheme.name, aux, launch.lo, n_real,
                                trace_mode, decimate, conservation_tol)
        final_np = {"delivered": np.asarray(final.delivered),
                    "done_at_us": np.asarray(final.done_at_us)}
        wl_np = WorkloadParams(*(np.asarray(v) for v in sub_wlp))
        if rounds:
            final_np["stall_us"] = np.asarray(final.stall_us)
            if profile is not None:
                _round_counters(profile, wl_np, final, n_real)
        if trace_mode in ("metrics", "window"):
            acc = aux if trace_mode == "metrics" else aux.acc
            sub_rows = _metrics_streaming(sub_cfgs, wl_np, launch.scheme,
                                          channel, final_np, acc, steps,
                                          warm)
        else:
            traces_np = {k: np.asarray(v) for k, v in aux.items()}
            sub_rows = _metrics_batch(
                sub_cfgs, wl_np, launch.scheme.name, final_np, traces_np,
                decimate if trace_mode == "decimate" else 1,
                sim_us=steps * sub_cfgs[0].dt_us)
    return sub_rows[:n_real]


def _round_counters(profile: dict, wl: WorkloadParams, final,
                    n_real: int) -> None:
    """A rounds-on launch's record counts, over its real cells' round
    flows, the rounds released after each flow's first
    (``rounds_released``) and those released after their due step
    (``rounds_late``)."""
    mask = (np.asarray(wl.round_bytes) > 0)[:n_real]
    r = np.asarray(final.round_r)[:n_real]
    late = np.asarray(final.round_late)[:n_real]
    profile["rounds_released"] = int(np.where(mask, r - 1.0, 0.0).sum())
    profile["rounds_late"] = int(np.where(mask, late, 0.0).sum())


def _execute_plan(plan: Sequence[_Launch], cfgs, wlp: WorkloadParams,
                  grid_static, period_slots, trace_mode, decimate,
                  devices, channel=None, *,
                  checkpoint_dir: Optional[str] = None, resume: bool = False,
                  on_nonfinite: str = "keep",
                  strict_conservation: bool = False,
                  conservation_tol: float = 1e-3,
                  abort_after_launches: Optional[int] = None,
                  manifest_path: Optional[str] = None
                  ) -> Dict[object, list]:
    """Run every launch; returns scheme -> full row list (grid order).
    ``grid_static`` is the shared ``_grid_static`` tuple, so all chunks
    (and all schemes) see identical static shapes, hence one compiled
    program per scheme.

    Hardening knobs (all opt-in; docs/failures.md):
      * ``checkpoint_dir`` — write one atomic JSON checkpoint per finished
        launch; with ``resume=True`` a rerun of the SAME plan loads
        finished launches from disk (bit-identical rows — JSON floats
        round-trip exactly) and only executes the rest. A checkpoint from
        a different plan (fingerprint mismatch) raises.
      * ``on_nonfinite`` — ``"keep"`` (default) / ``"quarantine"`` (swap
        diverged cells' rows for structured failure records) / ``"raise"``.
      * ``strict_conservation`` — raise ``ConservationError`` with (cell,
        step) coordinates on the first ``cons_err > conservation_tol``.
      * ``abort_after_launches`` — deterministic crash-injection hook:
        raise after N launches have executed (checkpoints for those N are
        already on disk); the resume test kills sweeps with it.
      * ``manifest_path`` — write a JSONL run manifest (one header record
        with git rev + plan fingerprint + backend, one record per launch
        with the compile/execute wall-clock split and XLA memory
        figures). Every launch routes through the AOT profiling path;
        ``tools/obs_report.py`` summarizes and diffs manifests
        (docs/observability.md).
    """
    channel = get_channel_model(channel)
    if on_nonfinite not in ("keep", "quarantine", "raise"):
        raise ValueError(
            f"on_nonfinite must be 'keep', 'quarantine' or 'raise', "
            f"got {on_nonfinite!r}")
    with span("netsim.stack"):
        wlp_np = [np.asarray(v) for v in wlp]
    n_dev = len(devices) if devices is not None else len(jax.devices())

    fingerprint = None
    if checkpoint_dir is not None or manifest_path is not None:
        with span("netsim.manifest"):
            fingerprint = _plan_fingerprint(plan, cfgs, wlp_np, grid_static,
                                            period_slots, trace_mode,
                                            decimate, channel)
    if checkpoint_dir is not None:
        os.makedirs(checkpoint_dir, exist_ok=True)
    manifest = [] if manifest_path is not None else None

    rows: Dict[object, list] = {}
    executed = 0
    for launch in plan:
        ckpt = (_checkpoint_path(checkpoint_dir, launch)
                if checkpoint_dir is not None else None)
        if ckpt is not None and resume:
            cached = _load_checkpoint(ckpt, fingerprint)
            if cached is not None:
                rows.setdefault(launch.scheme, []).extend(cached)
                if manifest is not None:
                    manifest.append({"scheme": launch.scheme.name,
                                     "lo": launch.lo, "hi": launch.hi,
                                     "pad_to": launch.pad_to,
                                     "resumed": True})
                continue
        if abort_after_launches is not None \
                and executed >= abort_after_launches:
            raise RuntimeError(
                f"abort_after_launches: aborting sweep after {executed} "
                f"executed launches (crash-injection hook)")
        prof = {} if manifest is not None else None
        sub_rows = _run_launch(launch, cfgs, wlp_np, grid_static,
                               period_slots, trace_mode, decimate, devices,
                               channel, n_dev, strict_conservation,
                               conservation_tol, prof)
        with span("netsim.rows", prof):
            sub_rows = _guard_nonfinite(sub_rows, launch.lo, on_nonfinite)
        if ckpt is not None:
            _write_checkpoint(ckpt, fingerprint, launch, sub_rows)
        executed += 1
        if manifest is not None:
            prof.update(scheme=launch.scheme.name, lo=launch.lo,
                        hi=launch.hi, pad_to=launch.pad_to,
                        n_real=launch.hi - launch.lo)
            manifest.append(prof)
        rows.setdefault(launch.scheme, []).extend(sub_rows)
    if manifest_path is not None:
        executed_recs = [m for m in manifest if not m.get("resumed")]
        header = {
            "fingerprint": fingerprint,
            "backend": (devices[0].platform if devices is not None
                        else jax.default_backend()),
            "n_devices": n_dev,
            "trace_mode": trace_mode,
            "decimate": int(decimate),
            "horizon_us": float(grid_static[0]),
            "steps": int(grid_static[1]),
            "warm_steps": int(grid_static[2]),
            "n_cells": len(cfgs),
            "schemes": sorted({ln.scheme.name for ln in plan}),
            "n_launches": len(plan),
            "n_resumed": len(manifest) - len(executed_recs),
            "total_compile_s": sum(m.get("compile_s", 0.0)
                                   for m in executed_recs),
            "total_execute_s": sum(m.get("execute_s", 0.0)
                                   for m in executed_recs),
        }
        with span("netsim.manifest"):
            write_manifest(manifest_path, header, manifest)
    return rows


# ---------------------------------------------------------------------------
# Public entrypoints
# ---------------------------------------------------------------------------


def run_experiment(cfg: NetConfig, workload: Workload, scheme,
                   horizon_us: Optional[float] = None,
                   period_slots: int = 0, delay_pad: int = 0,
                   history_slots: int = 0, *,
                   trace_mode: str = "full",
                   decimate: int = 1, channel=None) -> Dict[str, float]:
    """Returns the Fig. 3 metric set for one (config, workload, scheme) —
    a B=1 delegation onto the batch-wide extractors (one copy of the
    metric definitions, no single-cell fork).

    ``scheme`` as a bare name string is deprecated here (pass
    ``get_scheme(name)``). ``channel``: registered channel-model name or
    instance (None = ``"ideal"``). ``delay_pad``/``history_slots``: minimum
    static ring sizes — pass a batch's padding to reproduce one of its
    cells exactly."""
    if isinstance(scheme, str):
        _warn_string_scheme("run_experiment")
    scheme = get_scheme(scheme)
    return run_experiment_batch(
        [cfg], workload, scheme, horizon_us, period_slots,
        trace_mode=trace_mode, decimate=decimate, delay_pad=delay_pad,
        history_slots=history_slots, channel=channel)[0]


def run_experiment_batch(cfgs: Sequence[NetConfig], workload, scheme,
                         horizon_us: Optional[float] = None,
                         period_slots: int = 0, *,
                         trace_mode: str = "full", decimate: int = 1,
                         chunk_cells: Optional[int] = None,
                         devices: Optional[Sequence] = None,
                         delay_pad: int = 0, history_slots: int = 0,
                         channel=None,
                         checkpoint_dir: Optional[str] = None,
                         resume: bool = False, on_nonfinite: str = "keep",
                         strict_conservation: bool = False,
                         conservation_tol: float = 1e-3,
                         abort_after_launches: Optional[int] = None,
                         manifest_path: Optional[str] = None
                         ) -> List[Dict[str, float]]:
    """Fig. 3 metrics for every scenario of a grid, from a chunked launch
    plan (one compiled program per scheme) and one vectorized metric pass
    per launch. ``workload``: shared ``Workload``, per-scenario sequence,
    or stacked ``WorkloadParams`` (see ``fluid.simulate_batch``).

    ``trace_mode="metrics"`` streams all reductions in-scan: device memory
    is O(B), no [B, T] trace array is ever allocated or transferred, and
    scheme-streamed columns (``Scheme.finalize_metrics``) join the rows.
    ``chunk_cells`` caps cells per device launch (None = bounded-memory
    auto size); ``devices`` restricts sharding of the scenario axis;
    ``channel`` selects the long-haul channel model (name or instance,
    None = ``"ideal"``) — non-ideal channels add the ``goodput_gbps`` /
    ``wire_gbps`` / ``retx_frac`` / ``p99_repair_latency_us`` columns in
    every trace mode.

    Hardening knobs (opt-in; see ``_execute_plan`` / docs/failures.md):
    ``checkpoint_dir`` + ``resume`` for crash-proof per-launch
    checkpointing, ``on_nonfinite`` for the per-cell finite guard,
    ``strict_conservation`` (+ ``conservation_tol``) to raise
    ``ConservationError`` with (cell, step) coordinates,
    ``abort_after_launches`` as the deterministic crash-injection hook,
    and ``manifest_path`` to emit a JSONL run manifest with per-launch
    compile/execute timings and memory figures (docs/observability.md)."""
    cfgs = list(cfgs)
    scheme = get_scheme(scheme)
    channel = get_channel_model(channel)
    with span("netsim.stack"):
        wlp = as_workload_batch(workload, len(cfgs))
        grid_static = _grid_static(cfgs, horizon_us, delay_pad,
                                   history_slots, wlp)
    n_dev = len(devices) if devices is not None else len(jax.devices())
    chunk = _auto_chunk_cells(grid_static[1], trace_mode, decimate,
                              chunk_cells, n_dev, cfgs[0].num_paths,
                              _sched_floats(cfgs[0]))
    plan = _plan_launches(len(cfgs), (scheme,), chunk, n_dev)
    return _execute_plan(plan, cfgs, wlp, grid_static, period_slots,
                         trace_mode, decimate, devices, channel=channel,
                         checkpoint_dir=checkpoint_dir, resume=resume,
                         on_nonfinite=on_nonfinite,
                         strict_conservation=strict_conservation,
                         conservation_tol=conservation_tol,
                         abort_after_launches=abort_after_launches,
                         manifest_path=manifest_path)[scheme]


def convergence_horizon_us(cfgs: Sequence[NetConfig],
                           floor_us: float = 20_000.0) -> float:
    """Horizon long enough for CC to converge at EVERY distance of a grid:
    at least 20 RTTs at the farthest scenario plus a fixed floor. The one
    definition of the convergence margin — distance sweeps
    (``sweep``, ``benchmarks/scheme_compare.py``) size their shared
    horizon with it so short-distance cells simply observe a longer
    steady state."""
    return 40.0 * max(c.one_way_delay_us for c in cfgs) + floor_us


def sweep(cfg: NetConfig, workload: Workload, schemes, distances_km,
          horizon_us: Optional[float] = None, period_slots: int = 0, **kw):
    """Cartesian (distance x scheme) sweep; returns list of metric dicts in
    the order ``for d in distances: for s in schemes``.

    Batched execution: each scheme's whole distance grid is one launch
    plan (one compile per scheme). All cells share one horizon — the
    longest any distance needs for CC convergence
    (``convergence_horizon_us``) — so short-distance cells simply observe
    a longer steady state. Keyword extras (``trace_mode``,
    ``chunk_cells``, ``devices``, ...) pass through to ``sweep_grid``.
    """
    cfgs = [dataclasses.replace(cfg, distance_km=float(d))
            for d in distances_km]
    h = horizon_us
    if h is None:
        h = max(cfg.horizon_us, convergence_horizon_us(cfgs))
    return sweep_grid(cfgs, workload, schemes, h, period_slots, **kw)


def sweep_grid(scenarios, workload=None, schemes=(),
               horizon_us: Optional[float] = None, period_slots: int = 0, *,
               trace_mode: str = "full", decimate: int = 1,
               chunk_cells: Optional[int] = None,
               devices: Optional[Sequence] = None, channel=None,
               checkpoint_dir: Optional[str] = None, resume: bool = False,
               on_nonfinite: str = "keep",
               strict_conservation: bool = False,
               conservation_tol: float = 1e-3,
               abort_after_launches: Optional[int] = None,
               manifest_path: Optional[str] = None):
    """Heterogeneous scenario grids × schemes, executed as ONE launch plan:
    the grid is stacked once, chunked once, and every (scheme, chunk) pair
    is a device launch sharing the grid-wide static shapes. Returns rows in
    the order ``for scenario: for scheme``.

    Two spellings:
      * unified axis — ``sweep_grid([Scenario(cfg, wl), ...], schemes)``:
        each cell carries its own config AND workload (mixed OTN
        capacities, asymmetric buffers, different flow sets — one launch);
      * config axis only — ``sweep_grid(cfgs, shared_workload, schemes)``:
        the historical form, one workload across the grid.

    ``trace_mode="metrics"`` makes the whole sweep O(B) in device memory
    (plus per-scheme streamed columns); with auto ``chunk_cells`` a
    10k-cell grid runs in bounded memory on a single device and shards
    across all of ``jax.devices()`` when more are visible. ``channel``
    selects the long-haul channel model for every cell (name or instance,
    None = ``"ideal"``); impairment KNOBS (loss_rate, jitter_us, ...) are
    traced ``NetParams`` leaves, so an impairment grid still runs as one
    compiled program per scheme.

    Hardening knobs (opt-in; see ``_execute_plan`` / docs/failures.md):
    ``checkpoint_dir`` + ``resume`` checkpoint each finished launch
    atomically and let a rerun of the SAME plan skip finished chunks with
    bit-identical rows; ``on_nonfinite`` quarantines or raises on diverged
    cells; ``strict_conservation`` raises ``ConservationError`` naming the
    (cell, step) of the first violation; ``abort_after_launches`` is the
    deterministic crash-injection hook the resume test kills sweeps with;
    ``manifest_path`` emits a JSONL run manifest with per-launch
    compile/execute timings and memory figures (docs/observability.md).
    """
    scenarios = list(scenarios)
    if not scenarios:
        raise ValueError("sweep_grid: empty scenario grid")
    if isinstance(scenarios[0], Scenario):
        if workload is not None and not schemes \
                and not isinstance(workload, (Workload, WorkloadParams)):
            # positional sweep_grid(scenarios, schemes)
            workload, schemes = None, workload
        if workload is not None:
            raise ValueError(
                "sweep_grid: Scenario cells carry their own workloads — "
                "drop the workload argument")
        cfgs = [s.net for s in scenarios]
        wl = [s.workload for s in scenarios]
    else:
        cfgs, wl = scenarios, workload
        if wl is None:
            raise ValueError(
                "sweep_grid: pass a workload (or a grid of Scenario cells)")
    if isinstance(schemes, str):
        schemes = (schemes,)        # a lone name is a 1-scheme sweep
    if not schemes:
        raise ValueError(
            "sweep_grid: no schemes given — pass schemes=(\"dcqcn\", ...) "
            "(or positionally after the Scenario grid)")
    scheme_objs = [get_scheme(s) for s in schemes]
    channel = get_channel_model(channel)
    with span("netsim.stack"):
        wlp = as_workload_batch(wl, len(cfgs))
        grid_static = _grid_static(cfgs, horizon_us, 0, 0, wlp)
    n_dev = len(devices) if devices is not None else len(jax.devices())
    chunk = _auto_chunk_cells(grid_static[1], trace_mode, decimate,
                              chunk_cells, n_dev, cfgs[0].num_paths,
                              _sched_floats(cfgs[0]))
    plan = _plan_launches(len(cfgs), scheme_objs, chunk, n_dev)
    by_scheme = _execute_plan(plan, cfgs, wlp, grid_static, period_slots,
                              trace_mode, decimate, devices, channel=channel,
                              checkpoint_dir=checkpoint_dir, resume=resume,
                              on_nonfinite=on_nonfinite,
                              strict_conservation=strict_conservation,
                              conservation_tol=conservation_tol,
                              abort_after_launches=abort_after_launches,
                              manifest_path=manifest_path)
    return [by_scheme[s][i]
            for i in range(len(cfgs)) for s in scheme_objs]
