"""Observability-layer tests (docs/observability.md).

The load-bearing claim: the obs machinery is *additive*. With the obs
knobs SET but ``trace_mode != "window"`` every scheme stays bit-identical
to the goldens (the knobs are static config fields the non-window modes
never read), and window mode itself streams — its jaxpr holds no [B, T]
buffer, only the O(B·W) ring + O(B·E) event ring.
"""
import json
import os
import sys

import dataclasses
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from repro.config.base import NetConfig
from repro.netsim import (
    EVENT_KINDS, decode_events, get_scheme, read_manifest, simulate,
    simulate_batch, sweep_grid, timeline_from_window, unroll_window,
    write_manifest,
)
from repro.netsim.fluid import WindowAux
from repro.netsim.obs.events import (event_count, init_event_ring,
                                     kind_name, push_events)
from repro.netsim.obs.timeline import timeline_cell
from repro.netsim.schemes import ALL_SCHEMES, Scheme
from repro.netsim.workload import congestion_workload, throughput_workload

from test_streaming_metrics import _max_buffer_elems  # noqa: E402

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "netsim_scheme_traces.npz")

# the golden scenarios, verbatim from tests/golden/generate_goldens.py
SEQ_CFG_KW = dict(distance_km=100.0)
SEQ_WL_KW = dict(num_inter=4, num_intra=4, burst_start_us=3_000.0,
                 burst_len_us=4_000.0, horizon_us=10_000.0)
SEQ_HORIZON_US = 10_000.0
BATCH_DISTS = (1.0, 300.0)
BATCH_HORIZON_US = 8_000.0

# a scenario hot enough to actually fire events (the golden congestion
# workload is too gentle for matchrdma's brake at 100 km)
HOT_WL_KW = dict(num_inter=8, num_intra=8, burst_start_us=2_000.0,
                 burst_len_us=6_000.0, horizon_us=12_000.0)
HOT_HORIZON_US = 12_000.0


@pytest.fixture(scope="module")
def golden():
    return np.load(GOLDEN)


def _obs_cfg(**kw):
    """A config with the obs knobs SET (ring sized, window shrunk) — the
    non-window modes must not read them."""
    return dataclasses.replace(NetConfig(**kw), event_ring_slots=32,
                               trace_window_steps=64)


# ---------------------------------------------------------------------------
# obs-off bit-identity: knobs set, mode != window -> goldens untouched
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", ALL_SCHEMES)
def test_obs_knobs_do_not_perturb_sequential_goldens(golden, scheme):
    wl = congestion_workload(**SEQ_WL_KW)
    final, traces = simulate(_obs_cfg(**SEQ_CFG_KW), wl, get_scheme(scheme),
                             SEQ_HORIZON_US)
    golden_keys = {k.rsplit("/", 1)[1] for k in golden.files
                   if k.startswith(f"seq/{scheme}/traces/")}
    assert set(traces) == golden_keys
    for k, v in traces.items():
        np.testing.assert_array_equal(
            golden[f"seq/{scheme}/traces/{k}"], np.asarray(v),
            err_msg=f"{scheme}/{k}: obs knobs perturbed a full-mode run")
    for k in ("sent", "acked", "delivered", "done_at_us"):
        np.testing.assert_array_equal(
            golden[f"seq/{scheme}/final/{k}"],
            np.asarray(getattr(final, k)),
            err_msg=f"{scheme} final.{k}: obs knobs perturbed the run")


@pytest.mark.parametrize("scheme", ALL_SCHEMES)
def test_obs_knobs_do_not_perturb_batched_goldens(golden, scheme):
    cfgs = [_obs_cfg(distance_km=d) for d in BATCH_DISTS]
    wl = throughput_workload(msg_size=1 << 20, concurrency=1, num_flows=4)
    final, traces = simulate_batch(cfgs, wl, get_scheme(scheme),
                                   BATCH_HORIZON_US)
    keys = {k.rsplit("/", 1)[1] for k in golden.files
            if k.startswith(f"batch/{scheme}/traces/")}
    assert set(traces) == keys
    for k in keys:
        np.testing.assert_array_equal(
            golden[f"batch/{scheme}/traces/{k}"], np.asarray(traces[k]),
            err_msg=f"{scheme}/{k}: obs knobs perturbed a batched run")
    np.testing.assert_array_equal(
        golden[f"batch/{scheme}/final/delivered"],
        np.asarray(final.delivered))


def _trace_batch(cfgs, wl, steps, mode):
    from repro.config.base import batch_template, stack_net_params
    from repro.netsim import fluid
    from repro.netsim.workload import WorkloadParams, as_workload_batch
    wlp = as_workload_batch(wl, len(cfgs))
    wlp = WorkloadParams(*(jnp.asarray(np.asarray(v)) for v in wlp))
    tmpl = batch_template(cfgs)
    params = stack_net_params(cfgs)
    pad, hist = fluid.batch_padding(cfgs)
    return jax.make_jaxpr(
        lambda p, w: fluid._run_traced_batch(
            tmpl, p, w, get_scheme("dcqcn"), steps, 0, pad, hist, mode, 1,
            steps // 10))(params, wlp)


def test_obs_knobs_leave_full_mode_jaxpr_unchanged():
    """Stronger than value-identity: the traced program of a full-mode run
    is textually identical with and without the obs knobs — the window/
    ring machinery is entirely gated behind ``mode == 'window'``."""
    wl = congestion_workload(**SEQ_WL_KW)
    steps = NetConfig(**SEQ_CFG_KW).horizon_steps(SEQ_HORIZON_US)
    jaxprs = [str(_trace_batch([cfg], wl, steps, "full"))
              for cfg in (NetConfig(**SEQ_CFG_KW), _obs_cfg(**SEQ_CFG_KW))]
    assert jaxprs[0] == jaxprs[1]


# ---------------------------------------------------------------------------
# window mode: streaming footprint, parity, ring contents
# ---------------------------------------------------------------------------

def test_window_mode_allocates_no_bt_buffers():
    """Window mode's jaxpr may hold O(B·W) + O(B·E) buffers but never the
    full [B, T] trace block. Full mode on the same grid is the positive
    control."""
    cfgs = [_obs_cfg(distance_km=d) for d in (1.0, 5.0, 10.0, 2.0)]
    steps, b = 2000, len(cfgs)
    w = cfgs[0].trace_window_steps
    wl = congestion_workload(num_inter=4, num_intra=4,
                             burst_start_us=1_000.0, burst_len_us=5_000.0,
                             horizon_us=steps * cfgs[0].dt_us)
    assert w < steps  # else the bound below is vacuous
    win_max = _max_buffer_elems(_trace_batch(cfgs, wl, steps, "window"))
    full_max = _max_buffer_elems(_trace_batch(cfgs, wl, steps, "full"))
    assert full_max >= b * steps
    assert win_max < b * steps, \
        f"window mode materialized a [B,T]-sized buffer ({win_max} elems)"


def test_window_matches_metrics_and_full():
    """One seq run, three claims: (a) the streamed accumulators under
    window mode equal metrics mode bit-for-bit; (b) the trace ring's
    unrolled rows equal the last W steps of a full-mode run bit-for-bit;
    (c) the final state is identical across all three modes."""
    cfg = _obs_cfg(**SEQ_CFG_KW)
    wl = congestion_workload(**SEQ_WL_KW)
    scheme = get_scheme("dcqcn")
    steps = cfg.horizon_steps(SEQ_HORIZON_US)
    w = cfg.trace_window_steps

    fin_w, aux = simulate(cfg, wl, scheme, SEQ_HORIZON_US,
                          trace_mode="window")
    assert isinstance(aux, WindowAux)
    fin_m, acc = simulate(cfg, wl, scheme, SEQ_HORIZON_US,
                          trace_mode="metrics")
    fin_f, traces = simulate(cfg, wl, scheme, SEQ_HORIZON_US)

    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), aux.acc, acc)
    step_idx, ordered = unroll_window(aux.window, steps, w)
    np.testing.assert_array_equal(step_idx, np.arange(steps - w, steps))
    assert set(ordered) == set(traces)
    for k in traces:
        np.testing.assert_array_equal(
            np.asarray(traces[k])[-w:], ordered[k],
            err_msg=f"window ring diverged from full-mode tail at {k}")
    for fin in (fin_m, fin_f):
        np.testing.assert_array_equal(np.asarray(fin_w.delivered),
                                      np.asarray(fin.delivered))


def test_sweep_grid_window_rows_equal_metrics_rows():
    cfgs = [_obs_cfg(distance_km=d) for d in (100.0, 300.0)]
    wl = congestion_workload(**HOT_WL_KW)
    rows_w = sweep_grid(cfgs, wl, ("dcqcn", "matchrdma"), HOT_HORIZON_US,
                        trace_mode="window")
    rows_m = sweep_grid(cfgs, wl, ("dcqcn", "matchrdma"), HOT_HORIZON_US,
                        trace_mode="metrics")
    assert len(rows_w) == len(rows_m) == 4
    for a, b in zip(rows_w, rows_m):
        assert set(a) == set(b)
        for k in a:
            assert a[k] == b[k] or (a[k] != a[k] and b[k] != b[k]), \
                f"window/metrics row divergence at {k}"


# ---------------------------------------------------------------------------
# event ring semantics
# ---------------------------------------------------------------------------

def test_ring_overflow_evicts_oldest():
    """Scripted one-event-per-step pushes through a 4-slot ring inside a
    scan: count stays monotone past the capacity, survivors are exactly
    the last 4 events, oldest-first."""
    slots, n = 4, 11

    def step(ring, t):
        ring = push_events(ring, slots, t.astype(jnp.float32) * 5.0,
                           [("pfc_xoff", 7, t.astype(jnp.float32),
                             jnp.asarray(True))])
        return ring, ring.count

    ring, counts = jax.lax.scan(step, init_event_ring(slots),
                                jnp.arange(n))
    counts = np.asarray(counts)
    assert list(counts) == list(range(1, n + 1))  # monotone, never clipped
    assert int(event_count(ring)) == n
    evs = decode_events(ring, slots)
    assert len(evs) == slots
    assert [e["value"] for e in evs] == [float(v) for v in range(n - slots, n)]
    assert [e["t_us"] for e in evs] == [v * 5.0 for v in range(n - slots, n)]
    assert all(e["kind"] == "pfc_xoff" and e["obj"] == 7 for e in evs)


def test_ring_partial_firing_and_trash_slot():
    """Non-fired candidates land in the discard slot and never disturb the
    ring; multiple candidates in one step keep candidate order."""
    slots = 8

    def step(ring, t):
        fired_a = (t % 3) == 0
        fired_b = (t % 4) == 0
        ring = push_events(ring, slots, t.astype(jnp.float32), [
            ("pfc_xoff", 0, jnp.float32(1.0), fired_a),
            ("pfc_xon", 1, jnp.float32(2.0), fired_b),
        ])
        return ring, None

    ring, _ = jax.lax.scan(step, init_event_ring(slots), jnp.arange(6))
    # t=0: both; t=3: a; t=4: b -> 4 events total
    evs = decode_events(ring, slots)
    assert [(e["t_us"], e["kind"]) for e in evs] == [
        (0.0, "pfc_xoff"), (0.0, "pfc_xon"),
        (3.0, "pfc_xoff"), (4.0, "pfc_xon")]


def test_push_events_rejects_unknown_kind():
    ring = init_event_ring(4)
    with pytest.raises(ValueError, match="unknown event kind"):
        push_events(ring, 4, jnp.float32(0.0),
                    [("not_a_kind", 0, jnp.float32(0.0),
                      jnp.asarray(True))])


def test_window_mode_rejects_undersized_ring():
    """slots < number of per-step candidates is a config error caught at
    trace time, not a silent drop."""
    cfg = dataclasses.replace(NetConfig(**SEQ_CFG_KW), event_ring_slots=1)
    wl = congestion_workload(**SEQ_WL_KW)
    with pytest.raises(ValueError, match="event_ring_slots"):
        simulate(cfg, wl, get_scheme("dcqcn"), SEQ_HORIZON_US,
                 trace_mode="window")


def test_events_fire_pfc_and_brake():
    """The acceptance scenario: under the hot congestion workload at
    100 km, dcqcn must log PFC pause edges and matchrdma must log its
    proxy-brake engagements."""
    cfg = _obs_cfg(**SEQ_CFG_KW)
    wl = congestion_workload(**HOT_WL_KW)
    slots = cfg.event_ring_slots
    _, aux = simulate(cfg, wl, get_scheme("dcqcn"), HOT_HORIZON_US,
                      trace_mode="window")
    kinds_dcqcn = {e["kind"] for e in decode_events(aux.events, slots)}
    assert "pfc_xoff" in kinds_dcqcn and "pfc_xon" in kinds_dcqcn
    _, aux = simulate(cfg, wl, get_scheme("matchrdma"), HOT_HORIZON_US,
                      trace_mode="window")
    kinds_mr = {e["kind"] for e in decode_events(aux.events, slots)}
    assert "scheme_brake" in kinds_mr
    for evs in (kinds_dcqcn, kinds_mr):
        assert evs <= set(EVENT_KINDS)


def test_scheme_emit_events_default_empty_and_kind_names():
    assert Scheme.emit_events(object.__new__(Scheme), None, None, None,
                              {}) == ()
    for name, code in EVENT_KINDS.items():
        assert kind_name(code) == name
    assert kind_name(999).startswith("kind_")


# ---------------------------------------------------------------------------
# manifest + report + timeline round-trips
# ---------------------------------------------------------------------------

def test_manifest_roundtrip_and_obs_report(tmp_path):
    import io

    from tools import obs_report

    header = {"record": "header", "manifest_version": 1,
              "git_rev": "deadbee", "fingerprint": "f" * 16,
              "backend": "cpu", "n_devices": 1, "trace_mode": "window",
              "decimate": 1, "horizon_us": 1000.0, "steps": 200,
              "warm_steps": 20, "n_cells": 2, "schemes": ["dcqcn"],
              "n_launches": 2, "n_resumed": 0,
              "total_compile_s": 3.5, "total_execute_s": 0.25}
    launches = [
        {"record": "launch", "scheme": "dcqcn", "lo": 0, "hi": 1,
         "pad_to": 1, "n_real": 1, "compile_s": 2.0, "execute_s": 0.1,
         "temp_size_in_bytes": 1 << 20,
         "argument_size_in_bytes": 1 << 10},
        {"record": "launch", "scheme": "dcqcn", "lo": 1, "hi": 2,
         "pad_to": 1, "n_real": 1, "compile_s": 1.5, "execute_s": 0.15,
         "compile_cached": True},
    ]
    path = str(tmp_path / "manifest.jsonl")
    write_manifest(path, header, launches)
    h2, l2 = read_manifest(path)
    assert h2["fingerprint"] == header["fingerprint"]
    assert len(l2) == 2 and l2[1]["compile_cached"] is True

    buf = io.StringIO()
    obs_report.summarize(path, out=buf)
    text = buf.getvalue()
    assert "deadbee" in text and "totals:" in text and "dcqcn" in text

    # a second manifest with slower execute -> diff must flag the ratio
    launches_b = [dict(rec, execute_s=rec.get("execute_s", 0.0) * 2.0)
                  for rec in launches]
    path_b = str(tmp_path / "manifest_b.jsonl")
    write_manifest(path_b, dict(header, git_rev="cafef00"), launches_b)
    buf = io.StringIO()
    obs_report.diff(path, path_b, out=buf)
    text = buf.getvalue()
    assert "matched launches: 2" in text
    assert "2.00x" in text
    assert "deadbee" in text and "cafef00" in text  # both revs surfaced


def test_sweep_manifest_names_the_device_it_ran_on(tmp_path):
    """``manifest_path=`` on one explicit device: the launches are profiled
    there, header and launch records name that device's platform, and the
    rows equal the unprofiled sweep's."""
    cfgs = [NetConfig(distance_km=d) for d in BATCH_DISTS]
    wl = congestion_workload(**SEQ_WL_KW)
    dev = jax.devices("cpu")[0]
    path = str(tmp_path / "sweep.jsonl")
    kw = dict(trace_mode="metrics", devices=[dev])
    rows = sweep_grid(cfgs, wl, ("dcqcn", "matchrdma"), BATCH_HORIZON_US,
                      manifest_path=path, **kw)
    plain = sweep_grid(cfgs, wl, ("dcqcn", "matchrdma"), BATCH_HORIZON_US,
                       **kw)
    assert json.dumps(rows, sort_keys=True) == json.dumps(plain,
                                                          sort_keys=True)
    header, launches = read_manifest(path)
    assert header["backend"] == dev.platform
    assert [ln["scheme"] for ln in launches] == ["dcqcn", "matchrdma"]
    for ln in launches:
        assert ln["backend"] == dev.platform
        assert ln["execute_s"] > 0.0 and not ln.get("oom_split")


def test_configure_compile_cache(tmp_path, monkeypatch):
    """A fixed ``<root>/.jax_cache`` unless ``$JAX_COMPILATION_CACHE_DIR``
    is set (then no directory is set in code); every compile is written."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.netsim.obs.profile import configure_compile_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        own = str(tmp_path / ".jax_cache")
        assert configure_compile_cache(str(tmp_path)) == own
        assert jax.config.jax_compilation_cache_dir == own
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

        jax.config.update("jax_compilation_cache_dir",
                          was["jax_compilation_cache_dir"])
        env = str(tmp_path / "from_env")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        assert configure_compile_cache(str(tmp_path)) == env
        assert (jax.config.jax_compilation_cache_dir
                == was["jax_compilation_cache_dir"])
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_timeline_export_valid_chrome_trace(tmp_path):
    cfg = _obs_cfg(**SEQ_CFG_KW)
    wl = congestion_workload(**HOT_WL_KW)
    steps = cfg.horizon_steps(HOT_HORIZON_US)
    recs = []
    for pid, scheme in enumerate(("dcqcn", "matchrdma")):
        _, aux = simulate(cfg, wl, get_scheme(scheme), HOT_HORIZON_US,
                          trace_mode="window")
        recs.extend(timeline_cell(
            pid, label=scheme, dt_us=cfg.dt_us, steps=steps,
            window_steps=cfg.trace_window_steps, window=aux.window,
            events=decode_events(aux.events, cfg.event_ring_slots)))
    path = str(tmp_path / "timeline.json")
    from repro.netsim import export_timeline
    export_timeline(path, {"traceEvents": recs, "displayTimeUnit": "ms"})
    doc = json.loads(open(path).read())
    evs = doc["traceEvents"]
    assert evs and doc["displayTimeUnit"] == "ms"
    phases = {r["ph"] for r in evs}
    assert {"M", "C", "i"} <= phases
    names = {r["name"] for r in evs if r["ph"] == "i"}
    assert "pfc_xoff" in names and "scheme_brake" in names
    # counter samples live inside the window's absolute step range
    ts = [r["ts"] for r in evs if r["ph"] == "C"]
    lo = (steps - cfg.trace_window_steps) * cfg.dt_us
    assert min(ts) >= lo and max(ts) <= steps * cfg.dt_us
    # instant events carry args with the raw value
    inst = [r for r in evs if r["ph"] == "i"]
    assert all("args" in r and "value" in r["args"] for r in inst)


def test_timeline_from_window_batched(tmp_path):
    cfgs = [_obs_cfg(distance_km=d) for d in (100.0, 300.0)]
    wl = congestion_workload(**HOT_WL_KW)
    _, aux = simulate_batch(cfgs, wl, get_scheme("dcqcn"), HOT_HORIZON_US,
                            trace_mode="window")
    doc = timeline_from_window(
        aux, dt_us=cfgs[0].dt_us,
        steps=cfgs[0].horizon_steps(HOT_HORIZON_US),
        window_steps=cfgs[0].trace_window_steps,
        event_ring_slots=cfgs[0].event_ring_slots,
        labels=[f"{c.distance_km:.0f}km" for c in cfgs])
    pids = {r["pid"] for r in doc["traceEvents"]}
    assert pids == {0, 1}  # one Perfetto process per cell
    names = {r["name"] for r in doc["traceEvents"]
             if r["ph"] == "i" and r["pid"] == 0}
    assert "pfc_xoff" in names  # 100 km cell congests
