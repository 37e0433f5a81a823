"""Batched scenario engine: batch/sequential equivalence on BOTH scenario
axes (config grids and padded workload grids), the static-vs-traced config
split, heterogeneous per-scenario grids, and the Fig. 3 scheme-ordering
regression at 1000 km."""
import dataclasses

import numpy as np
import pytest
from _hypo import given, settings, st

from repro.config.base import NetConfig, NetParams, stack_net_params
from repro.netsim import (
    batch_padding, congestion_workload, get_scheme, run_experiment,
    run_experiment_batch, simulate, simulate_batch, sweep, sweep_grid,
    throughput_workload,
)

WL = throughput_workload(msg_size=1 << 20, concurrency=1, num_flows=4)
DISTS = (1.0, 100.0, 1000.0)
METRICS = ("throughput_gbps", "peak_buffer_mb", "mean_buffer_mb",
           "pause_ratio")


def _rel(a, b):
    # 1e-4 absolute floor: sub-kilobyte buffer noise must not register as
    # relative error between a zero and a near-zero cell
    return abs(a - b) / max(abs(a), abs(b), 1e-4)


def test_batch_matches_sequential_grid():
    """simulate_batch over a 3-distance grid must reproduce per-cell
    simulate metrics within 1e-3 relative tolerance, for both an e2e and
    the segmented scheme (the acceptance bar of the batched engine)."""
    cfgs = [NetConfig(distance_km=d) for d in DISTS]
    pad, hist = batch_padding(cfgs)
    for scheme in ("dcqcn", "matchrdma"):
        batch_rows = run_experiment_batch(cfgs, WL, scheme, 60_000.0)
        for cfg, row in zip(cfgs, batch_rows):
            ref = run_experiment(cfg, WL, get_scheme(scheme), 60_000.0,
                                 delay_pad=pad, history_slots=hist)
            for m in METRICS:
                assert _rel(row[m], ref[m]) < 1e-3, (scheme, cfg.distance_km,
                                                     m, row[m], ref[m])


def test_batch_traces_match_sequential_traces():
    """Stronger than metric equality: the full per-step traces agree."""
    cfgs = [NetConfig(distance_km=d) for d in (1.0, 300.0)]
    pad, hist = batch_padding(cfgs)
    _, batch_traces = simulate_batch(cfgs, WL, "matchrdma", 20_000.0)
    for i, cfg in enumerate(cfgs):
        _, ref_traces = simulate(cfg, WL, get_scheme("matchrdma"), 20_000.0,
                                 delay_pad=pad, history_slots=hist)
        for k in ("thr_inter", "q_dst", "pause_dst"):
            a = np.asarray(ref_traces[k])
            b = np.asarray(batch_traces[k])[i]
            denom = max(np.abs(a).max(), 1e-9)
            assert np.abs(a - b).max() / denom < 1e-3, (cfg.distance_km, k)


def test_fig3_ordering_regression_1000km():
    """Fig. 3 directions at 1000 km (congestion scenario): the segmented,
    rate-matched scheme must beat conventional e2e RDMA on throughput AND
    destination-OTN buffer stress."""
    cfgs = [NetConfig(distance_km=1000.0)]
    wl = congestion_workload()
    m = run_experiment_batch(cfgs, wl, "matchrdma", 100_000.0)[0]
    d = run_experiment_batch(cfgs, wl, "dcqcn", 100_000.0)[0]
    assert m["throughput_gbps"] >= d["throughput_gbps"]
    assert m["peak_buffer_mb"] < d["peak_buffer_mb"]


def test_sweep_order_and_batch_consistency():
    """The batched sweep keeps the historical row order (distance-major)
    and its rows equal the scheme-wise batched runs it is built from."""
    cfg = NetConfig()
    schemes = ("dcqcn", "matchrdma")
    rows = sweep(cfg, WL, schemes, DISTS, horizon_us=30_000.0)
    assert len(rows) == len(DISTS) * len(schemes)
    for i, d in enumerate(DISTS):
        for j, s in enumerate(schemes):
            r = rows[i * len(schemes) + j]
            assert r["distance_km"] == d
            assert r["scheme"] == s


def test_heterogeneous_capacity_and_buffer_grid():
    """Mixed OTN capacities / asymmetric buffer thresholds as first-class
    per-scenario leaves in ONE batch: more capacity must not hurt
    throughput; every metric stays finite and non-negative."""
    base = NetConfig(distance_km=100.0)
    cfgs = [
        dataclasses.replace(base, num_otn_links=4),      # 400 Gbps OTN
        dataclasses.replace(base, num_otn_links=16),     # 1.6 Tbps OTN
        dataclasses.replace(base, pfc_xoff_kb=512.0, pfc_xon_kb=256.0),
        dataclasses.replace(base, otn_buffer_bdp_frac=0.5),
    ]
    rows = sweep_grid(cfgs, WL, ("matchrdma",), horizon_us=40_000.0)
    assert len(rows) == len(cfgs)
    for r in rows:
        assert np.isfinite(r["throughput_gbps"])
        assert r["throughput_gbps"] >= 0.0
        assert r["peak_buffer_mb"] >= 0.0
    # both cells saturate near the 400 Gbps leaf; allow controller noise
    assert rows[1]["throughput_gbps"] >= 0.95 * rows[0]["throughput_gbps"]


def test_stack_net_params_shapes():
    cfgs = [NetConfig(distance_km=d) for d in DISTS]
    stacked = stack_net_params(cfgs)
    for name, leaf in zip(NetParams._fields, stacked):
        if name == "chan_schedule":
            assert leaf.shape == (len(DISTS), 1, 0, 3)  # [B, L, K=0, 3]
        elif name == "fail_windows":
            assert leaf.shape == (len(DISTS), 1, 0, 2)  # [B, L, W=0, 2]
        elif name.startswith("link_"):
            assert leaf.shape == (len(DISTS), 1)  # [B, L] at L=1
        else:
            assert leaf.shape == (len(DISTS),)
    np.testing.assert_allclose(
        np.asarray(stacked.one_way_delay_us),
        [c.one_way_delay_us for c in cfgs])
    single = NetParams.of(cfgs[0])
    assert len(single) == len(stacked)


HOST_STACK_GRIDS = {
    "fig3cd_l1": [NetConfig(distance_km=d)
                  for d in (1.0, 10.0, 50.0, 100.0, 300.0, 500.0, 1000.0)],
    "paths3": [NetConfig(distance_km=d, num_paths=3,
                         path_delay_scale=(1.0, 2.0, 4.0),
                         path_cap_frac=(0.5, 0.3, 0.2),
                         path_thresh_kb=(100.0, 200.0, 300.0))
               for d in (1.0, 300.0)],
    "schedule": [NetConfig(distance_km=d, channel_schedule_dt_us=50.0,
                           channel_schedule=(((0.0, 0.1, 1.0),
                                              (0.01, 0.0, 0.5)),))
                 for d in (1.0, 300.0)],
    "failures": [NetConfig(distance_km=d, failure_schedule=(
        ((1000.0 + d, 2000.0), (0.0, 0.0)),)) for d in (1.0, 300.0)],
}


@pytest.mark.parametrize("grid", sorted(HOST_STACK_GRIDS))
def test_stack_net_params_on_host_matches_device_stack(grid):
    """The stacked leaves are host NumPy arrays, byte for byte the device
    stack of the per-cell ``NetParams.of`` leaves, and put on the device
    they carry the same avals: the compiled program does not change."""
    import jax
    import jax.numpy as jnp
    cfgs = HOST_STACK_GRIDS[grid]
    host = stack_net_params(cfgs)
    dev = [jnp.stack(xs) for xs in zip(*(NetParams.of(c) for c in cfgs))]

    def aval(x):
        a = jax.typeof(x)
        return a.shape, a.dtype, a.weak_type

    for name, h, d in zip(NetParams._fields, host, dev):
        assert isinstance(h, np.ndarray) and not isinstance(h, jax.Array), \
            (name, type(h))
        assert aval(jnp.asarray(h)) == aval(d) \
            == (h.shape, jnp.float32, False), name
        d = np.asarray(d)
        assert (h.dtype, h.shape) == (d.dtype, d.shape), name
        assert h.tobytes() == d.tobytes(), name


def test_batch_rejects_mixed_static_structure():
    """Any non-traced field varying across a batch must fail loudly, not
    silently simulate every cell with one cell's value."""
    cfgs = [NetConfig(), dataclasses.replace(NetConfig(), dt_us=10.0)]
    with pytest.raises(ValueError, match="dt_us"):
        simulate_batch(cfgs, WL, "dcqcn", 10_000.0)
    # regression: DCQCN constants are compile-time too — mixing them used
    # to be silently collapsed onto the template's value
    cfgs = [NetConfig(),
            dataclasses.replace(NetConfig(), dcqcn_rai_mbps=30_000.0)]
    with pytest.raises(ValueError, match="dcqcn_rai_mbps"):
        simulate_batch(cfgs, WL, "dcqcn", 10_000.0)


def test_delay_ring_sizing_f32_consistent():
    """Distances whose delays are f32-equal must produce identical rings
    and bit-identical traces — regression for the f64 static sizing
    undercutting the f32 traced wrap index (ring rows were silently
    aliased through JAX index clamping, inflating throughput)."""
    a = simulate(NetConfig(distance_km=3.4999999), WL,
                 get_scheme("dcqcn"), 5_000.0)
    b = simulate(NetConfig(distance_km=3.5), WL,
                 get_scheme("dcqcn"), 5_000.0)
    for k in a[1]:
        np.testing.assert_array_equal(np.asarray(a[1][k]),
                                      np.asarray(b[1][k]), err_msg=k)


@settings(max_examples=3, deadline=None)
@given(st.integers(1, 8), st.sampled_from([64 << 10, 1 << 20]))
def test_workload_axis_equivalence_property(num_flows, msg):
    """Property: ANY workload run inside a padded (config x workload) batch
    matches its sequential twin — the active_mask keeps padding inert even
    when the cell is padded far above its own flow count."""
    wls = [throughput_workload(msg_size=msg, concurrency=1,
                               num_flows=num_flows),
           congestion_workload(num_inter=8, num_intra=8,
                               burst_start_us=3_000.0, burst_len_us=4_000.0,
                               horizon_us=12_000.0)]
    cfgs = [NetConfig(distance_km=100.0), NetConfig(distance_km=400.0)]
    pad, hist = batch_padding(cfgs)
    rows = run_experiment_batch(cfgs, wls, "matchrdma", 12_000.0)
    ref = run_experiment(cfgs[0], wls[0], get_scheme("matchrdma"), 12_000.0,
                         delay_pad=pad, history_slots=hist)
    for m in METRICS + ("goodput_bytes",):
        assert _rel(rows[0][m], ref[m]) < 1e-3, (m, rows[0][m], ref[m])


@settings(max_examples=3, deadline=None)
@given(st.integers(1, 500), st.sampled_from([100.0, 400.0]))
def test_batch_sequential_equivalence_property(distance_km, dst_gbps):
    """Property: ANY (distance, leaf-capacity) cell run inside a batch
    matches its padded sequential twin."""
    cfgs = [NetConfig(distance_km=float(distance_km), dst_dc_gbps=dst_gbps),
            NetConfig(distance_km=500.0)]
    pad, hist = batch_padding(cfgs)
    rows = run_experiment_batch(cfgs, WL, "matchrdma", 15_000.0)
    ref = run_experiment(cfgs[0], WL, get_scheme("matchrdma"), 15_000.0,
                         delay_pad=pad, history_slots=hist)
    for m in METRICS:
        assert _rel(rows[0][m], ref[m]) < 1e-3, (m, rows[0][m], ref[m])
