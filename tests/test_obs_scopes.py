"""Scan-step scopes, host spans and the persistent-cache counter
(docs/observability.md, "Phase scopes and host spans").

The step's phases and scheme hooks name the compiled program's HLO
metadata; the sweep's host work names its launch records. Sizes are those
of the benchmark's CPU checkout (``bench/tests/tiny.py``): two distances,
a 4 ms horizon.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.config.base import NetConfig, batch_template, stack_net_params
from repro.netsim import FailureSchedule, fluid, read_manifest, sweep_grid
from repro.netsim.obs import profile
from repro.netsim.schemes import Scheme, get_scheme
from repro.netsim.schemes.matchrdma import MatchRdmaScheme
from repro.netsim.workload import (FlowSpec, Workload, WorkloadParams,
                                   as_workload_batch, congestion_workload)

HORIZON_US = 4_000.0
DISTANCES = (1.0, 300.0)
PHASES = {"flow", "rings", "channel", "ack_rate", "src_otn", "dst_queues",
          "feedback", "cc", "accumulators"}
HOOKS = ("ack_view", "sender_rate", "retx_rate", "src_otn_release",
         "route_weights", "feedback", "extra_traces", "accumulate_metrics")


def _workload():
    return congestion_workload(num_inter=4, num_intra=4,
                               burst_start_us=HORIZON_US / 3,
                               burst_len_us=HORIZON_US / 3,
                               horizon_us=HORIZON_US)


def _compiled_scopes(cfgs, scheme, channel=None, wl=None, rounds=False):
    """(``hlo_scopes``, HLO text) of the batch program compiled for
    ``cfgs`` (and ``wl``, by default ``_workload()``)."""
    wlp = as_workload_batch(wl or _workload(), len(cfgs))
    wlp = WorkloadParams(*(jnp.asarray(np.asarray(v)) for v in wlp))
    params = stack_net_params(cfgs)
    params = type(params)(*(jnp.asarray(np.asarray(v)) for v in params))
    tmpl = batch_template(cfgs)
    steps = tmpl.horizon_steps(HORIZON_US)
    pad, hist = fluid.batch_padding(cfgs)
    compiled = fluid._jitted_traced_batch().lower(
        tmpl, params, wlp, get_scheme(scheme), steps, 0, pad, hist,
        "metrics", 1, steps // 10, channel, rounds).compile()
    text = compiled.as_text()
    return profile.hlo_scopes(text), text


def _phases(scopes):
    return {p for p, _, _ in scopes["ops"].values()}


def _hooks(text):
    """Every ``hook.*`` scope on an instruction's name stack, fused ones
    included."""
    return set(re.findall(r'op_name="[^"]*/hook\.(\w+)', text))


def test_compiled_hlo_carries_every_phase_but_the_idle_channel():
    scopes, text = _compiled_scopes(
        [NetConfig(distance_km=d) for d in DISTANCES], "matchrdma")
    assert scopes["module"].startswith("jit_")
    assert _phases(scopes) == PHASES - {"channel"} | {profile.OTHER}
    assert "netsim.channel" not in text
    assert {"sender_rate", "src_otn_release", "feedback",
            "accumulate_metrics"} <= _hooks(text)


def test_impaired_channel_and_failover_carry_channel_and_every_hook():
    """Under an impaired channel and a failure schedule on two paths,
    every phase appears, and every hook matchrdma overrides names some
    operation — but ``ack_view``, which returns a carried state leaf and
    so computes nothing."""
    fs = FailureSchedule.empty(2).link_outage(1, 1_000.0, 2_000.0)
    cfgs = [fs.apply(NetConfig(distance_km=d, num_paths=2))
            for d in DISTANCES]
    scopes, text = _compiled_scopes(cfgs, "matchrdma", "impaired")
    hooks = _hooks(text)
    assert _phases(scopes) == PHASES | {profile.OTHER}
    overridden = {h for h in HOOKS
                  if getattr(MatchRdmaScheme, h) is not getattr(Scheme, h)}
    assert "ack_view" in overridden
    assert overridden - {"ack_view"} <= hooks
    assert "retx_rate" in hooks       # the inherited hook runs under repair


def test_round_gated_flows_carry_the_rounds_phase():
    """Round-gated flows (docs/rounds.md) add the ``rounds`` phase to the
    default program's; without them the gate is not built at all (the
    default-program test above)."""
    rounds = tuple(FlowSpec(True, 1 << 20, 16, round_bytes=1.5e6,
                            round_period_us=300.0) for _ in range(4))
    wl = Workload(rounds + _workload().flows[4:])
    scopes, text = _compiled_scopes(
        [NetConfig(distance_km=d) for d in DISTANCES], "matchrdma", wl=wl,
        rounds=True)
    assert _phases(scopes) == PHASES - {"channel"} | {"rounds",
                                                      profile.OTHER}
    assert "netsim.rounds" in text


def test_hlo_scopes_on_a_made_up_module():
    text = "\n".join([
        "HloModule jit_f, entry_computation_layout={()->f32[]}",
        "",
        "%fused_computation.1 (p: f32[4]) -> f32[4] {",
        '  %a = f32[4] add(%p, %p), '
        'metadata={op_name="jit(f)/netsim.cc/add"}',
        '  ROOT %m = f32[4] multiply(%a, %p), metadata={op_name="jit(f)/'
        'netsim.feedback/hook.feedback/mul"}',
        "}",
        "",
        "ENTRY %main.2 (x: f32[4]) -> f32[4] {",
        "  %x = f32[4] parameter(0)",
        "  %fusion.3 = f32[4] fusion(%x), kind=kLoop, "
        "calls=%fused_computation.1, metadata={op_name=\"jit(f)/"
        "netsim.feedback/hook.feedback/mul\"}",
        '  ROOT %copy.4 = f32[4] copy(%fusion.3), metadata={op_name="jit(f)/'
        'netsim.src_otn/netsim.rings/scatter"}',
        "}",
    ])
    scopes = profile.hlo_scopes(text)
    assert scopes == {"module": "jit_f", "ops": {
        "x": ["other", "", False],
        "fusion.3": ["feedback", "feedback", True],
        "copy.4": ["rings", "", False]}}


@pytest.fixture(scope="module")
def tiny_sweep(tmp_path_factory):
    """A two-scheme sweep through manifests, then the scope maps of the
    matchrdma launch, then the same sweep again; the process AOT cache is
    this module's own."""
    from jax._src import dispatch
    saved = dict(profile._AOT_CACHE)
    profile._AOT_CACHE.clear()
    cfgs = [NetConfig(distance_km=d) for d in DISTANCES]
    tmp = tmp_path_factory.mktemp("sweep")
    paths = [str(tmp / f"m{i}.jsonl") for i in range(2)]

    def go(path):
        return sweep_grid(cfgs, _workload(), ("matchrdma", "dcqcn"),
                          HORIZON_US, trace_mode="metrics",
                          manifest_path=path)

    compiles = []

    def on(event, duration, **kw):
        if event == dispatch.BACKEND_COMPILE_EVENT:
            compiles.append(duration)

    flag = "jax_compilation_cache_include_metadata_in_key"
    try:
        rows0 = go(paths[0])
        flag_was = getattr(jax.config, flag)
        jax.monitoring.register_event_duration_secs_listener(on)
        maps = profile.trace_scopes("matchrdma")
        again = profile.trace_scopes("matchrdma")
        n_compiles = len(compiles)
        rows1 = go(paths[1])
        yield {"rows": (rows0, rows1), "maps": maps, "again": again,
               "n_compiles": n_compiles, "flag": (flag_was,
                                                  getattr(jax.config, flag)),
               "launches": [read_manifest(p)[1] for p in paths],
               "programs": dict(profile._AOT_CACHE)}
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
        profile._AOT_CACHE.clear()
        profile._AOT_CACHE.update(saved)


def test_launch_records_carry_host_spans_and_cache_state(tiny_sweep):
    first, second = tiny_sweep["launches"]
    assert len(first) == len(second) == 2
    for ln in first + second:
        for key in ("stack_s", "transfer_s", "rows_s"):
            assert 0.0 < ln[key] < 60.0, (key, ln)
        assert ln["persistent_cache"] in ("hit", "miss", "in_process")
        assert "flops" not in ln and "bytes_accessed" not in ln
    assert {ln["persistent_cache"] for ln in first} <= {"hit", "miss"}
    assert {ln["persistent_cache"] for ln in second} == {"in_process"}


def test_host_stacked_sweep_rows_match_run_experiment(tiny_sweep):
    """The sweep whose launches stack their parameters on the host returns,
    cell by cell, the rows a one-cell ``run_experiment`` returns."""
    from repro.netsim import batch_padding, run_experiment
    cfgs = [NetConfig(distance_km=d) for d in DISTANCES]
    pad, hist = batch_padding(cfgs)
    rows = iter(tiny_sweep["rows"][0])
    for cfg in cfgs:
        for scheme in ("matchrdma", "dcqcn"):
            row = next(rows)
            ref = run_experiment(cfg, _workload(), get_scheme(scheme),
                                 HORIZON_US, delay_pad=pad,
                                 history_slots=hist, trace_mode="metrics")
            assert (row["scheme"], row["distance_km"]) == (scheme,
                                                           cfg.distance_km)
            np.testing.assert_equal({k: row[k] for k in ref}, ref,
                                    err_msg=f"{scheme} {cfg.distance_km}")


def test_trace_scopes_read_the_executable_and_change_no_row(tiny_sweep):
    """An executable compiled by this program carries its scopes: the map
    is read from it, with no compile, and the launches run on as
    before."""
    maps = tiny_sweep["maps"]
    assert len(maps) == 1 and tiny_sweep["again"] == maps
    assert tiny_sweep["n_compiles"] == 0
    assert _phases(maps[0]) == PHASES - {"channel"} | {profile.OTHER}
    assert "feedback" in {h for _, h, _ in maps[0]["ops"].values()}
    assert tiny_sweep["flag"][0] == tiny_sweep["flag"][1]
    rows0, rows1 = tiny_sweep["rows"]
    assert repr(rows0) == repr(rows1)


class _Stripped:
    """An executable as another build's persistent-cache entry presents
    it: the same instructions, without this program's op metadata."""

    def __init__(self, compiled):
        self.text = re.sub(r'op_name="[^"]*"', 'op_name="jit(f)/x"',
                           compiled.as_text())

    def as_text(self):
        return self.text


def test_trace_scopes_match_a_twin_where_the_executable_lacks_metadata(
        tiny_sweep, monkeypatch):
    """Without scopes in the executable's HLO, a twin compiled with the
    metadata gives each instruction its scopes: the same map as the
    executable's own HLO gives where it has them. The executable stays."""
    key, prog = next((k, p) for k, p in tiny_sweep["programs"].items()
                     if k[1].name == "matchrdma")
    stripped = profile._Program(_Stripped(prog.compiled), prog.args)
    assert set(_phases(profile.hlo_scopes(stripped.compiled.as_text()))) \
        == {profile.OTHER}
    monkeypatch.setattr(profile, "_AOT_CACHE", {key: stripped})
    assert profile.trace_scopes("matchrdma") == tiny_sweep["maps"]
    assert isinstance(stripped.compiled, _Stripped)


def test_trace_scopes_leave_unmatched_instructions_to_other():
    mine = "\n".join([
        "HloModule jit_f",
        "ENTRY %main (x: f32[4]) -> f32[4] {",
        '  %a = f32[4] add(%x, %x), metadata={op_name="jit(f)/y"}',
        '  ROOT %b = f32[4] negate(%a), metadata={op_name="jit(f)/y"}',
        "}"])
    twin = "\n".join([
        "HloModule jit_f",
        "ENTRY %main (x: f32[4]) -> f32[4] {",
        '  %a = f32[4] add(%x, %x), metadata={op_name="jit(f)/netsim.cc/a"}',
        '  ROOT %b = f32[4] abs(%a), metadata={op_name="jit(f)/netsim.cc/b"}',
        "}"])
    out = profile._matched_scopes(profile.hlo_scopes(mine), mine, twin)
    assert out == {"module": "jit_f", "ops": {"a": ["cc", "", False],
                                              "b": ["other", "", False]}}


def test_span_adds_its_seconds_only_given_a_profile():
    prof = {}
    with profile.span("netsim.stack", prof):
        pass
    with profile.span("netsim.stack", prof):
        pass
    with profile.span("netsim.manifest"):
        pass
    assert set(prof) == {"stack_s"} and prof["stack_s"] >= 0.0


def test_persistent_cache_state_of_a_launch(tmp_path, monkeypatch):
    """A launch compiled into an empty persistent cache is a miss; the
    same launch in a fresh process state (AOT and jit caches emptied) is
    then a hit."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs")}
    cfgs = [NetConfig(distance_km=d) for d in DISTANCES]
    states = []
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        cc.reset_cache()
        for _ in range(2):
            monkeypatch.setattr(profile, "_AOT_CACHE", {})
            jax.clear_caches()
            prof = {}
            fluid.simulate_batch(cfgs, _workload(), "dcqcn", HORIZON_US,
                                 trace_mode="metrics", profile=prof)
            states.append(prof["persistent_cache"])
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
        jax.clear_caches()
    assert states == ["miss", "hit"]
