"""The readers of the program's spans, cache counter and scan-step scopes,
and their reduction (``bench/phases.py``): on synthetic records, on a
made-up TPU trace and on a trace recorded here on the CPU backend."""
import pytest

import bench.run as run
from bench import phases, trace_reduce
from bench.tests.test_bench_trace import _E, _L, _P

STEPS = 1000


def launch(scheme, execute_s, compile_s=0.0, backend="tpu"):
    return {"scheme": scheme, "execute_s": execute_s, "compile_s": compile_s,
            "backend": backend}


def spans(ln, stack_s, transfer_s, rows_s, cache="in_process"):
    """A launch record of a program with host spans and the cache
    counter."""
    return dict(ln, stack_s=stack_s, transfer_s=transfer_s, rows_s=rows_s,
                persistent_cache=cache)


# a program without phase scopes, host spans or the cache counter
PARENT_OBS = {
    "steps": STEPS,
    "setup_launches": [launch("dcqcn", 0.5, 2.0),
                       launch("matchrdma", 0.9, 3.0)],
    "grids": [
        {"wall_s": 1.5, "launches": [launch("dcqcn", 0.2),
                                     launch("matchrdma", 0.8)]},
    ],
    "trace": {"busy_s": 0.75, "window_s": 1.0, "idle_share": 0.25,
              "device_ops": [], "idle_gaps": []},
}

PHASE_S = {"flow": 0.1, "rings": 0.05, "ack_rate": 0.02, "src_otn": 0.03,
           "dst_queues": 0.2, "feedback": 0.4, "cc": 0.04,
           "accumulators": 0.06, "other": 0.1}
OBS = dict(
    PARENT_OBS,
    setup_launches=[spans(launch("dcqcn", 0.5, 2.0), 0.1, 0.1, 0.1, "miss"),
                    spans(launch("matchrdma", 0.9, 3.0), 0.1, 0.1, 0.1,
                          "hit")],
    grids=[
        {"wall_s": 1.5, "launches": [
            spans(launch("dcqcn", 0.2), 0.010, 0.002, 0.004),
            spans(launch("matchrdma", 0.8), 0.020, 0.004, 0.006)]},
        {"wall_s": 1.7, "launches": [
            spans(launch("dcqcn", 0.3), 0.030, 0.002, 0.004),
            spans(launch("matchrdma", 1.0), 0.040, 0.004, 0.006)]},
    ],
    # what phases.observe keeps: two traced matchrdma launches
    phases={"phase_s": PHASE_S, "hook_s": 0.2, "mixed_s": 0.1,
            "self_s": 1.0, "scheme": "matchrdma", "launches": 2},
)
# per scan step: seconds / (2 traced launches x 1000 steps), in us
EXPECT = {"phase_us." + p: v * 500.0 for p, v in PHASE_S.items()}
EXPECT.update({
    "phase_mixed_share": 0.1,
    "hook_us.matchrdma": 100.0,
    # summed over a grid's two launches, mean of two grids, in ms
    "host_ms.stack": 50.0,
    "host_ms.transfer": 6.0,
    "host_ms.rows": 10.0,
    "compile_cache_misses": 1,
})
HOST = ("host_ms.stack", "host_ms.transfer", "host_ms.rows",
        "compile_cache_misses")


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_new_reader_on_synthetic_records(name):
    value = run.load_reader(run.ROOT, name).read(dict(OBS))
    assert value == pytest.approx(EXPECT[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_new_reader_finds_nothing_returns_nothing(name):
    empty = {"steps": STEPS, "setup_launches": [], "grids": [],
             "trace": None, "phases": None}
    assert run.load_reader(run.ROOT, name).read(empty) is None


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_new_reader_silent_on_a_program_without_scopes_or_spans(name):
    """The benchmark runs over the parent's program too, whose records
    and process lack what these readers read."""
    obs = dict(PARENT_OBS)
    if not name.startswith(HOST):
        obs["phases"] = None
    assert run.load_reader(run.ROOT, name).read(obs) is None


@pytest.mark.parametrize("name", HOST)
def test_span_readers_silent_on_cpu_launches(name):
    def cpu(ln):
        return dict(ln, backend="cpu")
    obs = dict(OBS, setup_launches=[cpu(ln) for ln in OBS["setup_launches"]],
               grids=[dict(g, launches=[cpu(ln) for ln in g["launches"]])
                      for g in OBS["grids"]])
    assert run.load_reader(run.ROOT, name).read(obs) is None


def test_hook_reader_silent_when_another_scheme_was_traced():
    obs = dict(OBS, phases=dict(OBS["phases"], scheme="dcqcn"))
    assert run.load_reader(run.ROOT, "hook_us.matchrdma").read(obs) is None


def test_on_chip_wants_launches_all_off_the_cpu():
    assert phases.on_chip([launch("dcqcn", 0.1)])
    assert not phases.on_chip([])
    assert not phases.on_chip([launch("dcqcn", 0.1),
                               launch("dcqcn", 0.1, backend="cpu")])
    assert not phases.on_chip([{"scheme": "dcqcn"}])


SCOPES = [{"module": "jit__run_traced_batch_impl", "ops": {
    "while.4": ["other", "", False],
    "fusion.1": ["feedback", "feedback", False],
    "fusion.2": ["dst_queues", "", True],
    "copy.3": ["rings", "", False]}}]


def test_phases_of_a_made_up_nested_tpu_trace():
    """A ``%while`` encloses three operations (one nested in another) in
    an execution of the traced module; another module's operation, and
    one outside the benchmark span, count for nothing."""
    host = _P("/host:CPU", [_L("python3", [
        _E("bench.grid", 0, 2000), _E("netsim.launch", 50, 1100)])])
    dev = _P("/device:TPU:0", [
        _L("XLA Modules", [_E("jit__run_traced_batch_impl(7)", 100, 1000),
                           _E("jit_convert_element_type(3)", 1500, 100),
                           _E("jit__run_traced_batch_impl(7)", 2500, 50)]),
        _L("XLA Ops", [
            _E("%while.4 = (s32[]) while(x)", 100, 1000),
            _E("%fusion.1 = f32[8] fusion(y)", 200, 200),
            _E("%fusion.2 = f32[8] fusion(z)", 500, 400),
            _E("%copy.3 = f32[8] copy(w)", 600, 100),
            _E("%fusion.1 = f32[8] fusion(y)", 1500, 100),
            _E("%fusion.1 = f32[8] fusion(y)", 2500, 50)])])
    red = phases.reduce([host, dev], SCOPES, phases.SPANS)
    ns = pytest.approx
    assert red["phase_s"] == {"other": ns(400e-9), "feedback": ns(200e-9),
                              "dst_queues": ns(300e-9), "rings": ns(100e-9)}
    assert red["self_s"] == ns(1000e-9)
    assert red["hook_s"] == ns(200e-9)
    assert red["mixed_s"] == ns(300e-9)
    obs = {"phases": dict(red, scheme="matchrdma", launches=2), "steps": 10}
    assert phases.phase_us(obs, "rings") == ns(100e-9 / 20 * 1e6)
    assert phases.phase_us(obs, "cc") == 0.0
    assert run.load_reader(run.ROOT, "phase_mixed_share").read(obs) == \
        ns(0.3)


def test_self_times_split_overlapping_events_by_the_latest_started():
    evs = [(0, 100, "a"), (10, 30, "b"), (20, 60, "c"), (200, 210, "a")]
    assert phases.self_times(evs) == {"a": 60, "b": 10, "c": 40}


@pytest.fixture(scope="module")
def cpu_trace(tiny_root, tmp_path_factory):
    """A tiny cell's matchrdma sweep warmed up through a manifest, then
    traced on the CPU backend as ``bench/run.py`` traces it; the process
    AOT cache is this module's own."""
    import jax
    from bench import grid
    from bench.tests.tiny import DRIVE_CELL
    from repro.netsim.obs import profile
    saved = dict(profile._AOT_CACHE)
    profile._AOT_CACHE.clear()
    tmp = tmp_path_factory.mktemp("cputrace")
    try:
        sweep = run.Sweep(grid.Cell(tiny_root, DRIVE_CELL), 11)
        sweep(str(tmp / "setup.jsonl"), ("matchrdma",))
        tdir = str(tmp / "trace")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        with jax.profiler.trace(tdir, profiler_options=opts):
            with jax.profiler.TraceAnnotation(run.SPAN_GRID):
                sweep(str(tmp / "traced.jsonl"), ("matchrdma",))
        yield {"tdir": tdir, "launches": profile.traced_launches(),
               "scopes": profile.trace_scopes("matchrdma")}
    finally:
        profile._AOT_CACHE.clear()
        profile._AOT_CACHE.update(saved)


def test_phases_and_gap_spans_of_a_recorded_cpu_trace(cpu_trace):
    """The phases add up to the busy time, the step's phases all show,
    and the program's host spans name the idle gaps."""
    tdir = cpu_trace["tdir"]
    spans_ = ("netsim.stack", "netsim.transfer", "netsim.launch",
              "netsim.rows", "netsim.manifest")
    tr = trace_reduce.reduce_dir(tdir, phases.SPANS + spans_)
    red = phases.reduce_dir(tdir, cpu_trace["scopes"], phases.SPANS)
    assert red["self_s"] == pytest.approx(tr["busy_s"], rel=0.1)
    assert sum(red["phase_s"].values()) == pytest.approx(red["self_s"])
    step = {"flow", "rings", "ack_rate", "src_otn", "dst_queues",
            "feedback", "cc", "accumulators"}
    assert all(red["phase_s"].get(p, 0.0) > 0.0 for p in step)
    assert 0.0 < red["hook_s"] < red["self_s"]
    assert any(name.startswith("netsim.") for name, _ in tr["idle_gaps"])


def test_traced_launches_name_their_trace(cpu_trace):
    launches = cpu_trace["launches"]
    assert [ln["scheme"] for ln in launches] == ["matchrdma"]
    assert launches[0]["trace_dir"] == cpu_trace["tdir"]
    assert launches[0]["backend"] == "cpu"


def test_observe_reads_the_program_off_the_cpu_only(cpu_trace, monkeypatch):
    """On the CPU the readers find nothing; where the launches count as on
    a chip, ``observe`` reduces the program's own trace once, and the
    phase readers add up to its self time per step."""
    assert phases.observe({"steps": STEPS}) is None
    monkeypatch.setattr(phases, "on_chip", bool)
    obs = {"steps": STEPS}
    red = phases.observe(obs)
    assert red["scheme"] == "matchrdma" and red["launches"] == 1
    assert phases.observe(obs) is red
    total = sum(run.load_reader(run.ROOT, "phase_us." + p).read(obs)
                for p in list(red["phase_s"]))
    assert total == pytest.approx(red["self_s"] / STEPS * 1e6)
    hook = run.load_reader(run.ROOT, "hook_us.matchrdma").read(obs)
    assert 0.0 < hook < total
