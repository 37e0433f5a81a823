"""The trace reduction, on a trace recorded here with the JAX profiler on
the CPU backend, and on hand-made intervals."""
import glob
import time

import pytest

from bench import trace_reduce


def test_union_and_clip():
    iv = [(5, 9), (0, 2), (1, 3), (8, 12)]
    assert trace_reduce.union(iv) == [(0, 3), (5, 12)]
    assert trace_reduce.clip([(0, 3), (5, 12)], 2, 10) == [(2, 3), (5, 10)]
    assert trace_reduce.short_name("%while.4 = (s32[]) while(x)") == \
        "%while.4"


class _E:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _L:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _P:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def test_reduce_on_a_made_up_tpu_trace():
    host = _P("/host:CPU", [_L("python3", [
        _E("bench.grid", 0, 1000), _E("PjitFunction(x)", 100, 50),
        _E("concatenate", 600, 300)])])
    dev = _P("/device:TPU:0", [
        _L("XLA Modules", [_E("jit_a", 200, 300), _E("jit_b", 450, 100)]),
        _L("XLA Ops", [_E("%while.1 = (...)", 200, 300),
                        _E("%fusion.2 = f32[8]", 460, 80)])])
    out = trace_reduce.reduce([host, dev], ("bench.grid",))
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["busy_s"] == pytest.approx(350e-9)
    assert out["idle_share"] == pytest.approx(0.65)
    assert out["device_ops"][0] == ["%while.1", pytest.approx(300e-9)]
    assert out["device_ops"][1] == ["%fusion.2", pytest.approx(80e-9)]
    assert out["idle_gaps"][0] == ["bench.grid: concatenate",
                                   pytest.approx(450e-9)]
    assert out["idle_gaps"][1] == ["bench.grid: PjitFunction(x)",
                                   pytest.approx(200e-9)]


def test_reduce_without_spans_or_device_is_nothing():
    dev = _P("/device:TPU:0", [_L("XLA Modules", [_E("jit_a", 0, 5)])])
    assert trace_reduce.reduce([dev], ("bench.grid",)) is None
    host = _P("/host:CPU", [_L("python3", [_E("bench.grid", 0, 9)])])
    assert trace_reduce.reduce([host], ("bench.grid",)) is None


def test_reduce_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.grid"):
            for _ in range(3):
                f(x).block_until_ready()
                time.sleep(0.02)
    assert len(glob.glob(str(tmp_path / "**/*.xplane.pb"),
                         recursive=True)) == 1
    out = trace_reduce.reduce_dir(str(tmp_path), ("bench.grid",))
    assert out is not None
    assert out["window_s"] >= 0.06
    assert 0.0 < out["busy_s"] < out["window_s"]
    assert 0.0 < out["idle_share"] < 1.0
    assert out["idle_gaps"][0][1] >= 0.015
    assert out["idle_gaps"][0][0].startswith("bench.grid")
    assert 0 < len(out["device_ops"]) <= trace_reduce.TOP
