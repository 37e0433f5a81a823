"""A run with its timed path broken underneath must come out not correct:
a step that returns its state unchanged, half of the batch left out, an
answer altered where it is produced, a program compiled inside the
window."""
import jax
import jax.numpy as jnp

from bench import grid
from bench.tests.tiny import DRIVE_CELL


def test_step_returning_its_state_unchanged(drive, monkeypatch):
    from repro.netsim import fluid
    make = fluid.make_step_fn

    def frozen(*a, **kw):
        step = make(*a, **kw)

        def still(state, t):
            return state, step(state, t)[1]
        still.ctx = step.ctx
        return still
    monkeypatch.setattr(fluid, "make_step_fn", frozen)
    line = drive()
    assert line["correct"] is False and line["failed"] > 0


def test_half_the_batch_left_out(drive, monkeypatch):
    from repro.netsim import runner
    launch = runner.simulate_batch

    def half(cfgs, wlp, *a, **kw):
        n = len(cfgs)
        k = max(n // 2, 1)
        out = launch(cfgs[:k], jax.tree.map(lambda x: x[:k], wlp), *a, **kw)
        return jax.tree.map(
            lambda x: jnp.concatenate([x] + [x[:1]] * (n - k)), out)
    monkeypatch.setattr(runner, "simulate_batch", half)
    line = drive()
    assert line["correct"] is False and line["failed"] > 0


def test_an_answer_altered_where_produced(drive, tiny_root, monkeypatch):
    from repro.netsim import runner
    limit = grid.Cell(tiny_root, DRIVE_CELL).config["limits"]["row_gap"]
    rows_of = runner._metrics_streaming

    def altered(*a, **kw):
        rows = rows_of(*a, **kw)
        rows[-1]["throughput_gbps"] *= 1.0 + 3.0 * limit
        return rows
    monkeypatch.setattr(runner, "_metrics_streaming", altered)
    line = drive()
    assert line["correct"] is False and line["failed"] > 0


def test_a_compile_inside_the_window(drive, monkeypatch):
    import bench.run as run
    sweep = run.Sweep.__call__

    def compiling(self, *a, **kw):
        jax.jit(lambda x: x + 1)(jnp.zeros(3)).block_until_ready()
        return sweep(self, *a, **kw)
    monkeypatch.setattr(run.Sweep, "__call__", compiling)
    line = drive()
    assert line["correct"] is False
    assert {c["name"]: c["value"] for c in line["checks"]}[
        "compiles_in_window"] > 0
