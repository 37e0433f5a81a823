"""Lock-step rounds (docs/rounds.md): the round gate of flows with
``round_bytes > 0``, its static switch, both engines, the soft path and
its telemetry."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import NetConfig
from repro.netsim import Scenario, fluid, sweep_grid
from repro.netsim.channel import get_channel_model
from repro.netsim.obs import decode_events, read_manifest
from repro.netsim.schemes import get_scheme
from repro.netsim.schemes.dcqcn import DcqcnScheme
from repro.netsim.workload import FlowSpec, Workload, has_rounds

HORIZON_US = 4_000.0
# a round of 1.5 MB every 300 us: at 1 km each round lands within its
# period (releases wait on the period), at 100 km (1 ms RTT) it does not
# (releases wait on delivery)
B, P = 1.5e6, 300.0
DISTANCES = (1.0, 100.0)


def _wl(b=B, p=P, n_intra=2):
    inter = [FlowSpec(True, 1 << 20, 4, start_us=7.0 * i, round_bytes=b,
                      round_period_us=p) for i in range(4)]
    intra = [FlowSpec(False, 256 << 10, 8) for _ in range(n_intra)]
    return Workload(tuple(inter + intra))


def _plain(wl):
    """The same flows without round fields."""
    return Workload(tuple(
        FlowSpec(f.is_inter, f.msg_size, f.concurrency, start_us=f.start_us)
        for f in wl.flows))


def _per_step(cfg, wl, scheme, horizon_us=HORIZON_US, channel=None):
    """A rounds-on run driven step by step: sent, delivered, round_r,
    round_due_us, stall_us and the flow's bytes in the network (``in_net``:
    its queues and the long haul), each [T + 1, F], row 0 the initial
    state."""
    scheme = get_scheme(scheme)
    wlp = jax.tree.map(jnp.asarray, wl.params())
    steps = cfg.horizon_steps(horizon_us)
    state0 = fluid.init_state(cfg, wlp.num_flows, scheme=scheme,
                              channel=get_channel_model(channel),
                              round_wl=wlp)
    step = fluid.make_step_fn(cfg, wlp, scheme, rounds=True,
                              channel=channel)
    keys = ("sent", "delivered", "round_r", "round_due_us", "stall_us",
            "in_net")

    def get(st, k):
        if k == "in_net":
            return st.q_src + st.q_dst + st.q_leaf + st.inflight
        return getattr(st, k)

    def body(st, t):
        new, _ = step(st, t)
        return new, tuple(get(new, k) for k in keys)

    _, tr = jax.jit(lambda s: jax.lax.scan(
        body, s, jnp.arange(steps, dtype=jnp.int32)))(state0)
    first = {k: np.asarray(get(state0, k))[None] for k in keys}
    return {k: np.concatenate([first[k], np.asarray(v)])
            for k, v in zip(keys, tr)}


ALL = ("dcqcn", "pseudo_ack", "themis", "matchrdma", "geopipe", "sdr_rdma",
       "rdmacell")


def _landed(tr, b):
    """[T, F] whether each step's state, before the step, has round R
    landed: at most one MTU of R * b left to send or in the network."""
    r0 = tr["round_r"][:-1]
    return (r0 * np.float32(b) - tr["sent"][:-1]
            + tr["in_net"][:-1]) <= np.float32(fluid.MTU)


@pytest.fixture(scope="module", params=ALL)
def stepped(request):
    return request.param, {
        d: _per_step(NetConfig(distance_km=d), _wl(), request.param)
        for d in DISTANCES}


def test_sent_never_exceeds_the_rounds_released(stepped):
    for tr in stepped[1].values():
        cap = tr["round_r"][:, :4] * B
        assert (tr["sent"][:, :4] <= cap * (1.0 + 1e-6)).all()
        # and the cap binds: the sender waits with all of it sent
        assert (tr["sent"][:, :4] >= cap * (1.0 - 1e-6)).sum() > 10


class _Greedy(DcqcnScheme):
    """dcqcn with a rate law that ignores the window it is handed: the
    NIC's line rate on every step."""

    def sender_rate(self, ctx, state, base_rate):
        return jnp.full_like(base_rate, 400e9 / 8.0)


def test_the_engine_caps_what_any_rate_law_sends():
    tr = _per_step(NetConfig(distance_km=100.0), _wl(n_intra=0), _Greedy())
    cap = tr["round_r"] * B
    assert (tr["sent"] <= cap * (1.0 + 1e-6)).all()
    assert tr["round_r"][-1].min() >= 2


@pytest.mark.parametrize("channel", ["bernoulli_loss", "impaired"])
def test_with_a_lossy_channel_a_round_lands_once_repaired(channel):
    cfg = dataclasses.replace(NetConfig(distance_km=100.0), loss_rate=0.02,
                              loss_burst_len=4.0, jitter_us=20.0)
    tr = _per_step(cfg, _wl(n_intra=0), "dcqcn", channel=channel)
    r0, r1 = tr["round_r"][:-1], tr["round_r"][1:]
    released = r1 > r0
    assert tr["round_r"][-1].min() >= 2
    # lost bytes wait in the repair ledger, not in the queues: a round
    # is released only once they are delivered too
    lag = r0 * np.float32(B) - tr["delivered"][:-1]
    assert np.where(released, lag, 0.0).max() <= 2 * fluid.MTU


def test_a_round_is_released_once_due_and_landed_and_not_before(stepped):
    dt = NetConfig().dt_us
    scheme, runs = stepped
    kinds = {"on_time": 0, "late": 0}
    for tr in runs.values():
        t_us = np.arange(tr["sent"].shape[0] - 1, dtype=np.float32) * dt
        r0, r1 = tr["round_r"][:-1, :4], tr["round_r"][1:, :4]
        due0 = tr["round_due_us"][:-1, :4]
        due = t_us[:, None] >= due0
        landed = _landed(tr, B)[:, :4]
        released = r1 > r0
        np.testing.assert_array_equal(released, due & landed)
        assert ((r1 - r0) <= 1.0).all()
        np.testing.assert_array_equal(
            np.where(released, tr["round_due_us"][1:, :4], 0.0),
            np.where(released, t_us[:, None] + P, 0.0))
        stall = tr["stall_us"][1:, :4] - tr["stall_us"][:-1, :4]
        np.testing.assert_allclose(stall, np.where(due & ~landed, dt, 0.0),
                                   atol=1e-3)
        kinds["late"] += int((released & (t_us[:, None] - dt >= due0)).sum())
        kinds["on_time"] += int((released & (t_us[:, None] - dt < due0)).sum())
        # a released round has landed: delivered, to within the MTU of
        # the gate and the roundoff of the delivered counter
        lag = r0 * np.float32(B) - tr["delivered"][:-1, :4]
        assert (np.where(released, lag, 0.0) <= 2 * fluid.MTU).all()
    # the grid has rounds bound by the period and rounds bound by delivery
    # (the budgets of matchrdma and geopipe open from a quarter of the
    # leaf: at this horizon every one of their rounds waits on delivery)
    assert kinds["late"] > 0, kinds
    assert kinds["on_time"] > 0 or scheme in ("matchrdma", "geopipe"), kinds


@pytest.mark.parametrize("scheme", ["dcqcn", "matchrdma"])
def test_over_a_hundred_rounds_none_is_released_before_the_last_lands(
        scheme):
    # 64 KiB rounds due every 20 us at 1 km: each lands within a few steps,
    # so 4 ms hold well over 100 rounds a flow
    b = 65536.0
    tr = _per_step(NetConfig(distance_km=1.0), _wl(b=b, p=20.0, n_intra=0),
                   scheme)
    r0, r1 = tr["round_r"][:-1], tr["round_r"][1:]
    released = r1 > r0
    assert tr["round_r"][-1].min() > 100
    np.testing.assert_array_equal(
        released, _landed(tr, b)
        & (np.arange(r0.shape[0], dtype=np.float32)[:, None]
           * np.float32(NetConfig().dt_us) >= tr["round_due_us"][:-1]))
    # the gate's slack does not grow with the rounds: every round was
    # delivered to within two MTUs when the next was released
    lag = r0 * np.float32(b) - tr["delivered"][:-1]
    assert np.where(released, lag, 0.0).max() <= 2 * fluid.MTU
    assert (tr["sent"] <= tr["round_r"] * np.float32(b) * (1.0 + 1e-6)).all()


def _rows(scenarios, schemes=("dcqcn", "matchrdma"), **kw):
    return sweep_grid(scenarios, schemes, horizon_us=HORIZON_US,
                      trace_mode="metrics", **kw)


def test_zero_round_bytes_give_the_rounds_off_rows_bit_for_bit():
    wl = _wl(b=0.0)
    assert not has_rounds(wl.params())
    cfgs = [NetConfig(distance_km=d) for d in DISTANCES]
    rows = _rows([Scenario(c, wl) for c in cfgs])
    plain = _rows([Scenario(c, _plain(wl)) for c in cfgs])
    assert repr(rows) == repr(plain)
    assert "round_progress" not in rows[0]


def test_a_cell_without_rounds_in_a_rounds_on_grid_is_unaffected():
    cfgs = [NetConfig(distance_km=d) for d in DISTANCES]
    alone = _rows([Scenario(c, _plain(_wl())) for c in cfgs])
    mixed = _rows([Scenario(cfgs[0], _wl()), Scenario(cfgs[1], _wl())]
                  + [Scenario(c, _plain(_wl())) for c in cfgs])
    n = len(alone)
    got = [{k: v for k, v in r.items() if not k.startswith("round_")}
           for r in mixed[-n:]]
    assert repr(got) == repr(alone)
    assert mixed[-1]["round_progress"] == 0.0
    assert mixed[0]["round_progress"] > 1.0


@pytest.mark.parametrize("scheme", ["dcqcn", "matchrdma"])
def test_simulate_equals_simulate_batch_with_rounds(scheme):
    cfgs = [NetConfig(distance_km=d) for d in DISTANCES]
    pad, hist = fluid.batch_padding(cfgs)
    wl = _wl()
    finals, _ = fluid.simulate_batch(cfgs, wl, get_scheme(scheme),
                                     HORIZON_US, trace_mode="metrics")
    for i, cfg in enumerate(cfgs):
        final, _ = fluid.simulate(cfg, wl, get_scheme(scheme), HORIZON_US,
                                  delay_pad=pad, history_slots=hist,
                                  trace_mode="metrics")
        np.testing.assert_array_equal(np.asarray(final.round_r),
                                      np.asarray(finals.round_r)[i])
        for k in ("sent", "delivered", "stall_us", "round_due_us"):
            np.testing.assert_allclose(np.asarray(getattr(final, k)),
                                       np.asarray(getattr(finals, k))[i],
                                       rtol=1e-6, err_msg=k)


def test_soft_step_with_rounds_raises():
    cfg = NetConfig(distance_km=10.0, soft_step=True)
    with pytest.raises(ValueError, match="round_bytes"):
        fluid.simulate(cfg, _wl(), get_scheme("dcqcn"), HORIZON_US,
                       trace_mode="metrics")
    with pytest.raises(ValueError, match="round_period_us"):
        _rows([Scenario(cfg, _wl())], ("dcqcn",))
    # without rounds the soft step runs
    fluid.simulate(cfg, _plain(_wl()), get_scheme("dcqcn"), 500.0,
                   trace_mode="metrics")


def test_round_events_and_manifest_counters(tmp_path):
    cfgs = [dataclasses.replace(NetConfig(distance_km=d),
                                event_ring_slots=512) for d in DISTANCES]
    finals, aux = fluid.simulate_batch(cfgs, _wl(), get_scheme("dcqcn"),
                                       HORIZON_US, trace_mode="window")
    for i in range(len(cfgs)):
        ev = decode_events(aux.events, 512, cell=i)
        assert int(np.asarray(aux.events.count)[i]) <= 512
        released = sum(e["value"] for e in ev if e["kind"] == "round_release")
        late = sum(e["value"] for e in ev if e["kind"] == "round_late")
        r = np.asarray(finals.round_r)[i, :4]
        assert released == (r - 1.0).sum() > 0
        assert late == np.asarray(finals.round_late)[i, :4].sum()
    _, aux_off = fluid.simulate_batch(cfgs, _plain(_wl()),
                                      get_scheme("dcqcn"), HORIZON_US,
                                      trace_mode="window")
    assert not {e["kind"] for e in decode_events(aux_off.events, 512, cell=0)
                } & {"round_release", "round_late"}

    path = str(tmp_path / "m.jsonl")
    _rows([Scenario(NetConfig(distance_km=d), _wl()) for d in DISTANCES],
          ("dcqcn",), manifest_path=path)
    (launch,) = read_manifest(path)[1]
    assert 0 < launch["rounds_late"] < launch["rounds_released"]
    off = str(tmp_path / "off.jsonl")
    _rows([Scenario(NetConfig(distance_km=1.0), _plain(_wl()))], ("dcqcn",),
          manifest_path=off)
    assert "rounds_released" not in read_manifest(off)[1][0]
