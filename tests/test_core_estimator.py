"""Slot history + slot-weighted / periodic rate estimation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import NetConfig
from repro.core.estimator import periodic_estimate, slot_weighted_estimate
from repro.core.matchrdma import init_matchrdma, maybe_slot_update
from repro.core.slots import (
    SlotObs, SlotRing, classify_slot, init_ring, ordered_history, push_slot,
)

CFG = NetConfig()


def _obs(rate, ack=1.0, cnp=0.0, q=0.0):
    return SlotObs(egress_rate=jnp.float32(rate), ack_delay_us=jnp.float32(ack),
                   cnp_count=jnp.float32(cnp), local_queue=jnp.float32(q))


def _fill(ring, rates, **kw):
    for r in rates:
        ring = push_slot(ring, _obs(r, **kw), CFG)
    return ring


def test_classify_slot_levels():
    assert float(classify_slot(_obs(1.0), CFG)) == 0.0
    assert float(classify_slot(_obs(1.0, ack=100.0), CFG)) == 1.0
    assert float(classify_slot(_obs(1.0, ack=100.0, cnp=3.0), CFG)) == 2.0
    assert float(classify_slot(_obs(1.0, ack=100.0, cnp=3.0, q=1e9), CFG)) == 3.0


def test_ring_ordering_and_validity():
    ring = init_ring(16)
    ring = _fill(ring, range(20))            # wraps
    rates, cong, busy, valid = ordered_history(ring)
    assert float(valid.min()) == 1.0         # fully wrapped => all valid
    np.testing.assert_allclose(np.asarray(rates), np.arange(4, 20))


def test_partial_ring_validity():
    ring = init_ring(16)
    ring = _fill(ring, [5.0] * 4)
    _, _, _, valid = ordered_history(ring)
    assert float(valid.sum()) == 4.0


def test_stable_windows_weighted_higher():
    """History = old jittery low-rate slots + recent stable high-rate windows;
    the weighted estimate must sit near the stable rate."""
    ring = init_ring(32)
    rng = np.random.default_rng(0)
    jitter = 50.0 + 45.0 * rng.standard_normal(16)           # CV >> thresh
    ring = _fill(ring, jitter.tolist())
    ring = _fill(ring, [100.0] * 16)                          # stable
    est = slot_weighted_estimate(ring, CFG)
    assert abs(float(est.rate) - 100.0) < 15.0
    assert float(est.stable_frac) >= 0.5


def test_capability_only_from_busy_slots():
    ring = init_ring(32)
    ring = _fill(ring, [10.0] * 16, q=0.0)                    # idle: low egress
    ring = _fill(ring, [90.0] * 16, q=1e9)                    # busy: capability
    est = slot_weighted_estimate(ring, CFG)
    assert float(est.have_capability) == 1.0
    assert abs(float(est.capability) - 90.0) < 1.0
    # the plain estimate blends both
    assert float(est.rate) < 90.0


def test_periodic_predictor_fires_on_recurrence():
    """Rates repeat with period 16 slots; the predictor should forecast the
    NEXT phase's rates rather than the blended mean."""
    cfg = NetConfig()
    period = 16
    pattern = [100.0] * 8 + [20.0] * 8
    ring = init_ring(64)
    ring = _fill(ring, pattern * 4)
    est = periodic_estimate(ring, cfg, period_slots=period)
    assert float(est.recurrent) == 1.0
    # current window = the 20.0 phase; next-phase forecast = 100.0
    assert abs(float(est.rate) - 100.0) < 1.0


def test_periodic_predictor_falls_back_without_recurrence():
    cfg = NetConfig()
    rng = np.random.default_rng(1)
    ring = init_ring(64)
    ring = _fill(ring, rng.uniform(10, 200, 64).tolist())
    est = periodic_estimate(ring, cfg, period_slots=16)
    base = slot_weighted_estimate(ring, cfg)
    if float(est.recurrent) == 0.0:
        np.testing.assert_allclose(float(est.rate), float(base.rate), rtol=1e-6)


# ---------------------------------------------------------------------------
# Shift-register layout: the stored order IS the old ring's oldest-first view
# ---------------------------------------------------------------------------

def _old_ring_view(rates, congested, busy, r):
    """NumPy model of the write-index ring this layout replaced: slot k goes
    to position k % R, and the oldest-first view is x[(idx + arange(R)) % R]."""
    ring = {k: np.zeros(r, np.float32) for k in ("rates", "congested", "busy")}
    idx = 0
    for k, slot in enumerate(zip(rates, congested, busy)):
        for name, v in zip(("rates", "congested", "busy"), slot):
            ring[name][idx] = v
        idx = (idx + 1) % r
    order = (idx + np.arange(r)) % r
    valid = (np.arange(r) >= r - min(len(rates), r)).astype(np.float32)
    return (ring["rates"][order], ring["congested"][order],
            ring["busy"][order], valid)


def _slot_stream(n, seed):
    """n slots' observations: rates of period 16 with noise (so the periodic
    predictor can fire), ACK delay, CNPs and queue crossing their thresholds
    now and then. Returns the obs arrays and the model's flags."""
    rng = np.random.default_rng(seed)
    base = np.tile(np.r_[np.full(8, 100.0), np.full(8, 20.0)], n // 16 + 1)[:n]
    rates = (base * (1.0 + 0.01 * rng.standard_normal(n))).astype(np.float32)
    ack = rng.uniform(0.0, 2.0 * CFG.ack_delay_thresh_us, n).astype(np.float32)
    cnp = (rng.uniform(0.0, 1.0, n) < 0.2).astype(np.float32)
    q = rng.uniform(0.0, 2.0 * CFG.queue_thresh_kb * 1024.0, n).astype(np.float32)
    busy = (q > CFG.queue_thresh_kb * 1024.0).astype(np.float32)
    congested = ((ack > CFG.ack_delay_thresh_us) | (cnp > CFG.cnp_freq_thresh)
                 | (busy > 0)).astype(np.float32)
    return (rates, ack, cnp, q), congested, busy


def _push_all(ring, obs_arrays, active=None):
    """Push every slot of the stream through ``push_slot`` in a scan; where
    ``active`` is False the slot is not pushed (per-lane counts under vmap)."""
    rates, ack, cnp, q = obs_arrays
    if active is None:
        active = jnp.ones(rates.shape[0], bool)

    def body(ring, x):
        o, on = x
        new = push_slot(ring, SlotObs(*o), CFG)
        return jax.tree.map(lambda a, b: jnp.where(on, a, b), new, ring), None

    ring, _ = jax.lax.scan(body, ring, ((rates, ack, cnp, q), active))
    return ring


def _as_ring(view, count):
    rates, congested, busy, _ = (jnp.asarray(v) for v in view)
    return SlotRing(rates=rates, congested=congested, busy=busy,
                    count=jnp.asarray(count, jnp.int32))


def _estimates(ring):
    return (slot_weighted_estimate(ring, CFG),
            periodic_estimate(ring, CFG, period_slots=16))


def _assert_tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


PUSH_COUNTS = {"0": lambda r: 0, "1": lambda r: 1, "R-1": lambda r: r - 1,
               "R": lambda r: r, "R+1": lambda r: r + 1,
               "3R+5": lambda r: 3 * r + 5}


@pytest.mark.parametrize("r", [16, 208])
@pytest.mark.parametrize("pushes", list(PUSH_COUNTS))
def test_shift_register_matches_old_ring(r, pushes):
    n = PUSH_COUNTS[pushes](r)
    obs, congested, busy = _slot_stream(n, seed=r * 1000 + n)
    ring = _push_all(init_ring(r), tuple(jnp.asarray(a) for a in obs))

    # (a) the stored order is the old ring's oldest-first view, bit for bit
    old = _old_ring_view(obs[0], congested, busy, r)
    for got, want in zip(ordered_history(ring), old):
        np.testing.assert_array_equal(np.asarray(got), want)
    assert int(ring.count) == n

    # (b) the estimators read the same as when fed the old ordering
    _assert_tree_equal(_estimates(ring), _estimates(_as_ring(old, n)))

    # (c) both hold under vmap with a different push count per lane
    counts = [n, n // 2, max(n - 1, 0), 0]
    lanes = [_slot_stream(n, seed=r * 1000 + n + 1 + i)
             for i in range(len(counts))]
    obs_b = tuple(jnp.asarray(np.stack([ln[0][k] for ln in lanes]))
                  for k in range(4))
    active = jnp.asarray(np.arange(n)[None, :] < np.asarray(counts)[:, None])
    rings = jax.vmap(lambda o, a: _push_all(init_ring(r), o, a))(obs_b, active)
    olds = [_old_ring_view(ln[0][0][:c], ln[1][:c], ln[2][:c], r)
            for ln, c in zip(lanes, counts)]
    got_b = jax.vmap(ordered_history)(rings)
    for k in range(4):
        np.testing.assert_array_equal(np.asarray(got_b[k]),
                                      np.stack([o[k] for o in olds]))
    np.testing.assert_array_equal(np.asarray(rings.count), counts)
    old_b = SlotRing(*(jnp.asarray(np.stack([o[k] for o in olds]))
                       for k in range(3)),
                     count=jnp.asarray(counts, jnp.int32))
    _assert_tree_equal(jax.vmap(_estimates)(rings), jax.vmap(_estimates)(old_b))


def _primitives(jaxpr):
    """Every primitive name in a jaxpr and the jaxprs nested in it."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for v in eqn.params.values():
            for x in (v if isinstance(v, (list, tuple)) else (v,)):
                if hasattr(x, "jaxpr") or hasattr(x, "eqns"):
                    yield from _primitives(x)


def _push_then_estimate(ring, rate):
    ring = push_slot(ring, _obs(rate), CFG)
    return ordered_history(ring), slot_weighted_estimate(ring, CFG)


@pytest.mark.parametrize("fn", ["push_then_estimate", "maybe_slot_update"])
def test_batched_slot_history_has_no_gather_or_scatter(fn):
    """Per-cell slot histories under vmap read and write in place: a ring
    with a per-cell index would lower to a batched gather and scatter."""
    b = 3
    if fn == "push_then_estimate":
        f = _push_then_estimate
        args = (jax.vmap(lambda _: init_ring(208))(jnp.arange(b)),
                jnp.ones(b, jnp.float32))
    else:
        def f(state, step_idx):
            return maybe_slot_update(state, CFG, step_idx)
        args = (jax.vmap(lambda _: init_matchrdma(CFG, 4))(jnp.arange(b)),
                jnp.arange(b))
    prims = set(_primitives(jax.make_jaxpr(jax.vmap(f))(*args)))
    moved = {p for p in prims if "gather" in p or "scatter" in p}
    assert not moved, moved
    assert "concatenate" in prims                      # the shift is traced
