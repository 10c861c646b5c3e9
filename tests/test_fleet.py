"""Device-fleet layer tests (serving/fleet.py + simulator integration):
per-device trace determinism, EstimatorBank isolation / lag semantics,
outage-aware hedging firing exactly once per request, on-device
fallback accounting, and integer-keyed `ArrayFleet` populations."""

import numpy as np
import pytest

from repro.configs.paper_zoo import paper_profiles
from repro.core.selection import on_device_fallback_decision
from repro.serving.cluster import Cluster, make_tenant_workload
from repro.serving.fleet import (ArrayFleet, DeviceProfile, EstimatorBank,
                                 FleetMixture, device_tier_profile,
                                 make_fleet)
from repro.serving.network import MIN_T_INPUT_MS, EWMAEstimator
from repro.serving.simulator import SimConfig, simulate
from repro.serving.stack import SimReplicaStack


def _two_device_fleet(net_b="lte"):
    return FleetMixture([
        DeviceProfile("a", "campus_wifi", weight=0.5),
        DeviceProfile("b", net_b, weight=0.5),
    ])


# -- FleetMixture -----------------------------------------------------------

def test_fleet_trace_determinism_under_fixed_seed():
    fl = _two_device_fleet()
    t1 = fl.sample_trace(np.random.default_rng(7), 2000)
    t2 = fl.sample_trace(np.random.default_rng(7), 2000)
    assert np.array_equal(t1.t_input, t2.t_input)
    assert np.array_equal(t1.device_index, t2.device_index)
    assert np.array_equal(t1.regime, t2.regime)
    assert (t1.t_input >= MIN_T_INPUT_MS).all()


def test_fleet_per_device_streams_are_independent():
    """Changing device B's process must not change device A's draws
    (same seed, same weights -> same assignment, same A stream)."""
    t_lte = _two_device_fleet("lte").sample_trace(
        np.random.default_rng(7), 3000)
    t_hot = _two_device_fleet("cellular_hotspot").sample_trace(
        np.random.default_rng(7), 3000)
    assert np.array_equal(t_lte.device_index, t_hot.device_index)
    a = t_lte.device_index == 0
    assert np.array_equal(t_lte.t_input[a], t_hot.t_input[a])
    assert not np.array_equal(t_lte.t_input[~a], t_hot.t_input[~a])


def test_fleet_regime_names_are_device_prefixed_and_global():
    fl = FleetMixture([DeviceProfile("a", "campus_wifi"),
                       DeviceProfile("b", "lte_outages")])
    names = fl.regime_names()
    assert names[0] == "a:campus_wifi"
    assert names[1:] == ["b:lte", "b:degraded_lte", "b:outage"]
    tr = fl.sample_trace(np.random.default_rng(0), 5000)
    # Device a's requests sit in regime 0; b's occupy the offset block.
    assert (tr.regime[tr.device_index == 0] == 0).all()
    assert (tr.regime[tr.device_index == 1] >= 1).all()
    assert tr.regime.max() < len(names)


def test_fleet_validation_and_priors():
    with pytest.raises(ValueError):
        FleetMixture([])
    with pytest.raises(ValueError):
        FleetMixture([DeviceProfile("a", "lte"),
                      DeviceProfile("a", "campus_wifi")])
    with pytest.raises(ValueError):
        FleetMixture([DeviceProfile("a", "lte", weight=0.0)])
    fl = _two_device_fleet()
    assert fl.priors() == {"a": 63.0, "b": 95.0}
    assert fl.mean == pytest.approx(0.5 * 63.0 + 0.5 * 95.0)


def test_make_fleet_resolution():
    fl = make_fleet("lte_outage_fleet")
    assert [d.tier for d in fl.devices] == ["flagship", "midrange",
                                            "legacy"]
    assert fl.devices[1].network == "lte_outages"     # scenario override
    assert fl.devices[1].on_device_ms == 133.0        # pixel2 mnv1_025
    assert fl.devices[1].on_device_accuracy == pytest.approx(0.497)
    assert fl.devices[2].on_device_ms == 0.0          # legacy: no local CNN
    assert make_fleet(fl) is fl
    assert make_fleet(None) is None
    with pytest.raises(ValueError):
        make_fleet("no_such_fleet")
    with pytest.raises(ValueError):
        device_tier_profile("no_such_tier")


# -- EstimatorBank ----------------------------------------------------------

def test_bank_isolates_devices():
    """One device's outage must not move another device's estimate."""
    bank = EstimatorBank("ewma:0.2", priors={"a": 60.0, "b": 90.0})
    for _ in range(50):
        bank.observe("a", 900.0)          # device a collapses
    assert bank.estimate("a") > 500.0
    assert bank.estimate("b") == 90.0     # b still answers its prior
    bank.observe("b", 100.0)
    assert bank.estimate("b") == 100.0


def test_bank_series_matches_scalar_protocol():
    rng = np.random.default_rng(5)
    xs = rng.lognormal(4.0, 0.4, 400)
    keys = rng.choice(["a", "b", "c"], size=400)
    for spec, lag in (("ewma:0.3", 0), ("ewma:0.3", 1), ("ewma:0.3", 3),
                      ("pctl:85", 1), ("mean", 2), ("ewma:1.0", 1)):
        fast = EstimatorBank(spec, default_prior=55.0, lag=lag)
        out_fast = fast.estimate_series(xs, keys)
        slow = EstimatorBank(spec, default_prior=55.0, lag=lag)
        out_slow = np.empty_like(xs)
        for i, (x, k) in enumerate(zip(xs, keys)):
            out_slow[i] = slow.estimate(k, observed=float(x))
            slow.observe(k, float(x))
        np.testing.assert_allclose(out_fast, out_slow, rtol=1e-9,
                                   err_msg=f"{spec} lag={lag}")


def test_bank_series_streaming_continues_state():
    """Two estimate_series calls must equal one concatenated call
    (pending lag observations carry across the boundary)."""
    xs = np.random.default_rng(1).lognormal(4.0, 0.3, 100)
    keys = ["a"] * 100
    whole = EstimatorBank("ewma:0.4", default_prior=50.0, lag=2)
    ref = whole.estimate_series(xs, keys)
    split = EstimatorBank("ewma:0.4", default_prior=50.0, lag=2)
    got = np.concatenate([split.estimate_series(xs[:37], keys[:37]),
                          split.estimate_series(xs[37:], keys[37:])])
    np.testing.assert_allclose(got, ref, rtol=1e-9)


def test_bank_lag_shifts_observations():
    """lag=1 (ModiPick client-side view): the estimate at position i
    uses observations up to i-2 only."""
    xs = np.array([10.0, 20.0, 40.0, 80.0])
    lag0 = EstimatorBank("ewma:1.0", default_prior=5.0)
    np.testing.assert_allclose(lag0.estimate_series(xs, ["d"] * 4),
                               [5.0, 10.0, 20.0, 40.0])
    lag1 = EstimatorBank("ewma:1.0", default_prior=5.0, lag=1)
    np.testing.assert_allclose(lag1.estimate_series(xs, ["d"] * 4),
                               [5.0, 5.0, 10.0, 20.0])


def test_bank_guards():
    with pytest.raises(ValueError):
        EstimatorBank("observed", lag=1)       # undefined under staleness
    with pytest.raises(ValueError):
        EstimatorBank("ewma:0.2", lag=-1)
    with pytest.raises(ValueError):
        EstimatorBank("ewma:0.2", lag=1).estimate("a")   # no prior
    with pytest.raises(ValueError):
        EstimatorBank(EstimatorBank())         # no nesting
    # A prototype instance is copied per device, prior filled in.
    proto = EWMAEstimator(alpha=0.5)
    bank = EstimatorBank(proto, priors={"a": 40.0})
    assert bank.estimate("a") == 40.0
    bank.observe("a", 100.0)
    assert bank.estimate("a") == 100.0
    assert proto._est is None and proto.prior is None


# -- simulator integration --------------------------------------------------

def test_on_device_fallback_decision_boundaries():
    # Viable locally, cloud infeasible -> fallback.
    assert on_device_fallback_decision(300.0, 200.0, 25.0, 150.0)
    # Cloud feasible -> stay in the cloud.
    assert not on_device_fallback_decision(300.0, 50.0, 25.0, 150.0)
    # Device too slow for the SLA -> no fallback even in an outage.
    assert not on_device_fallback_decision(300.0, 900.0, 25.0, 400.0)
    # No on-device capability (0) -> never.
    assert not on_device_fallback_decision(300.0, 900.0, 25.0, 0.0)
    out = on_device_fallback_decision(
        300.0, np.array([200.0, 50.0]), 25.0, np.array([150.0, 150.0]))
    assert out.tolist() == [True, False]


def test_outage_hedge_fires_exactly_once_per_request():
    """Open loop on two replicas with fallback disabled: every degraded
    cloud-served request hedges exactly once — the hedge counter equals
    the degraded count, never more."""
    r = simulate(paper_profiles(), SimConfig(
        t_sla=350.0, n_requests=1500, seed=3, fleet="lte_outage_fleet",
        t_estimator="ewma:0.2", hedge="outage", on_device_fallback=False,
        arrival_rate_hz=12.0, n_servers=2))
    assert r.fallbacks == 0 and (r.selections >= 0).all()
    assert r.degraded is not None and r.degraded.any()
    assert r.hedges == int(r.degraded.sum())


def test_fallback_accounting():
    r = simulate(paper_profiles(), SimConfig(
        t_sla=350.0, n_requests=1500, seed=3, fleet="lte_outage_fleet",
        t_estimator="ewma:0.2", hedge="outage"))
    assert r.fallbacks == int((r.selections < 0).sum()) > 0
    fb = r.selections < 0
    # Fallbacks only on devices with an on-device profile, and they are
    # charged the device's on-device latency/accuracy.
    fl = make_fleet("lte_outage_fleet")
    od_ms = np.array([d.on_device_ms for d in fl.devices])[r.device_index]
    od_acc = np.array([d.on_device_accuracy
                       for d in fl.devices])[r.device_index]
    assert (od_ms[fb] > 0).all()
    np.testing.assert_allclose(r.accuracies[fb], od_acc[fb])
    assert r.latencies[fb].mean() < 200.0      # pixel2 mnv1_025 ~133ms
    hist = r.selection_histogram([p.name for p in paper_profiles()])
    assert hist["<on-device>"] == pytest.approx(fb.mean())


def test_outage_mode_beats_p95_for_degraded_tier():
    """The acceptance contrast: under lte_outage_fleet the midrange
    tier (radio = lte_outages) attains more under outage-aware
    hedging/fallback than under the p95-only knob."""
    base = dict(t_sla=350.0, n_requests=2000, seed=3,
                fleet="lte_outage_fleet", t_estimator="ewma:0.2",
                arrival_rate_hz=12.0, n_servers=2)
    p95 = simulate(paper_profiles(), SimConfig(**base, hedge="p95"))
    out = simulate(paper_profiles(), SimConfig(**base, hedge="outage"))
    assert (out.per_device()["midrange"]["attainment"]
            > p95.per_device()["midrange"]["attainment"])


def test_fleet_sim_deterministic_and_device_reported():
    cfg = SimConfig(t_sla=320.0, n_requests=800, seed=11,
                    fleet="mixed_fleet", t_estimator="ewma:0.2")
    a = simulate(paper_profiles(), cfg)
    b = simulate(paper_profiles(), cfg)
    assert np.array_equal(a.selections, b.selections)
    assert np.array_equal(a.latencies, b.latencies)
    pd = a.per_device()
    assert set(pd) == {"flagship", "midrange", "budget"}
    assert sum(v["share"] for v in pd.values()) == pytest.approx(1.0)


def test_estimator_scope_global_collapses_bank():
    """estimator_scope='global' must equal a fleet whose every request
    keys one shared estimator (the pre-fleet strawman)."""
    cfg = SimConfig(t_sla=320.0, n_requests=600, seed=2,
                    fleet="mixed_fleet", t_estimator="ewma:0.2",
                    estimator_scope="global")
    r = simulate(paper_profiles(), cfg)
    dev = simulate(paper_profiles(), SimConfig(
        t_sla=320.0, n_requests=600, seed=2, fleet="mixed_fleet",
        t_estimator="ewma:0.2"))
    assert not np.array_equal(r.selections, dev.selections)
    with pytest.raises(ValueError):
        simulate(paper_profiles(), SimConfig(
            t_sla=320.0, n_requests=10, fleet="mixed_fleet",
            t_estimator="ewma:0.2", estimator_scope="nope"))


def test_hedge_knob_validation():
    with pytest.raises(ValueError):
        simulate(paper_profiles(), SimConfig(t_sla=300.0, n_requests=10,
                                             hedge="sometimes"))
    with pytest.warns(DeprecationWarning, match="hedge_at_p95"), \
            pytest.raises(ValueError):
        simulate(paper_profiles(), SimConfig(t_sla=300.0, n_requests=10,
                                             hedge="outage",
                                             hedge_at_p95=True))


def test_estimator_bank_parses_spec_once(monkeypatch):
    """Regression: the bank must parse its spec string exactly once and
    stamp per-device estimators from the parsed factory — re-parsing on
    every cold device is an O(fleet) cost."""
    import repro.serving.fleet as fleet_mod
    calls = []
    real = fleet_mod.estimator_factory

    def counting(spec, **kw):
        calls.append(spec)
        return real(spec, **kw)

    monkeypatch.setattr(fleet_mod, "estimator_factory", counting)
    bank = EstimatorBank("ewma:0.3", default_prior=50.0)
    for key in range(64):
        bank.observe(key, 10.0 + key)
        bank.estimate(key)
    assert calls == ["ewma:0.3"]
    assert len(bank.keys()) == 64


# -- ArrayFleet -------------------------------------------------------------

def test_array_fleet_protocol():
    fl = make_fleet("array:40:3")
    assert make_fleet("array:40:3") is fl          # cached per spec
    assert np.array_equal(fl.prior_array(),
                          ArrayFleet(40, seed=3).prior_array())
    assert fl.priors() == dict(enumerate(fl.prior_array()))
    tr = fl.sample_trace(np.random.default_rng(0), 200)
    assert tr.device_ids is None
    assert 0 <= tr.device_index.min() and tr.device_index.max() < 40
    assert (tr.t_input >= MIN_T_INPUT_MS).all()
    with pytest.raises(ValueError):
        make_fleet("array:40:3:9")


def test_array_fleet_serves_through_simulate_and_cluster():
    """Integer-keyed devices run the per-device estimator banks and
    controller of `simulate()`, and the tenant columns of `Cluster`."""
    cfg = SimConfig(t_sla=350.0, n_requests=300, seed=5,
                    fleet=ArrayFleet(30, seed=2), policy="greedy_nw",
                    controller="reactive", estimator_lag=1)
    a = simulate(paper_profiles(), cfg)
    b = simulate(paper_profiles(), cfg)
    assert np.array_equal(a.selections, b.selections)
    assert np.array_equal(a.latencies, b.latencies)
    assert a.device_ids is None and a.device_index.max() < 30
    mix = [dict(tenant="t", sla_class="gold", fleet="array:30:2")]
    reqs = make_tenant_workload(mix, n_requests=120, rate_hz=20.0, seed=1)
    assert {r.device_id.split("/")[0] for r in reqs} == {"t"}
    cl = Cluster([SimReplicaStack(paper_profiles(), seed=4)], mix)
    assert len(cl.run(reqs).records) == 120
