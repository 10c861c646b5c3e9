"""Golden regression harness for the serving simulator.

Pins `SimResult.attainment` / `accuracy` / `mean_latency` for fixed
seeds across every registry policy and two networks, so refactors of
the network/selection/simulator layers cannot silently shift simulator
numbers. The goldens were captured from the pre-NetworkProcess
simulator (PR 1) and reproduced bit-for-bit by the refactor — a change
here must be intentional and called out in CHANGES.md.

Numbers are exact for numpy-driven policies; cnnselect additionally
pins the jax threefry/Gumbel stream, so a jax upgrade that changes RNG
semantics will (by design) trip these tests. The cnnselect rows and
the 10k pin follow the partitionable threefry stream
(`jax_threefry_partitionable`, on by default in JAX 0.9).
"""

import numpy as np
import pytest

from repro.configs.paper_zoo import paper_profiles
from repro.serving.simulator import SimConfig, simulate

SLA_MS = 300.0
N_REQUESTS = 400
SEED = 7

# (network, policy) -> (attainment, accuracy, mean_latency)
GOLDEN = {
    ("campus_wifi", "cnnselect"): (1.0, 0.8156574999999999,
                                   225.83746130757444),
    ("campus_wifi", "greedy"): (0.9675, 0.826, 233.83041029297434),
    ("campus_wifi", "greedy_nw"): (0.995, 0.82514, 232.85234511588246),
    ("campus_wifi", "random"): (1.0, 0.68475, 172.61296963778324),
    ("campus_wifi", "static:mobilenetv1_10"):
        (1.0, 0.718, 149.76329972073734),
    ("campus_wifi", "oracle"): (1.0, 0.8250774999999999,
                                232.74105129718745),
    ("lte", "cnnselect"): (0.92, 0.72182, 252.3465206504882),
    ("lte", "greedy"): (0.6275, 0.826, 293.4034219661994),
    ("lte", "greedy_nw"): (0.895, 0.7849249999999998, 272.0746307820539),
    ("lte", "random"): (0.855, 0.68475, 232.18598131100833),
    ("lte", "static:mobilenetv1_10"): (0.9175, 0.718, 209.33631139396238),
    ("lte", "oracle"): (0.92, 0.7894249999999998, 271.4706502329876),
}


@pytest.mark.parametrize("network,policy", sorted(GOLDEN),
                         ids=lambda v: str(v))
def test_simulator_numbers_pinned(network, policy):
    att, acc, lat = GOLDEN[(network, policy)]
    r = simulate(paper_profiles(), SimConfig(
        t_sla=SLA_MS, n_requests=N_REQUESTS, network=network,
        policy=policy, seed=SEED))
    assert r.attainment == pytest.approx(att, abs=1e-12)
    assert r.accuracy == pytest.approx(acc, abs=1e-12)
    assert r.mean_latency == pytest.approx(lat, abs=1e-9)


def test_fleet_none_is_the_golden_path():
    """`fleet=None` (the default) plus the new hedging/fleet knobs at
    their defaults must be byte-identical to the pinned pre-fleet
    simulator — the golden values above run through exactly this
    config."""
    profs = paper_profiles()
    base = simulate(profs, SimConfig(t_sla=SLA_MS, n_requests=N_REQUESTS,
                                     seed=SEED))
    explicit = simulate(profs, SimConfig(
        t_sla=SLA_MS, n_requests=N_REQUESTS, seed=SEED, fleet=None,
        hedge="none", estimator_lag=0, estimator_scope="device"))
    assert np.array_equal(base.selections, explicit.selections)
    assert np.array_equal(base.latencies, explicit.latencies)
    assert base.fallbacks == explicit.fallbacks == 0


def test_legacy_hedge_at_p95_maps_to_p95_mode():
    """The old boolean knob and hedge="p95" are the same policy."""
    profs = paper_profiles()
    kw = dict(t_sla=SLA_MS, n_requests=300, seed=SEED,
              arrival_rate_hz=30.0, n_servers=2)
    # The legacy boolean now carries a pinned DeprecationWarning
    # (mirroring NetworkModel.estimate_t_input, PR 3).
    with pytest.warns(DeprecationWarning, match="hedge_at_p95"):
        legacy = simulate(profs, SimConfig(**kw, hedge_at_p95=True))
    mode = simulate(profs, SimConfig(**kw, hedge="p95"))
    assert np.array_equal(legacy.latencies, mode.latencies)
    assert legacy.hedges == mode.hedges > 0


def test_estimator_none_is_pre_refactor_path():
    """t_estimator=None must be byte-identical to the legacy observed-
    upload-time budgeting — the explicit 'observed' estimator too."""
    profs = paper_profiles()
    base = simulate(profs, SimConfig(t_sla=SLA_MS, n_requests=N_REQUESTS,
                                     seed=SEED))
    obs = simulate(profs, SimConfig(t_sla=SLA_MS, n_requests=N_REQUESTS,
                                    seed=SEED, t_estimator="observed"))
    assert np.array_equal(base.selections, obs.selections)
    assert np.array_equal(base.latencies, obs.latencies)


def test_estimator_instance_not_mutated_across_runs():
    """simulate() must copy a prebuilt estimator instance — otherwise
    state leaks between runs and identical configs diverge (breaking
    sla_sweep / attainment_improvement determinism)."""
    from repro.serving.network import EWMAEstimator

    profs = paper_profiles()
    est = EWMAEstimator(alpha=0.2)
    cfg = SimConfig(t_sla=SLA_MS, n_requests=200, seed=SEED,
                    network="wifi_lte_handoff", t_estimator=est)
    a = simulate(profs, cfg)
    b = simulate(profs, cfg)
    assert np.array_equal(a.selections, b.selections)
    assert est._est is None              # caller's instance untouched
    assert est.prior is None
    # A prior-less instance gets the same process-mean cold-start prior
    # a string spec would: the two configs are equivalent.
    c = simulate(profs, SimConfig(t_sla=SLA_MS, n_requests=200, seed=SEED,
                                  network="wifi_lte_handoff",
                                  t_estimator="ewma:0.2"))
    assert np.array_equal(a.selections, c.selections)


@pytest.mark.slow
def test_10k_run_statistics_pinned():
    """The full-scale 10k-request run (paper §5.2) — slow suite only."""
    r = simulate(paper_profiles(), SimConfig(
        t_sla=SLA_MS, n_requests=10000, seed=0))
    assert r.attainment == pytest.approx(0.9988, abs=1e-12)
    assert r.accuracy == pytest.approx(0.8092194000000001, abs=1e-12)
    assert r.mean_latency == pytest.approx(228.1411196893983, abs=1e-9)
