"""Hypothesis property tests (selection invariants, Welford vs numpy,
error-feedback quantization, NetworkProcess/TInputEstimator
invariants). Split out of the per-module test files so the tier-1
suite collects cleanly without the optional `hypothesis` dependency
(install via the `test` extra); the plain (example-based) NetworkProcess
tests live in test_network.py."""

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.configs.paper_zoo import NETWORKS, sample_network
from repro.core.profiles import OnlineProfile
from repro.core.selection import ModelProfile, cnnselect
from repro.serving.network import (MIN_T_INPUT_MS, EWMAEstimator,
                                   MarkovProcess, StationaryProcess)


def mk_profiles(mus, sigmas, accs):
    return [ModelProfile(f"m{i}", a, m, s)
            for i, (m, s, a) in enumerate(zip(mus, sigmas, accs))]


# -- CNNSelect invariants (from test_selection.py) -------------------------

@settings(max_examples=200, deadline=None)
@given(
    mus=st.lists(st.floats(1, 1000), min_size=2, max_size=8),
    sigs=st.lists(st.floats(0.1, 100), min_size=8, max_size=8),
    accs=st.lists(st.floats(0.01, 1.0), min_size=8, max_size=8),
    t_sla=st.floats(10, 2000),
    t_input=st.floats(0, 300),
    t_threshold=st.floats(0, 500),
    seed=st.integers(0, 2**31 - 1),
)
def test_properties(mus, sigs, accs, t_sla, t_input, t_threshold, seed):
    k = len(mus)
    profs = mk_profiles(mus, sigs[:k], accs[:k])
    rng = np.random.default_rng(seed)
    r = cnnselect(profs, t_sla, t_input, t_threshold, rng)
    # 1. probabilities form a distribution supported on the eligible set
    assert abs(r.probs.sum() - 1.0) < 1e-6
    assert (r.probs >= 0).all()
    assert r.probs[~r.eligible].sum() < 1e-9
    # 2. the selected model is eligible
    assert r.eligible[r.index]
    # 3. the base model is always eligible
    assert r.eligible[r.base_index]
    # 4. fallback iff stage-1 constraints infeasible
    mu = np.array(mus[:k])
    sg = np.array(sigs[:k])
    feas = (mu + sg < r.t_up) & (mu - sg < r.t_low)
    assert r.fallback == (not feas.any())
    if r.fallback:
        assert r.index == int(np.argmin(mu))
    else:
        # 5. stage-1 base maximizes accuracy among feasible
        acc = np.array(accs[:k])
        assert acc[r.base_index] >= acc[feas].max() - 1e-9


# -- Welford profile store (from test_profiles.py) -------------------------

@settings(max_examples=100, deadline=None)
@given(st.lists(st.floats(-1e6, 1e6), min_size=2, max_size=200))
def test_welford_matches_numpy(xs):
    p = OnlineProfile()
    for x in xs:
        p.update(x)
    np.testing.assert_allclose(p.mean, np.mean(xs), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(p.std, np.std(xs, ddof=1), rtol=1e-5,
                               atol=1e-5)


# -- NetworkProcess invariants (plain variants in test_network.py) ---------

@settings(max_examples=100, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    mean=st.floats(0.5, 400.0),
    std=st.floats(0.01, 200.0),
    n=st.integers(1, 500),
    dist=st.sampled_from(["lognormal", "normal"]),
)
def test_network_process_positive_and_deterministic(seed, mean, std, n,
                                                    dist):
    proc = StationaryProcess("x", mean, std, dist=dist)
    a = proc.sample_t_input(np.random.default_rng(seed), n)
    b = proc.sample_t_input(np.random.default_rng(seed), n)
    assert np.array_equal(a, b)                 # seeded determinism
    assert (a >= MIN_T_INPUT_MS).all()          # unified clamp


@settings(max_examples=50, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    name=st.sampled_from(sorted(NETWORKS)),
    n=st.integers(1, 300),
)
def test_stationary_matches_legacy_draws_bit_for_bit(seed, name, n):
    """StationaryProcess consumes the identical RNG stream as the
    pre-refactor `sample_network`; the only difference is the clamp."""
    legacy = sample_network(name, np.random.default_rng(seed), n)
    proc = StationaryProcess.named(name).sample_t_input(
        np.random.default_rng(seed), n)
    assert np.array_equal(np.maximum(legacy, MIN_T_INPUT_MS), proc)


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    p01=st.floats(0.05, 0.5),
    p10=st.floats(0.05, 0.5),
)
def test_markov_occupancy_converges_to_stationary(seed, p01, p10):
    mk = MarkovProcess([("a", 50.0, 10.0), ("b", 100.0, 20.0)],
                       [[1.0 - p01, p01], [p10, 1.0 - p10]])
    pi = mk.stationary_distribution()
    np.testing.assert_allclose(
        pi, [p10 / (p01 + p10), p01 / (p01 + p10)], atol=1e-8)
    _, reg = mk.sample_trace(np.random.default_rng(seed), 40000)
    occ = np.bincount(reg, minlength=2) / 40000.0
    # Worst-case occupancy std here is ~0.011 (rho = 1-p01-p10 = 0.9);
    # 0.05 is a >4-sigma bound.
    np.testing.assert_allclose(occ, pi, atol=0.05)


@settings(max_examples=50, deadline=None)
@given(
    xs=st.lists(st.floats(1.0, 1e4), min_size=2, max_size=100),
    alpha=st.floats(0.01, 1.0),
    prior=st.floats(1.0, 1e4),
)
def test_ewma_series_causal_and_bounded(xs, alpha, prior):
    xs = np.asarray(xs)
    s = EWMAEstimator(alpha=alpha, prior=prior).estimate_series(xs)
    # Cold start answers the prior; every estimate is a convex
    # combination of the prior and past observations.
    assert s[0] == prior
    lo, hi = min(prior, xs.min()), max(prior, xs.max())
    tol = 1e-6 * max(1.0, hi)        # blocked closed-form round-off
    assert ((s >= lo - tol) & (s <= hi + tol)).all()
    # Causality: changing the last observation cannot move any earlier
    # estimate (identical float ops -> bitwise equality).
    mutated = xs.copy()
    mutated[-1] = 12345.0
    s2 = EWMAEstimator(alpha=alpha, prior=prior).estimate_series(mutated)
    assert np.array_equal(s[:-1], s2[:-1])


# -- Change-point detector calibration (serving/control.py, §12) -----------

@settings(max_examples=100, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    n=st.integers(50, 400),
    kind=st.sampled_from(["cusum", "ph"]),
)
def test_detector_false_positive_rate_on_stationary_stream(seed, n, kind):
    """Calibration: on standardized stationary residuals the default
    thresholds alarm at most once per 400 observations (the in-control
    ARL is ~70k+ for cusum h=10 k=0.5; empirically 12/3000 streams of
    400 see one alarm, none see two)."""
    from repro.serving.control import CusumDetector, PageHinkleyDetector

    det = (CusumDetector(threshold=10.0, drift=0.5, scale=1.0)
           if kind == "cusum"
           else PageHinkleyDetector(threshold=12.0, delta=0.5,
                                    scale=1.0))
    draws = np.random.default_rng(seed).normal(0.0, 1.0, n)
    alarms = sum(det.update(float(z)) != 0 for z in draws)
    assert alarms <= 1


@settings(max_examples=100, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    prefix=st.integers(0, 200),
    shift=st.floats(3.0, 8.0),
)
def test_detector_bounded_delay_on_injected_mean_step(seed, prefix,
                                                      shift):
    """Calibration: a >=3-sigma injected mean step fires the up-alarm
    within 30 post-shift observations (empirical worst case over 3000
    seeds: 7), regardless of the stationary prefix length."""
    from repro.serving.control import CusumDetector

    det = CusumDetector(threshold=10.0, drift=0.5, scale=1.0)
    rng = np.random.default_rng(seed)
    for z in rng.normal(0.0, 1.0, prefix):
        det.update(float(z))
    post = rng.normal(shift, 1.0, 30)
    assert any(det.update(float(z)) == 1 for z in post)


# -- Trace codec round trip (serving/trace.py, DESIGN.md §11) --------------

_trace_strategy = st.integers(1, 40).flatmap(lambda n: st.fixed_dictionaries({
    "t_arrival": st.lists(st.floats(0, 1e7, allow_nan=False,
                                    allow_infinity=False),
                          min_size=n, max_size=n),
    "device_id": st.lists(st.text(
        alphabet=st.characters(min_codepoint=33, max_codepoint=126),
        max_size=12), min_size=n, max_size=n),
    "t_input_ms": st.lists(st.floats(1e-3, 1e6, allow_nan=False,
                                     allow_infinity=False,
                                     exclude_min=True),
                           min_size=n, max_size=n),
    "regime_id": st.lists(st.integers(0, 5), min_size=n, max_size=n),
    "model": st.lists(st.text(
        alphabet=st.characters(min_codepoint=33, max_codepoint=126),
        max_size=12), min_size=n, max_size=n),
    "sla_ok": st.lists(st.sampled_from([-1, 0, 1]), min_size=n,
                       max_size=n),
}))


@settings(max_examples=60, deadline=None)
@given(cols=_trace_strategy, ext=st.sampled_from(["jsonl", "npz"]),
       name=st.text(max_size=16), seed=st.integers(0, 2**31 - 1))
def test_trace_codec_roundtrip_bit_exact(cols, ext, name, seed):
    """Any valid trace survives save/load bit-exact through both
    codecs (json float text is shortest-repr, which parses back to the
    identical double)."""
    import tempfile

    from repro.serving.trace import Trace

    tr = Trace(regime_names=[f"r{k}" for k in range(6)], name=name,
               source="property", meta={"seed": seed}, **cols)
    with tempfile.TemporaryDirectory() as d:
        path = f"{d}/t.{ext}"
        tr.save(path)
        back = Trace.load(path)
    for col in ("t_arrival", "device_id", "t_input_ms", "regime_id",
                "model", "sla_ok"):
        assert np.array_equal(getattr(tr, col), getattr(back, col)), col
    assert back.regime_names == tr.regime_names
    assert (back.name, back.source, back.meta) == (tr.name, tr.source,
                                                   tr.meta)
    assert back.schema_version == tr.schema_version


@settings(max_examples=30, deadline=None)
@given(cols=_trace_strategy, bad_schema=st.integers(-5, 100))
def test_trace_schema_mismatch_fails_fast(cols, bad_schema):
    import json as _json
    import tempfile

    from repro.serving.trace import TRACE_SCHEMA_VERSION, Trace

    hypothesis.assume(bad_schema != TRACE_SCHEMA_VERSION)
    tr = Trace(regime_names=[f"r{k}" for k in range(6)], **cols)
    with tempfile.TemporaryDirectory() as d:
        path = f"{d}/t.jsonl"
        tr.save(path)
        with open(path) as f:
            lines = f.read().splitlines()
        header = _json.loads(lines[0])
        header["schema"] = bad_schema
        with open(path, "w") as f:
            f.write("\n".join([_json.dumps(header)] + lines[1:]) + "\n")
        with pytest.raises(ValueError, match="schema version"):
            Trace.load(path)


# -- int8 error feedback (from test_quant.py) ------------------------------

@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 1000), steps=st.integers(2, 30))
def test_error_feedback_unbiased_accumulation(seed, steps):
    """sum of dequantized ef-compressed xs tracks sum of xs: the residual
    absorbs the quantization error instead of letting it accumulate."""
    import jax.numpy as jnp

    from repro.quant import dequantize_int8, ef_compress

    rng = np.random.default_rng(seed)
    shape = (8, 16)
    resid = jnp.zeros(shape, jnp.float32)
    total_true = np.zeros(shape, np.float32)
    total_sent = np.zeros(shape, np.float32)
    for _ in range(steps):
        x = jnp.asarray(rng.normal(size=shape), jnp.float32)
        q, s, resid = ef_compress(x, resid)
        total_true += np.asarray(x)
        total_sent += np.asarray(dequantize_int8(q, s))
    # Residual bounds the drift: |sum_true - sum_sent| == |resid|
    np.testing.assert_allclose(total_true - total_sent, np.asarray(resid),
                               atol=1e-4)
    assert float(np.abs(np.asarray(resid)).max()) < 0.1  # one-step error


# -- masked flash kernel vs naive attention (from test_kernels.py) ---------

@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    b=st.integers(1, 3),
    t=st.integers(4, 24),
    window=st.sampled_from([0, 8]),
    edge=st.booleans(),
)
def test_flash_valid_from_matches_naive(seed, b, t, window, edge):
    """flash(valid_from) == naive(valid_from) for arbitrary per-row
    valid_from in [0, T] — including rows masked past every key (exact
    zeros) and, when edge, values pinned to block boundaries so the
    early-skip path is exercised."""
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.models.layers import attention_naive

    rng = np.random.default_rng(seed)
    hq, kv, hd = 4, 2, 8
    q = jnp.asarray(rng.normal(size=(b, t, hq, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, kv, hd)), jnp.float32)
    vf_np = rng.integers(0, t + 1, size=b)
    if edge:
        vf_np = np.minimum((vf_np // 8) * 8, t)
    vf = jnp.asarray(vf_np, jnp.int32)
    pos = jnp.arange(t, dtype=jnp.int32)
    flash = ops.flash_attention_btHd(q, k, v, vf, window=window,
                                     block_q=8, block_k=8)
    naive = attention_naive(q, k, v, pos, pos, window=window, cap=0.0,
                            scale=hd ** -0.5, valid_from=vf)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(naive),
                               atol=2e-5, rtol=2e-5)


# -- continuous batcher slot lifecycle (from test_serving.py) --------------

@settings(max_examples=60, deadline=None)
@given(
    batch_size=st.integers(1, 4),
    specs=st.lists(
        st.tuples(st.floats(0, 50, allow_nan=False, allow_infinity=False),
                  st.integers(1, 5)),
        min_size=1, max_size=16),
    budget=st.one_of(st.none(), st.integers(1, 8)),
)
def test_batcher_slot_lifecycle(batch_size, specs, budget):
    """Arbitrary arrival schedules: the form_group -> decode ->
    backfill loop retires every request exactly once with its full
    token quota, never double-books a slot, never starts a request
    before it arrives, and defers over-budget joiners rather than
    dropping them. The harness (shared with the deterministic
    test_serving tests, so the logic runs without hypothesis too)
    asserts the invariants every round."""
    from test_serving import drive_batcher

    drive_batcher(batch_size, 4, specs, budget)
