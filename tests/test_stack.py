"""ServingStack conformance suite (DESIGN.md §16).

One behavioural contract run against every implementation —
`CNNSelectServer` (batch-of-one), `ServingLoop` (continuous batching),
`SimReplicaStack` (simulated replica), and `Cluster` (the composite):
protocol shape, submit -> metrics round trip, tenant tagging, and the
observe_outcome feedback path. Plus the deprecation pins for the
pre-unification metrics aliases and `Router.enqueue`, and the
`BlockNormals` pins (blocked draws are the scalar gaussian stream).
"""

import warnings
from dataclasses import replace

import numpy as np
import pytest

import jax

from repro.configs import reduced_config
from repro.configs.paper_zoo import paper_profiles
from repro.models import init_params
from repro.serving.batching import Request
from repro.serving.cluster import Cluster
from repro.serving.engine import InferenceEngine
from repro.serving.loop import ServingLoop
from repro.serving.metrics import ServingMetrics
from repro.serving.router import Router
from repro.serving.server import CNNSelectServer, ServedModel
from repro.serving.stack import (BlockNormals, ServingStack,
                                 SimReplicaStack, StackOutcome)

MODELS = ["mobilenetv1_025", "mobilenetv1_10"]


@pytest.fixture(scope="module")
def engine():
    cfg = reduced_config("stablelm_1_6b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(cfg, params, batch_size=2, max_seq=32)
    eng.warmup(8)
    return eng


def _make_stack(kind, engine):
    if kind == "server":
        s = CNNSelectServer(
            [ServedModel("a", engine, 0.9),
             ServedModel("b", engine, 0.8)], t_threshold=10.0,
            n_tokens=2)
        s.profile_models(prompt_len=8, reps=1)
        return s
    if kind == "loop":
        profs = [replace(p, name=n) for p, n in
                 zip(paper_profiles(MODELS), ("a", "b"))]
        return ServingLoop({"a": engine, "b": engine}, profiles=profs,
                           t_threshold=10.0)
    if kind == "sim":
        return SimReplicaStack(paper_profiles(MODELS), seed=7)
    if kind == "cluster":
        return Cluster(
            [SimReplicaStack(paper_profiles(MODELS), seed=7 + i)
             for i in range(2)],
            [{"tenant": "t0", "sla_class": "bronze"}])
    raise AssertionError(kind)


def _req(rid=0, tenant="t0", arrival=0.0):
    return Request(arrival=arrival, rid=rid,
                   prompt=np.zeros(4, np.int32), max_new_tokens=2,
                   sla_ms=1e6, t_input_ms=5.0,
                   device_id=f"{tenant}/dev", tenant=tenant)


KINDS = ["server", "loop", "sim", "cluster"]


@pytest.mark.parametrize("kind", KINDS)
def test_stack_protocol_shape(kind, engine):
    s = _make_stack(kind, engine)
    assert isinstance(s, ServingStack)
    assert isinstance(s.metrics, ServingMetrics)


@pytest.mark.parametrize("kind", KINDS)
def test_stack_submit_metrics_round_trip(kind, engine):
    s = _make_stack(kind, engine)
    outs = [s.submit(_req(i, arrival=float(5 * i)), now=float(5 * i))
            for i in range(3)]
    s.drain()
    assert all(isinstance(o, StackOutcome) for o in outs)
    assert s.metrics.served == 3
    # Outcomes resolve either inline or at drain — never silently.
    for o in outs:
        assert o.pending or o.ok is not None
    for rec in s.metrics.records:
        assert rec["model"]
        assert rec["ok"] is not None
        assert rec["e2e_ms"] >= 2 * 5.0       # 2*T_input floor
    # Unified summary schema.
    sm = s.metrics.summary()
    for key in ("served", "attainment", "mean_ms", "p95_ms",
                "selections"):
        assert key in sm
    assert sm["served"] == 3


@pytest.mark.parametrize("kind", KINDS)
def test_stack_tenant_tagging(kind, engine):
    s = _make_stack(kind, engine)
    s.submit(_req(0, tenant="t0"))
    s.drain()
    assert [r["tenant"] for r in s.metrics.records] == ["t0"]
    assert "t0" in s.metrics.per_tenant()


@pytest.mark.parametrize("kind", KINDS)
def test_stack_observe_outcome(kind, engine):
    # The feedback path must accept measured latencies without a prior
    # submit (the cluster fans it to replicas that never saw the req).
    s = _make_stack(kind, engine)
    name = "a" if kind in ("server", "loop") else MODELS[0]
    s.observe_outcome(name, 12.5)
    s.observe_outcome(name, 14.0, cold=True, now=1.0)


# -- deprecation pins ------------------------------------------------------

def test_metrics_aliases_warn():
    m = ServingMetrics()
    m.add(_req(0), "a", 1.0, 2.0)
    for name, repl in [("latencies_ms", "records"),
                       ("accuracies", "records"),
                       ("selections", "summary()['selections']"),
                       ("by_device", "per_device()"),
                       ("by_mode", "per_mode()")]:
        with pytest.deprecated_call(
                match=f"ServingMetrics.{name} is deprecated"):
            getattr(m, name)
    # The aliases still return the old shapes.
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        assert m.latencies_ms == [m.records[0]["e2e_ms"]]
        assert m.selections == {"a": 1}
        assert m.by_mode == {"static": 1}


def test_router_enqueue_warns():
    r = Router(paper_profiles(MODELS), t_threshold=10.0)
    with pytest.deprecated_call(match="Router.enqueue is deprecated"):
        r.enqueue(_req(0), MODELS[0])
    # Deprecated path still admits: the request reached the queue.
    assert len(r.queues[MODELS[0]]) == 1


# -- BlockNormals (SimReplicaStack's gaussian stream) -----------------------

def test_blocknormals_matches_scalar_stream():
    ref = np.random.default_rng(123)
    bn = BlockNormals(np.random.default_rng(123), block=7)
    locs = np.random.default_rng(1).uniform(-50, 50, 300)
    scales = np.random.default_rng(2).uniform(0.1, 20, 300)
    for loc, scale in zip(locs, scales):
        assert bn.normal(loc, scale) == ref.normal(loc, scale)


def test_blocknormals_refills_across_blocks_like_scalars():
    """Block refills consume the generator exactly like scalar draws:
    after whole blocks the two generators hold the same state, and the
    draws either side of each boundary match bit for bit."""
    ref = np.random.default_rng(9)
    bn = BlockNormals(np.random.default_rng(9), block=5)
    got = [bn.normal(), *[bn.normal() for _ in range(13)],
           bn.normal(2.0, 3.0), *[bn.normal() for _ in range(5)]]
    want = [ref.normal(), *[ref.normal() for _ in range(13)],
            ref.normal(2.0, 3.0), *[ref.normal() for _ in range(5)]]
    assert got == want
    assert bn.gen.bit_generator.state == ref.bit_generator.state
    assert bn.normal(-1.0, 0.5) == ref.normal(-1.0, 0.5)
