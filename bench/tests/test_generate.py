"""The traffic generator: a seed changes the order, not the work."""

import json
import os

import numpy as np
import pytest

from bench import generate

MIXES = sorted(f[:-5] for f in os.listdir(generate.TRAFFIC_DIR)
               if f.endswith(".json"))
TINY = os.path.join(os.path.dirname(__file__), "tiny-cell.json")


def mixes():
    with open(TINY) as f:
        tiny = json.load(f)["mix"]
    return [generate.load_mix(m) for m in MIXES] + [tiny]


def work(s):
    """The schedule as multisets: gaps, lengths, uploads and classes
    (a request's SLA follows from its class and output length)."""
    due = [0.0] + [x.due_ms for x in s]
    return ([round(b - a, 6) for a, b in zip(due, due[1:])],
            [len(x.prompt) for x in s], [x.max_new_tokens for x in s],
            [round(x.t_input_ms, 6) for x in s],
            [x.tier for x in s], [x.sla_class for x in s])


def same_work(a, b):
    return all(sorted(x) == sorted(y) for x, y in zip(work(a), work(b)))


@pytest.mark.parametrize("m", mixes())
def test_same_seed_same_schedule(m):
    a = generate.schedule(m, 2 ** 31 + 11, 10.0, 64000)
    b = generate.schedule(m, 2 ** 31 + 11, 10.0, 64000)
    assert [(x.due_ms, x.max_new_tokens, x.t_input_ms, x.sla_ms)
            for x in a] == [(x.due_ms, x.max_new_tokens, x.t_input_ms,
                             x.sla_ms) for x in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


@pytest.mark.parametrize("m", mixes())
def test_seeds_share_the_work_in_another_order(m):
    """Two seeds deal the same gaps, lengths, uploads and classes, in
    another order, with other prompt ids."""
    a = generate.schedule(m, 3, 10.0, 64000)
    b = generate.schedule(m, 2 ** 33 + 5, 10.0, 64000)
    assert len(a) == len(b) == round(m["arrivals"]["rate_per_s"] * 10)
    assert same_work(a, b)
    order = lambda s: [(len(x.prompt), x.t_input_ms) for x in s]
    assert order(a) != order(b)
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b)
               if len(x.prompt) == len(y.prompt))


@pytest.mark.parametrize("m", mixes())
def test_lengths_are_uniform(m):
    """Lengths are the uniform distribution's quantiles: a long schedule
    holds every length of the range about equally often."""
    s = generate.schedule(m, 1, 4000.0 / m["arrivals"]["rate_per_s"], 1000)
    for key, got in (("prompt_tokens", [len(x.prompt) for x in s]),
                     ("output_tokens", [x.max_new_tokens for x in s])):
        lo, hi = m[key]["min"], m[key]["max"]
        counts = np.bincount(np.asarray(got) - lo, minlength=hi - lo + 1)
        assert len(counts) == hi - lo + 1
        assert counts.max() - counts.min() <= 1


@pytest.mark.parametrize("m", mixes())
def test_requests_fit_the_mix(m):
    s = generate.schedule(m, 7, 10.0, 500)
    due = [x.due_ms for x in s]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 10_000
    p, o = m["prompt_tokens"], m["output_tokens"]
    assert all(p["min"] <= len(x.prompt) <= p["max"] for x in s)
    assert all(o["min"] <= x.max_new_tokens <= o["max"] for x in s)
    assert all(0 <= t < 500 for x in s for t in x.prompt)
    assert all(x.t_input_ms > 0 for x in s)
    classes = {c["name"]: c for c in m["sla_classes"]}
    for x in s:
        c = classes[x.sla_class]
        assert x.sla_ms == c["t_sla_ms"] + c["per_token_ms"] * x.max_new_tokens


def test_lognormal_matches_mean_and_std():
    mu, s = generate.lognormal_params(95.0, 35.0)
    mean = np.exp(mu + s * s / 2)
    std = np.sqrt((np.exp(s * s) - 1) * np.exp(2 * mu + s * s))
    assert mean == pytest.approx(95.0) and std == pytest.approx(35.0)
