"""The one traffic generator: a mix file of parameters in, a seeded
open-loop schedule of requests out.

Every seed gets the same set of work: the same number of requests, and
the same inter-arrival gaps, prompt and output lengths, upload times
(T_input) and SLA classes, each taken at stratified quantiles of its
distribution. The run's seed draws the order in which each of these is
dealt to the requests, and the prompt token ids (and, in the harness,
the weights). So two seeds differ in arrangement, never in amount.

Mix file (bench/traffic/<name>.json):
  arrivals:       {"process": "poisson", "rate_per_s": r}
  prompt_tokens:  {"min", "max"}  uniform over the whole numbers min..max
  output_tokens:  same
  fleet:          [{"tier", "share", "network": {"mean_ms", "std_ms"}}]
                  T_input lognormal with that mean and std
  sla_classes:    [{"name", "share", "t_sla_ms", "per_token_ms"}]
A request's SLA is t_sla_ms + per_token_ms x its requested output tokens.
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")


@dataclass
class Spec:
    rid: int
    due_ms: float
    prompt: np.ndarray
    max_new_tokens: int
    t_input_ms: float
    sla_ms: float
    tier: str
    sla_class: str


def load_mix(name: str) -> dict:
    with open(os.path.join(TRAFFIC_DIR, f"{name}.json")) as f:
        return json.load(f)


def _quantiles(n: int):
    return (np.arange(n) + 0.5) / n


def lognormal_params(mean, std):
    """(mu, sigma) of the lognormal with this mean and std."""
    s2 = math.log(1.0 + (std / mean) ** 2)
    return math.log(mean) - s2 / 2.0, math.sqrt(s2)


def _t_input_q(mean, std, n):
    mu, s = lognormal_params(mean, std)
    z = np.array([NormalDist().inv_cdf(p) for p in _quantiles(n)])
    return np.exp(mu + s * z)


def _counts(shares, n):
    """Largest-remainder split of n by shares."""
    raw = np.asarray(shares, np.float64) / sum(shares) * n
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out))[:n - out.sum()]:
        out[i] += 1
    return out


def _lengths(d, n, rng):
    lo, hi = int(d["min"]), int(d["max"])
    x = lo + np.floor(_quantiles(n) * (hi - lo + 1)).astype(int)
    return rng.permutation(x)


def schedule(mix: dict, seed: int, seconds: float, vocab: int):
    """The run's requests, in order of due time (ms from window start);
    all are due inside the window."""
    rng = np.random.default_rng([seed, 0])
    ids = np.random.default_rng([seed, 1])
    arr = mix["arrivals"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    rate = float(arr["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = rng.permutation(-np.log(1.0 - _quantiles(n)) / rate)
    due = np.cumsum(gaps)
    due *= seconds * 1e3 * n / (n + 1) / due[-1]
    prompts = _lengths(mix["prompt_tokens"], n, rng)
    outputs = _lengths(mix["output_tokens"], n, rng)

    fleet = mix["fleet"]
    tiers = rng.permutation(np.repeat(np.arange(len(fleet)),
                                      _counts([t["share"] for t in fleet], n)))
    t_input = np.zeros(n)
    for ti, t in enumerate(fleet):
        idx = np.flatnonzero(tiers == ti)
        net = t["network"]
        t_input[idx] = rng.permutation(
            _t_input_q(net["mean_ms"], net["std_ms"], len(idx)))

    classes = mix["sla_classes"]
    cls = rng.permutation(np.repeat(np.arange(len(classes)),
                                    _counts([c["share"] for c in classes], n)))
    out = []
    for i in range(n):
        c = classes[cls[i]]
        new = int(outputs[i])
        out.append(Spec(
            rid=i, due_ms=float(due[i]),
            prompt=ids.integers(0, vocab, int(prompts[i])).astype(np.int32),
            max_new_tokens=new, t_input_ms=float(t_input[i]),
            sla_ms=float(c["t_sla_ms"] + c["per_token_ms"] * new),
            tier=fleet[tiers[i]]["tier"], sla_class=c["name"]))
    return out
