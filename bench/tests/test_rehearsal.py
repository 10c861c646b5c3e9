"""A CPU rehearsal of a whole run at a tiny size: the window loop drives
ServingLoop in interpret mode for a few seconds, the metrics are read
and the served tokens are checked against the reference."""

import math
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import rehearse

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def result():
    """A run in which both candidates served requests. CNNSelect routes
    on latencies measured on the wall clock, which on the CPU are close
    for the two tiny candidates, so a run may send everything to one;
    the next run is then taken."""
    for _ in range(4):
        r = rehearse.run(control=True)
        if not any(c.get("unserved") for c in r["checks"].values()):
            return r
    pytest.fail("four runs in a row served no request on one candidate")


def test_last_line_parses_and_is_correct(result):
    line = rehearse.last_line(result)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    for name in ("engine_ms_per_request", "accuracy_mean", "setup_s"):
        v = line["metrics"][name]["value"]
        assert math.isfinite(v) and v > 0


def test_every_request_is_served_and_stamped(result):
    # the tiny cell's mix: 6 requests/s for 3 s
    assert result["attempted"] == 18
    for c in result["checks"].values():
        assert c["value"] is not None and c["value"] <= c["limit"]


def test_a_candidate_that_served_nothing_is_not_held():
    checks = harness.check_limits({"limits": {"gap_max.a": 1.0,
                                              "gap_max.b": 1.0}},
                                  {"gap_max.a": 0.5}, {"b"})
    assert checks["gap_max.b"] == {"value": None, "limit": 1.0,
                                   "unserved": True}
    assert harness.passed(checks)
    checks["gap_max.a"]["value"] = None
    assert not harness.passed(checks)


def test_one_token_requests_are_checked():
    result = rehearse.run(one_token=True)
    assert result["correct"] is True and result["attempted"] == 18
    assert all(c.get("unserved") or c["value"] is not None
               for c in result["checks"].values())


def test_a_configuration_of_one_candidate_is_served_and_checked():
    """A loop of one engine routes nothing: the harness names the
    candidate on each request itself."""
    result = rehearse.run(only="tiny_bf16")
    assert result["correct"] is True and result["attempted"] == 18
    assert result["metrics"]["accuracy_mean"]["value"] == 1.0
    assert sorted(result["checks"]) == ["gap_max.tiny_bf16",
                                        "gap_mean.tiny_bf16"]
    assert all(c["value"] is not None for c in result["checks"].values())


def test_control_fails_the_limits(result):
    """The int8 candidate's control, the reference computed at int4 in
    its place, is not correct by the cell's limits. (The bf16
    candidate's control, the program's own int8 path, separates from
    bf16 rounding only at the published widths; it is read on the chip,
    see PERF.md.)"""
    checks = result["checks"]
    ctrl = {k: v for k, v in result["control"].items()
            if "int8" in k and k in checks}
    assert ctrl
    assert any(v > checks[k]["limit"] for k, v in ctrl.items())
    assert all(v > checks[k]["limit"] for k, v in ctrl.items()
               if k.startswith("gap_mean"))


def test_run_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        rehearse.CELL, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


def test_a_prompt_window_other_than_the_programs_is_refused():
    """The program keeps the last max_seq // 4 prompt tokens; a
    configuration that gives the reference another window is refused
    before any request is served."""
    from types import SimpleNamespace
    loop = SimpleNamespace(batchers={"a": SimpleNamespace(prompt_len=128)})
    harness.check_prompt_window({"prompt_len": 128}, loop)
    with pytest.raises(ValueError, match="prompt_len"):
        harness.check_prompt_window({"prompt_len": 256}, loop)


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(ROOT, "bench", "configs"))))
def test_each_configuration_matches_the_programs_prompt_window(name):
    cfg = harness.load_json(os.path.join(ROOT, "bench", "configs",
                                         f"{name}.json"))
    assert cfg["max_seq"] // 4 == cfg["prompt_len"]
