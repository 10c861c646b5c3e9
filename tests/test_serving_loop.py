"""Continuous-batching serving loop integration."""

import dataclasses
import gc
import glob
import os

import numpy as np
import pytest

import jax

from repro.configs import reduced_config
from repro.core.selection import ModelProfile
from repro.models import init_params
from repro.serving.engine import InferenceEngine
from repro.serving.batching import Request
from repro.serving.loop import ServingLoop


def _engine(batch_size=2, seed=0):
    cfg = reduced_config("stablelm_1_6b")
    params = init_params(cfg, jax.random.PRNGKey(seed))
    eng = InferenceEngine(cfg, params, batch_size=batch_size, max_seq=32)
    eng.warmup(8)
    return eng


@pytest.fixture(scope="module")
def loop():
    return ServingLoop({"m": _engine()})


def _reqs(n, rng, sla=1e9):
    return [Request(arrival=float(i * 5), rid=i,
                    prompt=rng.integers(0, 50, 6).astype(np.int32),
                    max_new_tokens=3, sla_ms=sla, t_input_ms=5.0)
            for i in range(n)]


def test_loop_serves_all_requests(loop):
    rng = np.random.default_rng(0)
    metrics = loop.run(_reqs(5, rng))
    s = metrics.summary()
    assert s["served"] == 5
    assert s["attainment"] == 1.0  # generous SLA
    assert all(len(r["model"]) for r in metrics.records)
    # every request produced its tokens
    done = loop.batchers["m"].done
    assert all(len(r.tokens) == 3 for r in done)


def test_loop_groups_by_batch_capacity():
    loop = ServingLoop({"m": _engine()})
    rng = np.random.default_rng(1)
    reqs = _reqs(4, rng)
    for r in reqs:
        r.arrival = 0.0  # all at once; batch_size=2 -> 2 groups
    metrics = loop.run(reqs)
    assert metrics.summary()["served"] == 4
    # second group queued behind the first
    q = sorted(r["queue_ms"] for r in metrics.records)
    assert q[-1] > 0.0


def test_loop_routes_with_cnnselect():
    engines = {"fast": _engine(seed=0), "slow": _engine(seed=1)}
    profiles = [ModelProfile("fast", accuracy=0.5, mu=5.0, sigma=1.0),
                ModelProfile("slow", accuracy=0.9, mu=400.0, sigma=10.0)]
    loop = ServingLoop(engines, profiles=profiles, t_threshold=20.0)
    rng = np.random.default_rng(2)
    tight = _reqs(3, rng, sla=40.0)
    loose = _reqs(3, rng, sla=5000.0)
    for i, r in enumerate(loose):
        r.rid = 100 + i
    loop.run(tight + loose)
    by_model = {}
    for rec in loop.metrics.records:
        by_model.setdefault(rec["model"], []).append(rec["rid"])
    # tight SLAs must land on the fast engine
    assert set(by_model.get("fast", [])) >= {0, 1, 2}


def test_loop_adaptive_controller_switches_modes():
    """The loop drives the shared control plane (DESIGN.md §12): with a
    controller attached, a device whose uploads degrade mid-trace is
    escalated live and the per-mode breakdown reports both modes."""
    engines = {"fast": _engine(seed=0), "slow": _engine(seed=1)}
    profiles = [ModelProfile("fast", accuracy=0.5, mu=5.0, sigma=1.0),
                ModelProfile("slow", accuracy=0.9, mu=400.0, sigma=10.0)]
    loop = ServingLoop(engines, profiles=profiles, t_threshold=20.0,
                       controller="reactive")
    rng = np.random.default_rng(3)
    reqs = []
    for i in range(60):
        t_in = 20.0 if i < 30 else 400.0   # mid-trace degradation
        reqs.append(Request(arrival=float(i * 5), rid=i,
                            prompt=rng.integers(0, 50, 6).astype(np.int32),
                            max_new_tokens=2, sla_ms=5000.0,
                            t_input_ms=t_in, device_id="phone"))
    metrics = loop.run(reqs)
    assert metrics.summary()["served"] == 60
    pm = metrics.per_mode()
    assert set(pm) == {"stationary", "degraded"}
    assert pm["stationary"]["served"] + pm["degraded"]["served"] == 60
    assert loop.control.controller.events
    assert loop.control.controller.events[0]["to"] == "degraded"


def test_loop_recorder_captures_run(loop):
    """The ServingLoop recorder hook (DESIGN.md §11): every drained
    request lands in the trace with its outcome and measured exec."""
    from repro.serving.trace import TraceRecorder
    rng = np.random.default_rng(2)
    with TraceRecorder().attach(loop) as rec:
        loop.run(_reqs(4, rng))
    assert loop.recorder is None
    tr = rec.to_trace(source="loop")
    assert len(tr) == 4
    assert (tr.sla_ok == 1).all()           # generous SLA, outcomes known
    assert set(tr.model) == {"m"}
    assert len(tr.meta["exec_ms"]) == 4
    assert all(e > 0 for e in tr.meta["exec_ms"])
    # sla_ms=0 means "no SLA": captured as unknown, not fabricated MET.
    with TraceRecorder().attach(loop) as rec2:
        loop.run(_reqs(2, rng, sla=0.0))
    assert (rec2.to_trace(source="loop").sla_ok == -1).all()


# -- Telemetry: wall-clock stamps, loop counters, engine fetch, spans ---

def _req(rid, arrival, max_new, rng):
    return Request(arrival=arrival, rid=rid,
                   prompt=rng.integers(0, 50, 6).astype(np.int32),
                   max_new_tokens=max_new, sla_ms=1e9, t_input_ms=5.0)


def _served(loop, reqs):
    """Submit every request, drain, and return the loop's counters
    gained meanwhile."""
    before = dataclasses.replace(loop.stats)
    for r in reqs:
        loop.submit(r)
    loop.drain()
    return {k: getattr(loop.stats, k) - getattr(before, k)
            for k in vars(before)}


def _stamps_ordered(r):
    return (r.wall_queued is not None
            and r.wall_queued <= r.wall_start <= r.wall_first
            <= r.wall_finish)


def test_backfill_schedule_counts_and_stamps(loop):
    """Batch 2: a one-token and a four-token request seed the group; the
    first retires at once and the third, queued all along, joins its
    slot mid-group. Every request's wall stamps come in order."""
    rng = np.random.default_rng(4)
    reqs = [_req(900, 0.0, 1, rng), _req(901, 0.0, 4, rng),
            _req(902, 0.0, 2, rng)]
    got = _served(loop, reqs)
    assert got["groups"] == 1 and got["group_rows"] == 2
    assert got["queued_at_group"] == 3 and got["backfill_joins"] == 1
    assert got["sample_s"] > 0 and got["retire_s"] > 0
    assert all(_stamps_ordered(r) for r in reqs)
    # the joiner got its slot after the group was seeded
    assert reqs[2].wall_start > reqs[0].wall_start == reqs[1].wall_start
    assert reqs[0].wall_first == reqs[0].wall_finish


def test_first_group_of_a_drain_takes_only_the_earliest(loop):
    """The replay clock: two requests queued with no protocol time
    given, due 0 and 5 ms apart. The present stays 0, so the drain's
    clock starts at the first arrival, the first group seeds one row and
    the other waits behind it; the counter records both seedings."""
    rng = np.random.default_rng(5)
    reqs = [_req(910, 0.0, 1, rng), _req(911, 5.0, 1, rng)]
    got = _served(loop, reqs)
    assert got["groups"] == 2 and got["group_rows"] == 2
    assert got["queued_at_group"] == 3      # 2 queued, then 1
    assert got["queued_at_group"] - got["group_rows"] == 1
    assert got["backfill_joins"] == 0
    assert all(_stamps_ordered(r) for r in reqs)


@pytest.mark.parametrize("now", [5.0, 50.0])
def test_group_seeds_at_the_protocols_present(loop, now):
    """The same two requests submitted at a protocol time at or past
    both arrivals: both have arrived by then, so one group seeds both
    rows and leaves nothing behind."""
    fresh = ServingLoop({"m": loop.engines["m"]})
    rng = np.random.default_rng(5)
    reqs = [_req(930, 0.0, 1, rng), _req(931, 5.0, 1, rng)]
    for r in reqs:
        fresh.submit(r, now=now)
    fresh.drain()
    s = fresh.stats
    assert s.groups == 1 and s.group_rows == 2
    assert s.queued_at_group - s.group_rows == 0
    assert [r.start_exec for r in reqs] == [now, now]
    assert sorted(rec["queue_ms"] for rec in fresh.metrics.records) == [
        now - 5.0, now]


def test_rounds_with_rising_present_never_start_early(loop):
    """Submit/drain rounds as an open-loop client makes them, `now`
    rising and each request due at or before the `now` it is submitted
    at: no request starts before it arrives, and a drain's first group
    leaves behind only what exceeds the batch."""
    fresh = ServingLoop({"m": loop.engines["m"]})
    batch = loop.engines["m"].batch_size
    rng = np.random.default_rng(8)
    reqs, now, rid = [], 0.0, 940
    for _ in range(5):
        prev, now = now, now + 20.0
        before = dataclasses.replace(fresh.stats)
        n = int(rng.integers(1, 2 * batch + 1))
        for arrival in np.sort(rng.uniform(prev, now, n)):
            r = _req(rid, float(arrival), int(rng.integers(1, 4)), rng)
            fresh.submit(r, now=now)
            reqs.append(r)
            rid += 1
        fresh.drain()
        seeded = fresh.stats.group_rows - before.group_rows
        assert seeded + fresh.stats.backfill_joins \
            - before.backfill_joins == n
        first = [r for r in reqs[-n:] if r.start_exec == now]
        assert len(first) == min(n, batch)
    assert all(len(r.tokens) == r.max_new_tokens for r in reqs)
    assert all(r.start_exec >= r.arrival for r in reqs)
    assert all(r.finish >= r.start_exec for r in reqs)


# (arrival ms, max_new_tokens): three at once at batch 2, so a backfill,
# then arrivals far enough apart that no measured engine time joins them.
_REPLAY = [(0.0, 1), (0.0, 3), (0.0, 2), (1e6, 1), (2e6, 2), (2e6, 1),
           (3e6, 2)]


def test_replay_groups_as_before(loop):
    """`run()` gives no protocol time, so it groups by the replay clock
    exactly as before the present was kept: records (their measured
    `queue_ms`, `exec_ms` and the `e2e_ms` summed from them aside),
    seeding counts and seeding times pinned from the earlier rule."""
    fresh = ServingLoop({"m": loop.engines["m"]})
    rng = np.random.default_rng(7)
    reqs = [_req(i, a, m, rng) for i, (a, m) in enumerate(_REPLAY)]
    metrics = fresh.run(reqs)
    measured = {"queue_ms", "exec_ms", "e2e_ms"}
    got = [{k: v for k, v in rec.items() if k not in measured}
           for rec in metrics.records]
    assert got == [
        {"rid": rid, "model": "m", "device": None, "mode": "static",
         "ok": True, "tenant": None, "accuracy": None, "fallback": False,
         "hedged": False, "replica": None}
        for rid in (0, 2, 1, 3, 5, 4, 6)]
    s = fresh.stats
    assert (s.groups, s.group_rows, s.queued_at_group,
            s.backfill_joins) == (4, 6, 15, 1)
    assert [reqs[i].start_exec for i in (0, 2, 3, 4, 5, 6)] == [
        0.0, 0.0, 1e6, 2e6, 2e6, 3e6]
    assert reqs[1].start_exec > 0.0          # joined by backfill


def test_fetch_time_grows_with_every_engine_call(loop):
    eng = loop.engines["m"]
    toks = np.ones((eng.batch_size, 8), np.int32)
    calls = [lambda: eng.run_prefill(toks),
             lambda: eng.run_decode(toks[:, :1]),
             lambda: eng.prefill_row(toks[0], 1)]
    for call in calls:
        before = dataclasses.replace(eng.stats)
        call()
        assert eng.stats.fetch_time_s > before.fetch_time_s
        n = (eng.stats.prefill_calls + eng.stats.decode_calls
             + eng.stats.backfill_calls)
        assert n == (before.prefill_calls + before.decode_calls
                     + before.backfill_calls + 1)


def test_gc_span_is_installed_once(loop):
    from repro.serving import telemetry
    ServingLoop({"m": loop.engines["m"]})
    assert gc.callbacks.count(telemetry._gc_span) == 1


def _host_spans(log_dir):
    """[(start_ns, end_ns, name)] of every `serve.` host event."""
    path = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    pd = jax.profiler.ProfileData.from_file(path)
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("serve.")]


def test_spans_of_a_traced_run(loop, tmp_path):
    """Under the profiler, the loop's phases and the engine's launch,
    sync and fetch appear as `serve.` host spans, the engine's nested
    inside the loop's prefill span of the candidate."""
    rng = np.random.default_rng(6)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _served(loop, [_req(920, 0.0, 1, rng), _req(921, 0.0, 3, rng),
                       _req(922, 0.0, 2, rng)])
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    names = {n for _, _, n in spans}
    assert names >= {"serve.submit", "serve.drain.m", "serve.group.m",
                     "serve.prefill.m", "serve.decode.m", "serve.backfill.m",
                     "serve.sample", "serve.retire", "serve.launch",
                     "serve.sync", "serve.fetch", "serve.gc"}
    assert not any("bench" in n for n in names)
    prefills = [(s, e) for s, e, n in spans if n == "serve.prefill.m"]
    assert len(prefills) == 1
    s0, e0 = prefills[0]
    inner = [n for s, e, n in spans if s0 <= s and e <= e0 and n !=
             "serve.prefill.m"]
    assert {"serve.launch", "serve.sync", "serve.fetch"} <= set(inner)
    drain = [(s, e) for s, e, n in spans if n == "serve.drain.m"][0]
    assert drain[0] <= s0 and e0 <= drain[1]
