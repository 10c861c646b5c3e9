"""The readers of the program's own counters and stamps: on a window
built by hand, on one whose program has none of them (they then read
nothing), and on a CPU rehearsal of the tiny cell, where the group
fill agrees with the loop's own count."""

import time
from types import SimpleNamespace

import pytest
from repro.serving.engine import EngineStats

from bench import harness
from bench.tests import rehearse

NEW = ("queue_wait_ms", "rows_per_prefill", "logits_fetch_ms")


def request(queued, start, n_tokens=1, max_new=1):
    return SimpleNamespace(model="tiny_bf16", prompt=[1, 2], arrival=0.0,
                           max_new_tokens=max_new, tokens=[0] * n_tokens,
                           wall_queued=queued, wall_start=start)


def read(ctx):
    entries = [m for m in harness.metric_entries(rehearse.CELL, True)
               if m["name"] in NEW]
    assert {m["name"] for m in entries} == set(NEW)
    return {k: v["value"] for k, v in harness.read_metrics(entries,
                                                           ctx).items()}


def context(requests, stats):
    served = harness.Served(requests=requests, t0=0.0, seconds=2.0)
    return harness.Context(workload="tiny", cfg=None, mix=None,
                           served=served, stats=stats, setup_s=1.0)


def test_readings_by_construction():
    # three group-seeded requests, one backfill joiner, one unfinished
    reqs = [request(10.0, 10.1), request(10.0, 10.1), request(10.2, 10.5),
            request(11.0, 11.3, n_tokens=2, max_new=2),
            request(12.0, 12.9, n_tokens=0, max_new=2)]
    stats = {"tiny_bf16": EngineStats(prefill_calls=2, decode_calls=3,
                                      backfill_calls=1, fetch_time_s=0.012),
             "tiny_int8": EngineStats(prefill_calls=1, fetch_time_s=0.004)}
    got = read(context(reqs, stats))
    # finished: waits 100, 100, 300, 300 ms
    assert got["queue_wait_ms"] == pytest.approx(200.0)
    # five started, one of them joined mid-group, over three prefills
    assert got["rows_per_prefill"] == pytest.approx(4 / 3)
    # 16 ms of copies over 7 engine calls
    assert got["logits_fetch_ms"] == pytest.approx(16.0 / 7)


def test_a_program_without_stamps_or_fetch_counter_reads_nothing():
    reqs = [SimpleNamespace(model="tiny_bf16", prompt=[1], arrival=0.0,
                            max_new_tokens=1, tokens=[0])]
    old = SimpleNamespace(prefill_calls=1, decode_calls=0, backfill_calls=0,
                          prefill_time_s=0.1)
    assert read(context(reqs, {"tiny_bf16": old})) == {}


def test_rehearsal_reads_the_program(monkeypatch):
    """A CPU run of the tiny cell: every request is stamped, and the
    group fill read from the stamps and the engine counters is the
    loop's own count of rows over groups."""
    kept = {}
    setup = harness.setup

    def keep(*a, **kw):
        engines, loop = setup(*a, **kw)
        kept["loop"] = loop
        return engines, loop

    monkeypatch.setattr(harness, "setup", keep)
    cfg, cell = rehearse.tiny()
    entries = [m for m in harness.metric_entries(rehearse.CELL, True)
               if m["name"] in NEW]
    result = harness.run("tiny", 2 ** 31 + 99, 3.0, False,
                         t_start=time.perf_counter(), cfg=cfg, cell=cell,
                         entries=entries)
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(got) == set(NEW)
    st = kept["loop"].stats
    assert st.groups > 0
    assert got["rows_per_prefill"] == pytest.approx(st.group_rows / st.groups)
    assert st.group_rows + st.backfill_joins == result["attempted"]
    assert got["queue_wait_ms"] >= 0 and got["logits_fetch_ms"] > 0
    assert st.queued_at_group >= st.group_rows
