"""Device idle time by the program's `serve.*` spans, on a small trace
whose answer is known by construction: one drain of the program inside
the harness's window, a group prefill and a decode step, a garbage
collection while retiring, and an idle stretch under `bench.wait` with
no program span open."""

import pytest

from bench import program_spans as ps
from bench import trace_reduce as tr
from bench.tests.test_trace_reduce import _plane

SPANS = [
    (0, 1000, "bench.window"), (100, 900, "bench.drain"),
    (110, 890, "serve.drain.m"), (120, 140, "serve.group.m"),
    (150, 500, "serve.prefill.m"), (150, 500, "bench.prefill.m"),
    (155, 170, "serve.launch"), (170, 480, "serve.sync"),
    (480, 495, "serve.fetch"), (500, 510, "serve.sample"),
    (510, 520, "serve.retire"), (515, 518, "serve.gc"),
    (600, 860, "serve.decode.m"), (600, 615, "serve.launch"),
    (615, 840, "serve.sync"), (840, 855, "serve.fetch"),
    (900, 1000, "bench.wait"),
]
OPS = [(160, 260, "%fusion.1 = bf16[16,256]{1,0} fusion(%p)"),
       (270, 470, "%int8_matmul.7 = bf16[16,64]{1,0} custom-call(%x)"),
       (620, 700, "%decode_attention.10 = bf16[16,1]{1,0} custom-call(%q)"),
       (700, 760, "%int8_matmul.8 = bf16[16,64]{1,0} custom-call(%x)"),
       (950, 1100, "%copy.5 = bf16[16,64]{1,0} copy(%c)")]

# Idle gaps: 0-160, 260-270, 470-620, 760-950 (ns), split at the spans.
WANT = {ps.OUTSIDE: 170, "drain.m": 130, "group.m": 20, "prefill.m": 10,
        "launch": 20, "sync": 105, "fetch": 30, "sample": 10,
        "retire": 7, "gc": 3, "decode.m": 5}


def profile(*device_ops):
    import jax
    text = "".join(_plane(i + 1, f"/device:TPU:{i}", "XLA Ops", ops)
                   for i, ops in enumerate(device_ops))
    text += _plane(len(device_ops) + 1, "/host:CPU", "python", SPANS)
    return jax.profiler.ProfileData.from_text_proto(text)


@pytest.fixture(scope="module")
def pd():
    return profile(OPS)


def test_idle_by_innermost_program_span(pd):
    got = ps.attribute(pd)
    assert got["idle_by_program_span"] == pytest.approx(
        {k: v * 1e-9 for k, v in WANT.items()})
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["spans"] == 13


def test_idle_outside_program_spans_is_not_the_programs(pd):
    """The gap under `bench.wait` (900-950) and the idle before the
    drain opened count as idle, not as idle the program held."""
    got = ps.attribute(pd)
    assert got["idle_host_s"] == pytest.approx(340e-9)
    assert ps.device_idle_host(got) == pytest.approx(34.0)


def test_the_parts_add_up_to_the_idle_reduce_reports(pd):
    red = tr.reduce(pd)
    got = ps.attribute(pd)
    idle = red["window_s"] - red["busy_s"]
    assert got["idle_s"] == pytest.approx(idle, abs=1e-6)
    assert sum(got["idle_by_program_span"].values()) == pytest.approx(
        sum(red["idle_by_span"].values()), abs=1e-6)
    assert got["idle_host_s"] <= idle
    assert ps.device_idle_host(got) <= 100.0 * idle / red["window_s"]


def test_idle_is_averaged_over_devices():
    # the second device runs only the first op: idle 0-160 and 260-1000
    got = ps.attribute(profile(OPS, OPS[:1]))
    one = ps.attribute(profile(OPS[:1]))
    assert got["idle_s"] == pytest.approx((510 + 900) / 2 * 1e-9)
    assert got["idle_host_s"] == pytest.approx(
        (340e-9 + one["idle_host_s"]) / 2)


def test_an_explicit_window_clips_the_gaps(pd):
    # 450-650: busy 450-470 and 620-650; idle 470-620 as above
    got = ps.attribute(pd, window=(450, 650))
    assert got["idle_s"] == pytest.approx(150e-9)
    assert got["idle_by_program_span"][ps.OUTSIDE] == 0


def test_segments_take_the_innermost_span():
    spans = [(0, 100, "serve.drain.m"), (10, 40, "serve.prefill.m"),
             (10, 20, "serve.launch"), (20, 40, "serve.sync"),
             (60, 70, "serve.gc"), (200, 210, "serve.submit")]
    assert ps.labelled_segments(spans) == [
        (0, 10, "drain.m"), (10, 20, "launch"), (20, 40, "sync"),
        (40, 60, "drain.m"), (60, 70, "gc"), (70, 100, "drain.m"),
        (200, 210, "submit")]


def test_no_device_plane_is_an_error():
    import jax
    pd = jax.profiler.ProfileData.from_text_proto(
        _plane(1, "/host:CPU", "python", SPANS))
    with pytest.raises(ValueError, match="device plane"):
        ps.attribute(pd)
