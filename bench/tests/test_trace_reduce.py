"""The trace reduction on small traces built here, whose busy time,
kernel times and labelled idle gaps are known by construction: a group
prefill and a decode step inside one drain of the harness's window."""

import pytest

from bench import trace_reduce as tr

# Host spans of the harness, (start_ns, end_ns, name).
SPANS = [(0, 1000, "bench.window"), (100, 900, "bench.drain"),
         (150, 500, "bench.prefill.m"), (600, 860, "bench.decode.m")]

# Device ops, (start_ns, end_ns, HLO text). The `while` contains the two
# kernels after it; the last copy runs past the window's close.
OPS = [
    (160, 260, "%fusion.1 = bf16[16,256,2048]{2,1,0} fusion(bf16[16,256] %p)"),
    (270, 480, "%while.3 = (s32[]) while(%t)"),
    (280, 380, "%int8_matmul.7 = bf16[4096,2048]{1,0} custom-call(%x)"),
    (380, 470, "%flash_attention_btHd.2 = bf16[16,256,32,64]{3,2,1,0} "
               "custom-call(%q)"),
    (620, 700, "%decode_attention.10 = bf16[16,32,1,64]{3,2,1,0} "
               "custom-call(%q)"),
    (700, 760, "%int8_matmul.8 = bf16[16,2048]{1,0} custom-call(%x)"),
    (950, 1100, "%copy.5 = bf16[16,512,32,64]{3,2,1,0} copy(%c)"),
]


def _plane(pid, name, line, events):
    names = sorted({n for _, _, n in events})
    meta = {n: i + 1 for i, n in enumerate(names)}
    evs = "\n".join(
        f"    events {{ metadata_id: {meta[n]} offset_ps: {s * 1000} "
        f"duration_ps: {(e - s) * 1000} }}" for s, e, n in events)
    md = "\n".join(
        f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in meta.items())
    return (f'planes {{\n  id: {pid}\n  name: "{name}"\n  lines {{\n'
            f'    id: {pid}\n    name: "{line}"\n    timestamp_ns: 0\n'
            f'{evs}\n  }}\n{md}\n}}\n')


def profile(*device_ops):
    """ProfileData of one TPU plane per entry of `device_ops` and one
    host plane holding SPANS."""
    import jax
    text = "".join(_plane(i + 1, f"/device:TPU:{i}", "XLA Ops", ops)
                   for i, ops in enumerate(device_ops))
    text += _plane(len(device_ops) + 1, "/host:CPU", "python", SPANS)
    return jax.profiler.ProfileData.from_text_proto(text)


@pytest.fixture(scope="module")
def red():
    return tr.reduce(profile(OPS))


def test_window_is_the_harness_span(red):
    assert red["window_s"] == pytest.approx(1000e-9)


def test_busy_is_the_union_of_ops_inside_the_window(red):
    # 160-260, 270-480 (the while covers its kernels), 620-760, 950-1000
    assert red["busy_s"] == pytest.approx((100 + 210 + 140 + 50) * 1e-9)


def test_busy_is_averaged_over_devices():
    red2 = tr.reduce(profile(OPS, OPS[:1]))
    assert red2["devices"] == 2
    assert red2["busy_s"] == pytest.approx((500 + 100) / 2 * 1e-9)


def test_kernels_by_name(red):
    assert red["kernel_calls"]["int8_matmul"] == 2
    assert red["kernel_s"]["int8_matmul"] == pytest.approx(160e-9)
    assert red["kernel_s"]["flash_attention_btHd"] == pytest.approx(90e-9)
    assert red["kernel_s"]["decode_attention"] == pytest.approx(80e-9)
    assert red["kernel_s"]["copy"] == pytest.approx(50e-9)
    assert "while" not in red["kernel_s"]


def test_idle_is_attributed_to_spans(red):
    assert red["idle_by_span"] == pytest.approx(
        {"window": 160e-9, "prefill.m": 10e-9, "drain": 140e-9,
         "decode.m": 190e-9})
    assert [lab for lab, _ in red["idle_gaps"]] == [
        "decode.m", "window", "drain", "prefill.m"]
    assert [g for _, g in red["idle_gaps"]] == pytest.approx(
        [190e-9, 160e-9, 140e-9, 10e-9])


def test_device_ops_are_ranked(red):
    times = [t for _, t in red["device_ops"]]
    # every op but the while, each its own label
    assert times == sorted(times, reverse=True) and len(times) == 6
    assert red["device_ops"][0] == ["fusion bf16[16,256,2048]",
                                    pytest.approx(100e-9)]
    assert "int8_matmul bf16[16,2048]" in {n for n, _ in red["device_ops"]}


def test_an_explicit_window_clips_the_ops():
    red = tr.reduce(profile(OPS), window=(200, 400))
    assert red["window_s"] == pytest.approx(200e-9)
    assert red["busy_s"] == pytest.approx((60 + 130) * 1e-9)


def test_op_names():
    ev = "%decode_attention.10 = bf16[8,32,1,64]{3,2,1,0:T(2,128)} custom-call(s32[1] %x)"
    assert tr.op_name(ev) == "decode_attention"
    assert tr.op_label(ev) == "decode_attention bf16[8,32,1,64]"
    assert tr.op_name("%while.4 = (s32[]) while(%t)") == "while"


def test_gap_labels_pick_the_innermost_span():
    spans = [(0, 100, "bench.window"), (10, 40, "bench.drain"),
             (20, 30, "bench.decode.m")]
    got = tr._labels(spans, [5, 15, 25, 35, 60, 200])
    assert got == {5: "window", 15: "drain", 25: "decode.m", 35: "drain",
                   60: "window", 200: "outside spans"}
