"""Operation and byte counts against hand counts at a small shape, and
the peak table."""

import os

import pytest

from bench import flops, harness, peaks

ARCH = flops.Arch(n_layers=2, d_model=8, n_heads=4, n_kv_heads=2,
                  head_dim=2, d_ff=16, vocab=10)


def test_projections_and_params():
    # q 8x8, k 8x4, v 8x4, o 8x8, up 8x16, gate 8x16, down 16x8
    assert ARCH.projections() == [(8, 8), (8, 4), (8, 4), (8, 8), (8, 16),
                                  (8, 16), (16, 8)]
    assert ARCH.layer_matmul_params == 64 + 32 + 32 + 64 + 128 + 128 + 128


def test_token_flops_by_hand():
    # per layer: 2 x 576 projections + 4 x ctx(3) x 4 heads x 2 = 1152 + 96
    per_layer = 2 * 576 + 4 * 3 * 4 * 2
    assert flops.token_flops(ARCH, 3, logits=False) == 2 * per_layer
    assert flops.token_flops(ARCH, 3, logits=True) == 2 * per_layer + 2 * 8 * 10


def test_prompt_flops_is_the_sum_of_its_tokens():
    n = 5
    by_token = sum(flops.token_flops(ARCH, i + 1, logits=False)
                   for i in range(n)) + 2 * 8 * 10
    assert flops.prompt_flops(ARCH, n) == pytest.approx(by_token)


def test_the_counts_readers_ask_are_the_formulas():
    assert ARCH.prompt_ops(5) == flops.prompt_flops(ARCH, 5)
    assert ARCH.token_ops(7) == flops.token_flops(ARCH, 7, logits=True)
    assert ARCH.int8_matmuls() == [(k, n, 2) for k, n in ARCH.projections()]


def test_the_dense_reference_counts_at_published_width():
    cfg = harness.load_json(os.path.join(harness.BENCH, "configs",
                                         "stablelm-1.6b-zoo.json"))
    a = harness.reference_module(cfg).counts(cfg["archs"]["stablelm-1.6b"])
    assert [a.prompt_ops(n) for n in (32, 144, 256)] == [
        79434874880.0, 357603737600.0, 638238851072.0]
    assert a.token_ops(257) == 2927820800.0
    assert a.int8_matmuls() == [(2048, 2048, 24)] * 4 + [
        (2048, 5632, 24)] * 2 + [(5632, 2048, 24)]


def test_int8_matmul_cost_by_hand():
    ops, nbytes = flops.int8_matmul_cost(4, 8, 16)
    assert ops == 2 * 4 * 8 * 16
    assert nbytes == 4 * 8 * 2 + 8 * 16 + 4 * 16 + 4 * 16 * 2


def test_roofline_time_names_its_bound():
    assert flops.roofline_time(10.0, 1.0, 10.0, 10.0) == (1.0, "compute")
    assert flops.roofline_time(1.0, 10.0, 10.0, 10.0) == (1.0, "memory")


def test_peaks_are_keyed_by_device_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["int8_ops"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["source"]
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
