"""Pallas kernels vs pure-jnp oracles, interpret mode, shape/dtype sweeps
(assignment: sweep shapes/dtypes and assert_allclose against ref.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as R
from repro.quant import quantize_int8

SHAPES = [
    # (B, Hq, KV, T, hd, window, cap)
    (1, 2, 2, 32, 16, 0, 0.0),
    (2, 4, 2, 64, 16, 0, 0.0),
    (1, 4, 1, 32, 8, 16, 0.0),     # MQA + window
    (2, 8, 2, 48, 32, 0, 50.0),    # softcap
    (1, 2, 2, 40, 64, 24, 30.0),   # window + softcap
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_flash_attention_vs_ref(shape, dtype, rng):
    B, Hq, KV, T, hd, win, cap = shape
    q = jnp.asarray(rng.normal(size=(B, T, Hq, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, T, KV, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, T, KV, hd)), dtype)
    out = ops.flash_attention_btHd(q, k, v, window=win, softcap=cap,
                                   block_q=16, block_k=16)
    ref = R.flash_attention_ref(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                                jnp.moveaxis(v, 2, 1), window=win, cap=cap)
    ref = jnp.moveaxis(ref, 1, 2)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


def test_flash_attention_nonmultiple_lengths(rng):
    """Padding path: T, S not multiples of the block size."""
    B, Hq, KV, T, hd = 1, 2, 1, 37, 16
    q = jnp.asarray(rng.normal(size=(B, T, Hq, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, KV, hd)), jnp.float32)
    out = ops.flash_attention_btHd(q, k, v, block_q=16, block_k=16)
    ref = R.flash_attention_ref(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                                jnp.moveaxis(v, 2, 1))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.moveaxis(ref, 1, 2)),
                               atol=3e-5)


@pytest.mark.parametrize("ring", [False, True])
@pytest.mark.parametrize("dtype", DTYPES)
def test_decode_attention_vs_ref(ring, dtype, rng):
    B, Hq, KV, S, hd = 2, 4, 2, 64, 16
    cache_pos = 50
    q = jnp.asarray(rng.normal(size=(B, 1, Hq, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), dtype)
    if ring:
        # ring layout: slot s holds position (cache_pos - window + ...) etc.
        pos = jnp.asarray((np.arange(S) + 17) % 61, jnp.int32)
        pos = jnp.where(pos <= cache_pos, pos, -1)
    else:
        pos = jnp.asarray(np.where(np.arange(S) <= cache_pos,
                                   np.arange(S), -1), jnp.int32)
    out = ops.decode_attention(q, k, v, pos, jnp.int32(cache_pos),
                               block_s=16)
    ref = R.decode_attention_ref(q[:, 0], jnp.moveaxis(k, 2, 1),
                                 jnp.moveaxis(v, 2, 1), pos, cache_pos)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out[:, 0], np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


def test_decode_attention_window(rng):
    B, Hq, KV, S, hd = 1, 2, 2, 64, 16
    q = jnp.asarray(rng.normal(size=(B, 1, Hq, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    pos = jnp.asarray(np.arange(S), jnp.int32)
    out = ops.decode_attention(q, k, v, pos, jnp.int32(63), window=16,
                               block_s=16)
    ref = R.decode_attention_ref(q[:, 0], jnp.moveaxis(k, 2, 1),
                                 jnp.moveaxis(v, 2, 1), pos, 63, window=16)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref),
                               atol=2e-5)


# -- valid_from masking (block_q = block_k = 16 everywhere below) -----------
#
# The batch covers every block-boundary case at once: vf=0 (no-op),
# vf=7 (mid-block), vf=16 (exact block edge: block 0 skippable), and a
# fully-masked row (vf past every attendable key -> exact zeros).

def _qkv(rng, B, T, Hq, KV, hd, dtype=jnp.float32):
    q = jnp.asarray(rng.normal(size=(B, T, Hq, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, T, KV, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, T, KV, hd)), dtype)
    return q, k, v


@pytest.mark.parametrize("win,cap", [(0, 0.0), (24, 0.0), (0, 30.0)])
def test_flash_attention_valid_from_vs_ref(win, cap, rng):
    B, Hq, KV, T, hd = 4, 4, 2, 48, 16
    q, k, v = _qkv(rng, B, T, Hq, KV, hd)
    vf = jnp.asarray([0, 7, 16, T], jnp.int32)
    out = ops.flash_attention_btHd(q, k, v, vf, window=win, softcap=cap,
                                   block_q=16, block_k=16)
    ref = R.flash_attention_ref(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                                jnp.moveaxis(v, 2, 1), window=win, cap=cap,
                                valid_from=vf)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.moveaxis(ref, 1, 2)),
                               atol=1e-5, rtol=1e-5)
    # Fully-masked row: every query attends to nothing -> exact zeros.
    assert not np.asarray(out[3]).any()


def test_flash_attention_valid_from_zero_bit_identical(rng):
    """vf=0 must be bitwise equal to the unmasked kernel — engines keep
    one jit trace by always passing an array (PR 7 pin, now in-kernel)."""
    B, Hq, KV, T, hd = 2, 4, 2, 48, 16
    q, k, v = _qkv(rng, B, T, Hq, KV, hd)
    a = ops.flash_attention_btHd(q, k, v, block_q=16, block_k=16)
    b = ops.flash_attention_btHd(q, k, v, jnp.zeros((B,), jnp.int32),
                                 block_q=16, block_k=16)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_flash_attention_valid_from_offset_positions(rng):
    """The ops wrapper rebases absolute valid_from into kernel
    coordinates (pos_k[0] shift) — the backfill prefill_row path."""
    B, Hq, KV, T, hd, off = 1, 2, 2, 32, 16, 64
    q, k, v = _qkv(rng, B, T, Hq, KV, hd)
    pos = jnp.arange(off, off + T, dtype=jnp.int32)
    vf = jnp.asarray([off + 9], jnp.int32)
    out = ops.flash_attention(q, k, v, pos, pos, vf)
    ref = R.flash_attention_ref(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                                jnp.moveaxis(v, 2, 1),
                                valid_from=jnp.asarray([9], jnp.int32))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.moveaxis(ref, 1, 2)),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("ring", [False, True])
def test_decode_attention_valid_from_vs_ref(ring, rng):
    B, Hq, KV, S, hd = 4, 4, 2, 64, 16
    cache_pos = 40
    q = jnp.asarray(rng.normal(size=(B, 1, Hq, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    if ring:
        pos = jnp.asarray((np.arange(S) + 17) % 61, jnp.int32)
        pos = jnp.where(pos <= cache_pos, pos, -1)
    else:
        pos = jnp.asarray(np.where(np.arange(S) <= cache_pos,
                                   np.arange(S), -1), jnp.int32)
    # vf=41 > cache_pos: nothing attendable -> exact zeros.
    vf = jnp.asarray([0, 7, 16, 41], jnp.int32)
    out = ops.decode_attention(q, k, v, pos, jnp.int32(cache_pos), vf,
                               block_s=16, linear=not ring)
    ref = R.decode_attention_ref(q[:, 0], jnp.moveaxis(k, 2, 1),
                                 jnp.moveaxis(v, 2, 1), pos, cache_pos,
                                 valid_from=vf)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    assert not np.asarray(out[3]).any()


def test_decode_attention_valid_from_zero_bit_identical(rng):
    B, Hq, KV, S, hd = 2, 4, 2, 64, 16
    q = jnp.asarray(rng.normal(size=(B, 1, Hq, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    pos = jnp.asarray(np.arange(S), jnp.int32)
    a = ops.decode_attention(q, k, v, pos, jnp.int32(50), block_s=16,
                             linear=True)
    b = ops.decode_attention(q, k, v, pos, jnp.int32(50),
                             jnp.zeros((B,), jnp.int32), block_s=16,
                             linear=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_attention_block_skip_matches_full_scan(rng):
    """linear=True enables the early block skip; the ring path (no skip)
    over the same linear cache must agree to the last ulp — the skipped
    blocks contribute exactly nothing."""
    B, Hq, KV, S, hd = 2, 4, 2, 64, 16
    q = jnp.asarray(rng.normal(size=(B, 1, Hq, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    pos = jnp.asarray(np.arange(S), jnp.int32)
    vf = jnp.asarray([33, 18], jnp.int32)
    skip = ops.decode_attention(q, k, v, pos, jnp.int32(50), vf,
                                block_s=16, linear=True)
    full = ops.decode_attention(q, k, v, pos, jnp.int32(50), vf,
                                block_s=16, linear=False)
    np.testing.assert_array_equal(np.asarray(skip), np.asarray(full))


@pytest.mark.parametrize("mnk", [(32, 48, 64), (64, 80, 96), (16, 16, 128)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_int8_matmul_vs_ref(mnk, dtype, rng):
    M, N, K = mnk
    x = jnp.asarray(rng.normal(size=(M, K)), dtype)
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    wq, sc = quantize_int8(w, axis=0)
    out = ops.int8_matmul(x, wq, sc.reshape(-1), block_m=16, block_n=16,
                          block_k=32)
    ref = R.int8_matmul_ref(x, wq, sc.reshape(-1))
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


# (K, N) of stablelm-1.6b's projections (q/k/v/o, gate/up, down), then
# yi-9b's MLP (d_model 4096, d_ff 11008 = 2^8 * 43).
STABLELM_KN = [(2048, 2048), (2048, 5632), (5632, 2048)]
INT8_KN = STABLELM_KN + [(4096, 11008), (11008, 4096)]


@pytest.mark.parametrize("K,N", INT8_KN)
@pytest.mark.parametrize("M", [4, 16, 256, 4096])
@pytest.mark.parametrize("dtype", DTYPES)
def test_int8_tiles_fit(M, K, N, dtype):
    """The wrapper's own tiles: each divides its dimension, or the
    dimension has no aligned divisor and pads by less than one block;
    nothing pads at stablelm widths; the double-buffered estimate lies
    under the VMEM limit the kernel sets, and that under a v5e's
    128 MiB."""
    from repro.kernels import int8_matmul as K8
    bm, bn, bk = K8.tiles(M, K, N, dtype)
    sublanes = 32 // jnp.dtype(dtype).itemsize
    for d, t, align in ((M, bm, sublanes), (N, bn, 128), (K, bk, 128)):
        assert t == d or t % align == 0
        assert d % t == 0 or d % align, (d, t)
        assert (-d) % t < t
    if (K, N) in STABLELM_KN:
        assert M % bm == 0 and N % bn == 0 and K % bk == 0
    n_k = -(-K // bk)
    need = K8.vmem_bytes(bm, bn, bk, n_k, dtype)
    assert need < K8.vmem_limit(bm, bn, bk, n_k, dtype) <= 128 * 2**20


@pytest.mark.parametrize("K,N", STABLELM_KN)
def test_int8_tiles_traffic_under_compute(K, N):
    """At a 16 x 256 bf16 prefill, the tiles' HBM traffic (x re-read per
    N block, Wq per M block, C written once) takes less time at a v5e's
    819 GB/s than the bf16 MXU needs for the products at 197 TFLOP/s."""
    from repro.kernels import int8_matmul as K8
    M = 4096
    bm, bn, bk = K8.tiles(M, K, N, jnp.bfloat16)
    traffic = M * K * 2 * (N // bn) + K * N * (M // bm) + M * N * 2
    assert traffic / 819e9 < 2 * M * K * N / 197e12


@pytest.mark.parametrize("mnk", [(512, 384, 1408),   # one K block
                                 (512, 384, 2816),   # two K blocks
                                 (16, 1100, 256),    # N pads
                                 (1100, 128, 256)])  # M pads
@pytest.mark.parametrize("dtype", DTYPES)
def test_int8_matmul_own_tiles_vs_ref(mnk, dtype, rng):
    M, N, K = mnk
    x = jnp.asarray(rng.normal(size=(M, K)), dtype)
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    wq, sc = quantize_int8(w, axis=0)
    out = ops.int8_matmul(x, wq, sc.reshape(-1))
    assert out.shape == (M, N) and out.dtype == dtype
    ref = np.asarray(R.int8_matmul_ref(x, wq, sc.reshape(-1)), np.float32)
    # f32 sums of K terms differ by block order: the absolute part of the
    # tolerance is relative to the output's RMS (about sqrt(K)).
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    rms = float(np.sqrt(np.mean(ref ** 2)))
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               atol=tol * rms, rtol=tol)


def test_int8_quantization_error_small(rng):
    """End-to-end: int8 matmul approximates the fp32 matmul (paper Fig 6:
    small accuracy cost for 75% storage saving)."""
    x = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 48)), jnp.float32)
    wq, sc = quantize_int8(w, axis=0)
    exact = x @ w
    approx = ops.int8_matmul(x, wq, sc.reshape(-1), block_m=16, block_n=16,
                             block_k=32)
    rel = float(jnp.linalg.norm(approx - exact) / jnp.linalg.norm(exact))
    assert rel < 0.02
    # storage: int8 + per-col scale vs fp32
    bytes_q = wq.size + 4 * sc.size
    assert bytes_q < 0.27 * (w.size * 4)
