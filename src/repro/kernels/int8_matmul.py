"""int8-weight matmul Pallas TPU kernel (paper Fig 6: 8-bit post-training
quantization, adapted to TPU serving).

C[M,N] = X[M,K] @ (Wq[K,N] * scale[N])   with Wq int8, per-output-channel
fp32 scales: int8 weights, activations in their own float dtype (W8A16
for the bf16 served path).

Dtype rule: each weight tile is converted on-chip to the activation's
dtype, once per grid step (through f32, which every TPU generation
converts to), and the activation tile is fed to the MXU as it arrives,
never cast down. Every int8 value is exact in bf16, so a bf16 x bf16
dot with f32 accumulation (`preferred_element_type`) forms the same
products as an f32 one; f32 activations keep the f32 path. The scale is
applied once per output tile at flush, not per K block.

Tile rule (`tiles`): from the shape and dtype alone. Each of M, N and K
is taken whole up to a cap (1024 rows, 1024 output columns, 2048
contracted), else as the largest tile under the cap that divides it,
a multiple of 128 (of the dtype's sublane count for M); only a
dimension with no such divisor pads, by less than one block. So the
stablelm-1.6b widths (2048, 5632 = 11 * 512) never pad, decode
(M = batch) and the backfill row (M 256) take M whole, and a 4096-row
prefill takes bm 1024. Grid (nM, nN, nK), K innermost; where K is one
block the x tile stays in VMEM across the N sweep and no accumulator
is kept. `vmem_limit_bytes` is raised over the 16 MiB scoped default to
cover the double-buffered tiles (`vmem_limit`).

Ceiling: the MXU runs W8A16 at the bf16 peak (197 TFLOP/s on a v5e),
half the int8 peak (393 TOP/s) that int8 x int8 would reach, so this
kernel reads at most 50% of an int8 roofline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SCOPED_VMEM = 16 * 2**20     # Mosaic's default scoped VMEM limit


def tile(d: int, cap: int, align: int) -> int:
    """d itself if it fits under cap; else the largest multiple of align
    under cap that divides d; else the fewest blocks of at most cap,
    rounded up to align, so that d pads by less than one block."""
    if d <= cap:
        return d
    for t in range(cap - cap % align, 0, -align):
        if d % t == 0:
            return t
    per_block = -(-d // -(-d // cap))
    return -(-per_block // align) * align


def tiles(M: int, K: int, N: int, dtype) -> tuple[int, int, int]:
    """(bm, bn, bk) for x (M, K) of `dtype` against Wq (K, N)."""
    sublanes = 32 // jnp.dtype(dtype).itemsize
    return tile(M, 1024, sublanes), tile(N, 1024, 128), tile(K, 2048, 128)


def vmem_bytes(bm: int, bn: int, bk: int, n_k: int, dtype) -> int:
    """VMEM the kernel asks for: double-buffered x, Wq, scale (padded to
    8 sublanes) and output blocks, the weight tile's f32 and converted
    copies, the f32 product and, over several K blocks, the accumulator."""
    xb = jnp.dtype(dtype).itemsize
    blocks = bm * bk * xb + bk * bn + 8 * bn * 4 + bm * bn * xb
    return (2 * blocks + bk * bn * (4 + xb)
            + bm * bn * 4 * (2 if n_k > 1 else 1))


def vmem_limit(bm: int, bn: int, bk: int, n_k: int, dtype) -> int:
    """The scoped VMEM limit the kernel sets: twice `vmem_bytes`, for
    Mosaic's own staging, and never under the default."""
    return max(SCOPED_VMEM, 2 * vmem_bytes(bm, bn, bk, n_k, dtype))


def _kernel(x_ref, w_ref, s_ref, o_ref, *acc, n_k: int):
    x = x_ref[...]
    w = w_ref[...].astype(jnp.float32).astype(x.dtype)
    part = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    if n_k == 1:
        o_ref[...] = (part * s_ref[...]).astype(o_ref.dtype)
        return
    acc, = acc
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _first():
        acc[...] = part

    @pl.when(t > 0)
    def _add():
        acc[...] += part

    @pl.when(t == n_k - 1)
    def _flush():
        o_ref[...] = (acc[...] * s_ref[...]).astype(o_ref.dtype)


def int8_matmul(x, w_q, w_scale, *, block_m: int | None = None,
                block_n: int | None = None, block_k: int | None = None,
                interpret: bool = False):
    """x: (M, K) float; w_q: (K, N) int8; w_scale: (N,) f32 -> (M, N).
    Blocks not given come from `tiles`."""
    M, K = x.shape
    K2, N = w_q.shape
    assert K == K2
    tm, tn, tk = tiles(M, K, N, x.dtype)
    bm, bn, bk = (min(block_m or tm, M), min(block_n or tn, N),
                  min(block_k or tk, K))
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, \
        "pad operands to block multiples"
    n_k = K // bk
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, t: (i, t)),
            pl.BlockSpec((bk, bn), lambda i, j, t: (t, j)),
            pl.BlockSpec((1, bn), lambda i, j, t: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=([pltpu.VMEM((bm, bn), jnp.float32)]
                        if n_k > 1 else []),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(bm, bn, bk, n_k, x.dtype)),
        interpret=interpret,
    )(x, w_q, w_scale.reshape(1, N))
