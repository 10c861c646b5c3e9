"""Flash attention (prefill) Pallas TPU kernel.

Online-softmax over KV blocks: grid (B, Hq / Hb, nQ, nK) with the
KV-block index innermost, so the accumulator, running max and
denominator of a step's Hb heads live in VMEM scratch across the inner
sweep and the output block is flushed once on the last KV step.

Several heads per step. The q/o blocks carry Hb heads and the dots are
batched over them. Under GQA (rep = Hq / KV q heads per kv head) Hb is
a multiple or a divisor of rep, so a step's q heads read Hk = max(1,
Hb / rep) whole kv heads: the g = Hb / Hk q heads of one kv head are
folded into the rows of one (g * bq, hd) operand, and the k/v index
map picks the step's kv heads.

Dtype rule. q and k go to the MXU in their stored dtype and the scores
accumulate in f32 (`preferred_element_type`); the products of two bf16
numbers are exact in f32, so the scores are the f32 ones up to
summation order. The scale is applied to the f32 scores. The
probabilities p stay f32 through the softmax; for p . v against bf16 v,
p is carried as two bf16 terms, hi = bf16(p) and lo = bf16(p - hi), and
hi . v + lo . v accumulates in f32: 16 significant bits of p, what a
3-pass f32 product gives when v is already bf16. f32 operands keep the
f32 path.

Skipping. `runs` is the gate of one (q block, k block) pair: causal
(block above the diagonal), window (block before the window) and the
row's `valid_from` (block entirely below the row's first attendable
key). A pair it rules out does no MXU, VPU or exp work, and `kv_block`
points the k/v index map of such a step at a block the sweep fetches
anyway, so it costs no DMA either. The host helper `blocks_run` counts
the pairs the gate runs with the same function.

Rows with no attendable key at all (valid_from past the last key) flush
zeros: with a finite NEG_INF the softmax of an all-masked row would
otherwise renormalize garbage (exp(0) per masked entry). The online
rescale already self-heals any all-masked *block* (corr -> 0 once a
valid key appears); the flush guard covers the only case it cannot.

The running max and denominator are held with a kept unit axis,
(.., rows, 1), and every reduction keeps its axis: Mosaic lays a 1-D
vector out along the lanes, and broadcasting it back across a
(rows, bk) tile is a shape cast it refuses ("unsupported shape cast").

Tile rule (`tiles`): from the shape and dtype alone. bq and bk take the
sequence whole up to 128, else the largest aligned divisor under 128
(else the sequence pads by less than a block), so skipping works in
blocks of at most 128 keys and queries. Hb is the most heads, among the
divisors of Hq that are multiples or divisors of rep, whose step fits
`VMEM_BUDGET` by `vmem_bytes`; `vmem_limit_bytes` is raised over the
16 MiB scoped default to twice that estimate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.int8_matmul import SCOPED_VMEM, tile

NEG_INF = -1e30
MAX_BLOCK = 128            # the widest q or k block: the skip's grain
VMEM_BUDGET = 24 * 2**20   # what one step's working set may take


def runs(q_start, k_start, vf, bq: int, bk: int, causal: bool,
         window: int):
    """Whether the (q block, k block) pair at these starts holds a key
    some query of the block may attend: jnp scalars in the kernel, numpy
    arrays (broadcast) on the host."""
    run = k_start + bk - 1 >= vf
    if causal:
        run = run & (k_start <= q_start + bq - 1)
    if window:
        run = run & (k_start + bk - 1 > q_start - window)
    return run


def kv_block(t, q_start, vf, bq: int, bk: int, n_k: int, causal: bool,
             window: int):
    """The k/v block the step at kv index t reads: t itself where the
    gate runs it (the blocks that run form one interval [lo, hi]), else
    the nearest end of that interval, a block the sweep fetches anyway.
    Where no block runs it stays on lo, so the sweep fetches one block."""
    lo = vf // bk
    if window:
        lo = jnp.maximum(lo, jnp.maximum(q_start - window + 1, 0) // bk)
    hi = (q_start + bq - 1) // bk if causal else n_k - 1
    return jnp.minimum(jnp.maximum(jnp.minimum(t, hi), lo), n_k - 1)


def blocks_run(valid_from, T: int, S: int, bq: int, bk: int,
               causal: bool = True, window: int = 0) -> int:
    """(row, q block, k block) triples the gate runs over a call whose
    rows start at `valid_from` (kernel coordinates), T and S padded to
    their blocks as the wrapper pads them."""
    vf = np.asarray(valid_from, np.int64).reshape(-1, 1, 1)
    q = (np.arange(-(-T // bq)) * bq)[:, None]
    k = (np.arange(-(-S // bk)) * bk)[None, :]
    return int(np.count_nonzero(
        np.broadcast_to(runs(q, k, vf, bq, bk, causal, window),
                        (vf.shape[0], q.shape[0], k.shape[1]))))


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def vmem_bytes(Hb: int, Hk: int, bq: int, bk: int, hd: int, dtype) -> int:
    """VMEM one step asks for: double-buffered q, k, v and o blocks, the
    f32 accumulator and the (lane-padded) running max and denominator,
    and the step's temporaries (f32 scores and probabilities, the two
    bf16 terms of p, the two f32 products)."""
    isz = jnp.dtype(dtype).itemsize
    d = _lanes(hd)
    blocks = 2 * Hb * bq * d * isz + 2 * Hk * bk * d * isz
    scratch = Hb * bq * d * 4 + 2 * Hb * bq * 128 * 4
    temps = (2 * Hb * bq * _lanes(bk) * 4
             + (2 * Hb * bq * _lanes(bk) * isz if isz < 4 else 0)
             + 2 * Hb * bq * d * 4)
    return 2 * blocks + scratch + temps


def tiles(B: int, Hq: int, KV: int, T: int, S: int, hd: int,
          dtype) -> tuple[int, int, int]:
    """(Hb, bq, bk) for q (B, Hq, T, hd) against k/v (B, KV, S, hd)."""
    del B  # every row runs the same step
    sublanes = 32 // jnp.dtype(dtype).itemsize
    bq, bk = tile(T, MAX_BLOCK, sublanes), tile(S, MAX_BLOCK, sublanes)
    rep = Hq // KV
    fits = [h for h in range(1, Hq + 1)
            if Hq % h == 0 and (h % rep == 0 or rep % h == 0)
            and vmem_bytes(h, max(1, h // rep), bq, bk, hd, dtype)
            <= VMEM_BUDGET]
    return (max(fits) if fits else 1), bq, bk


def _pv(p, v):
    """p (f32) . v with f32 accumulation; p as hi + lo bf16 terms where
    v is not f32."""
    dims = (((2,), (1,)), ((0,), (0,)))
    if v.dtype == jnp.float32:
        return jax.lax.dot_general(p, v, dims,
                                   preferred_element_type=jnp.float32)
    hi = p.astype(v.dtype)
    lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
    return (jax.lax.dot_general(hi, v, dims,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(lo, v, dims,
                                  preferred_element_type=jnp.float32))


def _kernel(vf_ref, q_ref, k_ref, v_ref, o_ref, acc, m_i, l_i, *,
            scale: float, cap: float, window: int, causal: bool,
            bq: int, bk: int):
    b = pl.program_id(0)  # batch row (selects this row's valid_from)
    j = pl.program_id(2)  # q block
    t = pl.program_id(3)  # kv block (innermost)
    vf = vf_ref[b]
    Hb, _, hd = q_ref.shape[1:]
    Hk = k_ref.shape[1]
    rows = Hb // Hk * bq   # the q heads of one kv head, folded into rows

    @pl.when(t == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)

    q_start = j * bq
    k_start = t * bk

    @pl.when(runs(q_start, k_start, vf, bq, bk, causal, window))
    def _step():
        q = q_ref[0].reshape(Hk, rows, hd)
        s = jax.lax.dot_general(q, k_ref[0], (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        s = s * scale                                     # (Hk, rows, bk)
        if cap:
            s = cap * jnp.tanh(s / cap)
        pos_q = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0)
        if rows > bq:
            pos_q = jax.lax.rem(pos_q, bq)
        pos_q = q_start + pos_q
        pos_k = k_start + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
        mask = pos_k >= vf
        if causal:
            mask &= pos_k <= pos_q
        if window:
            mask &= pos_k > pos_q - window
        s = jnp.where(mask[None], s, NEG_INF)

        m_prev = m_i[...]                                 # (Hk, rows, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_i[...] = l_i[...] * corr + p.sum(axis=2, keepdims=True)
        acc[...] = acc[...] * corr + _pv(p, v_ref[0])
        m_i[...] = m_new

    @pl.when(t == pl.num_programs(3) - 1)
    def _flush():
        # m_i still at NEG_INF <=> the row never saw an attendable key.
        seen = m_i[...] > NEG_INF * 0.5
        out = acc[...] / jnp.maximum(l_i[...], 1e-30)
        out = jnp.where(seen, out, 0.0).reshape(Hb, bq, hd)
        o_ref[0] = out.astype(o_ref.dtype)


def flash_attention(q, k, v, valid_from=None, *, window: int = 0,
                    softcap: float = 0.0, scale: float | None = None,
                    causal: bool = True, block_q: int | None = None,
                    block_k: int | None = None, block_h: int | None = None,
                    interpret: bool = False):
    """q: (B, Hq, T, hd); k, v: (B, KV, S, hd) -> (B, Hq, T, hd).

    valid_from: optional (B,) int32 — per row, the first key index that
    may be attended (kernel-relative, i.e. on the same 0-based axis as
    the implicit positions). None == zeros == unmasked (bit-identical:
    the masking terms are value-level no-ops on causal rows). Blocks
    not given come from `tiles`; block_h must be a divisor of Hq that
    is a multiple or a divisor of Hq / KV."""
    B, Hq, T, hd = q.shape
    KV, S = k.shape[1], k.shape[2]
    assert Hq % KV == 0, (Hq, KV)
    rep = Hq // KV
    th, tq, tk = tiles(B, Hq, KV, T, S, hd, q.dtype)
    Hb, bq, bk = block_h or th, min(block_q or tq, T), min(block_k or tk, S)
    assert Hq % Hb == 0 and (Hb % rep == 0 or rep % Hb == 0), (Hb, rep)
    assert T % bq == 0 and S % bk == 0, "pad sequences to block multiples"
    Hk = max(1, Hb // rep)
    n_k = S // bk
    scale = hd ** -0.5 if scale is None else scale
    if valid_from is None:
        valid_from = jnp.zeros((B,), jnp.int32)
    vf = jnp.asarray(valid_from, jnp.int32).reshape(B)

    def kv_map(b, h, j, t, vf_ref):
        return (b, h * Hb // rep // Hk,
                kv_block(t, j * bq, vf_ref[b], bq, bk, n_k, causal, window),
                0)

    kern = functools.partial(
        _kernel, scale=scale, cap=softcap, window=window, causal=causal,
        bq=bq, bk=bk)
    rows = Hb // Hk * bq
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                       # valid_from (B,)
            grid=(B, Hq // Hb, T // bq, n_k),
            in_specs=[
                pl.BlockSpec((1, Hb, bq, hd),
                             lambda b, h, j, t, vf_ref: (b, h, j, 0)),
                pl.BlockSpec((1, Hk, bk, hd), kv_map),
                pl.BlockSpec((1, Hk, bk, hd), kv_map),
            ],
            out_specs=pl.BlockSpec((1, Hb, bq, hd),
                                   lambda b, h, j, t, vf_ref: (b, h, j, 0)),
            scratch_shapes=[
                pltpu.VMEM((Hk, rows, hd), jnp.float32),
                pltpu.VMEM((Hk, rows, 1), jnp.float32),
                pltpu.VMEM((Hk, rows, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(
                SCOPED_VMEM, 2 * vmem_bytes(Hb, Hk, bq, bk, hd, q.dtype))),
        interpret=interpret,
    )(vf, q, k, v)
