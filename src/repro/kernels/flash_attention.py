"""Flash attention (prefill) Pallas TPU kernel.

Online-softmax over KV blocks: grid (B, Hq, nQ, nK) with the KV-block
index innermost, so the (bq, hd) accumulator, running max and denominator
live in VMEM scratch across the inner sweep and the output block is
flushed once on the last KV step. GQA is folded into the K/V BlockSpec
index maps (q head h reads kv head h // rep). Causal + sliding-window +
per-row `valid_from` masking is block-skipped: fully-masked KV blocks
contribute nothing and their compute is gated behind pl.when — a
left-padded (or backfilled) row whose first attendable key is
valid_from[b] never pays FLOPs for KV blocks entirely below it.

Rows with no attendable key at all (valid_from past the last key) flush
zeros: with a finite NEG_INF the softmax of an all-masked row would
otherwise renormalize garbage (exp(0) per masked entry). The online
rescale already self-heals any all-masked *block* (corr -> 0 once a
valid key appears); the flush guard covers the only case it cannot.

The running max and denominator are held 2-D, (bq, 1), and every
reduction keeps its axis: Mosaic lays a 1-D (bq,) vector out along the
lanes, and broadcasting it back across a (bq, bk) tile is a shape cast
it refuses ("unsupported shape cast").

VMEM budget per step (defaults bq=bk=512, hd<=256, fp32 scratch):
q (512*256*4) + k/v (2*512*256*4) + acc (512*256*4) ~= 2 MiB << 16 MiB
v5e VMEM; block dims are multiples of (8,128) MXU/VREG tiling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(vf_ref, q_ref, k_ref, v_ref, o_ref, acc, m_i, l_i, *,
            scale: float, cap: float, window: int, causal: bool,
            bq: int, bk: int):
    b = pl.program_id(0)  # batch row (selects this row's valid_from)
    j = pl.program_id(2)  # q block
    t = pl.program_id(3)  # kv block (innermost)
    vf = vf_ref[b]

    @pl.when(t == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)

    q_start = j * bq
    k_start = t * bk
    # Block-level skip: fully-masked KV blocks are gated off entirely —
    # causal (block above the diagonal), window (block before the
    # window) and valid_from (block entirely below this row's first
    # attendable key).
    run = k_start + bk - 1 >= vf
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + bq - 1)
    if window:
        run = jnp.logical_and(run, k_start + bk - 1 > q_start - window)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale   # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if cap:
            s = cap * jnp.tanh(s / cap)
        pos_q = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        pos_k = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = pos_k >= vf
        if causal:
            mask &= pos_k <= pos_q
        if window:
            mask &= pos_k > pos_q - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_i[...]                                 # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_i[...] = l_i[...] * corr + p.sum(axis=1, keepdims=True)
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_i[...] = m_new

    @pl.when(t == pl.num_programs(3) - 1)
    def _flush():
        # m_i still at NEG_INF <=> the row never saw an attendable key.
        seen = m_i[...] > NEG_INF * 0.5
        out = acc[...] / jnp.maximum(l_i[...], 1e-30)
        o_ref[0, 0] = jnp.where(seen, out, 0.0).astype(o_ref.dtype)


def flash_attention(q, k, v, valid_from=None, *, window: int = 0,
                    softcap: float = 0.0, scale: float | None = None,
                    causal: bool = True, block_q: int = 512,
                    block_k: int = 512, interpret: bool = False):
    """q: (B, Hq, T, hd); k, v: (B, KV, S, hd) -> (B, Hq, T, hd).

    valid_from: optional (B,) int32 — per row, the first key index that
    may be attended (kernel-relative, i.e. on the same 0-based axis as
    the implicit positions). None == zeros == unmasked (bit-identical:
    the masking terms are value-level no-ops on causal rows)."""
    B, Hq, T, hd = q.shape
    KV, S = k.shape[1], k.shape[2]
    assert Hq % KV == 0, (Hq, KV)
    rep = Hq // KV
    bq = min(block_q, T)
    bk = min(block_k, S)
    assert T % bq == 0 and S % bk == 0, "pad sequences to block multiples"
    scale = hd ** -0.5 if scale is None else scale
    grid = (B, Hq, T // bq, S // bk)
    if valid_from is None:
        valid_from = jnp.zeros((B,), jnp.int32)
    vf = jnp.asarray(valid_from, jnp.int32).reshape(B)

    kern = functools.partial(
        _kernel, scale=scale, cap=softcap, window=window, causal=causal,
        bq=bq, bk=bk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # valid_from (B,)
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, j, t: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, j, t, rep=rep: (b, h // rep, t, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, j, t, rep=rep: (b, h // rep, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, j, t: (b, h, j, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(vf, q, k, v)
