"""Flash-decode Pallas TPU kernel: one query token vs. a (ring-buffer)
KV cache.

Grid (B, KV, nS) with the cache-block index innermost; the per-(b, kv)
accumulator covers all `rep = Hq/KV` query heads of the group at once —
(rep, hd) tiles keep the MXU busy even at rep=1 because hd>=128.
Validity masking uses the stored position array (slot -> position,
-1 = unwritten), which makes the same kernel correct for linear and
ring-buffer (sliding-window) caches; per-row `valid_from` folds into
the same content mask (pos >= valid_from[b]), masking left-padding and
a backfilled slot's stale previous-occupant entries.

`linear=True` declares slot index == stored position (full-seq caches,
the serving engine's layout), unlocking a block-level early-skip: cache
blocks entirely below this row's valid_from, or entirely past
cache_pos, are gated off without reading k/v. Ring caches (slot !=
position) keep the always-correct content mask only. The online
rescale self-heals any all-masked block (corr -> 0 once a valid slot
appears); rows with no attendable slot at all flush zeros. As in the
flash kernel, the softmax stats and the position row stay 2-D ((rep, 1)
and (1, bs)): Mosaic refuses to broadcast a 1-D vector back across a
tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(cpos_ref, vf_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
            acc, m_i, l_i, *, scale: float, cap: float, window: int,
            rep: int, bs: int, linear: bool):
    b = pl.program_id(0)
    t = pl.program_id(2)
    cache_pos = cpos_ref[0]
    vf = vf_ref[b]

    @pl.when(t == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)

    if linear:
        # Slot s holds position s (or -1): a block wholly below
        # valid_from or wholly past cache_pos cannot contribute.
        run = jnp.logical_and(t * bs + bs - 1 >= vf, t * bs <= cache_pos)
    else:
        run = jnp.bool_(True)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale   # (rep, hd)
        k = k_ref[0, 0].astype(jnp.float32)           # (bs, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        pos = pos_ref[...]                            # (1, bs) stored positions

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (rep, bs)
        if cap:
            s = cap * jnp.tanh(s / cap)
        valid = (pos >= vf) & (pos <= cache_pos)
        if window:
            valid &= pos > cache_pos - window
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_i[...]                             # (rep, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_i[...] = l_i[...] * corr + p.sum(axis=1, keepdims=True)
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_i[...] = m_new

    @pl.when(t == pl.num_programs(2) - 1)
    def _flush():
        seen = m_i[...] > NEG_INF * 0.5
        out = acc[...] / jnp.maximum(l_i[...], 1e-30)
        o_ref[0, 0] = jnp.where(seen, out, 0.0).astype(o_ref.dtype)


def decode_attention(q, k, v, pos, cache_pos, valid_from=None, *,
                     window: int = 0, softcap: float = 0.0,
                     scale: float | None = None, block_s: int = 512,
                     linear: bool = False, interpret: bool = False):
    """q: (B, Hq, hd); k, v: (B, KV, S, hd); pos: (S,) int32;
    cache_pos: scalar int32. valid_from: optional (B,) int32 first
    attendable stored position per row (None == zeros == unmasked).
    linear: slot index == stored position (enables block early-skip).
    Returns (B, Hq, hd)."""
    B, Hq, hd = q.shape
    KV, S = k.shape[1], k.shape[2]
    assert Hq % KV == 0
    rep = Hq // KV
    bs = min(block_s, S)
    assert S % bs == 0
    scale = hd ** -0.5 if scale is None else scale
    qg = q.reshape(B, KV, rep, hd)
    cpos = jnp.asarray(cache_pos, jnp.int32).reshape(1)
    if valid_from is None:
        valid_from = jnp.zeros((B,), jnp.int32)
    vf = jnp.asarray(valid_from, jnp.int32).reshape(B)

    kern = functools.partial(_kernel, scale=scale, cap=softcap,
                             window=window, rep=rep, bs=bs, linear=linear)
    out = pl.pallas_call(
        kern,
        grid=(B, KV, S // bs),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # cache_pos scalar
            pl.BlockSpec(memory_space=pltpu.SMEM),   # valid_from (B,)
            pl.BlockSpec((1, 1, rep, hd), lambda b, g, t: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, g, t: (b, g, t, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, g, t: (b, g, t, 0)),
            pl.BlockSpec((1, bs), lambda b, g, t: (0, t)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, hd), lambda b, g, t: (b, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, rep, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep, hd), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
        ],
        interpret=interpret,
    )(cpos, vf, qg, k, v, pos.reshape(1, S))
    return out.reshape(B, Hq, hd)
