"""jit'd wrappers: shape checking, padding to block multiples, and the
model-facing entry point used when `cfg.attn_impl == "pallas"`.

Interpret mode follows from the platform alone: on the CPU backend the
kernels run in the Pallas interpreter, on a TPU the same calls compile
to Mosaic. Nothing else can switch it."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.flash_attention import tiles as flash_tiles
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.int8_matmul import int8_matmul as _int8mm
from repro.kernels.int8_matmul import tiles as int8_tiles


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("window", "softcap", "scale",
                                             "block_q", "block_k"))
def flash_attention_btHd(q, k, v, valid_from=None, *, window=0, softcap=0.0,
                         scale=None, block_q=None, block_k=None):
    """Model-layout wrapper: q (B,T,H,hd), k/v (B,S,KV,hd) — transposes to
    the kernel's (B,H,T,hd) layout and pads T/S to block multiples.
    valid_from: optional (B,) first attendable key index (0-based, same
    axis as the kernel's implicit positions). Blocks not given come from
    the kernel's tile rule for this shape and dtype."""
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    _, tq, tk = flash_tiles(B, H, KV, T, S, hd, q.dtype)
    bq = min(block_q or tq, max(T, 1))
    bk = min(block_k or tk, max(S, 1))
    pad_q = (-T) % bq
    pad_k = (-S) % bk
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    out = _flash(qt, kt, vt, valid_from, window=window, softcap=softcap,
                 scale=scale, block_q=bq, block_k=bk, interpret=_interpret())
    out = out[:, :, :T]
    return jnp.moveaxis(out, 1, 2)


def flash_attention(q, k, v, pos_q, pos_k, valid_from=None, *, window=0,
                    softcap=0.0, scale=None):
    """Entry point matching repro.models.layers.attention's signature
    (prefill path: pos_q == pos_k, contiguous). The kernel's positions
    are implicit 0-based indices; `valid_from` is absolute (engine
    coordinates), so shift it by the window start — prefill_row runs at
    offset..offset+T-1 and causal/window masking is shift-invariant,
    but valid_from is not."""
    if valid_from is not None:
        valid_from = valid_from - pos_k[0]
    return flash_attention_btHd(q, k, v, valid_from, window=window,
                                softcap=softcap, scale=scale)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "scale",
                                             "block_s", "linear"))
def decode_attention(q, k, v, pos, cache_pos, valid_from=None, *, window=0,
                     softcap=0.0, scale=None, block_s=512, linear=False):
    """q: (B,1,H,hd) or (B,H,hd); k/v: (B,S,KV,hd) model layout.
    valid_from: optional (B,) first attendable stored position; linear
    declares slot == position (full-seq caches), enabling block skip."""
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    S = kt.shape[2]
    bs = min(block_s, S)
    pad = (-S) % bs
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        pos = jnp.pad(pos, (0, pad), constant_values=-1)
    out = _decode(q, kt, vt, pos, cache_pos, valid_from, window=window,
                  softcap=softcap, scale=scale, block_s=bs, linear=linear,
                  interpret=_interpret())
    return out[:, None] if squeeze else out


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def int8_matmul(x, w_q, w_scale, *, block_m=None, block_n=None, block_k=None):
    """Blocks not given come from the kernel's tile rule for this shape
    and dtype. Operands pad to block multiples; under the tile rule only
    a dimension with no aligned divisor pads."""
    M, K = x.shape
    N = w_q.shape[1]
    tm, tn, tk = int8_tiles(M, K, N, x.dtype)
    bm, bn, bk = (min(block_m or tm, M), min(block_n or tn, N),
                  min(block_k or tk, K))
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    xp = jnp.pad(x, ((0, pm), (0, pk))) if (pm or pk) else x
    wp = jnp.pad(w_q, ((0, pk), (0, pn))) if (pk or pn) else w_q
    sp = jnp.pad(w_scale, (0, pn)) if pn else w_scale
    out = _int8mm(xp, wp, sp, block_m=bm, block_n=bn, block_k=bk,
                  interpret=_interpret())
    return out[:M, :N]
