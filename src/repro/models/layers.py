"""Core layer math shared by every architecture.

Everything is a pure function over parameter pytrees. Attention comes in
three implementations selected by `cfg.attn_impl`:

- ``naive``: materializes the (T, S) logit matrix; fine for short context.
- ``jax_chunked``: pure-JAX flash attention (double scan over query/key
  chunks with running max/denominator) — O(chunk^2) live memory; this is
  the path used by the multi-pod dry-run (the Pallas kernel targets TPU
  and is validated separately in interpret mode).
- ``pallas``: the TPU kernel from `repro.kernels` (real hardware only).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.utils import dtype_of


# --------------------------------------------------------------------------
# Norms & activations
# --------------------------------------------------------------------------

def rms_norm(x, weight, eps: float = 1e-6, *, zero_centered: bool = True):
    """RMSNorm with fp32 accumulation. `zero_centered`: gemma-style (1+w)."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    scale = (1.0 + w) if zero_centered else w
    return (xf * scale).astype(dt)


def softcap(x, cap: float):
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": functools.partial(jax.nn.gelu, approximate=True)}[name]


# --------------------------------------------------------------------------
# Rotary position embeddings (partial-rotary supported)
# --------------------------------------------------------------------------

def rope(x, positions, *, theta: float, rotary_pct: float = 1.0):
    """x: (..., T, H, hd); positions: broadcastable to (..., T)."""
    hd = x.shape[-1]
    rot = int(hd * rotary_pct)
    rot -= rot % 2
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., T, half)
    cos = jnp.cos(ang)[..., None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x_rot[..., :half], x_rot[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)
    if x_pass.shape[-1]:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------

def _qk_norm(q, k, p, eps):
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], eps)
        k = rms_norm(k, p["k_norm"], eps)
    return q, k


def _attn_mask(pos_q, pos_k, window: int):
    """(Tq, Tk) bool mask: causal + optional sliding window + validity.

    Invalid (unwritten) cache slots carry position -1 and are masked by
    the causality test (pos_k <= pos_q fails only if pos_q < 0, never true).
    """
    m = pos_k[None, :] <= pos_q[:, None]
    m &= pos_k[None, :] >= 0
    if window:
        m &= pos_k[None, :] > pos_q[:, None] - window
    return m


def _row_mask(pos_k, valid_from):
    """(B, Tk) bool: per-row first-valid key position.

    Rows in a batched cache can start at different positions (left-padded
    prompts, or a backfilled slot whose previous occupant left stale k/v
    behind): key position p is attendable for row b only if
    p >= valid_from[b]. The shared cache `pos` array stays (S,)."""
    return pos_k[None, :] >= valid_from[:, None]


def _repeat_kv(k, rep: int):
    """(B,S,KV,hd) -> (B,S,KV*rep,hd).

    GQA via explicit head repetition rather than a (KV, rep) reshape of
    the q-head dim: the flat head dim keeps its TP sharding (a 2D split
    would force GSPMD to shard the often-indivisible KV dim — v0
    roofline showed it replicating attention instead, §Perf iter 1).
    Each rank materializes only its local heads' copies."""
    return jnp.repeat(k, rep, axis=2) if rep > 1 else k


def attention_naive(q, k, v, pos_q, pos_k, *, window: int, cap: float,
                    scale: float, valid_from=None):
    """q: (B,Tq,Hq,hd); k,v: (B,Tk,KV,hd). Returns (B,Tq,Hq,hd)."""
    B, Tq, Hq, hd = q.shape
    KV = k.shape[2]
    k = _repeat_kv(k, Hq // KV)
    v = _repeat_kv(v, Hq // KV)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k,
                        preferred_element_type=jnp.float32)
    logits = softcap(logits, cap)
    mask = _attn_mask(pos_q, pos_k, window)
    logits = jnp.where(mask[None, None], logits, -1e30)
    if valid_from is not None:
        rm = _row_mask(pos_k, valid_from)  # (B, Tk)
        logits = jnp.where(rm[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    if valid_from is not None:
        # Shared masked-attention semantic (DESIGN.md §15): a query row
        # with no attendable key produces zeros, not the uniform-softmax
        # average the -1e30 fill would otherwise renormalize to.
        any_valid = (mask[None] & rm[:, None, :]).any(-1)  # (B, Tq)
        out = jnp.where(any_valid[:, :, None, None], out, 0.0)
    return out


def attention_chunked(q, k, v, pos_q, pos_k, *, window: int, cap: float,
                      scale: float, chunk_q: int, chunk_k: int,
                      valid_from=None):
    """Pure-JAX flash attention: scan over query chunks, inner scan over
    key chunks, maintaining running (max, denom, acc)."""
    B, Tq, Hq, hd = q.shape
    Tk, KV = k.shape[1], k.shape[2]
    k = _repeat_kv(k, Hq // KV)
    v = _repeat_kv(v, Hq // KV)
    cq = min(chunk_q, Tq)
    ck = min(chunk_k, Tk)
    # Pad to chunk multiples; padded q rows are discarded, padded k columns
    # are masked via position -1.
    pad_q = (-Tq) % cq
    pad_k = (-Tk) % ck
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        pos_q = jnp.pad(pos_q, (0, pad_q), constant_values=-(10 ** 9))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        pos_k = jnp.pad(pos_k, (0, pad_k), constant_values=-1)
    nq, nk = q.shape[1] // cq, k.shape[1] // ck

    qs = q.reshape(B, nq, cq, Hq, hd).transpose(1, 0, 2, 3, 4)
    pqs = pos_q.reshape(nq, cq)
    ks = k.reshape(B, nk, ck, Hq, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, ck, Hq, hd).transpose(1, 0, 2, 3, 4)
    pks = pos_k.reshape(nk, ck)

    def q_body(_, q_in):
        qc, pq = q_in  # (B,cq,H,hd), (cq,)
        m0 = jnp.full((B, Hq, cq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Hq, cq), jnp.float32)
        a0 = jnp.zeros((B, Hq, cq, hd), jnp.float32)

        def k_step(carry, kc, vc, pk):
            m, l, acc = carry
            logits = jnp.einsum("bqhd,bkhd->bhqk", qc * scale, kc,
                                preferred_element_type=jnp.float32)
            logits = softcap(logits, cap)
            mask = _attn_mask(pq, pk, window)
            logits = jnp.where(mask[None, None], logits, -1e30)
            if valid_from is not None:
                rm = _row_mask(pk, valid_from)  # (B, ck)
                logits = jnp.where(rm[:, None, None, :], logits, -1e30)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vc, preferred_element_type=jnp.float32)
            return m_new, l, acc

        def k_body(carry, k_in):
            kc, vc, pk = k_in
            if valid_from is None:
                return k_step(carry, kc, vc, pk), None
            # Early-skip invariant (shared with the pallas kernels,
            # DESIGN.md §15): a key chunk entirely below every row's
            # valid_from is fully masked for the whole batch and
            # contributes nothing — skip its compute outright.
            run = pk.max() >= jnp.min(valid_from)
            return jax.lax.cond(
                run, lambda c: k_step(c, kc, vc, pk), lambda c: c,
                carry), None

        (m, l, acc), _ = jax.lax.scan(k_body, (m0, l0, a0), (ks, vs, pks))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        if valid_from is not None:
            # Fully-masked rows (m never rose above the -1e30 fill; the
            # -inf init marks rows whose every chunk was skipped): zeros.
            out = jnp.where((m > -5e29)[..., None], out, 0.0)
        out = out.transpose(0, 2, 1, 3)  # (B,cq,H,hd)
        return None, out.astype(v.dtype)

    _, outs = jax.lax.scan(q_body, None, (qs, pqs))  # (nq,B,cq,H,hd)
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nq * cq, Hq, hd)
    return out[:, :Tq]


def _impl_naive(q, k, v, pos_q, pos_k, cfg, *, window, cap, scale,
                valid_from):
    return attention_naive(q, k, v, pos_q, pos_k, window=window, cap=cap,
                           scale=scale, valid_from=valid_from)


def _impl_chunked(q, k, v, pos_q, pos_k, cfg, *, window, cap, scale,
                  valid_from):
    if q.shape[1] == 1:  # single-token: chunking buys nothing
        return attention_naive(q, k, v, pos_q, pos_k, window=window, cap=cap,
                               scale=scale, valid_from=valid_from)
    return attention_chunked(q, k, v, pos_q, pos_k, window=window, cap=cap,
                             scale=scale, chunk_q=cfg.attn_chunk,
                             chunk_k=cfg.attn_chunk, valid_from=valid_from)


def _impl_pallas(q, k, v, pos_q, pos_k, cfg, *, window, cap, scale,
                 valid_from):
    """The kernel fast path (interpret mode on CPU, Mosaic on TPU).

    Tq == 1 against a longer key set is a cache decode: the
    content-masked flash-decode kernel reads the stored-position array
    (correct for ring caches) and, on linear caches (window == 0 means
    every attention cache spans max_seq, so slot == position),
    block-skips slots outside [valid_from, cache_pos]. Anything else is
    a prefill over freshly computed contiguous k/v: the flash kernel's
    implicit positions match pos_q == pos_k, with valid_from shifted to
    kernel coordinates by the ops wrapper."""
    from repro.kernels import ops as kops  # deferred import
    if q.shape[1] == 1 and k.shape[1] > 1:
        return kops.decode_attention(q, k, v, pos_k, pos_q[0], valid_from,
                                     window=window, softcap=cap, scale=scale,
                                     linear=(window == 0))
    return kops.flash_attention(q, k, v, pos_q, pos_k, valid_from,
                                window=window, softcap=cap, scale=scale)


# Kernel dispatch registry (DESIGN.md §15). Every impl accepts the same
# signature — including per-row valid_from — so the serving engine keeps
# a single jit trace regardless of cfg.attn_impl.
ATTN_IMPLS = {
    "naive": _impl_naive,
    "jax_chunked": _impl_chunked,
    "pallas": _impl_pallas,
}


def attention(q, k, v, pos_q, pos_k, cfg: ModelConfig, *, window: int,
              valid_from=None):
    scale = cfg.head_dim ** -0.5
    cap = cfg.attn_softcap
    impl = cfg.attn_impl
    Tq, Tk = q.shape[1], k.shape[1]
    if impl == "auto":
        impl = "naive" if Tq * Tk <= 4096 * 4096 and Tq > 1 else (
            "naive" if Tq == 1 else "jax_chunked")
    try:
        fn = ATTN_IMPLS[impl]
    except KeyError:
        raise ValueError(
            f"unknown attn_impl {impl!r}; valid impls: "
            f"{', '.join(sorted(ATTN_IMPLS))} (or 'auto')") from None
    return fn(q, k, v, pos_q, pos_k, cfg, window=window, cap=cap,
              scale=scale, valid_from=valid_from)


def _proj(x, w, spec: str):
    """Projection dispatch (DESIGN.md §15): fp32/bf16 weight leaves run
    the given einsum; int8 execution leaves ({"q","scale"} dicts from
    `quant.int8.quantize_exec_tree`) dispatch to the int8 matmul kernel,
    so quantized zoo candidates get real int8 compute instead of a
    dequantized-fp32 round-trip. x's leading two axes are (batch, seq);
    every trailing x axis contracts against w's leading axes, so the
    flattened (B*T, K) @ (K, N) kernel call covers qkv (d -> (H, hd)),
    the output projection ((H, hd) -> d) and both MLP matmuls."""
    if isinstance(w, dict):
        from repro.kernels import ops as kops  # deferred import
        B, T = x.shape[0], x.shape[1]
        nc = x.ndim - 2                         # contracted x axes
        out_shape = w["q"].shape[nc:]
        x2 = x.reshape(B * T, -1)
        w2 = w["q"].reshape(x2.shape[1], -1)
        s2 = w["scale"].reshape(-1)
        out = kops.int8_matmul(x2, w2, s2).astype(x.dtype)
        return out.reshape((B, T) + out_shape)
    return jnp.einsum(spec, x, w)


def attn_block(p, x, cfg: ModelConfig, kind: str, positions,
               cache: Optional[dict] = None, cache_pos=None,
               constrain=None, parallel=None, valid_from=None):
    """Pre-norm attention block. Returns (x_out, new_cache).

    Train/prefill: cache is None, positions = (T,) absolute positions.
    Decode: cache = {"k","v"} ring/linear buffers, cache_pos = scalar of
    tokens already in context (the new token's position).
    constrain: optional residual sharding constraint (sequence
    parallelism) applied after every residual add, so GSPMD turns the
    row-parallel all-reduces into reduce-scatters.
    valid_from: optional (B,) int32 — per row, the first key position this
    row may attend to (masks left-padding and, on backfilled slots, the
    previous occupant's stale cache entries).
    """
    window = cfg.window if kind == "local" else 0
    eps = cfg.norm_eps
    h = rms_norm(x, p["ln1"], eps)
    B, T, _ = h.shape
    Hq, KV, hd = cfg.q_heads_padded, cfg.n_kv_heads, cfg.head_dim
    q = _proj(h, p["wq"], "btd,dhk->bthk")
    k = _proj(h, p["wk"], "btd,dhk->bthk")
    v = _proj(h, p["wv"], "btd,dhk->bthk")
    # Per-arch lever (§Perf): pinning q/k/v head-sharded stops GSPMD from
    # replicating attention over the model axis. On dense archs (whose
    # MLP anchors the propagation) it HURT (~2x gather/RS ping-pong); on
    # MoE archs (shard_map FFN gives no anchor) attention otherwise runs
    # fully replicated with fp32 dq/dk all-reduces. Off by default;
    # enabled per measured cell via ParallelConfig.attn_pin.
    if parallel is not None and getattr(parallel, "attn_pin", False) and T > 1:
        from jax.sharding import PartitionSpec as P_
        tpn = parallel.mesh.shape[parallel.tp_axis]
        qspec = P_(parallel.data_axes, None, parallel.tp_axis, None)
        kvspec = qspec if KV % tpn == 0 else P_(parallel.data_axes, None,
                                                None, None)
        q = jax.lax.with_sharding_constraint(q, qspec)
        k = jax.lax.with_sharding_constraint(k, kvspec)
        v = jax.lax.with_sharding_constraint(v, kvspec)
    q, k = _qk_norm(q, k, p, eps)
    q = rope(q, positions, theta=cfg.rope_theta, rotary_pct=cfg.rotary_pct)
    k = rope(k, positions, theta=cfg.rope_theta, rotary_pct=cfg.rotary_pct)

    new_cache = None
    out = None
    if cache is not None and T == 1 and parallel is not None and \
            cfg.n_kv_heads % parallel.mesh.shape[parallel.tp_axis] != 0:
        # Sequence-sharded cache (kv < tp): explicit distributed
        # flash-decode — masked local cache write + partial-softmax merge
        # (GSPMD's generic handling all-gathered the cache per layer).
        # valid_from folds into the per-shard content mask before the
        # partial-softmax stats merge.
        from repro.models.flash_decode import flash_decode_sharded
        out, ckn, cvn, cpn = flash_decode_sharded(
            q, k, v, cache["k"], cache["v"], cache["pos"], cache_pos,
            cfg, parallel, window=window, valid_from=valid_from)
        new_cache = {"k": ckn, "v": cvn, "pos": cpn}
    elif cache is not None and T == 1:
        # Decode: ring-buffer write. Windowed layers allocate S == window so
        # the modulo wraps; full layers allocate S == max_seq (identity).
        S = cache["k"].shape[1]
        slot = cache_pos % S
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, slot, 0, 0))
        # Stored positions make masking correct for both ring & linear cases
        # (unwritten slots stay -1 and are masked out).
        cpos = jax.lax.dynamic_update_slice(
            cache["pos"], positions.astype(cache["pos"].dtype), (slot,))
        new_cache = {"k": ck, "v": cv, "pos": cpos}
        k, v, pos_k = ck, cv, cpos
        pos_q = positions
    elif cache is not None:
        # Prefill from position 0 into an empty cache: attend over the
        # freshly computed k/v and write them into the cache preserving
        # the ring invariant (position p lives at slot p % S). Every slot
        # is written here, the unused ones as zeros / position -1, and
        # the incoming buffer is read for its shape only: the TPU
        # compiler drops the zero fill of a layer-scan-carried cache when
        # each layer updates only part of its slice, which left the
        # unwritten slots (and their stored positions) uninitialized.
        S = cache["k"].shape[1]
        kd, vd = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
        pd = positions.astype(cache["pos"].dtype)
        if T >= S:
            slots = np.arange(T - S, T) % S
            ck = cache["k"].at[:, slots].set(kd[:, T - S:])
            cv = cache["v"].at[:, slots].set(vd[:, T - S:])
            cpos = cache["pos"].at[slots].set(pd[T - S:])
        else:
            pad = ((0, 0), (0, S - T), (0, 0), (0, 0))
            ck, cv = jnp.pad(kd, pad), jnp.pad(vd, pad)
            cpos = jnp.pad(pd, (0, S - T), constant_values=-1)
        new_cache = {"k": ck, "v": cv, "pos": cpos}
        pos_q = pos_k = positions
    else:
        pos_q = pos_k = positions

    if out is None:
        out = attention(q, k, v, pos_q, pos_k, cfg, window=window,
                        valid_from=valid_from)
    out = _proj(out, p["wo"], "bthk,hkd->btd")
    if cfg.sandwich_norm:
        out = rms_norm(out, p["post_attn_norm"], eps)
    x = x + out
    if constrain is not None:
        x = constrain(x)

    # FFN half (dense; MoE blocks override this in model.py).
    if "mlp" in p:
        h = rms_norm(x, p["ln2"], eps)
        out = mlp(p["mlp"], h, cfg)
        if cfg.sandwich_norm:
            out = rms_norm(out, p["post_ffn_norm"], eps)
        x = x + out
        if constrain is not None:
            x = constrain(x)
    return x, new_cache


def mlp(p, x, cfg: ModelConfig):
    act = act_fn(cfg.mlp_act)
    if cfg.mlp_gated:
        u = _proj(x, p["w_up"], "btd,df->btf")
        g = _proj(x, p["w_gate"], "btd,df->btf")
        h = act(g) * u
    else:
        h = act(_proj(x, p["w_up"], "btd,df->btf"))
    return _proj(h, p["w_down"], "btf,fd->btd")
