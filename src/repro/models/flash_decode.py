"""Distributed flash-decode (sequence-sharded KV cache).

For architectures with n_kv_heads < tp (gemma2, yi, deepseek, chameleon,
qwen3, grok, recurrentgemma), the KV cache shards its SEQUENCE dim over
the model axis. GSPMD handles that layout correctly but conservatively —
the v1 roofline showed it all-gathering every layer's cache per decode
step (~1 GB/layer). This shard_map implements what the hardware should
do instead:

  - the new token's k/v is written by the one shard owning the slot
    (masked local dynamic-update-slice, no communication),
  - each shard computes attention over its local S/tp cache chunk for
    ALL heads (model-parallel over sequence, heads replicated — q is a
    single token, so replication is free),
  - partial softmax stats merge with a pmax + two psums of (B, H)-sized
    tensors — KBs instead of GBs per layer.

On real TPU the per-shard inner loop is the Pallas decode_attention
kernel (repro/kernels/decode_attention.py) applied to the local chunk;
the pure-jnp body below is its oracle-equivalent and what the dry-run
lowers."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig


def flash_decode_sharded(q, k_new, v_new, ck, cv, cpos, cache_pos,
                         cfg: ModelConfig, parallel, *, window: int,
                         valid_from=None):
    """q/k_new/v_new: (B,1,H|KV,hd); ck/cv: (B,S,KV,hd); cpos: (S,);
    cache_pos: scalar; valid_from: optional (B,) first attendable stored
    position per row (masked into each shard's local chunk before the
    partial-softmax merge; rows with no attendable slot produce zeros).
    Returns (out (B,1,H,hd), ck', cv', cpos')."""
    tp = parallel.tp_axis
    tp_size = parallel.mesh.shape[tp]
    B, S = ck.shape[0], ck.shape[1]
    data_ok = all(B % parallel.mesh.shape[a] == 0
                  for a in parallel.data_axes) and B >= _prod(
                      parallel.mesh.shape[a] for a in parallel.data_axes)
    baxes = parallel.data_axes if data_ok else None
    bspec4 = P(baxes, None, None, None)
    cspec = P(baxes, tp, None, None)
    scale = cfg.head_dim ** -0.5
    cap = cfg.attn_softcap

    def device_fn(qb, knb, vnb, ckb, cvb, posb, cpos_s, vfb):
        i = jax.lax.axis_index(tp)
        S_loc = ckb.shape[1]
        slot_g = cpos_s % S
        local = slot_g - i * S_loc
        in_range = (local >= 0) & (local < S_loc)
        idx = jnp.clip(local, 0, S_loc - 1)
        ck_up = jax.lax.dynamic_update_slice(
            ckb, knb.astype(ckb.dtype), (0, idx, 0, 0))
        cv_up = jax.lax.dynamic_update_slice(
            cvb, vnb.astype(cvb.dtype), (0, idx, 0, 0))
        pos_up = jax.lax.dynamic_update_slice(
            posb, cpos_s[None].astype(posb.dtype), (idx,))
        ckb = jnp.where(in_range, ck_up, ckb)
        cvb = jnp.where(in_range, cv_up, cvb)
        posb = jnp.where(in_range, pos_up, posb)

        KV = ckb.shape[2]
        H = qb.shape[2]
        rep = H // KV
        Bq = qb.shape[0]
        hd = qb.shape[3]
        # Grouped-GQA einsums: repeating KV to H heads would multiply the
        # cache read traffic by rep (measured 8x on chameleon decode).
        qg = (qb[:, 0] * scale).reshape(Bq, KV, rep, hd)
        s = jnp.einsum("bgrd,bkgd->bgrk", qg, ckb,
                       preferred_element_type=jnp.float32)  # (B,KV,rep,S)
        if cap:
            s = cap * jnp.tanh(s / cap)
        valid = (posb >= 0) & (posb <= cpos_s)
        if window:
            valid &= posb > cpos_s - window
        if valid_from is None:
            s = jnp.where(valid[None, None, None, :], s, -1e30)
        else:
            vmask = valid[None, :] & (posb[None, :] >= vfb[:, None])  # (B,S)
            s = jnp.where(vmask[:, None, None, :], s, -1e30)
        m_loc = s.max(axis=-1)                                  # (B,KV,rep)
        m = jax.lax.pmax(m_loc, tp)
        p = jnp.exp(s - m[..., None])
        l = jax.lax.psum(p.sum(axis=-1), tp)                    # (B,KV,rep)
        acc = jnp.einsum("bgrk,bkgd->bgrd", p, cvb.astype(jnp.float32))
        acc = jax.lax.psum(acc, tp)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        if valid_from is not None:
            # Rows with no attendable slot anywhere (m still at the
            # -1e30 fill after the global pmax) produce zeros, matching
            # the shared masked-attention semantic (DESIGN.md §15).
            out = jnp.where((m > -5e29)[..., None], out, 0.0)
        out = out.astype(qb.dtype)
        return out.reshape(Bq, 1, H, hd), ckb, cvb, posb

    vf = (jnp.zeros((B,), jnp.int32) if valid_from is None
          else jnp.asarray(valid_from, jnp.int32))
    fn = jax.shard_map(
        device_fn,
        mesh=parallel.mesh,
        in_specs=(bspec4, bspec4, bspec4, cspec, cspec, P(tp), P(),
                  P(baxes)),
        out_specs=(bspec4, cspec, cspec, P(tp)),
        check_vma=False,
    )
    return fn(q, k_new, v_new, ck, cv, cpos,
              jnp.asarray(cache_pos, jnp.int32), vf)


def _prod(it):
    out = 1
    for x in it:
        out *= x
    return out
