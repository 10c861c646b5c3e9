"""Mixture-of-Experts FFN.

Two execution paths:

- **dense reference** (no mesh): every expert computed for every token,
  combined with renormalized top-k router probs. O(T*E*ff) — used for
  small smoke/property tests and as the oracle for the sharded path.

- **sharded** (`shard_map`): expert parallelism without any all_to_all.
  Activations are TP-replicated over the `model` axis when they reach
  the FFN, so every model-rank already holds all of its data-shard's
  tokens. Two weight layouts:

    * ``ep``  (E % tp == 0, e.g. qwen3 128e): experts sharded over the
      model axis; each rank dispatches its local tokens to its local
      experts via a capacity-bounded scatter (Mesh-TF position-in-expert
      cumsum), runs a grouped FFN, scatter-adds, and the closing
      ``psum(model)`` combines expert contributions across ranks.
    * ``tp``  (E < tp, e.g. grok 8e): every rank holds all experts but
      only an ff-slice; the same closing psum combines ff partial sums.

  Weights are additionally FSDP-sharded over `fsdp_axes` and
  all-gathered per layer inside the scan (ZeRO-3).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.layers import act_fn


def router_topk(p, x2d, cfg: ModelConfig):
    """x2d: (T, d). Returns (vals (T,k), idx (T,k), probs (T,E) fp32)."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, m.top_k)
    vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)  # renorm
    return vals, idx, probs


def moe_ffn_dense(p, x, cfg: ModelConfig):
    """Reference path: (B,T,d) -> ((B,T,d), aux_loss)."""
    m = cfg.moe
    B, T, d = x.shape
    x2 = x.reshape(B * T, d)
    vals, idx, probs = router_topk(p, x2, cfg)
    act = act_fn(cfg.mlp_act)
    # (T, E) combine weights.
    comb = jnp.zeros((B * T, m.n_experts), jnp.float32)
    comb = comb.at[jnp.arange(B * T)[:, None], idx].add(vals)
    g = jnp.einsum("td,edf->tef", x2, p["w_gate"])
    u = jnp.einsum("td,edf->tef", x2, p["w_up"])
    h = act(g) * u
    y = jnp.einsum("tef,efd->ted", h, p["w_down"])
    out = jnp.einsum("ted,te->td", y, comb.astype(y.dtype))
    aux = _load_balance_loss(comb, probs, m.n_experts)
    return out.reshape(B, T, d).astype(x.dtype), aux


def _load_balance_loss(comb, probs, E):
    """Switch-transformer load-balance loss: E * sum_e f_e * P_e."""
    f = (comb > 0).astype(jnp.float32).mean(0)  # fraction routed per expert
    pbar = probs.mean(0)
    return E * jnp.sum(f * pbar)


def _dispatch_indices(idx, vals, E_loc, off, C):
    """Capacity-bounded dispatch bookkeeping (per device).

    idx/vals: (T,k) global expert ids / gate weights. Experts
    [off, off+E_loc) are local. Returns (idx_buf (E_loc*C,) token ids,
    gate_buf (E_loc*C,) weights, comb_local for aux loss).
    """
    T, k = idx.shape
    flat_e = idx.reshape(-1) - off  # (T*k,) local expert or out of range
    flat_v = vals.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)
    valid = (flat_e >= 0) & (flat_e < E_loc)
    one_hot = jax.nn.one_hot(jnp.where(valid, flat_e, E_loc), E_loc + 1,
                             dtype=jnp.int32)[:, :E_loc]  # (T*k, E_loc)
    pos = jnp.cumsum(one_hot, axis=0) - one_hot  # exclusive count before me
    my_pos = jnp.sum(pos * one_hot, axis=1)  # (T*k,)
    keep = valid & (my_pos < C)
    slot = jnp.where(keep, jnp.where(valid, flat_e, 0) * C + my_pos,
                     E_loc * C)  # dropped -> out-of-range slot
    size = E_loc * C
    idx_buf = jnp.zeros((size,), jnp.int32).at[slot].set(
        flat_t.astype(jnp.int32), mode="drop")
    gate_buf = jnp.zeros((size,), jnp.float32).at[slot].set(
        flat_v, mode="drop")
    return idx_buf, gate_buf


def moe_weight_specs(mode: str, tp, fsdp):
    """Per-mode expert weight layouts (shard_map in_specs; the same
    mapping drives the stored-parameter shardings via repro.sharding).

    - ep:   experts/tp, d/fsdp    + per-layer FSDP gather of the weights
    - tp:   ff/tp, d/fsdp         + per-layer FSDP gather (E < tp_size)
    - ep2d: experts/tp, ff/fsdp   NO weight movement; activations are
            gathered over data instead (decode: x is tiny, weights huge)
    - tp2d: ff/(fsdp x tp)        NO weight movement (decode, E < tp)
    """
    if mode == "ep":
        return P(tp, fsdp, None), P(tp, None, fsdp)
    if mode == "tp":
        return P(None, fsdp, tp), P(None, tp, fsdp)
    if mode == "ep2d":
        return P(tp, None, fsdp), P(tp, fsdp, None)
    if mode == "tp2d":
        both = tuple(fsdp) + (tp,)
        return P(None, None, both), P(None, both, None)
    raise ValueError(mode)


def moe_ffn_sharded(p, x, cfg: ModelConfig, parallel):
    """shard_map path: (B,T,d) -> ((B,T,d), aux_loss)."""
    from repro.sharding import moe_mode_for

    m = cfg.moe
    tp = parallel.tp_axis
    tp_size = parallel.mesh.shape[tp]
    mode = moe_mode_for(cfg, parallel)
    fsdp = parallel.fsdp_axes
    data_axes = parallel.data_axes
    bspec = P(data_axes, None, None)
    wspec_in, wspec_out = moe_weight_specs(mode, tp, fsdp)
    rspec = P(None, None)
    twod = mode.endswith("2d")

    def device_fn(router_w, wg, wu, wd, xb):
        if twod:
            # Decode layout: move the (tiny) activations, not the weights.
            for ax in reversed(data_axes):
                xb = jax.lax.all_gather(xb, ax, axis=0, tiled=True)
        else:
            # Gather the FSDP shards of this layer's expert weights
            # (ZeRO-3). Innermost axis first so tiled concatenation
            # reconstructs the outer-major layout.
            for ax in reversed(fsdp):
                wg = jax.lax.all_gather(wg, ax, axis=1, tiled=True)
                wu = jax.lax.all_gather(wu, ax, axis=1, tiled=True)
                wd = jax.lax.all_gather(wd, ax, axis=2, tiled=True)
        B_loc, T, d = xb.shape
        x2 = xb.reshape(B_loc * T, d)
        vals, idx, probs = router_topk({"router": router_w}, x2, cfg)
        T_tok = B_loc * T
        if mode.startswith("ep"):
            E_loc = m.n_experts // tp_size
            off = jax.lax.axis_index(tp) * E_loc
        else:
            E_loc = m.n_experts
            off = 0
        C = max(1, math.ceil(T_tok * m.top_k / m.n_experts * m.capacity_factor))
        C = min(C, T_tok)
        idx_buf, gate_buf = _dispatch_indices(idx, vals, E_loc, off, C)
        buf = x2[idx_buf]  # (E_loc*C, d)
        act = act_fn(cfg.mlp_act)
        bufe = buf.reshape(E_loc, C, d)
        g = jnp.einsum("ecd,edf->ecf", bufe, wg)
        u = jnp.einsum("ecd,edf->ecf", bufe, wu)
        y = jnp.einsum("ecf,efd->ecd", act(g) * u, wd).reshape(E_loc * C, d)
        y = y * gate_buf[:, None].astype(y.dtype)
        out = jnp.zeros((T_tok, d), y.dtype).at[idx_buf].add(y)
        out = jax.lax.psum(out, tp)
        if twod:
            # Combine the ff partial sums across data AND re-shard the
            # batch in one collective.
            out = out.reshape(B_loc, T, d)
            for ax in data_axes:
                out = jax.lax.psum_scatter(out, ax, scatter_dimension=0,
                                           tiled=True)
            B_out = out.shape[0]
            out = out.reshape(B_out, T, d)
        else:
            out = out.reshape(B_loc, T, d)
        # Aux loss: identical across tp ranks (same tokens & router);
        # pmean over the data axes makes it fully replicated.
        comb = jnp.zeros((T_tok, m.n_experts), jnp.float32).at[
            jnp.arange(T_tok)[:, None], idx].add(vals)
        aux = _load_balance_loss(comb, probs, m.n_experts)
        aux = jax.lax.pmean(aux, data_axes)
        return out, aux

    fn = jax.shard_map(
        device_fn,
        mesh=parallel.mesh,
        in_specs=(rspec, wspec_in, wspec_in, wspec_out, bspec),
        out_specs=(bspec, P()),
        check_vma=False,
    )
    out, aux = fn(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)
    return out.astype(x.dtype), aux


def moe_block_ffn(p, x, cfg: ModelConfig, parallel=None):
    if parallel is None:
        return moe_ffn_dense(p, x, cfg)
    return moe_ffn_sharded(p, x, cfg, parallel)
