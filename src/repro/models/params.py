"""Parameter tree construction with logical sharding axes.

Every builder receives a ``mk(shape, axes, init)`` callback so the same
structural code yields (a) real initialized arrays, (b) the parallel tree
of logical-axis tuples, and (c) ShapeDtypeStruct stand-ins for the
dry-run — guaranteeing the three can never drift apart.

Logical axis vocabulary (mapped to mesh axes by `repro.sharding` rules):
  vocab, embed        embedding table dims
  hidden_in           d_model as a matmul input dim
  heads, kv_heads, head_dim
  ff                  dense FFN hidden
  experts, expert_ff  MoE dims
  rnn_width           RG-LRU width
  ssd_inner, ssd_heads, ssd_gn
  norm, conv_k, layers(stacked scan dim)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig, ATTN_KINDS
from repro.utils import dtype_of


def block_tree(cfg: ModelConfig, kind: str, mk):
    """One block's parameter tree via the mk callback."""
    d = cfg.d_model
    p = {}
    if kind in ATTN_KINDS:
        Hq, KV, hd = cfg.q_heads_padded, cfg.n_kv_heads, cfg.head_dim
        p["ln1"] = mk((d,), ("norm",), "zeros")
        p["wq"] = mk((d, Hq, hd), ("hidden_in", "heads", "head_dim"), "fan_in")
        p["wk"] = mk((d, KV, hd), ("hidden_in", "kv_heads", "head_dim"), "fan_in")
        p["wv"] = mk((d, KV, hd), ("hidden_in", "kv_heads", "head_dim"), "fan_in")
        p["wo"] = mk((Hq, hd, d), ("heads", "head_dim", "hidden_in"), "fan_io")
        if cfg.qk_norm:
            p["q_norm"] = mk((hd,), ("norm",), "zeros")
            p["k_norm"] = mk((hd,), ("norm",), "zeros")
        if cfg.sandwich_norm:
            p["post_attn_norm"] = mk((d,), ("norm",), "zeros")
            p["post_ffn_norm"] = mk((d,), ("norm",), "zeros")
        p["ln2"] = mk((d,), ("norm",), "zeros")
    if kind == "moe":
        m = cfg.moe
        p["router"] = mk((d, m.n_experts), ("hidden_in", "router"), "fan_in")
        p["w_up"] = mk((m.n_experts, d, m.d_ff_expert),
                       ("experts", "expert_in", "expert_ff"), "fan_in3")
        if cfg.mlp_gated:
            p["w_gate"] = mk((m.n_experts, d, m.d_ff_expert),
                             ("experts", "expert_in", "expert_ff"), "fan_in3")
        p["w_down"] = mk((m.n_experts, m.d_ff_expert, d),
                         ("experts", "expert_ff", "expert_in"), "fan_in3")
    elif kind in ("attn", "global", "local"):
        p["mlp"] = _mlp_tree(cfg, mk)
    elif kind == "rglru":
        w = cfg.lru_width
        K = cfg.rglru.conv_width
        p["ln1"] = mk((d,), ("norm",), "zeros")
        p["w_gate_branch"] = mk((d, w), ("hidden_in", "rnn_width"), "fan_in")
        p["w_in"] = mk((d, w), ("hidden_in", "rnn_width"), "fan_in")
        p["conv_w"] = mk((w, K), ("rnn_width", "conv_k"), "conv")
        p["w_a"] = mk((w, w), ("rnn_in", "rnn_width"), "fan_in")
        p["w_x"] = mk((w, w), ("rnn_in", "rnn_width"), "fan_in")
        p["b_a"] = mk((w,), ("rnn_width",), "zeros")
        p["b_x"] = mk((w,), ("rnn_width",), "zeros")
        p["lam"] = mk((w,), ("rnn_width",), "lambda")
        p["w_out"] = mk((w, d), ("rnn_width", "hidden_in"), "fan_in")
        p["ln2"] = mk((d,), ("norm",), "zeros")
        p["mlp"] = _mlp_tree(cfg, mk)
    elif kind == "ssd":
        s = cfg.ssd
        di, nh = cfg.d_inner_ssd, cfg.ssd_heads
        gn = s.n_groups * s.d_state
        K = s.conv_width
        p["ln1"] = mk((d,), ("norm",), "zeros")
        p["w_z"] = mk((d, di), ("hidden_in", "ssd_inner"), "fan_in")
        p["w_x"] = mk((d, di), ("hidden_in", "ssd_inner"), "fan_in")
        p["w_B"] = mk((d, gn), ("hidden_in", "ssd_gn"), "fan_in")
        p["w_C"] = mk((d, gn), ("hidden_in", "ssd_gn"), "fan_in")
        p["w_dt"] = mk((d, nh), ("hidden_in", "ssd_heads"), "fan_in")
        p["conv_x"] = mk((di, K), ("ssd_inner", "conv_k"), "conv")
        p["conv_B"] = mk((gn, K), ("ssd_gn", "conv_k"), "conv")
        p["conv_C"] = mk((gn, K), ("ssd_gn", "conv_k"), "conv")
        p["A_log"] = mk((nh,), ("ssd_heads",), "a_log")
        p["dt_bias"] = mk((nh,), ("ssd_heads",), "dt_bias")
        p["D"] = mk((nh,), ("ssd_heads",), "ones")
        p["norm_w"] = mk((di,), ("ssd_inner",), "ones")
        p["w_out"] = mk((di, d), ("ssd_inner", "hidden_in"), "fan_in")
    return p


def _mlp_tree(cfg: ModelConfig, mk):
    d, f = cfg.d_model, cfg.d_ff
    p = {"w_up": mk((d, f), ("hidden_in", "ff"), "fan_in"),
         "w_down": mk((f, d), ("ff", "hidden_in"), "fan_in")}
    if cfg.mlp_gated:
        p["w_gate"] = mk((d, f), ("hidden_in", "ff"), "fan_in")
    return p


def model_tree(cfg: ModelConfig, mk, mk_stacked):
    """Full model parameter tree.

    mk_stacked(shape, axes, init, n) creates a leaf with a leading
    ("layers", n) dim for the scanned groups.
    """
    d = cfg.d_model
    params = {
        "embed": mk((cfg.padded_vocab, d), ("vocab", "embed"), "embed"),
        "final_norm": mk((d,), ("norm",), "zeros"),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = mk((d, cfg.padded_vocab), ("hidden_in", "vocab"),
                               "fan_in")
    G = cfg.n_groups_scan
    blocks = []
    for kind in cfg.pattern:
        stacked_mk = lambda shape, axes, init: mk_stacked(shape, axes, init, G)
        blocks.append(block_tree(cfg, kind, stacked_mk))
    params["blocks"] = tuple(blocks)
    params["tail"] = tuple(block_tree(cfg, kind, mk) for kind in cfg.tail_kinds)
    return params


# --------------------------------------------------------------------------
# The three concrete instantiations of mk
# --------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> dict:
    dtype = dtype_of(cfg.param_dtype)
    counter = [0]

    def draw(shape, init, n_stacked=0):
        # n_stacked leading ("layers",) dims are not fan dims.
        counter[0] += 1
        k = jax.random.fold_in(key, counter[0])
        if init == "zeros":
            return jnp.zeros(shape, dtype)
        if init == "ones":
            return jnp.ones(shape, dtype)
        if init == "embed":
            return (jax.random.normal(k, shape) * 1.0).astype(dtype)
        if init == "lambda":
            # RG-LRU Lambda init: a in [0.9, 0.999] => Lambda = logit-ish.
            u = jax.random.uniform(k, shape, minval=0.9, maxval=0.999)
            # a = exp(-c*softplus(lam)) at r=1 -> softplus(lam) = -log(a)/c
            sp = -jnp.log(u) / 8.0
            return jnp.log(jnp.expm1(jnp.maximum(sp, 1e-8))).astype(dtype)
        if init == "a_log":
            # mamba2: A in [1, 16) -> A_log = log(A).
            u = jax.random.uniform(k, shape, minval=1.0, maxval=16.0)
            return jnp.log(u).astype(dtype)
        if init == "dt_bias":
            # dt in [1e-3, 1e-1] through softplus.
            u = jax.random.uniform(k, shape, minval=1e-3, maxval=1e-1)
            return jnp.log(jnp.expm1(u)).astype(dtype)
        if init == "conv":
            fan = shape[-1]
            return (jax.random.normal(k, shape) / np.sqrt(fan)).astype(dtype)
        # fan_in variants: scale by 1/sqrt(prod of input dims).
        one = shape[n_stacked:]
        if init == "fan_in3":
            fan = one[1]
        elif init == "fan_io":
            fan = one[0] * one[1]
        else:
            fan = one[0]
        return (jax.random.normal(k, shape) / np.sqrt(fan)).astype(dtype)

    def mk(shape, axes, init):
        return draw(shape, init)

    def mk_stacked(shape, axes, init, n):
        return draw((n,) + shape, init, n_stacked=1)

    return model_tree(cfg, mk, mk_stacked)


def param_logical_axes(cfg: ModelConfig) -> dict:
    mk = lambda shape, axes, init: axes
    mk_stacked = lambda shape, axes, init, n: ("layers",) + axes
    return model_tree(cfg, mk, mk_stacked)


def abstract_params(cfg: ModelConfig) -> dict:
    dtype = dtype_of(cfg.param_dtype)
    mk = lambda shape, axes, init: jax.ShapeDtypeStruct(shape, dtype)
    mk_stacked = lambda shape, axes, init, n: jax.ShapeDtypeStruct(
        (n,) + shape, dtype)
    return model_tree(cfg, mk, mk_stacked)
