"""Plain reference of a dense decoder: pre-norm causal self-attention
with grouped key/value heads and partial rotary embeddings, then a
gated SiLU MLP, both on a residual stream; a final norm and an untied
unembedding. RMSNorm scales by (1 + w).

It is written from the published architecture in `jax.numpy` and
float32 at the highest matmul precision: no kernels, no cache, no
padding, no batching of different requests into one row. It imports
nothing of the program. The weights come from `init_weights`, which the
benchmark also uses to make the weights it hands the program, in the
layout the program's engines take (layers stacked on a leading axis).

`bits` quantizes each projection weight per output channel, symmetric,
to 2**(bits-1)-1 levels, the way an int8 (or int4) serving copy is made;
the embedding, unembedding and norms stay as they are.

What the harness asks of a reference module: `make_weights` and
`logit_stats` (below), `unmodelled`, which names the fields of the
program's config that this reference does not model, and `counts`, the
yardstick's operation counts for these sizes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import flops

# Trailing output axes of each projection (everything before them in one
# layer's weight contracts).
PROJ_OUT_AXES = {"wq": 2, "wk": 2, "wv": 2, "wo": 1,
                 "w_up": 1, "w_gate": 1, "w_down": 1}


def leaf_specs(s: dict):
    """{path: (shape, std)} of every weight, std 0 meaning 'norm'."""
    L, d, H, KV, hd, f, V = (s["n_layers"], s["d_model"], s["n_heads"],
                             s["n_kv_heads"], s["head_dim"], s["d_ff"],
                             s["vocab"])
    return {
        "embed": ((V, d), 1.0),
        "final_norm": ((d,), 0.0),
        "lm_head": ((d, V), d ** -0.5),
        "ln1": ((L, d), 0.0),
        "wq": ((L, d, H, hd), d ** -0.5),
        "wk": ((L, d, KV, hd), d ** -0.5),
        "wv": ((L, d, KV, hd), d ** -0.5),
        "wo": ((L, H, hd, d), (H * hd) ** -0.5),
        "ln2": ((L, d), 0.0),
        "w_up": ((L, d, f), d ** -0.5),
        "w_gate": ((L, d, f), d ** -0.5),
        "w_down": ((L, f, d), f ** -0.5),
    }


NORM_STD = 0.1   # norms scale by (1 + w), w ~ N(0, NORM_STD)


def unmodelled(program: dict) -> list:
    """The fields of the program's model config, given as
    `dataclasses.asdict` of it, whose values this reference does not
    model; empty where it models them all."""
    p = program
    plain = {"pattern": tuple(p["pattern"]) == ("attn",),
             "tie_embeddings": not p["tie_embeddings"],
             "mlp_act": p["mlp_act"] == "silu",
             "mlp_gated": p["mlp_gated"],
             "qk_norm": not p["qk_norm"],
             "sandwich_norm": not p["sandwich_norm"],
             "window": not p["window"],
             "attn_softcap": not p["attn_softcap"],
             "final_softcap": not p["final_softcap"],
             "embed_scale": not p["embed_scale"],
             "tp_pad_heads": not p["tp_pad_heads"],
             "tp_pad_vocab": not p["tp_pad_vocab"],
             "input_mode": p["input_mode"] == "tokens"}
    return [k for k, ok in plain.items() if not ok]


def counts(sizes: dict) -> flops.Arch:
    """Operations and int8 matmuls of a dense decoder of these sizes."""
    return flops.Arch.from_sizes(sizes)


def init_weights(sizes: dict, key, dtype=jnp.bfloat16) -> dict:
    """Seeded random weights in the engines' tree layout. Call it under
    `jax.jit` so that they are made on the device in one program."""
    flat = {}
    for i, (name, (shape, std)) in enumerate(sorted(leaf_specs(sizes).items())):
        k = jax.random.fold_in(key, i)
        x = jax.random.normal(k, shape, jnp.float32)
        flat[name] = (x * (std if std else NORM_STD)).astype(dtype)
    block = {"ln1": flat["ln1"], "wq": flat["wq"], "wk": flat["wk"],
             "wv": flat["wv"], "wo": flat["wo"], "ln2": flat["ln2"],
             "mlp": {"w_up": flat["w_up"], "w_gate": flat["w_gate"],
                     "w_down": flat["w_down"]}}
    return {"embed": flat["embed"], "final_norm": flat["final_norm"],
            "lm_head": flat["lm_head"], "blocks": (block,), "tail": ()}


def make_weights(sizes: dict, key, dtype=jnp.bfloat16,
                 out_shardings=None) -> dict:
    """`init_weights` as one jitted program: on the default device, or,
    given `out_shardings` (a tree of shardings shaped as the weights),
    each leaf made where its sharding places it."""
    kw = {} if out_shardings is None else {"out_shardings": out_shardings}
    return jax.jit(functools.partial(init_weights, sizes, dtype=dtype),
                   **kw)(key)


def fake_quant(w, out_axes: int, bits: int):
    """Per-output-channel symmetric quantize then dequantize, float32."""
    qmax = 2.0 ** (bits - 1) - 1.0
    red = tuple(range(w.ndim - out_axes))
    amax = jnp.max(jnp.abs(w), axis=red, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    return jnp.clip(jnp.round(w / scale), -qmax, qmax) * scale


def _norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, positions, theta, rotary_pct):
    hd = x.shape[-1]
    rot = int(hd * rotary_pct)
    rot -= rot % 2
    half = rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs       # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _layer(x, w, sizes, bits):
    f32 = {k: (v.astype(jnp.float32) if not isinstance(v, dict) else
               {kk: vv.astype(jnp.float32) for kk, vv in v.items()})
           for k, v in w.items()}
    mlp = f32.pop("mlp")
    f32.update(mlp)
    if bits:
        for k, n in PROJ_OUT_AXES.items():
            f32[k] = fake_quant(f32[k], n, bits)
    eps, H, KV = sizes["norm_eps"], sizes["n_heads"], sizes["n_kv_heads"]
    hd = sizes["head_dim"]
    R, T, _ = x.shape
    pos = jnp.arange(T)
    h = _norm(x, f32["ln1"], eps)
    q = jnp.einsum("rtd,dhk->rthk", h, f32["wq"])
    k = jnp.einsum("rtd,dhk->rthk", h, f32["wk"])
    v = jnp.einsum("rtd,dhk->rthk", h, f32["wv"])
    q = _rope(q, pos, sizes["rope_theta"], sizes["rotary_pct"])
    k = _rope(k, pos, sizes["rope_theta"], sizes["rotary_pct"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("rqhk,rshk->rhqs", q, k) * hd ** -0.5
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("rhqs,rshk->rqhk", p, v)
    x = x + jnp.einsum("rthk,hkd->rtd", a, f32["wo"])
    h = _norm(x, f32["ln2"], eps)
    g = jax.nn.silu(jnp.einsum("rtd,df->rtf", h, f32["w_gate"]))
    u = jnp.einsum("rtd,df->rtf", h, f32["w_up"])
    return x + jnp.einsum("rtf,fd->rtd", g * u, f32["w_down"])


@functools.partial(jax.jit, static_argnames=("sizes_items", "bits", "chunk"))
def _stats(weights, tokens, probes, gather, *, sizes_items, bits, chunk):
    sizes = dict(sizes_items)
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)

    def body(x, w):
        return _layer(x, w, sizes, bits), None

    x, _ = jax.lax.scan(body, x, weights["blocks"][0])
    x = _norm(x, weights["final_norm"].astype(jnp.float32), sizes["norm_eps"])
    head = weights["lm_head"].astype(jnp.float32)
    R, T, d = x.shape
    xs = x.reshape(R, T // chunk, chunk, d).transpose(1, 0, 2, 3)
    ps = probes.reshape(R, T // chunk, chunk, -1).transpose(1, 0, 2, 3)

    def block(args):
        xc, pc = args
        logits = jnp.einsum("rtd,dv->rtv", xc, head)
        return (logits.max(-1), logits.argmax(-1).astype(jnp.int32),
                jnp.take_along_axis(logits, pc, axis=-1))

    top, arg, probe = jax.lax.map(block, (xs, ps))
    back = lambda a: a.transpose(1, 0, 2, *range(3, a.ndim)).reshape(
        R, T, *a.shape[3:])
    rows = x[gather[:, 0], gather[:, 1]] @ head
    return back(top), back(arg), back(probe), rows


def logit_stats(weights, tokens, probes, sizes: dict, *, gather, bits=None,
                chunk: int = 128):
    """tokens (R, T) int32, one sequence per row from position 0 (pad
    the end; causal attention keeps padding out of earlier positions);
    probes (R, T, P) int32 token ids; gather (K, 2) int32 (row,
    position) pairs. At every position: the largest logit, its token,
    and the logits of the P probe tokens; and the whole logit row at
    each gathered position, (K, vocab)."""
    with jax.default_matmul_precision("highest"):
        return _stats(weights, tokens, probes, gather,
                      sizes_items=tuple(sorted(sizes.items())), bits=bits,
                      chunk=chunk)
