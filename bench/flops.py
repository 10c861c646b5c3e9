"""Operations and bytes that the algorithm needs, from shapes alone.

These are the yardstick of every roofline share and utilization the
benchmark reports: the same work whatever implements it, and never
read from the lowered program. A multiply-add counts as two operations.

A reference module's `counts(sizes)` returns the object the readers ask
for a model's work; `Arch` is the dense decoder's. Any such object
answers `prompt_ops(length)`, `token_ops(context)` and `int8_matmuls()`.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Arch:
    """The sizes of a dense decoder that the counts depend on."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    mlp_gated: bool = True

    @classmethod
    def from_sizes(cls, sizes: dict) -> "Arch":
        return cls(**{k: sizes[k] for k in cls.__dataclass_fields__
                      if k in sizes})

    def projections(self):
        """(K, N) of each projection matmul of one layer, in the order
        q, k, v, o, up, gate, down (gate only when the MLP is gated)."""
        d, hq, hkv = self.d_model, self.n_heads * self.head_dim, \
            self.n_kv_heads * self.head_dim
        out = [(d, hq), (d, hkv), (d, hkv), (hq, d), (d, self.d_ff)]
        if self.mlp_gated:
            out.append((d, self.d_ff))
        out.append((self.d_ff, d))
        return out

    @property
    def layer_matmul_params(self) -> int:
        return sum(k * n for k, n in self.projections())

    def prompt_ops(self, length: int) -> float:
        """Operations of a prompt of `length` real tokens."""
        return prompt_flops(self, length)

    def token_ops(self, context: int) -> float:
        """Operations of one generated token at `context` keys."""
        return token_flops(self, context, logits=True)

    def int8_matmuls(self):
        """(K, N, layers that have it) of each projection matmul that
        the int8 kernel runs."""
        return [(k, n, self.n_layers) for k, n in self.projections()]


def token_flops(arch: Arch, context: int, *, logits: bool) -> float:
    """Forward operations for one token that attends to `context` keys
    (itself included): the layer projections, attention's two products
    (q.k and p.v), and the unembedding when its logits are needed."""
    proj = 2.0 * arch.layer_matmul_params
    attn = 4.0 * context * arch.n_heads * arch.head_dim
    head = 2.0 * arch.d_model * arch.vocab if logits else 0.0
    return arch.n_layers * (proj + attn) + head


def prompt_flops(arch: Arch, length: int) -> float:
    """A prompt of `length` real tokens, causal, logits at its last
    position only (what serving needs to pick the next token)."""
    proj = 2.0 * arch.layer_matmul_params * length
    attn = 4.0 * arch.n_heads * arch.head_dim * length * (length + 1) / 2
    return arch.n_layers * (proj + attn) + 2.0 * arch.d_model * arch.vocab


def int8_matmul_cost(m: int, k: int, n: int, *, x_bytes: int = 2,
                     out_bytes: int = 2):
    """C[m, n] = X[m, k] @ (Wq[k, n] * scale[n]), Wq int8, scales f32:
    read X, Wq and the scales once, write C once."""
    ops = 2.0 * m * k * n
    nbytes = m * k * x_bytes + k * n + 4 * n + m * n * out_bytes
    return ops, nbytes


def roofline_time(ops: float, nbytes: float, peak_ops: float,
                  peak_bytes_per_s: float):
    """The least time the chip could take, and which bound sets it."""
    t_ops, t_bytes = ops / peak_ops, nbytes / peak_bytes_per_s
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
