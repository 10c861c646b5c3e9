"""Pallas TPU kernels for the serving hot spots.

- flash_attention: prefill attention (GQA + sliding window + logit
  softcap + causal), online-softmax over KV blocks in VMEM — bf16
  operands on the MXU with f32 accumulation, several heads a step, and
  (q block, k block) pairs of at most 128 skipped by one gate (`runs`).
- decode_attention: flash-decode over a (possibly ring-buffer) KV cache.
- int8_matmul: per-channel-scaled int8-weight matmul (the TPU adaptation
  of the paper's 8-bit post-training quantization study — weight tiles
  converted to the activation's dtype for the MXU, tiles sized from the
  shape, scales applied once per output tile at flush).

Each kernel ships with `ops.py` (jit'd wrappers used by the model when
`attn_impl="pallas"`) and `ref.py` (pure-jnp oracles); tests sweep
shapes/dtypes in interpret mode against the oracles.
"""
