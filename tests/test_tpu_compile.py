"""Ahead-of-time compiles of the three Pallas kernels for a described
TPU v5e, at the widths the served path uses.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
tile-misaligned slices, 1-D vectors broadcast back across a tile, more
VMEM than a kernel may use. Here the TPU compiler itself lowers each
kernel for a chip that is described, not attached, so a refusal fails
the test with no chip in the loop. Nothing runs: these tests say nothing
about results or speed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and the test workers all
import this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_matmul import int8_matmul


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# stablelm-1.6b: 32 heads over 32 kv heads, head_dim 64; batch 4.
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("T", [8, 128])
def test_flash_attention_compiles_stablelm(one_chip, T, dtype):
    qkv = ((4, 32, T, 64), dtype)
    txt = _compiled_text(
        lambda q, k, v, vf: flash_attention(q, k, v, vf, interpret=False),
        one_chip, qkv, qkv, qkv, ((4,), jnp.int32))
    assert "tpu_custom_call" in txt


# The wrapper with its own tiles at the benchmark cell's group prefill
# (batch 16, T 256: 32 heads a step, 128-wide blocks) and at yi-9b's
# GQA layout (32 q heads over 4 kv heads, hd 128), with the skipped
# steps' k/v index maps read from valid_from: a tile that asks more
# VMEM than Mosaic allows, or a misaligned fold of q heads into rows,
# fails here.
@pytest.mark.parametrize("Hq,KV,hd", [(32, 32, 64), (32, 4, 128)],
                         ids=["stablelm", "yi_gqa"])
def test_flash_attention_own_tiles_compile(one_chip, monkeypatch, Hq, KV,
                                           hd):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    B, T = 16, 256
    txt = _compiled_text(
        lambda q, k, v, vf: ops.flash_attention_btHd.__wrapped__(q, k, v,
                                                                 vf),
        one_chip, ((B, T, Hq, hd), jnp.bfloat16),
        ((B, T, KV, hd), jnp.bfloat16), ((B, T, KV, hd), jnp.bfloat16),
        ((B,), jnp.int32))
    assert "tpu_custom_call" in txt
    assert " pad(" not in txt


# rep = Hq / KV: 1 for stablelm-1.6b (hd 64), 8 for yi-9b (32 over 4,
# hd 128). A 512-slot linear cache, the serving engine's layout.
@pytest.mark.parametrize("Hq,KV,hd", [(32, 32, 64), (32, 4, 128)],
                         ids=["rep1", "rep8"])
def test_decode_attention_compiles(one_chip, Hq, KV, hd):
    S = 512
    kv = ((4, KV, S, hd), jnp.bfloat16)
    txt = _compiled_text(
        lambda q, k, v, pos, cp, vf: decode_attention(
            q, k, v, pos, cp, vf, linear=True, interpret=False),
        one_chip, ((4, Hq, hd), jnp.bfloat16), kv, kv, ((S,), jnp.int32),
        ((), jnp.int32), ((4,), jnp.int32))
    assert "tpu_custom_call" in txt


# M = batch rows (decode) or batch x prompt (prefill); stablelm-1.6b's
# MLP up projection, d_model 2048 -> d_ff 5632.
@pytest.mark.parametrize("M", [4, 512])
def test_int8_matmul_compiles(one_chip, M):
    K, N = 2048, 5632
    txt = _compiled_text(
        lambda x, w, s: int8_matmul(x, w, s, block_m=min(256, M),
                                    interpret=False),
        one_chip, ((M, K), jnp.bfloat16), ((K, N), jnp.int8),
        ((N,), jnp.float32))
    assert "tpu_custom_call" in txt


# The wrapper with its own tiles (no block_*), at decode (M = batch 16),
# the backfill row (256) and a 16 x 256 prefill, for stablelm-1.6b's
# q/k/v/o, gate/up and down widths: a tile that asks more VMEM than
# Mosaic allows fails here. The wrapper asks the backend whether to
# interpret, and the backend here is the CPU: the test says no.
@pytest.mark.parametrize("K,N", [(2048, 2048), (2048, 5632), (5632, 2048)])
@pytest.mark.parametrize("M", [16, 256, 4096])
def test_int8_matmul_own_tiles_compile(one_chip, monkeypatch, M, K, N):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    txt = _compiled_text(
        lambda x, w, s: ops.int8_matmul.__wrapped__(x, w, s),
        one_chip, ((M, K), jnp.bfloat16), ((K, N), jnp.int8),
        ((N,), jnp.float32))
    assert "tpu_custom_call" in txt
    assert " pad(" not in txt
