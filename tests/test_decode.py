"""KV-cache correctness: prefill + decode must reproduce the full
forward for every architecture (exercises ring buffers, RG-LRU and SSD
state passing, and the carried-cache scan)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, reduced_config
from repro.models import init_params, forward, decode_step, init_cache
from repro.models.model import prefill

TOKEN_ARCHS = [a for a in ARCH_IDS
               if a not in ("musicgen_large", "chameleon_34b")]


@pytest.mark.parametrize("arch", TOKEN_ARCHS)
def test_prefill_then_decode_matches_forward(arch):
    cfg = reduced_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, T = 2, 14
    x = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab)
    full, _ = forward(params, x, cfg)
    _, cache = prefill(params, x[:, :T - 3], cfg, max_seq=32)
    pos = T - 3
    for t in range(T - 3, T):
        logits, cache = decode_step(params, x[:, t:t + 1], cache,
                                    jnp.int32(pos), cfg)
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   np.asarray(full[:, t]), atol=5e-3,
                                   rtol=1e-3)
        pos += 1


@pytest.mark.parametrize("arch", ["gemma2_9b", "recurrentgemma_2b"])
def test_ring_buffer_window_decode(arch):
    """Decode far beyond the window: ring-buffer cache must agree with a
    full forward over the whole sequence (window masking equal)."""
    cfg = reduced_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    B = 1
    T = 3 * cfg.window  # several wraps
    x = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab)
    full, _ = forward(params, x, cfg)
    _, cache = prefill(params, x[:, :4], cfg, max_seq=T)
    pos = 4
    for t in range(4, T):
        logits, cache = decode_step(params, x[:, t:t + 1], cache,
                                    jnp.int32(pos), cfg)
        pos += 1
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full[:, -1]), atol=5e-3, rtol=1e-3)


def test_prefill_longer_than_window_ring_layout():
    cfg = reduced_config("gemma2_9b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, T = 1, 20  # window is 8 in the reduced config
    x = jax.random.randint(jax.random.PRNGKey(1), (B, T + 1), 0, cfg.vocab)
    full, _ = forward(params, x, cfg)
    _, cache = prefill(params, x[:, :T], cfg, max_seq=64)
    logits, _ = decode_step(params, x[:, T:], cache, jnp.int32(T), cfg)
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full[:, -1]), atol=5e-3, rtol=1e-3)


def test_embeddings_input_decode():
    cfg = reduced_config("chameleon_34b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, T = 2, 10
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.d_model))
    full, _ = forward(params, x, cfg)
    _, cache = prefill(params, x[:, :T - 1], cfg, max_seq=16)
    logits, _ = decode_step(params, x[:, T - 1:], cache, jnp.int32(T - 1), cfg)
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full[:, -1]), atol=5e-3, rtol=1e-3)


@pytest.mark.parametrize("arch", ["stablelm_1_6b", "gemma2_9b"])
def test_prefill_cache_unused_slots_are_empty(arch):
    """Slots past the prompt hold zeros at stored position -1 after a
    prefill. Decode masks them by position, and its attention multiplies
    their (zero-weight) values, so a slot left uninitialized would feed
    stray positions or NaN into every later step."""
    cfg = reduced_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    T, S = 5, 32
    x = jax.random.randint(jax.random.PRNGKey(1), (2, T), 0, cfg.vocab)
    _, cache = prefill(params, x, cfg, max_seq=S)
    layers = list(cache["blocks"]) + list(cache["tail"])
    for c in layers:
        if "pos" not in c:
            continue
        n = c["pos"].shape[-1]
        pos = np.asarray(c["pos"]).reshape(-1, n)
        assert (pos[:, :T] == np.arange(T)).all()
        if n > T:
            assert (pos[:, T:] == -1).all()
            for key in ("k", "v"):
                assert not np.asarray(c[key][..., T:, :, :]).any()
