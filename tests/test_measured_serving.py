"""Measured serving (DESIGN.md §14): padded-prompt masking vs the
kernel oracle, slot backfill vs a from-scratch prefill, fp32/int8
engine equivalence, the prefill/per-token profile split, and the
exec_ms capture -> exec_override replay pin."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import reduced_config
from repro.kernels.flash_attention import blocks_run
from repro.kernels.flash_attention import tiles as flash_tiles
from repro.kernels.ref import flash_attention_ref
from repro.models import init_params
from repro.models.layers import attention_naive
from repro.models.model import prefill
from repro.serving.batching import Request
from repro.serving.engine import InferenceEngine
from repro.serving.measured import build_model
from repro.serving.server import CNNSelectServer, ServedModel
from repro.serving.simulator import SimConfig, simulate
from repro.serving.trace import CapturedTraceProcess, Trace, TraceRecorder


@pytest.fixture(scope="module", params=["auto", "pallas"])
def small(request):
    """Every engine-level pin runs twice: once on the default (auto)
    impl and once forced onto the masked pallas fast path, so the PR 7
    batching/backfill behaviour is pinned on both."""
    cfg = dataclasses.replace(reduced_config("stablelm_1_6b"),
                              attn_impl=request.param)
    params = init_params(cfg, jax.random.PRNGKey(1))
    return cfg, params


def _engine(cfg, params, batch_size=2, max_seq=32):
    eng = InferenceEngine(cfg, params, batch_size=batch_size,
                          max_seq=max_seq)
    eng.warmup(prompt_len=8)
    return eng


# -- padded-prompt masking --------------------------------------------------

def test_attention_valid_from_matches_ref():
    """Left-padded rows with valid_from equal the kernel oracle run on
    the unpadded slice (causality is relative, so the absolute-position
    shift cancels)."""
    rng = np.random.default_rng(0)
    B, Hq, KV, hd, T, pad = 1, 4, 2, 16, 6, 3
    S = T + pad
    q = jnp.asarray(rng.normal(size=(B, S, Hq, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)
    out = attention_naive(q, k, v, pos, pos, window=0, cap=0.0,
                          scale=hd ** -0.5,
                          valid_from=jnp.asarray([pad], jnp.int32))
    ref = flash_attention_ref(
        jnp.transpose(q[:, pad:], (0, 2, 1, 3)),
        jnp.transpose(k[:, pad:], (0, 2, 1, 3)),
        jnp.transpose(v[:, pad:], (0, 2, 1, 3)))
    np.testing.assert_allclose(
        np.asarray(out[:, pad:]),
        np.asarray(jnp.transpose(ref, (0, 2, 1, 3))), atol=1e-5)


def test_padded_prefill_matches_unpadded(small):
    """Engine-level pin: a left-padded row with lengths= produces the
    same logits as the unpadded prompt (RoPE is shift-invariant, pads
    are masked out of attention)."""
    cfg, params = small
    rng = np.random.default_rng(2)
    full = rng.integers(0, cfg.vocab, 8, dtype=np.int32)
    short = rng.integers(0, cfg.vocab, 5, dtype=np.int32)
    padded = np.zeros((2, 8), np.int32)
    padded[0] = full
    padded[1, 3:] = short
    eng = _engine(cfg, params)
    lp = eng.run_prefill(padded, lengths=[8, 5])
    ref = _engine(cfg, params)
    lu = ref.run_prefill(np.stack([short, short]))
    np.testing.assert_allclose(lp[1], lu[0], atol=1e-4)


def test_valid_from_zero_is_exact_noop(small):
    """valid_from=0 rows are bit-identical to the unmasked path (the
    causal mask already enforces pos_k >= 0), so maskable engines can
    always pass an array and keep a single jit trace."""
    cfg, params = small
    eng = _engine(cfg, params)
    toks = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab, (2, 8), dtype=np.int32))
    a, _ = eng._prefill(params, toks, None)
    b, _ = eng._prefill(params, toks, jnp.zeros((2,), jnp.int32))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_padded_prefill_rejected_without_mask_support(small):
    cfg, params = small
    rec = dataclasses.replace(cfg, pattern=("rglru",))
    eng = InferenceEngine(rec, params, batch_size=2, max_seq=32)
    assert not eng._maskable and not eng._backfillable
    with pytest.raises(NotImplementedError, match="recurrent"):
        eng.run_prefill(np.zeros((2, 8), np.int32), lengths=[8, 4])


# -- engine equivalence -----------------------------------------------------

def test_engine_matches_unjitted_forward(small):
    cfg, params = small
    eng = _engine(cfg, params)
    toks = np.random.default_rng(4).integers(0, cfg.vocab, (2, 8),
                                             dtype=np.int32)
    got = eng.run_prefill(toks)
    want, _ = prefill(params, jnp.asarray(toks), cfg,
                      max_seq=eng.max_seq, logits_last_only=True)
    np.testing.assert_allclose(got, np.asarray(want)[:, 0], atol=1e-5)


def test_int8_engine_within_tolerance_of_fp32():
    """Same seed -> same base weights; the int8 zoo variant must differ
    (the quantization error is real) but stay close on the logit scale,
    and must be smaller on disk (the memory-budget frontier story)."""
    a = build_model("lm_small", batch_size=2, max_seq=32, seed=5)
    b = build_model("lm_small_int8", batch_size=2, max_seq=32, seed=5)
    assert b.size_bytes < a.size_bytes
    toks = np.random.default_rng(5).integers(
        0, a.engine.cfg.vocab, (2, 8), dtype=np.int32)
    la = a.engine.run_prefill(toks)
    lb = b.engine.run_prefill(toks)
    assert not np.array_equal(la, lb)
    assert np.abs(la - lb).max() < 0.1 * np.abs(la).max()


def test_int8_engine_holds_resident_int8_weights():
    """int8 zoo engines execute from the quantized tree directly: the
    live params contain int8 projection leaves (no dequantized fp32
    copy), and the reported size is the bytes the engine actually holds
    — well under half the fp32 twin."""
    m8 = build_model("lm_small_int8", batch_size=2, max_seq=32, seed=5)
    mf = build_model("lm_small", batch_size=2, max_seq=32, seed=5)
    leaves = jax.tree.leaves(m8.engine.params)
    n_int8 = sum(1 for x in leaves if x.dtype == jnp.int8)
    assert n_int8 > 0
    assert m8.size_bytes == m8.engine.resident_bytes
    assert m8.size_bytes < 0.55 * mf.size_bytes


def test_int8_exec_same_tokens_across_impls():
    """Greedy generation from the same int8 exec tree agrees between the
    naive reference attention and the masked pallas kernels — the int8
    matmul dispatch is orthogonal to the attention impl."""
    mp = build_model("lm_small_int8", batch_size=2, max_seq=32, seed=6,
                     attn_impl="pallas")
    mn = build_model("lm_small_int8", batch_size=2, max_seq=32, seed=6,
                     attn_impl="naive")
    prompts = np.random.default_rng(6).integers(
        0, mp.engine.cfg.vocab, (2, 6), dtype=np.int32)
    np.testing.assert_array_equal(
        mp.engine.generate(prompts, 5, greedy=True),
        mn.engine.generate(prompts, 5, greedy=True))


# -- decode fail-fast & profile split ---------------------------------------

def test_run_decode_fail_fast(small):
    cfg, params = small
    eng = InferenceEngine(cfg, params, batch_size=1, max_seq=16)
    with pytest.raises(RuntimeError, match="no KV cache"):
        eng.run_decode(np.zeros((1, 1), np.int32))


def test_measured_profile_reports_prefill_decode_split(small):
    cfg, params = small
    eng = _engine(cfg, params)
    p = eng.measured_profile(prompt_len=8, n_tokens=3, reps=2)
    assert set(p) == {"mu", "sigma", "prefill_ms", "per_token_ms",
                      "resident_bytes"}
    assert p["prefill_ms"] > 0 and p["per_token_ms"] > 0
    assert p["resident_bytes"] == eng.resident_bytes > 0
    # The split is a decomposition of the same timed reps, not an
    # independent measurement: mu == prefill + n_tokens * per_token.
    assert p["mu"] == pytest.approx(
        p["prefill_ms"] + 3 * p["per_token_ms"], rel=1e-9)


# -- slot backfill ----------------------------------------------------------

def test_backfill_matches_from_scratch_prefill(small):
    """Retire -> backfill lifecycle: a request joining mid-group via
    prefill_row sees logits (and subsequent decode steps) equal to a
    from-scratch prefill at the same absolute positions."""
    cfg, params = small
    rng = np.random.default_rng(6)
    p0, p1 = (rng.integers(0, cfg.vocab, 8, dtype=np.int32)
              for _ in range(2))
    p2 = rng.integers(0, cfg.vocab, 5, dtype=np.int32)
    eng = _engine(cfg, params)
    logits = eng.run_prefill(np.stack([p0, p1]))
    hist1 = list(p1)
    for _ in range(2):                      # row0 retires after 2 tokens
        nxt = logits.argmax(-1).astype(np.int32)
        hist1.append(int(nxt[1]))
        logits = eng.run_decode(nxt[:, None])
    # cache_pos is now 10; join p2 (5 real tokens) into freed slot 0.
    prompt = np.zeros(8, np.int32)
    prompt[3:] = p2
    lj = eng.prefill_row(prompt, 0, length=5)
    # Reference: fresh engine, both rows prefilled from scratch at the
    # same absolute positions (p2 right-aligned in width 10 -> 5..9).
    row0 = np.zeros(10, np.int32)
    row0[5:] = p2
    row1 = np.asarray(hist1, np.int32)
    ref = _engine(cfg, params)
    lr = ref.run_prefill(np.stack([row0, row1]), lengths=[5, 10])
    np.testing.assert_allclose(lj, lr[0], atol=1e-4)
    np.testing.assert_allclose(logits[1], lr[1], atol=1e-4)
    # Aligned decode continues identically for both rows.
    nxt = np.stack([lj.argmax(-1), logits[1].argmax(-1)]
                   ).astype(np.int32)
    np.testing.assert_allclose(eng.run_decode(nxt[:, None]),
                               ref.run_decode(nxt[:, None]), atol=1e-4)
    assert eng.stats.backfill_calls == 1


@pytest.mark.parametrize("arch", ["stablelm_1_6b", "gemma2_9b"])
def test_attn_block_counters_follow_the_gate(arch):
    """One group prefill and one backfill row at T 256 (128-wide blocks):
    the engine's flash-kernel block counters are blocks_run of the
    valid_from it builds, per attention layer with the layer's window;
    an engine on another attention impl counts nothing."""
    cfg = dataclasses.replace(reduced_config(arch), attn_impl="pallas")
    params = init_params(cfg, jax.random.PRNGKey(3))
    B, T = 3, 256
    lengths = np.array([T, 100, 1])
    tokens = np.random.default_rng(3).integers(0, cfg.vocab, (B, T),
                                               dtype=np.int32)
    eng = InferenceEngine(cfg, params, batch_size=B, max_seq=2 * T)
    eng.run_prefill(tokens, lengths=lengths)
    _, bq, bk = flash_tiles(B, cfg.q_heads_padded, cfg.n_kv_heads, T, T,
                            cfg.head_dim, cfg.compute_dtype)
    assert (bq, bk) == (128, 128)
    kinds = [cfg.pattern[i % len(cfg.pattern)] for i in range(cfg.n_layers)]
    windows = [cfg.window if kind == "local" else 0 for kind in kinds]
    vf = T - lengths
    assert eng.stats.attn_blocks == len(windows) * B * 4
    assert eng.stats.attn_blocks_run == sum(
        blocks_run(vf, T, T, bq, bk, True, w) for w in windows)
    if arch == "stablelm_1_6b":
        assert eng.stats.attn_blocks_run == len(windows) * (3 + 1 + 1)
        eng.prefill_row(tokens[0], 1, length=200)
        assert eng.stats.attn_blocks == len(windows) * (B + 1) * 4
        assert eng.stats.attn_blocks_run == len(windows) * (5 + 3)
    other = InferenceEngine(dataclasses.replace(cfg, attn_impl="naive"),
                            params, batch_size=B, max_seq=2 * T)
    other.run_prefill(tokens, lengths=lengths)
    assert other.stats.attn_blocks == other.stats.attn_blocks_run == 0


def test_prefill_row_guards(small):
    cfg, params = small
    eng = _engine(cfg, params)
    with pytest.raises(RuntimeError, match="no KV cache"):
        eng.prefill_row(np.zeros(4, np.int32), 0)
    eng.run_prefill(np.zeros((2, 8), np.int32))
    with pytest.raises(ValueError, match="slot"):
        eng.prefill_row(np.zeros(4, np.int32), 9)
    with pytest.raises(ValueError, match="longer than current context"):
        eng.prefill_row(np.zeros(12, np.int32), 0)


# -- exec_ms capture -> exec_override replay --------------------------------

def test_exec_ms_capture_replay_bit_exact(small, tmp_path):
    """Measured exec_ms survives trace save/load bit-for-bit, and an
    exact replay with exec_override reproduces each matched request's
    latency as exactly 2*t_input + exec_ms (no resampling)."""
    cfg, params = small
    models = [
        ServedModel(name=n, accuracy=acc,
                    engine=InferenceEngine(cfg, init_params(
                        cfg, jax.random.PRNGKey(s)),
                        batch_size=1, max_seq=32))
        for n, acc, s in [("a", 0.6, 0), ("b", 0.9, 1)]]
    srv = CNNSelectServer(models, t_threshold=10.0, n_tokens=2)
    srv.profile_models(prompt_len=8, reps=2)
    names = [m.name for m in models]
    rng = np.random.default_rng(7)
    t_sla = 60.0
    with TraceRecorder(name="pin").attach(srv) as rec:
        for i in range(12):
            srv.handle(Request(
                arrival=float(i), rid=i,
                prompt=rng.integers(0, 50, 8).astype(np.int32),
                t_input_ms=float(5.0 + (i % 3))), t_sla=t_sla)
        tr = rec.to_trace(source="server", meta={"models": names})
    path = tmp_path / "pin.jsonl"
    tr.save(path)
    back = Trace.load(path)
    exec_ms = np.asarray(tr.meta["exec_ms"], np.float64)
    np.testing.assert_array_equal(
        exec_ms, np.asarray(back.meta["exec_ms"], np.float64))
    # Replay: inject the measured exec time of each captured selection.
    over = np.full((len(back), len(names)), np.nan)
    for i, m in enumerate(back.model):
        over[i, names.index(str(m))] = exec_ms[i]
    profs = [dataclasses.replace(p, cold_mu=0.0, cold_sigma=0.0)
             for p in srv.current_profiles()]
    rep = simulate(profs, SimConfig(
        t_sla=t_sla, n_requests=len(back), seed=7,
        network=CapturedTraceProcess(back, mode="exact"),
        t_threshold=10.0), exec_override=over)
    cap_sel = np.array([names.index(str(m)) for m in back.model])
    matched = rep.selections == cap_sel
    assert matched.any()
    np.testing.assert_array_equal(
        rep.latencies[matched],
        2.0 * np.asarray(back.t_input_ms)[matched] + exec_ms[matched])
