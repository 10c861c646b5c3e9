"""Real inference engine: jit'd prefill + decode with KV-cache slots.

This is the execution backend behind the CNNSelect server for models
that actually run in this process (CPU here; the same step functions are
what the dry-run lowers for the TPU meshes). Decode steps are *aligned*
within a batch group; the continuous-batching scheduler (batching.py)
regroups requests between steps and backfills freed slots via
`prefill_row` mid-group."""

from __future__ import annotations

import time
from collections import Counter
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.kernels.flash_attention import blocks_run
from repro.kernels.flash_attention import tiles as flash_tiles
from repro.models import decode_step, forward, init_cache
from repro.models.config import ATTN_KINDS, ModelConfig
from repro.models.model import prefill


@dataclass
class EngineStats:
    prefill_calls: int = 0
    decode_calls: int = 0
    backfill_calls: int = 0
    prefill_time_s: float = 0.0
    decode_time_s: float = 0.0
    backfill_time_s: float = 0.0
    compile_time_s: float = 0.0
    # Copying the returned logits to the host, every kind of call; the
    # *_time_s above stop at the device result and leave it out.
    fetch_time_s: float = 0.0
    # The flash prefill kernel's (row, q block, k block) triples, summed
    # over the attention layers of every group prefill and backfill row:
    # those its causal/window/valid_from gate runs, and all of them.
    attn_blocks_run: int = 0
    attn_blocks: int = 0


class InferenceEngine:
    """One model's runnable engine with a fixed batch capacity."""

    def __init__(self, cfg: ModelConfig, params, *, batch_size: int,
                 max_seq: int, parallel=None):
        self.cfg = cfg
        self.params = params
        self.batch_size = batch_size
        self.max_seq = max_seq
        self.parallel = parallel
        self.stats = EngineStats()
        self.cache = None
        self.cache_pos = 0
        self.valid_from = None
        kinds = set(cfg.pattern) | set(cfg.tail_kinds)
        # Per-row masking (left-padded prompts / slot backfill) only works
        # on attention caches; recurrent state integrates pads irrevocably.
        self._maskable = kinds <= set(ATTN_KINDS)
        # Slot backfill additionally needs every layer's cache to span
        # max_seq (a windowed ring smaller than max_seq wraps slots).
        self._backfillable = self._maskable and not (
            "local" in kinds and cfg.window and cfg.window < max_seq)
        # Window -> number of layers whose prefill runs the flash kernel
        # with it (none unless attn_impl is "pallas"), for the counters.
        layer_kinds = (cfg.pattern * cfg.n_groups_scan) + cfg.tail_kinds
        self._flash_windows = Counter(
            cfg.window if kind == "local" else 0 for kind in layer_kinds
            if kind in ATTN_KINDS) if cfg.attn_impl == "pallas" else {}

        def _prefill(params, tokens, valid_from=None):
            return prefill(params, tokens, cfg, max_seq=max_seq,
                           parallel=parallel, logits_last_only=True,
                           valid_from=valid_from)

        def _decode(params, token, cache, pos, valid_from=None):
            return decode_step(params, token, cache, pos, cfg,
                               parallel=parallel, valid_from=valid_from)

        def _prefill_row(params, tokens, offset, valid_from):
            # Single-row prefill at absolute positions offset..offset+T-1
            # into a fresh (B=1) cache; merged into the live batch cache by
            # `_merge`. RoPE is applied at the true absolute positions so
            # the merged keys are indistinguishable from ones written by a
            # from-scratch group prefill.
            T = tokens.shape[1]
            positions = offset + jnp.arange(T, dtype=jnp.int32)
            cache = init_cache(cfg, 1, max_seq)
            logits, extras = forward(params, tokens, cfg, parallel=parallel,
                                     cache=cache, positions=positions,
                                     logits_last_only=True,
                                     valid_from=valid_from)
            return logits, extras["cache"]

        def _merge(bcache, rcache, row, offset, T):
            # Copy the row cache's first T seq slots into batch slot `row`
            # at seq offset `offset`. The shared (S,) pos array needs no
            # update: group prefill + aligned decode already maintain
            # pos[s] == s for every slot below cache_pos.
            def one(bd, rd):
                out = dict(bd)
                for key in ("k", "v"):
                    b, r = bd[key], rd[key]
                    if b.ndim == 5:     # stacked blocks: (G, B, S, KV, hd)
                        upd = r[:, :, :T].astype(b.dtype)
                        out[key] = jax.lax.dynamic_update_slice(
                            b, upd, (0, row, offset, 0, 0))
                    else:               # tail: (B, S, KV, hd)
                        upd = r[:, :T].astype(b.dtype)
                        out[key] = jax.lax.dynamic_update_slice(
                            b, upd, (row, offset, 0, 0))
                return out
            return {
                "blocks": tuple(one(bd, rd) for bd, rd in
                                zip(bcache["blocks"], rcache["blocks"])),
                "tail": tuple(one(bd, rd) for bd, rd in
                              zip(bcache["tail"], rcache["tail"])),
            }

        self._prefill = jax.jit(_prefill)
        self._decode = jax.jit(_decode, donate_argnums=(2,))
        self._prefill_row = jax.jit(_prefill_row)
        self._merge = jax.jit(_merge, donate_argnums=(0,),
                              static_argnums=(4,))

    def warmup(self, prompt_len: int = 8):
        """Cold-start work: first-call compilation (the serving analogue
        of the paper's model-load phase). Returns compile seconds."""
        t0 = time.perf_counter()
        toks = jnp.zeros((self.batch_size, prompt_len), jnp.int32)
        vf = jnp.zeros((self.batch_size,), jnp.int32) if self._maskable \
            else None
        logits, cache = self._prefill(self.params, toks, vf)
        logits.block_until_ready()
        out = self._decode(self.params, toks[:, :1], cache,
                           jnp.int32(prompt_len), vf)
        out[0].block_until_ready()
        if self._backfillable:
            # Compile the backfill pair too: a first mid-group join must
            # not charge jit time to a measured request.
            rl, rc = self._prefill_row(self.params, toks[:1],
                                       jnp.int32(0),
                                       jnp.zeros((1,), jnp.int32))
            _ = self._merge(out[1], rc, jnp.int32(0), jnp.int32(0),
                            prompt_len)
            rl.block_until_ready()
        dt = time.perf_counter() - t0
        self.stats.compile_time_s += dt
        return dt

    def _fetch(self, logits, index) -> np.ndarray:
        """`logits[index]`, the next-token logits, on the host
        (`serve.fetch`)."""
        t0 = time.perf_counter()
        with TraceAnnotation("serve.fetch"):
            out = np.asarray(logits[index])
        self.stats.fetch_time_s += time.perf_counter() - t0
        return out

    def _valid_from_for(self, tokens, lengths):
        """(B,) first attendable absolute position per row on the host,
        or None."""
        B, T = tokens.shape
        if lengths is None:
            if not self._maskable:
                return None
            return np.zeros((B,), np.int32)
        if not self._maskable:
            raise NotImplementedError(
                f"padded prompts need per-row masking, which recurrent "
                f"blocks in pattern {self.cfg.pattern} do not support")
        lengths = np.asarray(lengths, np.int64)
        if lengths.shape != (B,) or np.any(lengths < 1) or np.any(lengths > T):
            raise ValueError(f"lengths must be (B,) in [1, {T}]")
        return (T - lengths).astype(np.int32)

    def _count_attn_blocks(self, valid_from, T: int):
        """Add one prefill call's flash-kernel block triples to the
        stats: valid_from (rows,) in the kernel's coordinates, over T
        queries and T keys."""
        if not self._flash_windows:
            return
        cfg = self.cfg
        rows = len(valid_from)
        _, bq, bk = flash_tiles(rows, cfg.q_heads_padded, cfg.n_kv_heads,
                                T, T, cfg.head_dim, cfg.compute_dtype)
        per_layer = rows * -(-T // bq) * -(-T // bk)
        for window, layers in self._flash_windows.items():
            self.stats.attn_blocks_run += layers * blocks_run(
                valid_from, T, T, bq, bk, True, window)
            self.stats.attn_blocks += layers * per_layer

    def run_prefill(self, tokens: np.ndarray, lengths=None):
        """tokens: (B, T) int32, left-padded; lengths: optional (B,) count
        of real (right-aligned) tokens per row — padding positions are
        masked out of attention so they cannot contaminate logits or
        later cache reads. Returns next-token logits; stores cache."""
        assert tokens.shape[0] == self.batch_size
        with TraceAnnotation("serve.launch"):
            vf_host = self._valid_from_for(tokens, lengths)
            vf = None if vf_host is None else jnp.asarray(vf_host)
            t0 = time.perf_counter()
            logits, cache = self._prefill(self.params, jnp.asarray(tokens),
                                          vf)
        self._count_attn_blocks(
            np.zeros((len(tokens),), np.int32) if vf_host is None
            else vf_host, tokens.shape[1])
        with TraceAnnotation("serve.sync"):
            logits.block_until_ready()
        self.stats.prefill_calls += 1
        self.stats.prefill_time_s += time.perf_counter() - t0
        self.cache = cache
        self.cache_pos = tokens.shape[1]
        self.valid_from = vf
        return self._fetch(logits, (slice(None), 0))

    def run_decode(self, tokens: np.ndarray):
        """tokens: (B, 1) int32 next tokens. Returns logits (B, V)."""
        if self.cache is None:
            raise RuntimeError(
                "no KV cache — call run_prefill first (run_decode on a "
                "fresh engine would donate cache=None into jit)")
        if self.cache_pos >= self.max_seq:
            raise RuntimeError(
                f"KV cache full (cache_pos={self.cache_pos}, "
                f"max_seq={self.max_seq})")
        t0 = time.perf_counter()
        with TraceAnnotation("serve.launch"):
            logits, self.cache = self._decode(
                self.params, jnp.asarray(tokens), self.cache,
                jnp.int32(self.cache_pos), self.valid_from)
        with TraceAnnotation("serve.sync"):
            logits.block_until_ready()
        self.cache_pos += 1
        self.stats.decode_calls += 1
        self.stats.decode_time_s += time.perf_counter() - t0
        return self._fetch(logits, (slice(None), 0))

    def prefill_row(self, prompt: np.ndarray, slot: int, length=None):
        """Backfill: prefill one request into batch slot `slot` mid-group.

        prompt: (T,) int32, left-padded to the group prompt length;
        length: real token count (right-aligned; default: all T). The row
        is prefilled at absolute positions cache_pos-T .. cache_pos-1 in
        a private cache, then merged into the live batch cache; its
        valid_from masks both the padding and whatever the slot's retired
        previous occupant left behind. Returns next-token logits (V,)."""
        if self.cache is None:
            raise RuntimeError("no KV cache — call run_prefill first")
        if not self._backfillable:
            raise NotImplementedError(
                "slot backfill needs full-seq attention caches "
                f"(pattern {self.cfg.pattern}, window {self.cfg.window})")
        if not 0 <= slot < self.batch_size:
            raise ValueError(f"slot {slot} out of range")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        T = prompt.shape[0]
        offset = self.cache_pos - T
        if offset < 0:
            raise ValueError(
                f"prompt ({T} tokens) longer than current context "
                f"({self.cache_pos})")
        length = T if length is None else int(length)
        if not 1 <= length <= T:
            raise ValueError(f"length must be in [1, {T}]")
        vf_row = self.cache_pos - length
        t0 = time.perf_counter()
        with TraceAnnotation("serve.launch"):
            logits, rcache = self._prefill_row(
                self.params, jnp.asarray(prompt)[None], jnp.int32(offset),
                jnp.asarray([vf_row], jnp.int32))
            self.cache = self._merge(self.cache, rcache, jnp.int32(slot),
                                     jnp.int32(offset), T)
        self._count_attn_blocks(np.asarray([T - length]), T)
        with TraceAnnotation("serve.sync"):
            logits.block_until_ready()
        self.stats.backfill_calls += 1
        self.stats.backfill_time_s += time.perf_counter() - t0
        vf = np.asarray(self.valid_from).copy()
        vf[slot] = vf_row
        self.valid_from = jnp.asarray(vf)
        return self._fetch(logits, (0, 0))

    @property
    def free_context(self) -> int:
        """Decode steps left before the cache fills."""
        return max(0, self.max_seq - self.cache_pos)

    @property
    def resident_bytes(self) -> int:
        """Bytes of the LIVE parameter tree — int8 execution leaves
        count at one byte per weight (plus their fp32 scales), so the
        memory budget the ModelZoo enforces reflects what this engine
        actually holds, not a notional quantized copy."""
        from repro.quant.int8 import tree_bytes_quantized
        return tree_bytes_quantized(self.params)

    def generate(self, prompts: np.ndarray, n_tokens: int,
                 greedy: bool = True, rng: Optional[np.random.Generator] = None,
                 lengths=None):
        """Prefill + n_tokens decode steps. Returns (B, n_tokens) ints."""
        out = np.zeros((self.batch_size, n_tokens), np.int32)
        logits = self.run_prefill(prompts, lengths=lengths)
        for t in range(n_tokens):
            if greedy:
                nxt = logits.argmax(-1).astype(np.int32)
            else:
                e = rng.gumbel(size=logits.shape)
                nxt = (logits + e).argmax(-1).astype(np.int32)
            out[:, t] = nxt
            logits = self.run_decode(nxt[:, None])
        return out

    def measured_profile(self, prompt_len: int, n_tokens: int,
                         reps: int = 3) -> dict:
        """Measure hot latency (mu, sigma) of a full request on this
        engine — the on-line analogue of paper Table 5. The first rep is
        discarded (dispatch warmup) and the center is a trimmed mean, so
        a loaded host doesn't corrupt the profile. Prefill and decode are
        timed separately: per_token_ms is decode-only (the prefill is one
        batched pass, not n_tokens+1 of anything)."""
        tot, pre, dec = [], [], []
        for r in range(reps + 1):
            toks = np.random.default_rng(r).integers(
                0, self.cfg.vocab, (self.batch_size, prompt_len),
                dtype=np.int32)
            t0 = time.perf_counter()
            logits = self.run_prefill(toks)
            t1 = time.perf_counter()
            for _ in range(n_tokens):
                nxt = logits.argmax(-1).astype(np.int32)
                logits = self.run_decode(nxt[:, None])
            t2 = time.perf_counter()
            tot.append((t2 - t0) * 1000.0)
            pre.append((t1 - t0) * 1000.0)
            dec.append((t2 - t1) * 1000.0)
        # Drop the warmup rep; trim the slowest remaining rep (by total
        # latency) from every series so the three stats stay aligned.
        order = np.argsort(tot[1:])[:max(1, reps - 1)] + 1
        tot_c = np.array(tot)[order]
        pre_c = np.array(pre)[order]
        dec_c = np.array(dec)[order]
        return {"mu": float(np.mean(tot_c)),
                "sigma": float(np.std(tot_c)),
                "prefill_ms": float(np.mean(pre_c)),
                "per_token_ms": float(np.mean(dec_c) / max(1, n_tokens)),
                "resident_bytes": self.resident_bytes}
