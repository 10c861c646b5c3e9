"""Chip smoke: drive the CNNSelect serving path once on a TPU at a
published model width, and check what comes out.

    python chip_smoke.py             # one chip: stablelm-1.6b served path
    python chip_smoke.py --chips 4   # four chips: yi-9b sharded serve path

One chip. stablelm-1.6b at its published width (24 layers, d_model 2048,
32 heads, d_ff 5632, vocab 100352) with seeded random bf16 weights is
built as two co-resident selection candidates: the bf16 engine, and an
int8 engine quantized from the same weights. Sixteen fleet requests
(prompts of 32-128 tokens, 16 new tokens each, upload times from the
campus_wifi network) go through Router/CNNSelect admission, the
ContinuousBatcher and ServingLoop with mid-group backfill, the
InferenceEngine and the Pallas flash, decode and int8 kernels. The bf16
engine's prefill and first decode logits are then checked against a
float32 reference with naive attention at the highest matmul precision.

Four chips. yi-9b (bf16 weights ~17.6 GB, more than one v5e holds) is
cut to 4 layers and served in float32 on a (1, 4) ("data", "model")
mesh; its logits are compared with the same cut on one chip. Then the
full 48-layer model in bf16 runs a sharded prefill and 8 decode steps,
and each device's resident bytes are printed.

The last line of standard output is {"ok": true, "device": {...}} on
success. Any failure, a missing TPU included, raises: the exit code is
non-zero and no such line is printed. Nothing falls back to the CPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import init_params, param_logical_axes  # noqa: E402
from repro.quant.int8 import quantize_exec_tree  # noqa: E402
from repro.serving.batching import Request  # noqa: E402
from repro.serving.engine import InferenceEngine  # noqa: E402
from repro.serving.loop import ServingLoop  # noqa: E402
from repro.serving.network import make_network  # noqa: E402
from repro.sharding import (make_parallel, tree_shardings,  # noqa: E402
                            tree_specs)
from repro.utils.config import enable_compile_cache  # noqa: E402

# Pallas bf16 vs the float32 reference. A bf16 run differs from a float32
# one by its own rounding, which the layers compound; that floor is
# measured on the same input by the XLA (naive) attention on the same
# bf16 weights. The kernels pass when their logits are no further from
# the reference than FLOOR_X times the floor plus FLOOR_EPS, and within
# ABS_RMS outright. All three are relative to the reference logits' RMS:
# the bf16 floor is ~1% of it at small widths, while logits unrelated to
# the reference (a wrong mask or softmax) sit ~1.4 of it away.
FLOOR_X = 1.5
FLOOR_EPS = 0.01
ABS_RMS = 0.1
# Sharded vs one device, both float32 at the highest matmul precision:
# only the summation order differs.
SHARD_RMS = 1e-3

# Offline accuracy labels for the selection objective (random weights
# have no task score): int8 pays the small penalty MEASURED_ZOO gives
# lm_small_int8 against lm_small (0.652 / 0.66).
ACCURACY = {"stablelm_bf16": 1.0, "stablelm_int8": 0.988}


def check(ok, msg: str):
    if not ok:
        raise RuntimeError(f"chip_smoke: {msg}")


def device_info() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def require_tpu(n: int) -> dict:
    info = device_info()
    check(info["platform"] == "tpu",
          f"needs a TPU; JAX found {info['platform']}")
    check(info["count"] >= n,
          f"needs {n} TPU devices, found {info['count']}")
    print(f"[device] {info['platform']} {info['kind']} x{info['count']}",
          flush=True)
    return info


def bytes_in_use(device) -> int:
    return device.memory_stats()["bytes_in_use"]


def init_on_device(cfg, seed: int, shardings=None):
    """Seeded random params, generated on the device(s) in one program
    (already sharded when `shardings` is given)."""
    return jax.jit(init_params, static_argnums=0,
                   out_shardings=shardings)(cfg, jax.random.PRNGKey(seed))


def logit_error(got, ref) -> dict:
    """got - ref over (rows, vocab) logits: RMS and max, each relative to
    the RMS of ref, the max absolute difference, and the per-row max."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    check(got.shape == ref.shape, f"logits {got.shape} vs {ref.shape}")
    check(np.isfinite(got).all(), "non-finite logits")
    scale = float(np.sqrt(np.mean(ref ** 2)))
    err = np.abs(got - ref)
    return {"rms": float(np.sqrt(np.mean(err ** 2))) / scale,
            "max": float(err.max()) / scale, "abs": float(err.max()),
            "row_max": err.max(-1),
            "agree": got.argmax(-1) == ref.argmax(-1)}


def check_against_floor(what: str, got, floor, ref) -> float:
    """Pallas bf16 logits `got` against the float32 reference, with the
    XLA bf16 logits `floor` as the rounding floor. Greedy tokens must
    agree on every row whose reference top-1 margin exceeds twice the
    floor's largest difference on that row (below it, bf16 rounding
    alone may flip the argmax). Returns the max absolute difference."""
    e, f = logit_error(got, ref), logit_error(floor, ref)
    ref = np.asarray(ref, np.float64)
    top2 = np.sort(ref, axis=-1)[:, -2:]
    decided = (top2[:, 1] - top2[:, 0]) > 2 * f["row_max"]
    print(f"[check] {what}: pallas bf16 vs naive f32: max|diff| "
          f"{e['abs']:.6g}, rms {e['rms']:.6g}, max {e['max']:.6g} of ref "
          f"rms; naive bf16 floor: rms {f['rms']:.6g}, max {f['max']:.6g}; "
          f"greedy tokens agree on {int(e['agree'].sum())}/{e['agree'].size}"
          f" rows (floor {int(f['agree'].sum())}), {int(decided.sum())} "
          f"rows past the margin", flush=True)
    for k in ("rms", "max"):
        check(e[k] <= FLOOR_X * f[k] + FLOOR_EPS,
              f"{what}: {k} error {e[k]:.6g} above {FLOOR_X} x floor "
              f"{f[k]:.6g} + {FLOOR_EPS}")
    check(e["rms"] <= ABS_RMS, f"{what}: rms error {e['rms']:.6g} above "
          f"{ABS_RMS}")
    check(e["agree"][decided].all(), f"{what}: greedy token differs")
    return e["abs"]


def fleet_requests(vocab: int, *, n: int, prompt_max: int, new_tokens: int,
                   seed: int):
    """n requests 0.5 ms apart: prompts of prompt_max/4 .. prompt_max
    tokens, upload times from campus_wifi. Every fourth request carries
    an SLA no model can meet (CNNSelect falls back to the fastest
    model); the rest have two seconds of budget."""
    rng = np.random.default_rng(seed)
    t_in = make_network("campus_wifi").sample_t_input(rng, n)
    out = []
    for i in range(n):
        length = int(rng.integers(prompt_max // 4, prompt_max + 1))
        budget = 1.0 if i % 4 == 3 else 2000.0
        out.append(Request(
            arrival=0.5 * i, rid=i,
            prompt=rng.integers(0, vocab, length).astype(np.int32),
            max_new_tokens=new_tokens, t_input_ms=float(t_in[i]),
            sla_ms=2.0 * float(t_in[i]) + budget))
    return out


def count_kernels(eng: InferenceEngine) -> int:
    """tpu_custom_call sites in the engine's lowered decode step."""
    B = eng.batch_size
    lowered = eng._decode.lower(eng.params, jnp.zeros((B, 1), jnp.int32),
                                eng.cache, jnp.int32(eng.max_seq - 1),
                                jnp.zeros((B,), jnp.int32))
    return lowered.as_text().count("tpu_custom_call")


def serve_phase(cfg, *, batch_size: int, max_seq: int, n_requests: int,
                new_tokens: int, seed: int):
    """Build both candidates, serve the fleet trace through ServingLoop,
    and check the outcome. Returns the bf16 engine (its params stay
    resident for the logits check)."""
    t0 = time.perf_counter()
    params = init_on_device(cfg, seed)
    jax.block_until_ready(params)
    qparams = quantize_exec_tree(params)
    jax.block_until_ready(qparams)
    print(f"[build] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads} heads x {cfg.head_dim}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab}; params + int8 copy in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    engines = {
        "stablelm_bf16": InferenceEngine(cfg, params, batch_size=batch_size,
                                         max_seq=max_seq),
        "stablelm_int8": InferenceEngine(cfg, qparams,
                                         batch_size=batch_size,
                                         max_seq=max_seq),
    }
    # profiles="measured": each engine is compiled (warmup) and profiled
    # here, and the Router selects on those latencies.
    loop = ServingLoop(engines, profiles="measured", accuracies=ACCURACY,
                       seed=seed)
    for p in loop.router.current_profiles():
        eng = engines[p.name]
        print(f"[engine] {p.name}: compile {eng.stats.compile_time_s:.3f} s; "
              f"profiled mu {p.mu:.3f} ms sigma {p.sigma:.3f} ms; resident "
              f"{eng.resident_bytes} bytes", flush=True)
    requests = fleet_requests(cfg.vocab, n=n_requests,
                              prompt_max=max_seq // 4,
                              new_tokens=new_tokens, seed=seed)
    for eng in engines.values():     # serve-time stats only
        eng.stats.prefill_calls = eng.stats.decode_calls = 0
        eng.stats.prefill_time_s = eng.stats.decode_time_s = 0.0
    t0 = time.perf_counter()
    metrics = loop.run(requests)
    wall = time.perf_counter() - t0
    served = {name: 0 for name in engines}
    for rec in metrics.records:
        served[rec["model"]] += 1
    backfills = sum(e.stats.backfill_calls for e in engines.values())
    print(f"[serve] {metrics.served} requests in {wall:.3f} s wall; served "
          f"per model {served}; backfill joins {backfills}; SLA "
          f"attainment {metrics.attainment:.4f}", flush=True)
    for name, eng in engines.items():
        s = eng.stats
        print(f"[serve] {name}: prefill "
              f"{1e3 * s.prefill_time_s / max(1, s.prefill_calls):.3f} ms "
              f"x{s.prefill_calls}; decode "
              f"{1e3 * s.decode_time_s / max(1, s.decode_calls):.3f} "
              f"ms/token x{s.decode_calls}; backfill "
              f"{1e3 * s.backfill_time_s / max(1, s.backfill_calls):.3f} ms "
              f"x{s.backfill_calls}", flush=True)
    check(metrics.served == n_requests,
          f"served {metrics.served} of {n_requests}")
    check(all(len(r.tokens) == new_tokens for r in requests),
          "a request got the wrong number of tokens")
    check(all(0 <= t < cfg.vocab for r in requests for t in r.tokens),
          "a token outside the vocabulary")
    check(min(served.values()) > 0, f"a candidate served nothing: {served}")
    check(backfills > 0, "no backfill join happened")
    for name, eng in engines.items():
        n = count_kernels(eng)
        print(f"[kernels] {name}: {n} tpu_custom_call in the lowered "
              f"decode step", flush=True)
        check(n > 0, f"{name}: no Pallas kernel in the decode step")
    print(f"[memory] bytes_in_use {bytes_in_use(jax.devices()[0])} with "
          f"both candidates resident", flush=True)
    return engines["stablelm_bf16"]


def check_phase(eng: InferenceEngine, *, seed: int):
    """Pallas bf16 engine vs a float32 naive-attention reference on the
    same weights: prefill logits, then the first decode step's logits
    with the engine fed the reference's greedy tokens. The reference for
    that step reads no KV cache: it is the float32 prefill of the prompt
    plus that token. A naive bf16 engine on the same bf16 weights, run
    the same way as the reference, gives the rounding floor."""
    cfg, B, T = eng.cfg, eng.batch_size, eng.max_seq // 4
    rng = np.random.default_rng(seed + 1)
    lengths = np.linspace(T, T // 4, B).astype(np.int64)
    tokens = np.zeros((B, T), np.int32)
    for b, n in enumerate(lengths):
        tokens[b, T - n:] = rng.integers(0, cfg.vocab, n)
    floor = InferenceEngine(cfg.with_runtime(attn_impl="naive"), eng.params,
                            batch_size=B, max_seq=eng.max_seq)
    ref = InferenceEngine(
        cfg.with_runtime(param_dtype="float32", compute_dtype="float32",
                         attn_impl="naive"),
        jax.tree.map(lambda x: x.astype(jnp.float32), eng.params),
        batch_size=B, max_seq=eng.max_seq)
    print(f"[memory] bytes_in_use {bytes_in_use(jax.devices()[0])} with the "
          f"int8 candidate freed, the bf16 weights and their float32 copy "
          f"resident", flush=True)
    # Only the reference runs at the highest precision: the engine keeps
    # the programs it served with.
    with jax.default_matmul_precision("highest"):
        want = ref.run_prefill(tokens, lengths=lengths)
    diff = check_against_floor(
        "prefill", eng.run_prefill(tokens, lengths=lengths),
        floor.run_prefill(tokens, lengths=lengths), want)
    nxt = want.argmax(-1).astype(np.int32)[:, None]
    longer = np.concatenate([tokens, nxt], axis=1)
    with jax.default_matmul_precision("highest"):
        want = ref.run_prefill(longer, lengths=lengths + 1)
    diff = max(diff, check_against_floor(
        "decode 1", eng.run_decode(nxt),
        floor.run_prefill(longer, lengths=lengths + 1), want))
    print(f"[check] max logit difference {diff:.6g} (pallas bf16 vs naive "
          f"f32, highest matmul precision)", flush=True)


def one_chip(args):
    info = require_tpu(1)
    cfg = get_config("stablelm_1_6b", param_dtype="bfloat16",
                     compute_dtype="bfloat16", attn_impl="pallas")
    bf16 = serve_phase(cfg, batch_size=4, max_seq=512, n_requests=16,
                       new_tokens=16, seed=args.seed)
    # The int8 candidate and the serving loop are gone once collected;
    # HBM then holds the bf16 weights and, for the check, a float32 copy
    # (~10 GB).
    gc.collect()
    check_phase(bf16, seed=args.seed)
    return info


def run_tokens(eng: InferenceEngine, tokens, lengths, feed):
    """Prefill, then one decode step per column of `feed` (B, steps), or
    greedy steps when `feed` is an int. Returns every logits row
    (prefill first) stacked to ((steps + 1) * B, V) and the fed tokens."""
    out = [eng.run_prefill(tokens, lengths=lengths)]
    fed = []
    steps = feed if isinstance(feed, int) else feed.shape[1]
    for i in range(steps):
        nxt = (out[-1].argmax(-1) if isinstance(feed, int) else feed[:, i])
        fed.append(nxt.astype(np.int32))
        out.append(eng.run_decode(fed[-1][:, None]))
    return np.concatenate(out), np.stack(fed, 1)


def sharded_phase(cut_cfg, full_cfg, *, batch_size: int, max_seq: int,
                  steps: int, seed: int):
    """cut_cfg served on a (1, 4) mesh vs on one device; then full_cfg
    sharded, with each device's resident bytes."""
    devs = jax.devices()
    mesh = make_mesh((1, 4), ("data", "model"))
    par = make_parallel(mesh, "serve")
    B, T = batch_size, max_seq // 4
    rng = np.random.default_rng(seed + 2)
    lengths = np.linspace(T, T // 4, B).astype(np.int64)
    tokens = rng.integers(0, cut_cfg.vocab, (B, T)).astype(np.int32)

    def shardings(cfg):
        return tree_shardings(
            tree_specs(param_logical_axes(cfg), par, cfg), mesh)

    params = init_on_device(cut_cfg, seed, shardings(cut_cfg))
    sharded = InferenceEngine(cut_cfg, params, batch_size=B,
                              max_seq=max_seq, parallel=par)
    single = InferenceEngine(cut_cfg, jax.device_put(params, devs[0]),
                             batch_size=B, max_seq=max_seq)
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        want, fed = run_tokens(single, tokens, lengths, steps)
        t1 = time.perf_counter()
        got, _ = run_tokens(sharded, tokens, lengths, fed)
    print(f"[sharded] {cut_cfg.name} cut to {cut_cfg.n_layers} layers, "
          f"{cut_cfg.param_dtype}: one device {t1 - t0:.3f} s, (1, 4) mesh "
          f"{time.perf_counter() - t1:.3f} s (compile included)", flush=True)
    e = logit_error(got, want)
    print(f"[check] {cut_cfg.n_layers}-layer sharded vs one device, prefill "
          f"+ {steps} decode steps: max|diff| {e['abs']:.6g}, rms "
          f"{e['rms']:.6g} of ref rms (tol {SHARD_RMS}); greedy tokens agree "
          f"on {int(e['agree'].sum())}/{e['agree'].size} rows", flush=True)
    check(e["rms"] <= SHARD_RMS, f"sharded logits off by {e['rms']:.6g}")
    del sharded, single, params
    gc.collect()

    t0 = time.perf_counter()
    params = init_on_device(full_cfg, seed, shardings(full_cfg))
    jax.block_until_ready(params)
    print(f"[sharded] {full_cfg.name} {full_cfg.n_layers} layers, "
          f"{full_cfg.param_dtype}: sharded params in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    eng = InferenceEngine(full_cfg, params, batch_size=B, max_seq=max_seq,
                          parallel=par)
    t0 = time.perf_counter()
    logits, _ = run_tokens(eng, tokens % full_cfg.vocab, lengths, steps)
    print(f"[sharded] prefill + {steps} decode steps in "
          f"{time.perf_counter() - t0:.3f} s, compile included", flush=True)
    check(np.isfinite(logits).all(), "non-finite sharded logits")
    in_use = [bytes_in_use(d) for d in mesh.devices.flat]
    print(f"[memory] bytes_in_use per device {in_use}", flush=True)
    check(min(in_use) >= 0.5 * max(in_use),
          f"resident bytes not spread over the mesh: {in_use}")


def four_chips(args):
    info = require_tpu(4)
    # Naive attention: a pallas_call is not partitioned by GSPMD, so on a
    # mesh it would run replicated behind an all-gather of q/k/v.
    full = get_config("yi_9b", param_dtype="bfloat16",
                      compute_dtype="bfloat16", attn_impl="naive")
    cut = full.with_runtime(n_layers=4, param_dtype="float32",
                            compute_dtype="float32")
    sharded_phase(cut, full, batch_size=4, max_seq=512, steps=8,
                  seed=args.seed)
    return info


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: stablelm-1.6b served path (default); 4: the "
                         "yi-9b sharded serve path only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    print(f"[cache] compilation cache {enable_compile_cache()}", flush=True)
    info = four_chips(args) if args.chips == 4 else one_chip(args)
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
