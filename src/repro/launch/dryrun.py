import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# Test hook: reduced device count must be set BEFORE jax initializes.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
derive the three roofline terms from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-9b \
        --shape train_4k --mesh pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh pod multipod

Results land in benchmarks/results/<arch>_<shape>_<mesh>_<tag>.json and
feed EXPERIMENTS.md §Dry-run / §Roofline.
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, ALIASES, get_config
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_production_mesh, make_mesh
from repro.launch.shapes import SHAPE_DEFS, SHAPE_NAMES, cell_runnable, \
    input_specs, skip_reason
from repro.models import decode_step, param_logical_axes, cache_logical_axes
from repro.models.model import prefill, abstract_cache
from repro.models.params import abstract_params
from repro.sharding import (ParallelConfig, make_parallel, moe_mode_for,
                            tree_specs, tree_shardings)
from repro.training.optim import adamw, adafactor, cosine_schedule, \
    mixed_precision
from repro.training.step import (make_train_step, abstract_train_state,
                                 train_state_logical_axes)
from repro.utils.config import enable_compile_cache

# TPU v5e hardware model (assignment constants).
PEAK_FLOPS = 197e12       # bf16 FLOP/s per chip
HBM_BW = 819e9            # bytes/s per chip
ICI_BW = 50e9             # bytes/s per link

BIG_PARAM_THRESHOLD = 5e10  # adafactor above this (Adam state won't fit)


def runtime_config(cfg, kind: str, overrides: dict):
    kw = dict(compute_dtype="bfloat16", attn_chunk=512)
    if kind == "train":
        # bf16 live params (fp32 master in opt state: gradients are born
        # bf16 so DP reductions move half the bytes). naive attention +
        # remat: the (T,S) logits are transient and recomputed in backward
        # — differentiating the double-scan flash path would store
        # per-chunk carries instead (measured blow-up).
        kw.update(param_dtype="bfloat16", remat="block", attn_impl="naive")
    else:
        # q-chunk 2048: flash K-streaming traffic scales with S^2/chunk_q
        # (-17% on the prefill memory term; 4096 gave <5% more — §Perf).
        kw.update(param_dtype="bfloat16", remat="none",
                  attn_impl="jax_chunked", attn_chunk=2048)
    import dataclasses as _dc
    fields = {f.name for f in _dc.fields(cfg)}
    kw.update({k: v for k, v in overrides.items()
               if v is not None and k in fields})
    return cfg.with_runtime(**kw)


def act_batch_axes(parallel, batch: int):
    sizes = 1
    for a in parallel.data_axes:
        sizes *= parallel.mesh.shape[a]
    return parallel.data_axes if batch % sizes == 0 else None


def build_cell(cfg, shape_name: str, mesh, overrides: dict):
    """Returns (jit_fn, abstract_args, info)."""
    spec = input_specs(runtime_config(cfg, "probe", {}), shape_name)
    kind = spec["kind"]
    cfg = runtime_config(cfg, kind, overrides)
    spec = input_specs(cfg, shape_name)
    profile = "train" if kind == "train" else "serve"
    # Decode defaults to the weight-resident 2d MoE layouts: moving the
    # per-step activations (KBs) beats re-gathering expert weights (GBs)
    # every token (§Perf iteration 2).
    default_moe = "auto2d" if kind == "decode" else "auto"
    parallel = make_parallel(mesh, profile,
                             seq_shard=overrides.get("seq_shard"),
                             moe_mode=overrides.get("moe_mode") or default_moe,
                             attn_pin=bool(overrides.get("attn_pin")),
                             # carry-mode SP: -11% collective on the SSM
                             # family but +42 GB peak (replicated x live
                             # during backward) — rejected on memory fit;
                             # refuted outright on dense/MoE (§Perf).
                             seq_mode=overrides.get("seq_mode") or "full")
    info = {"profile": profile,
            "moe_mode": moe_mode_for(cfg, parallel) if cfg.moe else None,
            "seq_shard": parallel.seq_shard,
            "attn_pin": parallel.attn_pin}

    if kind == "train":
        opt_name = overrides.get("optimizer") or (
            "adafactor" if cfg.param_count() > BIG_PARAM_THRESHOLD
            else "adamw")
        sched = cosine_schedule(3e-4, 1000, 100000)
        opt = adafactor(sched) if opt_name == "adafactor" else adamw(sched)
        opt = mixed_precision(opt)
        info["optimizer"] = opt_name + "+mp"
        step_fn = make_train_step(cfg, opt, parallel)
        state_abs = abstract_train_state(cfg, opt)
        st_specs = tree_specs(train_state_logical_axes(cfg, opt), parallel, cfg)
        st_sh = tree_shardings(st_specs, mesh)
        baxes = act_batch_axes(parallel, SHAPE_DEFS[shape_name]["batch"])
        b_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, P(baxes, *([None] * (len(s.shape) - 1)))),
            spec["batch"])
        fn = jax.jit(step_fn, in_shardings=(st_sh, b_sh),
                     out_shardings=(st_sh, None), donate_argnums=(0,))
        return fn, (state_abs, spec["batch"]), info

    # serve profiles
    p_specs = tree_specs(param_logical_axes(cfg), parallel, cfg)
    p_sh = tree_shardings(p_specs, mesh)
    params_abs = abstract_params(cfg)
    B = SHAPE_DEFS[shape_name]["batch"]
    baxes = act_batch_axes(parallel, B)
    vocab_ax = "model" if cfg.padded_vocab % mesh.shape["model"] == 0 else None
    lg_sh = NamedSharding(mesh, P(baxes, None, vocab_ax))

    if kind == "prefill":
        S = spec["max_seq"]

        def prefill_fn(params, inputs):
            return prefill(params, inputs, cfg, max_seq=S, parallel=parallel,
                           logits_last_only=True)

        c_specs = tree_specs(cache_logical_axes(cfg), parallel, cfg)
        c_specs = _fix_cache_batch(c_specs, baxes)
        c_sh = tree_shardings(c_specs, mesh)
        in_sh = NamedSharding(mesh, P(baxes, *([None] * (len(spec["inputs"].shape) - 1))))
        fn = jax.jit(prefill_fn, in_shardings=(p_sh, in_sh),
                     out_shardings=(lg_sh, c_sh))
        return fn, (params_abs, spec["inputs"]), info

    # decode
    S = spec["max_seq"]

    def decode_fn(params, token, cache, cache_pos):
        return decode_step(params, token, cache, cache_pos, cfg,
                           parallel=parallel)

    cache_abs = abstract_cache(cfg, B, S)
    c_specs = tree_specs(cache_logical_axes(cfg), parallel, cfg)
    c_specs = _fix_cache_batch(c_specs, baxes)
    c_sh = tree_shardings(c_specs, mesh)
    t_sh = NamedSharding(mesh, P(baxes, *([None] * (len(spec["token"].shape) - 1))))
    pos_sh = NamedSharding(mesh, P())
    fn = jax.jit(decode_fn, in_shardings=(p_sh, t_sh, c_sh, pos_sh),
                 out_shardings=(lg_sh, c_sh), donate_argnums=(2,))
    return fn, (params_abs, spec["token"], cache_abs, spec["cache_pos"]), info


def _fix_cache_batch(c_specs, baxes):
    """Cache specs put cache_batch on the data axes; when the global batch
    does not divide them (long_500k B=1) fall back to replicated batch.
    The batch dim may sit at any position (stacked leaves lead with the
    layers dim), so strip data axes wherever they appear."""
    if baxes is not None:
        return c_specs
    data_like = {"data", "pod"}

    def strip(e):
        if e in data_like:
            return None
        if isinstance(e, tuple):
            kept = tuple(a for a in e if a not in data_like)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return e

    def fix(s):
        if isinstance(s, P):
            return P(*[strip(e) for e in s])
        return s
    return jax.tree.map(fix, c_specs, is_leaf=lambda x: isinstance(x, P))


def model_flops(cfg, shape_name: str) -> float:
    d = SHAPE_DEFS[shape_name]
    n = cfg.active_param_count()
    if d["kind"] == "train":
        return 6.0 * n * d["batch"] * d["seq"]
    if d["kind"] == "prefill":
        return 2.0 * n * d["batch"] * d["seq"]
    return 2.0 * n * d["batch"]  # decode: one token per sequence


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             overrides: dict, out_dir: str, tag: str, force: bool) -> dict:
    cfg0 = get_config(arch)
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{cfg0.name.replace('/', '_')}_{shape_name}_{mesh_name}_{tag}.json"
    path = os.path.join(out_dir, fname)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    if not cell_runnable(cfg0, shape_name):
        res = {"arch": cfg0.name, "shape": shape_name, "mesh": mesh_name,
               "skipped": True, "reason": skip_reason(cfg0, shape_name)}
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        print(f"[dryrun] SKIP {cfg0.name} x {shape_name}: sub-quadratic "
              f"requirement", flush=True)
        return res

    print(f"[dryrun] {cfg0.name} x {shape_name} x {mesh_name} "
          f"(devices={mesh.devices.size})", flush=True)
    t0 = time.time()
    fn, args, info = build_cell(cfg0, shape_name, mesh, overrides)
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1
    ma = compiled.memory_analysis()
    print("  memory_analysis:", ma, flush=True)
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):   # older jax: list of one dict
        ca = ca[0] if ca else {}
    print("  cost_analysis: flops=%.3e bytes=%.3e" % (
        ca.get("flops", 0.0), ca.get("bytes accessed", 0.0)), flush=True)
    hlo = analyze(compiled.as_text())

    chips = mesh.devices.size
    mf = model_flops(run_cfg(cfg0, shape_name, overrides), shape_name)
    compute_s = hlo["dot_flops"] / PEAK_FLOPS
    memory_s = hlo["traffic_bytes"] / HBM_BW
    coll_s = hlo["collective_traffic_total"] / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    dominant = max(terms, key=terms.get)
    hlo_global_flops = hlo["dot_flops"] * chips
    res = {
        "arch": cfg0.name, "shape": shape_name, "mesh": mesh_name,
        "devices": chips, "kind": SHAPE_DEFS[shape_name]["kind"],
        "skipped": False, "tag": tag, "info": info,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_estimate_bytes": (ma.argument_size_in_bytes
                                    + ma.output_size_in_bytes
                                    + ma.temp_size_in_bytes
                                    - ma.alias_size_in_bytes),
        },
        "cost_analysis": {"flops_body_once": ca.get("flops", 0.0),
                          "bytes_body_once": ca.get("bytes accessed", 0.0)},
        "hlo": hlo,
        "model_flops": mf,
        "useful_flops_ratio": (mf / hlo_global_flops) if hlo_global_flops else 0.0,
        "terms": terms,
        "dominant": dominant,
        "step_time_est_s": max(terms.values()),
        "params": cfg0.param_count(),
        "active_params": cfg0.active_param_count(),
    }
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    print(f"  terms: compute={compute_s:.4f}s memory={memory_s:.4f}s "
          f"collective={coll_s:.4f}s dominant={dominant} "
          f"useful_ratio={res['useful_flops_ratio']:.3f} "
          f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)", flush=True)
    return res


def run_cfg(cfg, shape_name, overrides):
    kind = SHAPE_DEFS[shape_name]["kind"]
    return runtime_config(cfg, kind, overrides)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None,
                    help="arch id (repeatable); default: all")
    ap.add_argument("--shape", action="append", default=None,
                    choices=list(SHAPE_NAMES))
    ap.add_argument("--mesh", nargs="+", default=["pod"],
                    choices=["pod", "multipod", "custom"])
    ap.add_argument("--mesh-shape", default=None,
                    help="custom mesh, e.g. 2,4 (test mode)")
    ap.add_argument("--mesh-axes", default=None, help="e.g. data,model")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="benchmarks/results")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--force", action="store_true")
    # hillclimb overrides
    ap.add_argument("--seq-shard", default=None, choices=["on", "off"])
    ap.add_argument("--moe-mode", default=None, choices=["ep", "tp", "ep2d", "tp2d"])
    ap.add_argument("--optimizer", default=None,
                    choices=["adamw", "adafactor"])
    ap.add_argument("--remat", default=None,
                    choices=["none", "block", "moe_save"])
    ap.add_argument("--attn-pin", default=None, choices=["on", "off"])
    ap.add_argument("--seq-mode", default=None, choices=["full", "carry"])
    ap.add_argument("--attn-impl", default=None,
                    choices=["naive", "jax_chunked"])
    ap.add_argument("--attn-chunk", type=int, default=None)
    ap.add_argument("--compute-dtype", default=None)
    args = ap.parse_args()

    enable_compile_cache()

    overrides = {
        "seq_shard": None if args.seq_shard is None else args.seq_shard == "on",
        "moe_mode": args.moe_mode,
        "optimizer": args.optimizer,
        "attn_impl": args.attn_impl,
        "remat": args.remat,
        "attn_pin": None if args.attn_pin is None else args.attn_pin == "on",
        "seq_mode": args.seq_mode,
        "attn_chunk": args.attn_chunk,
        "compute_dtype": args.compute_dtype,
    }
    archs = args.arch or ARCH_IDS
    shapes = args.shape or list(SHAPE_NAMES)

    failures = []
    for mesh_name in args.mesh:
        if mesh_name == "pod":
            mesh = make_production_mesh(multi_pod=False)
        elif mesh_name == "multipod":
            mesh = make_production_mesh(multi_pod=True)
        else:
            shape = tuple(int(x) for x in args.mesh_shape.split(","))
            axes = tuple(args.mesh_axes.split(","))
            mesh = make_mesh(shape, axes)
        for arch in archs:
            for shp in shapes:
                try:
                    run_cell(arch, shp, mesh, mesh_name, overrides,
                             args.out, args.tag, args.force)
                except Exception as e:
                    traceback.print_exc()
                    failures.append((arch, shp, mesh_name, str(e)[:200]))
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES:", flush=True)
        for f in failures:
            print("   ", f, flush=True)
        sys.exit(1)
    print("[dryrun] all requested cells OK", flush=True)


if __name__ == "__main__":
    main()
