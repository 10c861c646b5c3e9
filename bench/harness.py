"""The benchmark's run: set up a cell, serve its traffic through the
program's `ServingLoop` on the wall clock, read the metrics, and check
what the timed path produced against the plain reference.

Everything that belongs to one configuration, traffic mix, cell or
metric lives in a file of its own that this module finds by name:

  bench/configs/<config>.json      sizes, candidates, batch, reference,
                                   and optionally the mesh it runs on
  bench/references/<module>.py     the plain reference of those sizes:
                                   make_weights, logit_stats, unmodelled,
                                   counts
  bench/traffic/<mix>.json         parameters for bench/generate.py
  bench/cells/<workload>.json      the limits of the correctness check
  bench/metrics/<metric>.py        read(ctx) -> number or None
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import generate, peaks, readers, trace_reduce  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
NOT_FIELDS = ("program_config", "source")   # keys of an `archs` entry
CHECK_ROWS = 8            # rows per reference call
CHECK_TOKENS = 400        # served tokens to compare per candidate, at least
CHECK_MAX_REQUESTS = 256
LOGIT_STRIDE = 16         # keep the logit rows of every 16th output token


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def workload_entry(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metric_entries(workload: str, trace: bool) -> list:
    """The cell's end-to-end metrics (trace off) or per-layer metrics
    (trace on), as BENCHMARK.json lists them."""
    bm = benchmark()
    if not trace:
        return [m for m in bm["end_to_end"]
                if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in metric_entries(workload, False)}
    return [m for m in bm["per_layer"]
            if workload in m.get("workloads", [workload])
            and m["moves"] in reported]


def reference_module(cfg: dict):
    return _reference(cfg["reference"])


@functools.lru_cache(maxsize=None)
def _reference(name: str):
    return load_module(os.path.join(BENCH, "references", f"{name}.py"),
                       f"bench_ref_{name}")


def enable_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def seed_key(seed: int, index: int):
    """A PRNG key for weight set `index` from any non-negative seed
    (seeds may exceed 32 bits)."""
    import jax
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    k = jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(index))


class Stamped(list):
    """A request's token list that stamps the wall clock on every
    append: the program keeps only virtual times."""

    def __init__(self):
        super().__init__()
        self.stamps = []

    def append(self, x):
        self.stamps.append(time.perf_counter())
        super().append(x)


# --------------------------------------------------------------------------
# Set-up
# --------------------------------------------------------------------------

def program_config(cfg: dict, arch: dict):
    """The program's model config for one `archs` entry of configuration
    `cfg`: every key but NOT_FIELDS sets the field of that name (a dict
    sets fields of a nested config, a list is a tuple). Refuses a key
    that names no field, and what the configuration's reference does not
    model."""
    from repro.configs import get_config
    pcfg = get_config(arch["program_config"], param_dtype="bfloat16",
                      compute_dtype="bfloat16", attn_impl="pallas")
    fields = {f.name for f in dataclasses.fields(pcfg)}
    sizes = {}
    for k, v in arch.items():
        if k in NOT_FIELDS:
            continue
        if k not in fields:
            raise ValueError(f"{cfg['name']}: {arch['program_config']} has "
                             f"no field {k!r}")
        sizes[k] = field_value(getattr(pcfg, k), v, k)
    pcfg = pcfg.with_runtime(**sizes)
    missing = reference_module(cfg).unmodelled(dataclasses.asdict(pcfg))
    if missing:
        raise ValueError(f"{cfg['name']}: reference {cfg['reference']} does "
                         f"not model {arch['program_config']}'s "
                         f"{', '.join(missing)}")
    return pcfg


def field_value(base, v, key: str):
    """A configuration's value for a field that holds `base`."""
    if isinstance(v, list):
        return tuple(v)
    if not isinstance(v, dict):
        return v
    if not dataclasses.is_dataclass(base):
        raise ValueError(f"{key}: a dict of sizes for a field that holds "
                         f"{base!r}, not a nested config")
    fields = {f.name for f in dataclasses.fields(base)}
    for k in v:
        if k not in fields:
            raise ValueError(f"{key}: {type(base).__name__} has no field "
                             f"{k!r}")
    return dataclasses.replace(base, **{k: field_value(getattr(base, k), x,
                                                       f"{key}.{k}")
                                        for k, x in v.items()})


def chips(cfg: dict) -> int:
    """The chips a configuration runs on: its mesh's size, or 1 where it
    states no mesh."""
    m = cfg.get("mesh")
    return math.prod(m["shape"]) if m else 1


def check_chips(w: dict, cfg: dict) -> None:
    if w["chips"] != chips(cfg):
        raise ValueError(f"{w['name']}: the cell asks for {w['chips']} "
                         f"chip(s), configuration {cfg['name']} runs on "
                         f"{chips(cfg)} (its mesh's size; 1 without a mesh)")


def parallel_of(cfg: dict):
    """The program's serving ParallelConfig over the configuration's
    `mesh` ({"shape", "axes"}), built by the program's own make_mesh and
    make_parallel; None where the configuration states no mesh."""
    m = cfg.get("mesh")
    if m is None:
        return None
    import jax
    from repro.launch.mesh import make_mesh
    from repro.sharding import make_parallel
    have = len(jax.devices())
    if have < chips(cfg):
        raise ValueError(f"{cfg['name']}: the mesh {m['shape']} needs "
                         f"{chips(cfg)} devices, JAX sees {have}")
    return make_parallel(make_mesh(m["shape"], m["axes"]), "serve")


def make_weights(cfg: dict, arch: dict, seed: int, index: int, par):
    """Weight set `index` from the seed by the reference's make_weights:
    without a mesh, one jitted call on the default device; on a mesh,
    each leaf made where the program's sharding rules place it, so that
    no weight is ever whole on one device."""
    ref = reference_module(cfg)
    key = seed_key(seed, index)
    if par is None:
        return ref.make_weights(arch, key)
    import jax
    from repro.models import param_logical_axes
    from repro.sharding import tree_shardings, tree_specs
    pcfg = program_config(cfg, arch)
    shardings = tree_shardings(tree_specs(param_logical_axes(pcfg), par,
                                          pcfg), par.mesh)
    check_same_tree(cfg, jax.eval_shape(
        lambda k: ref.make_weights(arch, k), key), shardings)
    return ref.make_weights(arch, key, out_shardings=shardings)


def check_same_tree(cfg: dict, weights, shardings) -> None:
    """Refuses, naming the first path that one has and the other lacks,
    a reference's weight tree that is not the program's sharding tree."""
    import jax
    ref, prog = ([jax.tree_util.keystr(p) for p, _ in
                  jax.tree_util.tree_flatten_with_path(t)[0]]
                 for t in (weights, shardings))
    only = ([(p, "the program", "the reference") for p in prog
             if p not in ref]
            + [(p, "the reference", "the program") for p in ref
               if p not in prog])
    if only:
        path, has, lacks = only[0]
        raise ValueError(
            f"{cfg['name']}: reference {cfg['reference']}'s weights and "
            f"the program's sharding tree differ first at {path}: "
            f"{has} has it, {lacks} has not")


def check_placed(name: str, weights, par) -> None:
    """Refuses an engine's weight that does not lie on every device of
    the mesh, and logs how many leaves are partitioned over it and how
    many replicated."""
    import jax
    n = par.mesh.devices.size
    split = whole = 0
    for path, x in jax.tree_util.tree_flatten_with_path(weights)[0]:
        if len(x.sharding.device_set) != n:
            raise ValueError(
                f"{name}: weight {jax.tree_util.keystr(path)} lies on "
                f"{len(x.sharding.device_set)} device(s), not on the "
                f"mesh's {n}")
        if x.sharding.is_fully_replicated:
            whole += 1
        else:
            split += 1
    log(f"[mesh] {name}: {split} weight leaves partitioned over {n} "
        f"devices, {whole} replicated on each")


def build_engines(cfg: dict, seed: int):
    """Weights made on the device from the seed (one jitted program per
    weight set; on the configuration's mesh where it states one), the
    int8 copies by the program's quantizer, and one engine per
    candidate."""
    import jax
    from repro.quant.int8 import quantize_exec_tree
    from repro.serving.engine import InferenceEngine
    par = parallel_of(cfg)
    weights, engines = {}, {}
    for name, m in cfg["models"].items():
        arch = cfg["archs"][m["arch"]]
        pcfg = program_config(cfg, arch)
        if m["weights"] not in weights:
            weights[m["weights"]] = make_weights(cfg, arch, seed,
                                                 m["weights"], par)
        p = weights[m["weights"]]
        if m["precision"] == "int8":
            p = quantize_exec_tree(p)
        elif m["precision"] != "bf16":
            raise ValueError(f"{name}: unknown precision {m['precision']!r}")
        jax.block_until_ready(p)
        if par is not None:
            check_placed(name, p, par)
        engines[name] = InferenceEngine(pcfg, p,
                                        batch_size=cfg["batch_size"],
                                        max_seq=cfg["max_seq"],
                                        parallel=par)
    return engines


def warm(engines: dict, cfg: dict, mix: dict, rng) -> None:
    """Drive every call the window makes once through the engines'
    public entry points: group prefill, and where requests ask more than
    one token, a backfill join and a decode."""
    B, T = cfg["batch_size"], cfg["prompt_len"]
    vocab = prompt_vocab(cfg)
    for eng in engines.values():
        toks = rng.integers(0, vocab, (B, T)).astype(np.int32)
        lengths = rng.integers(1, T + 1, B)
        logits = eng.run_prefill(toks, lengths=lengths)
        if mix["output_tokens"]["max"] > 1:
            eng.prefill_row(toks[0], 0, length=int(lengths[0]))
            eng.run_decode(logits.argmax(-1).astype(np.int32)[:, None])


def setup(cfg: dict, mix: dict, seed: int):
    """Engines from the seed, the program's ServingLoop over them with
    CNNSelect's profiles measured on the chip, and every shape the
    mix's window uses warmed up."""
    from repro.serving.loop import ServingLoop
    engines = build_engines(cfg, seed)
    loop = ServingLoop(engines, profiles="measured",
                       seed=cfg.get("router_seed", 0),
                       accuracies={n: m["accuracy"]
                                   for n, m in cfg["models"].items()})
    check_prompt_window(cfg, loop)
    if loop.router is not None:
        loop.router.prewarm()
    warm(engines, cfg, mix, np.random.default_rng(seed % 2 ** 31))
    return engines, loop


def check_prompt_window(cfg: dict, loop) -> None:
    """The reference reads each prompt's last `prompt_len` tokens, and
    the program's batchers keep a window of their own (max_seq // 4):
    where the two differ, the check would compare other prompts than
    were served."""
    for name, b in loop.batchers.items():
        if b.prompt_len != cfg["prompt_len"]:
            raise ValueError(
                f"{name}: the program keeps the last {b.prompt_len} prompt "
                f"tokens (max_seq // 4), the configuration's prompt_len is "
                f"{cfg['prompt_len']}")


def prompt_vocab(cfg: dict) -> int:
    return cfg.get("prompt_vocab",
                   min(a["vocab"] for a in cfg["archs"].values()))


# --------------------------------------------------------------------------
# The window
# --------------------------------------------------------------------------

@dataclass
class Call:
    kind: str            # prefill | decode | backfill
    model: str
    t0: float
    t1: float
    rows: int            # batch rows of the call
    tokens: int          # sequence length of the call


@dataclass
class Served:
    requests: list
    t0: float                       # window start (perf_counter)
    seconds: float
    t_end: float = 0.0              # last completion
    admission_s: list = field(default_factory=list)
    lateness_ms: list = field(default_factory=list)
    calls: list = field(default_factory=list)
    compiles: int = 0
    backfilled: set = field(default_factory=set)
    logits: dict = field(default_factory=dict)  # rid -> {token index: row}


def keep_logits(served: Served, slots, rows) -> None:
    """Keep the logit row that picks each request's next token, for
    every LOGIT_STRIDE-th token (the first included)."""
    for r, row in zip(slots, rows):
        if r is not None and len(r.tokens) % LOGIT_STRIDE == 0:
            served.logits.setdefault(r.rid, {})[len(r.tokens)] = \
                np.array(row, np.float32)


def instrument(loop, engines: dict, served: Served):
    """Wrap the engines' entry points on the instances: host spans for
    the trace, a record of each call, and the logits returned for the
    check."""
    import jax

    for name, eng in engines.items():
        batcher = loop.batchers[name]

        def wrap(kind, fn, name=name, eng=eng, batcher=batcher):
            span = f"bench.{kind}.{name}"

            def call(*a, **kw):
                rows, toks = eng.batch_size, 1
                if kind == "prefill":
                    toks = a[0].shape[1]
                elif kind == "backfill":
                    rows, toks = 1, len(a[0])
                    r = batcher.slots[a[1]]
                    if r is not None:
                        served.backfilled.add(r.rid)
                with jax.profiler.TraceAnnotation(span):
                    t0 = time.perf_counter()
                    out = fn(*a, **kw)
                    t1 = time.perf_counter()
                served.calls.append(Call(kind, name, t0, t1, rows, toks))
                if kind == "backfill":
                    keep_logits(served, [batcher.slots[a[1]]], [out])
                else:
                    keep_logits(served, batcher.slots, out)
                return out
            return call

        eng.run_prefill = wrap("prefill", eng.run_prefill)
        eng.run_decode = wrap("decode", eng.run_decode)
        eng.prefill_row = wrap("backfill", eng.prefill_row)


def make_requests(specs):
    from repro.serving.batching import Request
    out = []
    for s in specs:
        r = Request(arrival=s.due_ms, rid=s.rid, prompt=s.prompt,
                    max_new_tokens=s.max_new_tokens, sla_ms=s.sla_ms,
                    t_input_ms=s.t_input_ms, tenant=s.sla_class)
        r.tokens = Stamped()
        r.tier = s.tier
        out.append(r)
    return out


def serve(loop, requests: list, seconds: float, served: Served) -> None:
    """Open loop on the wall clock: submit each request once it is due,
    drain whenever work is queued (arrivals that fall due meanwhile wait
    for the drain to end), idle until the window closes."""
    import jax
    pending = deque(sorted(requests, key=lambda r: r.arrival))
    t0 = served.t0
    end = t0 + seconds
    with jax.profiler.TraceAnnotation("bench.window"):
        while pending:
            now_ms = (time.perf_counter() - t0) * 1e3
            queued = 0
            while pending and pending[0].arrival <= now_ms:
                r = pending.popleft()
                with jax.profiler.TraceAnnotation("bench.submit"):
                    a = time.perf_counter()
                    # the router names the candidate on the request; a
                    # loop of one engine routes nothing
                    r.model = loop.submit(r, now=(a - t0) * 1e3).model
                    b = time.perf_counter()
                served.admission_s.append(b - a)
                served.lateness_ms.append((a - t0) * 1e3 - r.arrival)
                queued += 1
            if queued:
                with jax.profiler.TraceAnnotation("bench.drain"):
                    loop.drain()
            elif pending:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, t0 + pending[0].arrival / 1e3
                                   - time.perf_counter()))
        served.t_end = time.perf_counter()
        if served.t_end < end:
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(end - served.t_end)


class CompileCounter:
    """Counts XLA compilations while `on`."""

    def __init__(self):
        import jax.monitoring
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.n += 1


# --------------------------------------------------------------------------
# Correctness
# --------------------------------------------------------------------------

def pick_checked(requests: list, model: str, seed: int,
                 backfilled: set) -> list:
    """A sample drawn from the seed of the requests this candidate
    finished: the longest, one seeded by a group prefill and one joined
    by backfill where there are such, then others until CHECK_TOKENS
    served tokens, rounded up to whole reference calls."""
    done = [r for r in requests if r.model == model
            and len(r.tokens) == r.max_new_tokens]
    if not done:
        return []
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    order = [done[i] for i in rng.permutation(len(done))]
    chosen = [max(done, key=lambda r: (len(r.tokens), -r.rid))]
    for want in (True, False):
        for r in order:
            if (r.rid in backfilled) == want and r not in chosen:
                chosen.append(r)
                break
    for r in order:
        n_tok = sum(len(c.tokens) for c in chosen)
        if len(chosen) >= CHECK_MAX_REQUESTS or (
                n_tok >= CHECK_TOKENS and len(chosen) % CHECK_ROWS == 0):
            break
        if r not in chosen:
            chosen.append(r)
    return chosen


def sequences(reqs: list, prompt_len: int, t_ref: int):
    """Reference rows: prompt then served tokens (all but the last),
    from position 0, padded at the end; and the position at which each
    served token is predicted."""
    rows = np.zeros((len(reqs), t_ref), np.int32)
    where = []
    for i, r in enumerate(reqs):
        p = np.asarray(r.prompt[-prompt_len:], np.int32)
        seq = np.concatenate([p, np.asarray(r.tokens[:-1], np.int32)])
        rows[i, :len(seq)] = seq
        where.append(np.arange(len(p) - 1, len(p) - 1 + len(r.tokens)))
    return rows, where


@dataclass
class RefPass:
    """What one reference pass says about one request, at each of its
    served positions."""
    gap: np.ndarray          # served token's logit below the best
    probe_gap: np.ndarray    # the same for the probe tokens, if any
    first: np.ndarray        # the token the reference puts first
    rows: dict               # token index -> the reference's logit row


def reference_pass(ref, weights, sizes, reqs, *, prompt_len, t_ref, bits,
                   n_rows, probe_tokens=None, at=None) -> dict:
    """{rid: RefPass} from the reference at `bits`, CHECK_ROWS requests a
    call. `probe_tokens` {rid: tokens} are scored like the served ones;
    `at` {rid: token indexes} asks for the full logit rows there, at
    most `n_rows` per call."""
    out = {}
    for lo in range(0, len(reqs), CHECK_ROWS):
        batch = reqs[lo:lo + CHECK_ROWS]
        rows, where = sequences(batch, prompt_len, t_ref)
        tokens = np.zeros((CHECK_ROWS, t_ref), np.int32)
        tokens[:len(batch)] = rows
        probes = np.zeros((CHECK_ROWS, t_ref, 2), np.int32)
        gather = np.zeros((n_rows, 2), np.int32)
        asked = []
        for i, r in enumerate(batch):
            probes[i, where[i], 0] = r.tokens
            if probe_tokens is not None:
                probes[i, where[i], 1] = probe_tokens[r.rid]
            for idx in sorted((at or {}).get(r.rid, ())):
                gather[len(asked)] = (i, where[i][idx])
                asked.append((r.rid, idx))
        top, first, val, picked = (np.asarray(a) for a in ref.logit_stats(
            weights, tokens, probes, sizes, bits=bits, gather=gather))
        for i, r in enumerate(batch):
            g = top[i, where[i], None] - val[i, where[i]]
            out[r.rid] = RefPass(g[:, 0], g[:, 1], first[i, where[i]], {})
        for k, (rid, idx) in enumerate(asked):
            out[rid].rows[idx] = picked[k]
    return out


def logit_rms(pairs) -> float:
    """Root-mean-square of (served - reference) over the root-mean-square
    of the reference, pooled over (served row, reference row) pairs."""
    diff = ref = 0.0
    for a, b in pairs:
        b = np.asarray(b, np.float64)
        diff += float(np.sum((np.asarray(a, np.float64) - b) ** 2))
        ref += float(np.sum(b * b))
    return math.sqrt(diff / ref) if ref > 0 else None


def check_limits(cell: dict, readings: dict, unserved: set) -> dict:
    """{number: {"value", "limit"}} for every number compared; a number
    of a candidate that served no request in the run has no value and
    says so."""
    limits = cell["limits"]
    out = {k: {"value": readings.get(k), "limit": limits[k]}
           for k in sorted(limits)}
    for k, c in out.items():
        if k.split(".", 1)[1] in unserved:
            c["unserved"] = True
    return out


def passed(checks: dict) -> bool:
    return all(c.get("unserved") or (c["value"] is not None
                                     and c["value"] <= c["limit"])
               for c in checks.values())


# --------------------------------------------------------------------------
# One run
# --------------------------------------------------------------------------

@dataclass
class Context:
    """What a metric reader may read."""
    workload: str
    cfg: dict
    mix: dict
    served: Served
    stats: dict            # model -> EngineStats delta over the window
    setup_s: float
    trace: dict = None     # trace_reduce.reduce() of the traced window
    peaks: dict = None
    chips: int = 1         # the chips the configuration runs on

    def arch(self, model: str):
        """The counts of the configuration's reference for the model's
        sizes (`bench/flops.py` says what they answer)."""
        return reference_module(self.cfg).counts(
            self.cfg["archs"][self.cfg["models"][model]["arch"]])

    def due(self, r) -> float:
        return self.served.t0 + r.arrival / 1e3


def read_metrics(entries: list, ctx: Context) -> dict:
    out = {}
    for m in entries:
        mod = load_module(os.path.join(BENCH, "metrics", f"{m['name']}.py"),
                          f"bench_metric_{m['name'].replace('.', '_')}")
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def memory_peak(devices) -> int:
    """The peak bytes in use on the fullest of the devices (0 where the
    backend keeps no count)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def stats_delta(engines: dict, before: dict) -> dict:
    from repro.serving.engine import EngineStats
    out = {}
    for name, eng in engines.items():
        b, a = before[name], eng.stats
        out[name] = EngineStats(**{k: getattr(a, k) - getattr(b, k)
                                   for k in vars(a)})
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, cfg: dict = None, cell: dict = None,
        control: bool = False, entries: list = None,
        all_readings: bool = False) -> dict:
    """One run of a cell; returns the result line as a dict (with the
    control's readings under "control" when `control`, and every number
    the check reads, limited or not, under "readings" when
    `all_readings`)."""
    import copy
    import jax

    w = workload_entry(workload) if cfg is None else None
    if cfg is None:
        cfg = load_json(os.path.join(BENCH, "configs", f"{w['config']}.json"))
    mix = cell["mix"] if w is None else generate.load_mix(w["traffic"])
    if w is not None:
        check_chips(w, cfg)
    if cell is None:
        cell = load_json(os.path.join(BENCH, "cells", f"{workload}.json"))
    if entries is None:
        entries = metric_entries(workload, trace)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    counter = CompileCounter()

    specs = generate.schedule(mix, seed, seconds, prompt_vocab(cfg))
    requests = make_requests(specs)
    engines, loop = setup(cfg, mix, seed)
    served = Served(requests=requests, t0=0.0, seconds=seconds)
    instrument(loop, engines, served)
    before = {n: copy.copy(e.stats) for n, e in engines.items()}
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    counter.on = True
    served.t0 = time.perf_counter()
    serve(loop, requests, seconds, served)
    counter.on = False
    served.compiles = counter.n
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        reduced = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.newest_trace(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    stats = stats_delta(engines, before)
    device["memory_peak_bytes"] = memory_peak(jax.devices()[:chips(cfg)])
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]

    late = np.asarray(served.lateness_ms)
    log(f"[window] {workload} seed {seed}: {len(requests)} requests due in "
        f"{seconds} s, served by {served.t_end - served.t0:.3f} s; "
        f"compilations in the window: {served.compiles}; submit lateness "
        f"ms p50 {np.percentile(late, 50):.3f} p95 "
        f"{np.percentile(late, 95):.3f} max {late.max():.3f}; admission "
        f"us mean {1e6 * np.mean(served.admission_s):.3f}")
    for name, s in stats.items():
        log(f"[engine] {name}: prefill x{s.prefill_calls} "
            f"{s.prefill_time_s:.3f} s, decode x{s.decode_calls} "
            f"{s.decode_time_s:.3f} s, backfill x{s.backfill_calls} "
            f"{s.backfill_time_s:.3f} s")
    if reduced is not None:
        log(f"[trace] busy {reduced['busy_s']:.6f} s of "
            f"{reduced['window_s']:.6f} s; idle by span "
            f"{json.dumps(reduced['idle_by_span'])}")

    ctx = Context(workload=workload, cfg=cfg, mix=mix, served=served,
                  stats=stats, setup_s=setup_s, trace=reduced,
                  peaks=peaks.peaks_for(dev.device_kind)
                  if dev.platform == "tpu" else None, chips=chips(cfg))
    log(f"[window] request p95 ms "
        f"{readers.nearest_rank([readers.e2e_ms(ctx, r) for r in requests], 95)}")
    metrics = read_metrics(entries, ctx)

    # -- correctness: the timed path's own outputs ------------------------
    failed = [r for r in requests if len(r.tokens) != r.max_new_tokens
              or any(not 0 <= t < cfg["archs"][cfg["models"][r.model]
                                               ["arch"]]["vocab"]
                     for t in r.tokens)]
    checked = {name: pick_checked(requests, name, seed, served.backfilled)
               for name in cfg["models"]}
    out_max = mix["output_tokens"]["max"]
    t_ref = 128 * math.ceil((cfg["prompt_len"] + out_max - 1) / 128)
    del loop, engines
    gc.collect()
    kept = served.logits
    n_rows = CHECK_ROWS * math.ceil(out_max / LOGIT_STRIDE)
    program_ctrl = program_control(cfg, seed, checked, kept) if control \
        else {}
    readings, control_readings = reference_readings(
        cfg, seed, checked, kept, t_ref, n_rows, control, program_ctrl)
    checks = check_limits(cell, readings,
                          {n for n, reqs in checked.items() if not reqs})
    log(f"[check] readings {json.dumps(readings)}")
    for k, c in checks.items():
        log(f"[check] {k} {c['value']} limit {c['limit']}")
    result = {
        "correct": not failed and passed(checks),
        "attempted": len(requests),
        "failed": len(failed),
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if control:
        result["control"] = control_readings
    if all_readings:
        result["readings"] = readings
    result["checks"] = checks
    return result


def program_control(cfg: dict, seed: int, checked: dict, n_tokens) -> dict:
    """The program's own lower-precision path as the control of each
    bf16 candidate: a fresh int8 engine (the program's quantizer) of the
    same weights, fed the same prompts and served tokens. {model: (rid ->
    the token it puts first at each served position, rid -> {token index:
    its logit row} at the indexes the window kept)}."""
    from repro.quant.int8 import quantize_exec_tree
    from repro.serving.engine import InferenceEngine
    par = parallel_of(cfg)
    B, T = cfg["batch_size"], cfg["prompt_len"]
    out = {}
    for name, m in cfg["models"].items():
        reqs = checked[name]
        if m["precision"] != "bf16" or not reqs:
            continue
        arch = cfg["archs"][m["arch"]]
        q = quantize_exec_tree(make_weights(cfg, arch, seed, m["weights"],
                                            par))
        eng = InferenceEngine(program_config(cfg, arch), q, batch_size=B,
                              max_seq=cfg["max_seq"], parallel=par)
        picks, rows = {}, {}
        for lo in range(0, len(reqs), B):
            batch = reqs[lo:lo + B]
            toks = np.zeros((B, T), np.int32)
            lengths = np.ones(B, np.int64)
            for i, r in enumerate(batch):
                p = np.asarray(r.prompt[-T:], np.int32)
                toks[i, T - len(p):] = p
                lengths[i] = len(p)
            got = [[] for _ in batch]
            logits = eng.run_prefill(toks, lengths=lengths)
            for j in range(max(len(r.tokens) for r in batch)):
                if j:
                    feed = np.zeros((B, 1), np.int32)
                    for i, r in enumerate(batch):
                        if j < len(r.tokens):
                            feed[i, 0] = r.tokens[j - 1]
                    logits = eng.run_decode(feed)
                for i, r in enumerate(batch):
                    if j < len(r.tokens):
                        got[i].append(int(logits[i].argmax()))
                        if j in n_tokens.get(r.rid, {}):
                            rows.setdefault(r.rid, {})[j] = np.array(
                                logits[i], np.float32)
            for i, r in enumerate(batch):
                picks[r.rid] = np.asarray(got[i], np.int32)
        out[name] = (picks, rows)
        del eng, q
        gc.collect()
    return out


def reference_readings(cfg, seed, checked, kept, t_ref, n_rows, control,
                       program_ctrl):
    """Each candidate's numbers against the plain reference (its own
    precision's: f32, or int8 for an int8 candidate): the gaps of its
    served tokens (gap_max, gap_mean, gap_rms) and its kept logit rows
    (logit_rms); with `control`, the same numbers of the control. On a
    mesh the reference's weights are placed as the program's are."""
    ref = reference_module(cfg)
    par = parallel_of(cfg)
    readings, ctrl = {}, {}
    by_weights = {}
    for name, m in cfg["models"].items():
        by_weights.setdefault(m["weights"], []).append(name)
    for widx, names in sorted(by_weights.items()):
        arch = cfg["archs"][cfg["models"][names[0]]["arch"]]
        weights = make_weights(cfg, arch, seed, widx, par)
        for name in names:
            reqs = checked[name]
            if not reqs:
                continue
            bits = 8 if cfg["models"][name]["precision"] == "int8" else None
            at = {r.rid: kept.get(r.rid, {}) for r in reqs}
            kw = dict(prompt_len=cfg["prompt_len"], t_ref=t_ref,
                      n_rows=n_rows, at=at)
            c_tokens = c_rows = None
            if control and bits == 8:
                low = reference_pass(ref, weights, arch, reqs, bits=4, **kw)
                c_tokens = {rid: x.first for rid, x in low.items()}
                c_rows = {rid: x.rows for rid, x in low.items()}
            elif control:
                c_tokens, c_rows = program_ctrl[name]
            res = reference_pass(ref, weights, arch, reqs, bits=bits,
                                 probe_tokens=c_tokens, **kw)
            readings.update(gap_numbers(name, [x.gap for x in res.values()]))
            readings[f"logit_rms.{name}"] = logit_rms(
                (at[rid][i], x.rows[i]) for rid, x in res.items()
                for i in at[rid])
            log(f"[check] {name}: {len(reqs)} requests, "
                f"{sum(len(x.gap) for x in res.values())} served tokens and "
                f"{sum(len(a) for a in at.values())} logit rows compared")
            if control:
                ctrl.update(gap_numbers(
                    name, [x.probe_gap for x in res.values()]))
                ctrl[f"logit_rms.{name}"] = logit_rms(
                    (c_rows[rid][i], x.rows[i]) for rid, x in res.items()
                    for i in at[rid])
        del weights
        gc.collect()
    return readings, ctrl


def gap_numbers(name: str, gaps: list) -> dict:
    """The widest gap, and the mean and root-mean-square gap over every
    served position (a near-tie flipped by rounding adds little to them;
    a wrong token or a coarser precision, which flips at wider margins,
    adds much, and more so to the squares)."""
    flat = np.concatenate(gaps).astype(np.float64)
    return {f"gap_max.{name}": float(flat.max()),
            f"gap_mean.{name}": float(flat.mean()),
            f"gap_rms.{name}": float(np.sqrt(np.mean(flat ** 2)))}
