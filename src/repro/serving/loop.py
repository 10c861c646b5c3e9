"""Continuous-batching serving loop: ContinuousBatcher x InferenceEngine
with per-request SLA accounting and CNNSelect at admission.

The paper's observation that throughput-batching "may increase waiting
time of some requests" becomes measurable here: `ServingLoop.run`
processes an arrival trace and reports queue wait vs execution time per
request. Admission goes through the shared `Router`: the whole trace is
routed in one vectorized `route_batch` call (the jit'd cnnselect_batch
path) and lands in the per-model `ContinuousBatcher`s the router owns
as its queues — batching and selection compose (beyond-paper: the
paper serves batch-of-one). Mid-group, freed slots are backfilled with
queued arrivals via `InferenceEngine.prefill_row` (true continuous
batching), and each measured per-request exec_ms feeds
`ControlPlane.observe_outcome` so the online profiles track this
host's executed latencies."""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Union

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.selection import ModelProfile
from repro.serving.batching import ContinuousBatcher, Request
from repro.serving.control import ControlPlane
from repro.serving.engine import InferenceEngine
from repro.serving.metrics import ServingMetrics
from repro.serving.router import Router
from repro.serving.stack import StackOutcome
from repro.serving.telemetry import install_gc_span, span


class LoopMetrics(ServingMetrics):
    """The loop's ledger — now the unified `ServingMetrics` schema
    (serving/metrics.py); kept as a named subclass for imports. The
    pre-unification ``mean_e2e_ms``/``p95_e2e_ms`` summary keys are now
    ``mean_ms``/``p95_ms`` (migration note in CHANGES.md)."""


@dataclass
class LoopStats:
    """Counters of the loop's own work, summed over every drain (plain
    numbers, so two snapshots subtract field by field)."""
    groups: int = 0             # group prefills seeded
    group_rows: int = 0         # live requests in those prefills
    queued_at_group: int = 0    # queue length when each was seeded
    backfill_joins: int = 0     # requests joined mid-group
    sample_s: float = 0.0       # host argmax over returned logits
    retire_s: float = 0.0       # recording tokens, finishing requests


class ServingLoop:
    """Drives engines through a request trace in virtual time.

    Clock: each drain seeds its first group at the protocol's present,
    the largest `now` that `submit` has been given, or at the earliest
    queued arrival if that is later. `run` gives no protocol time, so
    its replay starts every drain at the earliest arrival.

    engines: {name: InferenceEngine}. The loop seeds an aligned group
    per model, then runs decode rounds with slot-level joins: a member
    retiring early frees its slot and the next queued arrival prefills
    into it mid-group (`InferenceEngine.prefill_row`) — the scheduler
    half of continuous batching (see DESIGN.md §14).

    profiles: a ModelProfile list, or the string ``"measured"`` to
    profile each engine on this host at construction (requires
    `accuracies={name: score}` for the selection objective)."""

    def __init__(self, engines: Dict[str, InferenceEngine],
                 profiles: Union[List[ModelProfile], str, None] = None,
                 t_threshold: float = 30.0, seed: int = 0,
                 policy="cnnselect", t_estimator=None, controller=None,
                 accuracies: Optional[Dict[str, float]] = None):
        self.engines = engines
        some = next(iter(engines.values()))
        self.batchers = {
            name: ContinuousBatcher(eng.batch_size,
                                    prompt_len=some.max_seq // 4)
            for name, eng in engines.items()}
        if isinstance(profiles, str):
            if profiles != "measured":
                raise ValueError(f"unknown profiles source {profiles!r}; "
                                 f"pass a list or 'measured'")
            if accuracies is None:
                raise ValueError("profiles='measured' needs accuracies="
                                 "{name: score}")
            prompt_len = some.max_seq // 4
            profiles = []
            for name, eng in engines.items():
                cold_s = eng.warmup(prompt_len)
                p = eng.measured_profile(prompt_len, n_tokens=4)
                profiles.append(ModelProfile(
                    name=name, accuracy=accuracies[name], mu=p["mu"],
                    sigma=max(p["sigma"], 1e-3), cold_mu=cold_s * 1000.0,
                    cold_sigma=100.0 * cold_s))
        if profiles is None or len(engines) == 1:
            # Single-engine loop: no selection, everything to one queue.
            self.router = None
            self.control = None
        else:
            # t_estimator: budget-side T_input source (DESIGN.md §9) —
            # None trusts each request's observed upload time; an
            # EstimatorBank keys estimation on each request's
            # `device_id` (fleet traces, DESIGN.md §10).
            self.router = Router(profiles, policy=policy,
                                 t_threshold=t_threshold, seed=seed,
                                 t_estimator=t_estimator)
            for name in self.router.order:
                self.router.attach_queue(name, self.batchers[name])
            # The shared per-request control step (DESIGN.md §12):
            # with a `controller` (CONTROLLER_SCENARIOS name or
            # AdaptiveController) admission adapts per request; without
            # one, admission stays the vectorized submit_many path.
            self.control = ControlPlane(self.router,
                                        controller=controller,
                                        seed=seed,
                                        t_threshold=t_threshold)
        self.metrics = LoopMetrics()
        self.stats = LoopStats()
        # The protocol's present: the largest `now` given to `submit`.
        self._present = 0.0
        install_gc_span()
        self._req_modes: Dict[int, str] = {}
        # Optional trace capture (serving/trace.py, DESIGN.md §11):
        # `run` records each drained request with its SLA outcome.
        # Attach here, not to self.router — the router hook would
        # record the same request again at admission.
        self.recorder = None

    def run(self, requests: List[Request]) -> LoopMetrics:
        ordered = sorted(requests, key=lambda r: r.arrival)
        if self.router is not None and self.control.controller is None:
            # Vectorized admission: one chunked jit call for the trace.
            self.router.submit_many(ordered)
        else:
            # Per-request admission (single-engine, or adaptive — the
            # controller's decisions are inherently sequential).
            for req in ordered:
                self.submit(req)
        self.drain()
        return self.metrics

    # -- ServingStack (serving/stack.py, DESIGN.md §16) ---------------

    def submit(self, req: Request, *, now: float = 0.0) -> StackOutcome:
        """Protocol admission: route (through the shared control step
        when a controller is attached) and queue on the chosen model's
        batcher; execution and the metrics row land at `drain`. A
        request submitted at `now` has arrived by `now`."""
        with TraceAnnotation("serve.submit"):
            self._present = max(self._present, now)
            return self._submit(req, now)

    def _submit(self, req: Request, now: float) -> StackOutcome:
        if self.router is None:
            only = next(iter(self.engines))
            self.batchers[only].submit(req)
            return StackOutcome(model=only, pending=True,
                                tenant=req.tenant)
        if self.control.controller is None:
            d = self.router.submit(req, now=now)
            return StackOutcome(model=d.name, pending=True,
                                tenant=req.tenant)
        # Adaptive: detect -> maybe switch mode -> estimate -> select.
        d = self.control.step(req.sla_ms or 1e9, req.t_input_ms,
                              device_id=req.device_id)
        self._req_modes[req.rid] = d.mode
        self.router.submit(req, name=d.name)
        return StackOutcome(model=d.name, mode=d.mode, pending=True,
                            tenant=req.tenant)

    def drain(self) -> None:
        """Drain each model's queue in arrival order (virtual clock per
        model; engines measure real exec time on this host). Each
        model's clock starts at the protocol's present, so the first
        group takes everything queued and due by then, up to a batch."""
        for name, batcher in self.batchers.items():
            self._drain(name, batcher)

    def observe_outcome(self, name: str, latency_ms: float, *,
                        cold: bool = False, now: float = 0.0) -> None:
        if self.control is not None:
            self.control.observe_outcome(name, latency_ms, cold=cold,
                                         now=now)

    def _finish(self, r: Request, name: str, exec_ms: float):
        """Per-request completion: metrics row, online profile feedback,
        trace capture — with the request's OWN measured exec_ms, not a
        group-shared wall time."""
        queue_ms = max(0.0, r.start_exec - r.arrival)
        self.metrics.add(r, name, queue_ms, exec_ms,
                         mode=self._req_modes.get(r.rid))
        if self.control is not None:
            self.control.observe_outcome(name, exec_ms)
        if self.recorder is not None:
            # sla_ms=0 means "no SLA": the outcome is unknown, not met
            # (metrics report ok=True for convenience, but a capture
            # must not fabricate attainment).
            self.recorder.record_request(
                r, model=name, exec_ms=exec_ms,
                sla_ok=(self.metrics.records[-1]["ok"]
                        if r.sla_ms else None))

    def _retire(self, name: str, batcher: ContinuousBatcher,
                acc: Dict[int, float], n_done: int) -> int:
        """Finish every request retired since `n_done`; the new count."""
        while n_done < len(batcher.done):
            r = batcher.done[n_done]
            self._finish(r, name, acc.pop(r.rid, 0.0))
            n_done += 1
        return n_done

    def _drain(self, name: str, batcher: ContinuousBatcher):
        with TraceAnnotation(span("drain", name)):
            self._serve_queue(name, batcher)

    def _serve_queue(self, name: str, batcher: ContinuousBatcher):
        eng = self.engines[name]
        stats = self.stats
        now = self._present
        # rid -> exec ms accumulated while the request occupied a slot.
        # Every engine call's wall time is charged to the requests that
        # were resident during it (aligned decode: they all stall
        # together), so per-request exec_ms is honest under backfill.
        acc: Dict[int, float] = {}
        n_done = len(batcher.done)     # done entries from previous runs
        logits = None
        while batcher.has_work:
            if batcher.n_active == 0:
                # Engine idle: advance the clock to the next arrival and
                # seed a fresh group.
                if not batcher.queue:
                    break
                with TraceAnnotation(span("group", name)):
                    queued = len(batcher.queue)
                    now = max(now, batcher.queue[0].arrival)
                    group = batcher.form_group(now)
                    if group is None:
                        break
                    prompts = batcher.pad_prompts()
                    lengths = batcher.prompt_lengths()
                stats.groups += 1
                stats.group_rows += len(group)
                stats.queued_at_group += queued
                t0 = time.perf_counter()
                with TraceAnnotation(span("prefill", name)):
                    logits = eng.run_prefill(prompts, lengths=lengths)
                dt = (time.perf_counter() - t0) * 1000.0
                now += dt
                for r in group:
                    acc[r.rid] = dt
            # One aligned decode round: sample, record/retire, backfill
            # freed slots, then step the whole group.
            t0 = time.perf_counter()
            with TraceAnnotation("serve.sample"):
                nxt = logits.argmax(-1).astype(np.int32)
            t1 = time.perf_counter()
            with TraceAnnotation("serve.retire"):
                batcher.record_tokens(nxt, now)
                n_done = self._retire(name, batcher, acc, n_done)
            stats.sample_s += t1 - t0
            stats.retire_s += time.perf_counter() - t1
            if batcher.n_active == 0:
                continue            # drained; next iteration reseeds
            if eng._backfillable:
                for slot, r in batcher.backfill(now, eng.free_context):
                    stats.backfill_joins += 1
                    prompt = np.zeros(batcher.prompt_len, np.int32)
                    p = r.prompt[-batcher.prompt_len:]
                    prompt[len(prompt) - len(p):] = p
                    t0 = time.perf_counter()
                    with TraceAnnotation(span("backfill", name)):
                        row = eng.prefill_row(prompt, slot, length=len(p))
                    t1 = time.perf_counter()
                    with TraceAnnotation("serve.sample"):
                        tok = int(row.argmax(-1))
                    t2 = time.perf_counter()
                    stats.sample_s += t2 - t1
                    dt = (t2 - t0) * 1000.0
                    now += dt
                    # The whole group stalls for the row prefill.
                    for rr in batcher.slots:
                        if rr is not None:
                            acc[rr.rid] = acc.get(rr.rid, 0.0) + dt
                    nxt[slot] = tok
                    t0 = time.perf_counter()
                    with TraceAnnotation("serve.retire"):
                        batcher.record_token(slot, tok, now)
                        n_done = self._retire(name, batcher, acc, n_done)
                    stats.retire_s += time.perf_counter() - t0
            if batcher.n_active == 0:
                continue
            t0 = time.perf_counter()
            with TraceAnnotation(span("decode", name)):
                logits = eng.run_decode(nxt[:, None])
            dt = (time.perf_counter() - t0) * 1000.0
            now += dt
            for rr in batcher.slots:
                if rr is not None:
                    acc[rr.rid] = acc.get(rr.rid, 0.0) + dt
