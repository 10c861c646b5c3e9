"""Device idle time attributed to the program's own host spans.

The program opens a `serve.*` span around each phase of its served path
(`repro.serving.telemetry`): the drain, the group seeding, each engine
call and, inside it, the launch, the wait for the device and the copy
of the logits to the host, the host argmax, retiring requests, and
every garbage collection. Idle time of the device while one of them is
open is idle that the program caused, not missing demand. `attribute`
splits each idle gap of the device planes at the spans' edges, gives
each piece to the innermost `serve.*` span open over it, and sums the
rest as "outside serve spans"; the parts add up to the idle time
`trace_reduce.reduce` reports.

Run as a script, it serves one traced run of a cell through
`harness.run`, unchanged, and from the same trace adds the program's
attribution and the loop's counters:

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>

It prints `[loop]` and `[trace] idle by program span` on standard error
and, last on standard output, the run's result line with a "program"
key beside the others.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import trace_reduce  # noqa: E402

PREFIX = "serve."
OUTSIDE = "outside serve spans"


def labelled_segments(spans):
    """[(start_ns, end_ns, label)]: the stretches of time in which some
    span is open, each labelled by the innermost open span (the one
    opened last) without the prefix; consecutive, never overlapping."""
    edges = defaultdict(list)
    for i, (s, e, _) in enumerate(spans):
        if e > s:
            edges[s].append(("open", i))
            edges[e].append(("close", i))
    out, active = [], set()
    times = sorted(edges)
    for t, nxt in zip(times, times[1:] + [None]):
        for what, i in edges[t]:
            if what == "close":
                active.discard(i)
        for what, i in edges[t]:
            if what == "open":
                active.add(i)
        if active and nxt is not None:
            inner = max(active, key=lambda i: (spans[i][0], -spans[i][1]))
            out.append((t, nxt, spans[inner][2][len(PREFIX):]))
    return out


def split_gaps(gaps, segments, into: dict) -> None:
    """Add each gap's overlap with each segment to `into[label]`, and
    what no segment covers to `into[OUTSIDE]`; both lists sorted."""
    j = 0
    for a, b in gaps:
        covered = 0
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            s, e, label = segments[k]
            part = min(b, e) - max(a, s)
            into[label] += part
            covered += part
            k += 1
        into[OUTSIDE] += (b - a) - covered


def device_gaps(plane, window):
    """The idle gaps of one device plane inside `window`, as
    `trace_reduce.reduce` finds them."""
    w0, w1 = window
    evs = []
    for line in plane.lines:
        if line.name == trace_reduce.OPS_LINE:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events]
    merged = trace_reduce._union([(max(s, w0), min(e, w1)) for s, e in evs
                                  if e > w0 and s < w1])
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def attribute(pd, *, window=None) -> dict:
    """Idle time of the device planes inside `window` (default: the
    harness's window span) by the innermost `serve.*` span open over
    it, in seconds, averaged over the device planes:

    window_s, idle_s: the window, and all its idle time;
    idle_host_s: the idle time under some `serve.*` span;
    idle_by_program_span: by span name without the prefix, and
        OUTSIDE for the rest;
    spans: the `serve.*` spans that lie in the window.
    """
    devices = [p for p in pd.planes if trace_reduce.DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("trace has no TPU device plane")
    if window is None:
        window = trace_reduce.window_of(pd)
    if window is None:
        raise ValueError("trace has no window span")
    spans = trace_reduce.host_spans(pd, PREFIX)
    segments = labelled_segments(spans)
    idle = defaultdict(float)
    for plane in devices:
        split_gaps(device_gaps(plane, window), segments, idle)
    n = len(devices)
    by = {k: v / n * 1e-9 for k, v in
          sorted(idle.items(), key=lambda kv: -kv[1])}
    total = sum(idle.values()) / n * 1e-9
    return {
        "window_s": (window[1] - window[0]) * 1e-9,
        "idle_s": total,
        "idle_host_s": total - by.get(OUTSIDE, 0.0),
        "idle_by_program_span": by,
        "spans": sum(1 for s, e, _ in spans
                     if s >= window[0] and e <= window[1]),
    }


def device_idle_host(program: dict) -> float:
    """% of the window with no device op while a `serve.*` span is
    open."""
    return 100.0 * program["idle_host_s"] / program["window_s"]


def loop_delta(loop, before) -> dict:
    return {k: getattr(loop.stats, k) - getattr(before, k)
            for k in vars(before)}


def traced_run(workload: str, seed: int, seconds: float, *,
               t_start: float) -> dict:
    """One traced `harness.run` of the cell, with the loop that its
    set-up builds kept, and the trace reduced by `attribute` as well
    before the harness removes it."""
    from bench import harness
    kept = {}
    setup, reduce = harness.setup, trace_reduce.reduce

    def setup_and_keep(*a, **kw):
        engines, loop = setup(*a, **kw)
        kept["loop"], kept["before"] = loop, dataclasses.replace(loop.stats)
        return engines, loop

    def reduce_and_attribute(pd, **kw):
        out = reduce(pd, **kw)
        kept["program"] = attribute(pd, window=kw.get("window"))
        kept["idle_s"] = out["window_s"] - out["busy_s"]
        return out

    harness.setup, trace_reduce.reduce = setup_and_keep, reduce_and_attribute
    try:
        result = harness.run(workload, seed, seconds, True, t_start=t_start)
    finally:
        harness.setup, trace_reduce.reduce = setup, reduce
    loop = loop_delta(kept["loop"], kept["before"])
    program = kept["program"]
    groups = loop["groups"]
    loop["rows_per_group"] = loop["group_rows"] / groups if groups else None
    loop["left_behind"] = loop["queued_at_group"] - loop["group_rows"]
    harness.log(f"[loop] {json.dumps(loop)}")
    harness.log(f"[trace] idle by program span "
                f"{json.dumps(program['idle_by_program_span'])}")
    result["program"] = {
        "device_idle_host": device_idle_host(program),
        "idle_host_s": program["idle_host_s"],
        "idle_s": program["idle_s"],
        "idle_s_by_reduce": kept["idle_s"],
        "spans": program["spans"],
        "idle_by_program_span": program["idle_by_program_span"],
        "loop": loop,
    }
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    from bench import harness
    harness.enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        harness.log("needs a TPU: the reduction reads the device planes")
        return 2
    result = traced_run(args.workload, args.seed, args.seconds,
                        t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
