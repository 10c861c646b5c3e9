"""From a `jax.profiler` trace to device busy and idle time, per-kernel
device time, and the longest idle gaps labelled by the host spans that
the harness opened around each call into the program.

Device operations are the events of each device plane's "XLA Ops" line;
an op's name is the HLO instruction's name with its numeric suffix cut
(`%decode_attention.10 = ...` is `decode_attention`). Control-flow ops
(`while`, `conditional`, `call`) contain the ops of their bodies on the
same line, so they count towards busy time but not towards op totals.
All times on every plane share the profiler's clock, in nanoseconds.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
CONTAINERS = {"while", "conditional", "call"}
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def newest_trace(log_dir: str) -> str:
    """The newest `.xplane.pb` that `jax.profiler.start_trace(log_dir)`
    wrote."""
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str):
    import jax
    if path.endswith(".pbtxt"):
        with open(path) as f:
            return jax.profiler.ProfileData.from_text_proto(f.read())
    return jax.profiler.ProfileData.from_file(path)


def op_name(event_name: str) -> str:
    """`%fusion.114 = bf16[...] fusion(...)` -> `fusion`."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def op_label(event_name: str) -> str:
    """Op name with its result type, without layouts: `fusion
    bf16[8,256,11008]`. Fusions are told apart by what they make."""
    parts = event_name.split(" = ", 1)
    if len(parts) < 2:
        return op_name(event_name)
    rtype = re.sub(r"\{[^}]*\}", "", parts[1]).split(" ", 1)[0]
    return f"{op_name(event_name)} {rtype[:48]}"


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def host_spans(pd, prefix: str = SPAN_PREFIX):
    """[(start_ns, end_ns, name)] of every host event whose name starts
    with `prefix`, over all host threads."""
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return spans


def window_of(pd):
    """(start_ns, end_ns) of the harness's window span, or None."""
    for s, e, name in host_spans(pd, WINDOW_SPAN):
        if name == WINDOW_SPAN:
            return s, e
    return None


def _labels(spans, times):
    """For each time, the innermost harness span that covers it. Spans
    come from one thread and nest, so a stack swept in time order holds
    the spans open at each time, innermost on top."""
    order = sorted(spans, key=lambda s: (s[0], -s[1]))
    out = {}
    stack = []
    i = 0
    for t in sorted(set(times)):
        while i < len(order) and order[i][0] <= t:
            while stack and stack[-1][1] < order[i][0]:
                stack.pop()
            stack.append(order[i])
            i += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        out[t] = (stack[-1][2][len(SPAN_PREFIX):] if stack
                  else "outside spans")
    return out


def reduce(pd, *, window=None, top: int = 10) -> dict:
    """Busy and idle time of the device planes inside `window`
    ((start_ns, end_ns); default: the harness's window span, else the
    extent of the device ops). Returns seconds throughout:

    busy_s, window_s: busy is the union of op intervals, averaged over
        the device planes;
    kernel_s, kernel_calls: per op name (summed over devices);
    device_ops: the `top` op labels that took most time;
    idle_gaps: the `top` longest gaps, each labelled by the innermost
        harness span at its midpoint;
    idle_by_span: all idle time summed by that label.
    """
    devices = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("trace has no TPU device plane")
    if window is None:
        window = window_of(pd)
    events = []
    for plane in devices:
        evs = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                       for ev in line.events]
        events.append(evs)
    if window is None:
        flat = [e for evs in events for e in evs]
        if not flat:
            raise ValueError("trace has no device ops")
        window = (min(e[0] for e in flat), max(e[1] for e in flat))
    w0, w1 = window
    spans = host_spans(pd)
    busy = 0.0
    kernel_ns = defaultdict(float)
    kernel_calls = defaultdict(int)
    label_ns = defaultdict(float)
    gaps = []
    for evs in events:
        inside = [(max(s, w0), min(e, w1), name) for s, e, name in evs
                  if e > w0 and s < w1]
        for s, e, name in inside:
            base = op_name(name)
            if base in CONTAINERS:
                continue
            kernel_ns[base] += e - s
            kernel_calls[base] += 1
            label_ns[op_label(name)] += e - s
        merged = _union([(s, e) for s, e, _ in inside])
        busy += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        found = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        labels = _labels(spans, [(a + b) / 2 for a, b in found])
        gaps += [(b - a, labels[(a + b) / 2]) for a, b in found]
    n = len(devices)
    idle_by = defaultdict(float)
    for g, lab in gaps:
        idle_by[lab] += g / n
    gaps.sort(key=lambda x: -x[0])
    return {
        "busy_s": busy / n * 1e-9,
        "window_s": (w1 - w0) * 1e-9,
        "devices": n,
        "kernel_s": {k: v * 1e-9 for k, v in kernel_ns.items()},
        "kernel_calls": dict(kernel_calls),
        "device_ops": [[k, v * 1e-9] for k, v in
                       sorted(label_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[lab, g * 1e-9] for g, lab in gaps[:top]],
        "idle_by_span": {k: v * 1e-9 for k, v in
                         sorted(idle_by.items(), key=lambda kv: -kv[1])},
    }
