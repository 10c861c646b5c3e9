"""Shared benchmark plumbing: timing, CSV rows, result-dir access."""

from __future__ import annotations

import glob
import json
import os
import sys
import time

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def time_call(fn, *args, reps: int = 5, warmup: int = 1):
    """Returns (mean_us, std_us) of fn(*args)."""
    import numpy as np
    for _ in range(warmup):
        fn(*args)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args)
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.mean(ts)), float(np.std(ts))


def row(name: str, us_per_call: float, derived) -> str:
    if isinstance(derived, dict):
        derived = ";".join(f"{k}={v}" for k, v in derived.items())
    return f"{name},{us_per_call:.1f},{derived}"


def emit(rows):
    for r in rows:
        print(r, flush=True)


def load_dryrun_results(mesh: str = "pod", tag: str = "baseline"):
    out = {}
    for f in glob.glob(os.path.join(RESULTS_DIR, f"*_{mesh}_{tag}.json")):
        d = json.load(open(f))
        out[(d["arch"], d["shape"])] = d
    return out
