"""Benchmark runner: one module per paper table/figure + assignment
artifacts. Prints ``name,us_per_call,derived`` CSV rows; ``--json``
additionally writes the rows as structured JSON (the CI benchmark
artifact).

    PYTHONPATH=src python -m benchmarks.run [--only fig13,roofline] [--fast]
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback

from benchmarks.common import emit

MODULES = [
    ("fig3_device_vs_cloud", "benchmarks.device_vs_cloud"),
    ("fig4_startup_latency", "benchmarks.startup_latency"),
    ("fig5_model_sweep", "benchmarks.model_sweep"),
    ("fig6_quantization", "benchmarks.quantization"),
    ("fig9_server_capacity", "benchmarks.server_capacity"),
    ("measured_serving", "benchmarks.measured_serving"),
    ("fig10_network_conditions", "benchmarks.network_conditions"),
    ("fig10x_network_dynamics", "benchmarks.network_dynamics"),
    ("table4x_fleet_dynamics", "benchmarks.fleet_dynamics"),
    ("ctrl_adaptive_control", "benchmarks.adaptive_control"),
    ("sim2real_trace_replay", "benchmarks.trace_replay"),
    ("fig12_prototype_e2e", "benchmarks.prototype_e2e"),
    ("fig13_selection_vs_greedy", "benchmarks.selection_vs_greedy"),
    ("kernels", "benchmarks.kernels_bench"),
    ("roofline_pod", "benchmarks.roofline"),
    ("table5_zoo", "benchmarks.zoo_table"),
    ("lmzoo_selection", "benchmarks.lm_zoo_selection"),
]


def parse_row(line: str) -> dict:
    """``name,us_per_call,k=v;k=v`` -> structured dict (the --json
    artifact shape)."""
    name, us, derived = line.split(",", 2)
    out: dict = {"name": name, "us_per_call": float(us)}
    if "=" in derived:
        out["derived"] = dict(kv.split("=", 1)
                              for kv in derived.split(";") if "=" in kv)
    else:
        out["derived"] = derived
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated substring filters")
    ap.add_argument("--fast", action="store_true",
                    help="skip the engine-executing benches")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the rows as structured JSON "
                         "(uploaded as a CI artifact on main pushes)")
    args = ap.parse_args()
    only = args.only.split(",") if args.only else None
    # measured_serving executes the zoo engines; under --fast its rows
    # still surface once via fig9_server_capacity (memoized), so the
    # standalone entry is skipped rather than run twice.
    slow = {"fig3_device_vs_cloud", "fig4_startup_latency",
            "fig5_model_sweep", "sim2real_trace_replay",
            "fig12_prototype_e2e", "kernels", "measured_serving"}
    print("name,us_per_call,derived")
    failures = 0
    records = []
    for name, mod in MODULES:
        if only and not any(o in name for o in only):
            continue
        if args.fast and name in slow:
            continue
        try:
            import importlib
            m = importlib.import_module(mod)
            rows = m.run()
            emit(rows)
            records.extend(parse_row(r) for r in rows)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{name},0.0,ERROR", flush=True)
            records.append({"name": name, "us_per_call": 0.0,
                            "derived": "ERROR"})
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": records, "failures": failures}, f,
                      indent=2, sort_keys=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
