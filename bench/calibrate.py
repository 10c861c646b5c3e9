"""Readings that the limits of a cell's correctness check are set from,
on the chip: the program's numbers over many seeds (the lower reading)
and the control's over the same seeds (the upper reading).

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10

Each seed is one whole run of the cell (bench/run.py's set-up, window
and check) at the cell's own load, with the control added after the
window: for a bf16 candidate the program's own int8 path (a fresh
engine quantized by the program from the same weights, fed the same
prompts and served tokens); for an int8 candidate the reference at
int4. With --fault, the runs are made with that fault planted
underneath the timed path. One JSON line per seed on standard output, then the largest
program reading and the smallest control reading of each number.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", default=None,
                    help="plant a fault of bench/faults.py first")
    args = ap.parse_args()

    from bench import harness
    harness.enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        harness.log("calibration needs a TPU")
        return 2
    if args.fault:
        from bench import faults
        faults.plant(args.fault)
    lower, upper = {}, {}
    t = T_START
    for seed in [int(s) for s in args.seeds.split(",")]:
        r = harness.run(args.workload, seed, args.seconds, False, t_start=t,
                        control=not args.fault, all_readings=True)
        t = time.perf_counter()
        prog = r["readings"]
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "program": prog, "control": r.get("control"),
                          "setup_s": r["metrics"]["setup_s"]["value"]}),
              flush=True)
        for k, v in prog.items():
            lower[k] = max(lower.get(k, 0.0), v)
        for k, v in r.get("control", {}).items():
            upper[k] = min(upper.get(k, float("inf")), v)
    print(json.dumps({"lower": lower, "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
