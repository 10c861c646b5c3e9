"""Run one cell of the benchmark on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up the cell (weights from the seed, engines, compilation through
the compile cache at <checkout>/.jax_cache, CNNSelect profiles, warm-up),
serves the cell's traffic for `--seconds` through the program's
ServingLoop, checks what was served against the plain reference, and
prints one JSON line last on standard output. With --trace 1 the window
runs under the profiler and the line holds the per-layer metrics; with
--trace 0 it holds the end-to-end metrics. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import harness
    w = harness.workload_entry(args.workload)
    harness.enable_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < w["chips"]:
        harness.log(f"needs {w['chips']} TPU chip(s); JAX found "
                    f"{len(devices)} {devices[0].platform} device(s)")
        return 2
    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
