"""Find a mix's knee on the chip: the highest Poisson rate the program
sustains without a growing backlog.

    python bench/sweep.py --workload <cell> --rates 1,2,3 --seconds 20 \
        [--seed n] [--write FACTOR]

One process sets the cell up once, then serves the cell's mix at each
rate for `--seconds` (the same harness window as bench/run.py) and
prints, per rate: requests due, completed inside the window, the
backlog at the window's close (due but unfinished), the median
end-to-end latency of the first and the last third of the requests, and
output tokens per second, and how long the drain ran past the close.
A rate is sustained when the last third's median latency is at most 1.5
times the first third's and the drain ends within twice the first
third's median latency (at least 5 s) of the close. With --write, FACTOR x the highest
sustained rate becomes the mix file's rate.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--write", type=float, default=None)
    args = ap.parse_args()

    from bench import generate, harness
    harness.enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        harness.log("the sweep needs a TPU")
        return 2
    w = harness.workload_entry(args.workload)
    cfg = harness.load_json(os.path.join(harness.BENCH, "configs",
                                         f"{w['config']}.json"))
    mix = generate.load_mix(w["traffic"])
    _, loop = harness.setup(cfg, mix, args.seed)
    harness.log(f"[sweep] set-up {time.perf_counter() - T_START:.3f} s")
    rows = []
    for rate in [float(x) for x in args.rates.split(",")]:
        m = json.loads(json.dumps(mix))
        m["arrivals"]["rate_per_s"] = rate
        specs = generate.schedule(m, args.seed, args.seconds,
                                  harness.prompt_vocab(cfg))
        reqs = harness.make_requests(specs)
        served = harness.Served(requests=reqs, t0=time.perf_counter(),
                                seconds=args.seconds)
        harness.serve(loop, reqs, args.seconds, served)
        close = served.t0 + args.seconds
        e2e = [(r.tokens.stamps[-1] - served.t0) * 1e3 - r.arrival
               for r in reqs]
        third = max(1, len(reqs) // 3)
        first, last = np.median(e2e[:third]), np.median(e2e[-third:])
        backlog = sum(1 for r in reqs if r.tokens.stamps[-1] > close)
        tokens = sum(1 for r in reqs for s in r.tokens.stamps if s < close)
        tail_s = served.t_end - close
        ok = bool(last <= 1.5 * first and tail_s <= max(5.0, 2e-3 * first))
        rows.append(dict(rate=rate, due=len(reqs),
                         done=len(reqs) - backlog, backlog=backlog,
                         e2e_first_ms=float(first), e2e_last_ms=float(last),
                         tokens_per_s=tokens / args.seconds, sustained=ok,
                         tail_s=float(tail_s)))
        harness.log(f"[sweep] {json.dumps(rows[-1])}")
    print("| rate /s | due | done in window | backlog at close | e2e p50 "
          "first third ms | e2e p50 last third ms | tokens/s | drain past "
          "close s | sustained |")
    print("| --- | --- | --- | --- | --- | --- | --- | --- | --- |")
    for r in rows:
        print(f"| {r['rate']} | {r['due']} | {r['done']} | {r['backlog']} | "
              f"{r['e2e_first_ms']:.1f} | {r['e2e_last_ms']:.1f} | "
              f"{r['tokens_per_s']:.1f} | {r['tail_s']:.1f} | "
              f"{r['sustained']} |")
    knee = max([r["rate"] for r in rows if r["sustained"]], default=None)
    print(f"knee {knee}")
    if args.write is not None and knee is not None:
        path = os.path.join(generate.TRAFFIC_DIR, f"{w['traffic']}.json")
        mix["arrivals"]["rate_per_s"] = round(args.write * knee, 3)
        mix["arrivals"]["knee_per_s"] = knee
        with open(path, "w") as f:
            json.dump(mix, f, indent=2)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
