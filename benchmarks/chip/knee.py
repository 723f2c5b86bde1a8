#!/usr/bin/env python3
"""Find an open-loop cell's knee on the chip: one set-up, then one window
per offered rate, each printed as a JSON line.

    python3 benchmarks/chip/knee.py --workload paper-4dev.point-zipf \
        --rates 250,500,1000,2000 --seconds 8 --seed 7

The knee is the highest rate whose p95 (from the due time, failures
counted as missing) stays within the budget while the backlog does not
grow: the latency of the window's last tenth is not far above its first
tenth's, and the drain after the window is short. A cell's rate, set in
``cells/<cell>.json``, is four fifths of it.
"""
import argparse
import json
import pathlib
import statistics
import sys

HERE = pathlib.Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--drain", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]
    from chipbench import harness

    s = harness.Session(args.workload)
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            traffic = {**s.traffic, "rate_per_s": rate,
                       "drain_s": args.drain}
            m = s.window(args.seed + i, args.seconds, traffic=traffic)
            recs = m["records"]
            lat = [((r["finish"] if r["status"] == 200 else m["w1"]
                     + args.drain) - r["due"]) * 1e3 for r in recs]
            tenth = max(1, len(lat) // 10)
            done = [r["finish"] for r in recs if r["finish"] is not None]
            print(json.dumps({
                "rate_per_s": rate, "requests": len(recs),
                "failed": sum(r["status"] != 200 for r in recs),
                "p50_ms": harness.percentile(lat, 0.50),
                "p95_ms": harness.percentile(lat, 0.95),
                "p99_ms": harness.percentile(lat, 0.99),
                "first_tenth_median_ms": statistics.median(lat[:tenth]),
                "last_tenth_median_ms": statistics.median(lat[-tenth:]),
                "drain_ms": (max(done) - m["w1"]) * 1e3 if done else None,
                "lateness_p99_ms": harness.percentile(
                    [(r["sent"] - r["due"]) * 1e3 for r in recs
                     if r["sent"] is not None], 0.99),
                "compiles_in_window": m["compiles_in_window"]}),
                flush=True)
    finally:
        s.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
