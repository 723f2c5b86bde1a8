#!/usr/bin/env python3
"""PROFET's on-chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload paper-4dev.point-zipf \
        --seed 1234 --seconds 20 --trace 0

Run it from the repository root on a machine that holds the chips the
cell asks for. It fails, and prints no result, where JAX finds no TPU.
Earlier lines (standard error) name the device, the fit cache's hit or
miss, the compiles inside the window and the generator's lateness; the
numbers compared for ``correct`` come last there. The last line of
standard output is the result: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` a ``breakdown``, and last
the ``checks`` with their limits.
"""
import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]
    from chipbench import harness
    t_start = harness.process_start()
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=t_start)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
