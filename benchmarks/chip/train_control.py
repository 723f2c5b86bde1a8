#!/usr/bin/env python3
"""Readings that set the limits of a training cell's ``correct``, at the
cell's own size, in one process: on each seed the program's checked steps
against the reference (the module the cell's configuration names); on the
control seeds also the control (the reference one precision lower, in the
program's place) and each planted fault (``chipbench/trainfaults.py``)
against the same reference.

    python3 benchmarks/chip/train_control.py \
        --workload granite-3-2b.train-4k --seeds 1,2,...,12 \
        --control-seeds 1,2,3 --faults half_the_batch,layer_skipped

One JSON line per seed: for the program, the control and each fault the
numbers ``trainstep.compare`` gives and ``correct``, the verdict of the
cell's own limits on them, as a run of the benchmark gives it. The
benchmark's own runs never run the control or a fault.
"""
import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", default="", help="comma-separated")
    ap.add_argument("--faults", default="", help="comma-separated")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]
    from chipbench import checks, spec, trainfaults, trainstep

    def log(line):
        print(line, file=sys.stderr, flush=True)
    limits = spec.cell_settings(args.workload)["limits"]
    s = trainstep.TrainSession(args.workload, log=log)
    broken = {}
    for f in (f for f in args.faults.split(",") if f):
        with trainfaults.planted(f):      # compiled into its session's step
            broken[f] = trainstep.TrainSession(args.workload, log=log)
    ref = s.ref
    names = ref.leaf_names(s.dims)
    controls = {int(x) for x in args.control_seeds.split(",") if x}
    seeds = [int(x) for x in args.seeds.split(",")]
    seeds += sorted(controls - set(seeds))
    try:
        for seed in seeds:
            t0 = time.monotonic()
            s.start(seed)
            got = {"program": s.got}
            s.free()
            if seed in controls:
                for f, b in broken.items():
                    b.start(seed)
                    got[f] = b.got
                    b.free()
            t1 = time.monotonic()
            batches = s.batches()
            want = ref.readings(s.wkey, batches, s.dims, s.adam,
                                ref.REFERENCE)
            t2 = time.monotonic()
            if seed in controls:
                got["control"] = ref.readings(s.wkey, batches, s.dims,
                                              s.adam, ref.CONTROL)
            out = {"seed": seed, "program_s": t1 - t0, "reference_s": t2 - t1,
                   "loss": want["loss"].tolist(), "limits": limits}
            for k, v in got.items():
                n = trainstep.compare(v, want, names)
                out[k] = {"correct": checks.verdict(n, limits), **n}
            print(json.dumps(out), flush=True)
    finally:
        s.close()
        for b in broken.values():
            b.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
