#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: on each seed, one window
of the cell's own traffic, the program's answers against the reference,
and the control's (the reference one precision step lower, in the
program's place) against the reference on the same requests.

    python3 benchmarks/chip/control.py --workload paper-4dev.point-zipf \
        --seeds 101,102,103 --seconds 20

One JSON line per seed: ``program`` and ``control``, each with the
numbers ``checks.compare`` gives. The benchmark's own runs never run the
control.
"""
import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent


def _explain(ref, row, plan) -> dict:
    """The program's answer behind the program's ``answer_gap``, beside
    the reference's answer and its three members on each phase-1 row."""
    import numpy as np
    out = {"served_ms": row["latency_ms"], "reference_ms": plan["latency_ms"],
           "mode": plan["mode"], "pair": [plan["anchor"], plan["target"]]}
    rows = plan.get("rows", ())
    if rows:
        X = np.stack([x for _, x in rows])
        g = np.array([p for p, _ in rows])
        out["members"] = {m: getattr(ref, "_" + m)(X, g).tolist()
                          for m in ref.members}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]
    from chipbench import checks, harness

    s = harness.Session(args.workload)
    control = s.ref.control()
    try:
        for seed in (int(x) for x in args.seeds.split(",")):
            m = s.window(seed, args.seconds)
            program = s.check(m)
            endpoint = m["gen"]["endpoint"]
            bodies = [m["bodies"][r["key"]] for r in m["records"]]
            ask = control.predict if endpoint == "predict" \
                else control.advise
            want = s.ref.predict if endpoint == "predict" else s.ref.advise
            expected = want(bodies)
            ctl = checks.compare(endpoint,
                                 checks.as_served(endpoint, ask(bodies)),
                                 [200] * len(bodies), expected)
            row, plan = checks.worst(
                endpoint, [r["body"] for r in m["records"]],
                [r["status"] for r in m["records"]], expected)
            print(json.dumps({"seed": seed, "requests": len(bodies),
                              "compiles_in_window": m["compiles_in_window"],
                              "program": program, "control": ctl,
                              "worst": _explain(s.ref, row, plan)}),
                  flush=True)
    finally:
        s.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
