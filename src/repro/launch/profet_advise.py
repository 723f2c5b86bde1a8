"""PROFET advisor CLI — the paper's end-to-end scenario (Fig 3) as a
framework feature: profile once on an anchor instance, get predicted latency
+ cost on every catalog device, and a recommendation.

    PYTHONPATH=src python -m repro.launch.profet_advise \
        --anchor T4 --model VGG16 --batch 64 --pix 128

The oracle is fit on the offline workload grid and persisted through the
versioned ``repro.api`` artifact store (refitting three regressors x 12
device pairs takes ~1 min). The artifact carries a ProfetConfig fingerprint,
so rerunning with different ``--epochs``/``--seed`` refits instead of
silently reusing a stale cache. The candidate sweep is answered through the
oracle's batched plan -> execute engine (``predict_many``): one fused
ensemble call per device pair, not one round-trip per candidate.
"""
import argparse
import pathlib
import sys


def fit_or_load(cache_path: pathlib.Path, *, dnn_epochs: int = 150,
                seed: int = 0):
    """Load the cached oracle if it matches (dnn_epochs, seed); else refit."""
    from repro import api
    from repro.core import workloads
    from repro.core.predictor import ProfetConfig

    cfg = ProfetConfig(dnn_epochs=dnn_epochs, seed=seed)
    return api.fit_or_load(
        cache_path, cfg,
        fit_fn=lambda: api.LatencyOracle.fit(workloads.generate(), cfg))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--anchor", default="T4",
                    help="instance the profile was taken on")
    ap.add_argument("--model", default="VGG16")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--pix", type=int, default=128)
    ap.add_argument("--steps", type=int, default=10_000,
                    help="training steps for the cost estimate")
    ap.add_argument("--cache", default="results/profet_cache.pkl")
    ap.add_argument("--epochs", type=int, default=150)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro import api
    from repro.core import simulator

    oracle = fit_or_load(pathlib.Path(args.cache),
                         dnn_epochs=args.epochs, seed=args.seed)
    workload = api.Workload(args.model, args.batch, args.pix)

    # client-side step: run once on the anchor with profiling enabled
    meas = simulator.measure(args.anchor, *workload.case)

    print(f"workload: {args.model} batch={args.batch} pix={args.pix} "
          f"(profiled on {args.anchor})\n")
    print(f"{'device':8s} {'pred ms/batch':>14s} {'$/hr':>7s} "
          f"{'$ for ' + str(args.steps) + ' steps':>18s}")
    rows = oracle.advise(args.anchor, workload, profile=meas.profile,
                         measured_ms=meas.latency_ms)
    for r in rows:
        tag = " (anchor, measured)" if r.mode == api.MODE_MEASURED else ""
        print(f"{r.target:8s} {r.latency_ms:14.2f} {r.price_hr:7.3f} "
              f"{r.cost_usd(args.steps):18.4f}{tag}")

    fastest = min(rows, key=lambda r: r.latency_ms)
    cheapest = min(rows, key=lambda r: r.cost_usd(args.steps))
    print(f"\n({len(rows) - 1} candidates answered through one fused "
          f"predict_many batch)")
    print(f"fastest:  {fastest.target} ({fastest.latency_ms:.1f} ms/batch)")
    print(f"cheapest: {cheapest.target} "
          f"(${cheapest.cost_usd(args.steps):.4f} for {args.steps} steps)")
    return 0


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    sys.exit(main())
