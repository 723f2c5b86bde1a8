"""Benchmark suite runner: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run fig9_10    # one
"""
from __future__ import annotations

import sys
import time

BENCHES = [
    ("fig2", "benchmarks.bench_fig2", "Fig 2 latency/cost variation"),
    ("fig9_10", "benchmarks.bench_fig9_10", "Fig 9/10 cross-instance accuracy"),
    ("fig11", "benchmarks.bench_fig11", "Fig 11 batch-size predictor"),
    ("fig12", "benchmarks.bench_fig12", "Fig 12 poly order ablation"),
    ("tab2", "benchmarks.bench_tab2", "Table II joint vs separate"),
    ("fig13", "benchmarks.bench_fig13", "Fig 13 feature clustering"),
    ("tab3_4_5", "benchmarks.bench_tab3_4_5", "Tables III-V vs baselines"),
    ("tab6", "benchmarks.bench_tab6", "Table VI new devices"),
    ("grid", "benchmarks.bench_grid", "predict_grid vectorization speedup"),
    ("fit", "benchmarks.bench_fit", "Profet.fit vectorization speedup"),
    ("serve", "benchmarks.bench_serve", "fused predict_many vs predict loop"),
    ("transport", "benchmarks.bench_transport",
     "HTTP transport concurrent vs sequential clients"),
    ("bank", "benchmarks.bench_bank",
     "stacked ModelBank wave vs per-group dispatch"),
    ("calibrate", "benchmarks.bench_calibrate",
     "live calibration drift->refit->canary->promote recovery"),
    ("faults", "benchmarks.bench_faults",
     "fault-injected replay resilience floors (zero lost requests)"),
    ("shard", "benchmarks.bench_shard",
     "multi-worker sharded wave execution vs single-worker bank"),
    ("multihost", "benchmarks.bench_multihost",
     "TCP-loopback multi-host shard plane vs single-worker bank"),
    ("recovery", "benchmarks.bench_recovery",
     "self-healing worker recovery: post-adoption throughput restoration"),
    ("roofline", "benchmarks.bench_roofline", "Roofline table (dry-run)"),
    ("perf", "benchmarks.bench_perf", "Perf before/after (dry-run)"),
    ("serving", "benchmarks.bench_serve:run_engine",
     "Continuous vs wave batching (token engine)"),
    ("tpu_advisor", "benchmarks.bench_tpu_advisor", "TPU cross-chip advisor"),
]


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    only = set(argv)
    failures = 0
    print("benchmark,seconds,summary")
    for name, module, desc in BENCHES:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            import importlib
            mod_name, _, attr = module.partition(":")
            mod = importlib.import_module(mod_name)
            summary = getattr(mod, attr or "run")()
            dt = time.time() - t0
            pretty = " ".join(f"{k}={v:.3f}" if isinstance(v, float)
                              else f"{k}={v}" for k, v in summary.items())
            print(f"{name},{dt:.1f},{pretty}", flush=True)
        except Exception as e:  # pragma: no cover
            failures += 1
            import traceback
            traceback.print_exc()
            print(f"{name},FAILED,{type(e).__name__}: {e}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    sys.exit(main())
