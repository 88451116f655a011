"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (one per measurement).
``--full`` uses paper-scale configs where feasible on CPU.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only dse,ablation,...]
"""
import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    quick = not args.full

    rows = []

    def report(name, us_per_call, derived=""):
        rows.append((name, us_per_call, derived))
        print(f"{name},{us_per_call:.3f},{derived}", flush=True)

    from benchmarks import (bench_dse, bench_cross_platform, bench_ablation,
                            bench_scalability, bench_kernels, bench_pipeline,
                            bench_roofline, bench_serve)
    suites = {
        "dse": lambda: bench_dse.run(report),
        "cross_platform": lambda: bench_cross_platform.run(report, quick),
        "ablation": lambda: bench_ablation.run(report, quick),
        "scalability": lambda: bench_scalability.run(report, quick),
        "kernels": lambda: bench_kernels.run(report, quick),
        "pipeline": lambda: bench_pipeline.run(report, quick),
        "roofline": lambda: bench_roofline.run(report, quick),
        "serve": lambda: bench_serve.run(report, quick),
    }
    only = [s for s in args.only.split(",") if s]
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if only and name not in only:
            continue
        print(f"# --- {name} ---", flush=True)
        t0 = time.time()
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            report(f"{name}_ERROR", -1.0, f"{type(e).__name__}: {e}")
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
