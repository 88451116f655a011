#!/usr/bin/env python3
"""Find the serving knee once: offer a serving cell's traffic at several
fixed rates to one server and print, per rate, the latency tails and
whether the backlog grew.

    python bench/sweep.py --workload sage-products.serve-zipf \\
        --rates 50,100,200,400 --seconds 15 --seed 3

Each rate is one open-loop window (``bench/drivers/serve_open_loop``) of
``--seconds``, the cell's own mix at that rate. The backlog grows where
the second half of the window's requests waits clearly longer than the
first half. The highest rate with p95 within the SLO and no growing
backlog is the knee; the cell's traffic file offers 0.8 of it. The
benchmark's own runs do not run this.
"""
import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import graphs, reference  # noqa: E402
from bench.drivers import serve_open_loop as sol  # noqa: E402
from bench.drivers.train_epochs import model_config_from  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(c for c in bench["workloads"] if c["name"] == args.workload)
    cfg = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                     .read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: FAIL: no TPU", file=sys.stderr)
        return 1
    from repro.data.graphs import Graph
    from repro.gnn import serve
    m = cfg["model"]
    arrays, _ = graphs.load(cfg["dataset"],
                            workers=int(cfg["host"]["num_sampler_workers"]))
    graph = Graph(arrays["indptr"], arrays["indices"], arrays["features"],
                  arrays["labels"], arrays["train_ids"],
                  int(cfg["dataset"]["num_classes"]), cfg["dataset"]["name"])
    params = reference.make_params(m, graph.features.shape[1],
                                   graph.num_classes, args.seed)
    slo = float(traffic["slo_ms"])
    with serve(model_config_from(cfg), graph=graph, params=params,
               algorithm=cfg["algorithm"], slo_ms=slo,
               num_workers=int(traffic["num_workers"]),
               seed=args.seed) as server:
        server.start()
        for rate in (float(r) for r in args.rates.split(",")):
            due, ids = sol.schedule(dict(traffic, rate_per_s=rate),
                                    args.seconds, args.seed,
                                    graph.num_vertices)
            t0, t1, done, late, futures = sol.offer(
                server, due, ids, args.seconds, float(traffic["drain_s"]))
            lat = sol.latencies_ms(due, done, t0, t1)
            half = len(lat) // 2
            first, second = float(np.mean(lat[:half])), float(
                np.mean(lat[half:]))
            p95 = float(np.percentile(lat, 95))
            print(json.dumps({
                "rate_per_s": rate, "requests": len(lat),
                "failed": int(np.isnan(done).sum()),
                "p50_ms": float(np.percentile(lat, 50)), "p95_ms": p95,
                "p99_ms": float(np.percentile(lat, 99)),
                "slo_met_share": float(np.mean(lat <= slo)),
                "mean_ms_first_half": first, "mean_ms_second_half": second,
                "backlog_grows": second > 1.5 * first + 5.0,
                "sustained": p95 <= slo and not second > 1.5 * first + 5.0,
                "lateness_p99_ms": 1e3 * float(np.percentile(late, 99))}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
