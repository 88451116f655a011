"""The paper's core comparison: DistDGL vs PaGraph vs P3 synchronous GNN
training algorithms expressed in the HitGNN abstraction (Table 1), with the
two-stage scheduler + host-fetch DC optimization active, reporting the
metrics of paper §7.4 (epoch time, NVTPS, beta).

Each run is the paper's "handful of lines": one model config, one platform
config, and the algorithm name — ``repro.gnn.train`` derives the partition,
feature placement and schedule per Table 1.

  PYTHONPATH=src python examples/three_algorithms.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

from repro.data.graphs import scaled_dataset
from repro.configs.gnn import GNNModelConfig, PlatformConfig
from repro.gnn import train


def main():
    graph = scaled_dataset("reddit", scale=11)
    cfg = GNNModelConfig("graphsage", num_layers=2, hidden=64,
                         fanouts=(10, 5), batch_targets=256)
    platform = PlatformConfig(num_devices=4)
    print(f"{'algorithm':<10s}{'loss':>8s}{'acc':>7s}{'beta':>7s}"
          f"{'util':>7s}{'NVTPS':>10s}  feature-storing strategy")
    for algo, desc in (
            ("distdgl", "partition-owned rows (METIS-like)"),
            ("pagraph", "hot out-degree rows replicated"),
            ("p3", "feature-dimension slices (intra-layer MP)")):
        with train(cfg, platform, algorithm=algo, graph=graph, epochs=5,
                   lr=5e-3) as result:
            m = result.final
            print(f"{algo:<10s}{m['loss']:8.3f}{m['acc']:7.2f}"
                  f"{m['beta']:7.2f}{m['utilization']:7.2f}"
                  f"{m['nvtps']:10.0f}  {desc}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
