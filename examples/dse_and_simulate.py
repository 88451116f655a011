"""Paper Listing-1 workflow through the HitGNN high-level APIs: specify the
algorithm + model + platform metadata, run the DSE engine, then project
scalability to 16 accelerators (paper Fig. 8).

  PYTHONPATH=src python examples/dse_and_simulate.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

from repro.core.abstraction import HitGNN
from repro.data.graphs import scaled_dataset
from repro.configs.gnn import DATASETS, GNNModelConfig
from repro.core.simulator import scaling_curve, SimConfig


def main():
    ### Design phase (paper Listing 1) ###
    hit = HitGNN()
    hit.Graph_Partition("metis_like", p=4)
    hit.Feature_Storing("distdgl")
    hit.GNN_Computation("graphsage")
    hit.GNN_Parameters(L=2, hidden=[128], fanouts=(25, 10),
                       batch_targets=1024)
    hit.Platform_Metadata(num_devices=4)
    design = hit.Generate_Design(DATASETS["ogbn-products"], beta=0.8)
    f = design["fpga"]
    print(f"DSE (FPGA model): n={f['n']} agg PEs, m={f['m']} update PEs, "
          f"throughput={f['throughput']/1e6:.1f}M NVTPS "
          f"(dsp={f.get('dsp', 0):.0%} lut={f.get('lut', 0):.0%})")
    t = design["tpu"]
    print(f"DSE (TPU adaptation): row_block={t['row_block']} "
          f"feat_block={t['feat_block']} vmem={t['vmem']/2**20:.0f}MB")

    ### Runtime phase ###
    hit.LoadInputGraph(scaled_dataset("ogbn-products", scale=10))
    history = hit.Start_training(epochs=3, lr=5e-3)
    for i, m in enumerate(history):
        print(f"epoch {i}: loss={m['loss']:.3f} acc={m['acc']:.2f} "
              f"NVTPS={m['nvtps']:.0f}")
    hit.Save_model("/tmp/hitgnn_model.npz")

    ### Scalability projection (paper Fig. 8) ###
    cfg = GNNModelConfig("graphsage", 2, 128, (25, 10), 1024)
    print("\nscaling (simulator, paper platform constants):")
    for r in scaling_curve(cfg, DATASETS["ogbn-products"], 0.8,
                           SimConfig(), max_p=16)[::3]:
        bar = "#" * int(r["speedup"])
        print(f"  p={r['p']:2d} speedup={r['speedup']:5.2f} {bar}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
