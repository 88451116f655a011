"""Quickstart: end-to-end synchronous GNN training (the paper's workload).

Trains a 2-layer GraphSAGE on a synthetic ogbn-products stand-in with the
DistDGL-style algorithm on 4 (simulated) devices, through the paper's
"handful of lines" surface: the user supplies the ALGORITHM, the MODEL and
the PLATFORM metadata — ``repro.gnn.train`` derives the whole host pipeline
(partition -> feature store -> sample -> two-stage schedule -> jit'd
synchronous step) from those three inputs.

  PYTHONPATH=src python examples/quickstart.py [--epochs 20]

Add ``--data-parallel`` (with XLA_FLAGS=--xla_force_host_platform_device_count=4
exported BEFORE launch) to run the devices as a real jax mesh.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

from repro.data.graphs import scaled_dataset
from repro.configs.gnn import GNNModelConfig, PlatformConfig
from repro.checkpoint.checkpointing import Checkpointer
from repro.gnn import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=20)
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--data-parallel", action="store_true")
    ap.add_argument("--ckpt", default="/tmp/hitgnn_ckpt")
    args = ap.parse_args()

    graph = scaled_dataset("ogbn-products", scale=11)
    print(f"graph: {graph.num_vertices} vertices, {graph.num_edges} edges, "
          f"{graph.features.shape[1]} features")

    # the paper's three user inputs: model, platform, algorithm
    cfg = GNNModelConfig("graphsage", num_layers=2, hidden=64,
                         fanouts=(10, 5), batch_targets=256)
    platform = PlatformConfig(num_devices=args.devices,
                              data_parallel=args.data_parallel)
    ckpt = Checkpointer(args.ckpt)

    def report(epoch, m):
        print(f"epoch {epoch:3d} loss={m['loss']:.3f} acc={m['acc']:.3f} "
              f"iters={m['iterations']} util={m['utilization']:.2f} "
              f"beta={m['beta']:.2f} NVTPS={m['nvtps']:.0f}")

    t0 = time.time()
    with train(cfg, platform, algorithm="distdgl", graph=graph,
               epochs=args.epochs, lr=5e-3, progress=report) as result:
        trainer = result.trainer
        ckpt.save(trainer.step_no, trainer.params, trainer.opt_state)
        ckpt.wait()
        print(f"done: {trainer.step_no} steps in {time.time()-t0:.1f}s; "
              f"checkpoints in {args.ckpt}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
