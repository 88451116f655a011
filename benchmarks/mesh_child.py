"""Mesh-scaling child: NVTPS vs simulated-device-count, in a fresh process.

``XLA_FLAGS=--xla_force_host_platform_device_count=N`` must be set BEFORE
jax is imported, so the device-count sweep cannot run inside an
already-initialized trainer/bench process — this script is the subprocess
both ``benchmarks/bench_pipeline.py`` (the ``mesh_scaling`` section) and
``tests/test_mesh.py`` spawn. It forces the device count itself from the
largest requested count, trains the same workload per count on a real
shard_map mesh, and prints one JSON object on stdout:

  {"nvtps": {"1": ..., "2": ..., "4": ...},       # best-of-rounds
   "losses": {"1": [per-epoch], ...},
   "vmap_equal": true,                            # mesh vs vmap step
   "iterations": {"1": ..., ...}}

Usage:
  PYTHONPATH=src python benchmarks/mesh_child.py \
      --device-counts 1,2,4 --epochs 3 --rounds 3 --scale 10
"""
import argparse
import json
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--device-counts", default="1,2,4")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--scale", type=int, default=10)
    ap.add_argument("--feat-dim", type=int, default=32)
    ap.add_argument("--batch-targets", type=int, default=64)
    ap.add_argument("--algorithm", default="distdgl")
    ap.add_argument("--check-vmap", action="store_true")
    args = ap.parse_args()
    counts = [int(c) for c in args.device_counts.split(",")]

    # must precede the first jax import anywhere in this process. The
    # simulated devices are CPU devices: on an accelerator host the parent
    # bench process already holds the chip, which one process owns at a
    # time, so the child never asks for it
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={max(counts)} "
        + os.environ.get("XLA_FLAGS", ""))
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

    from repro.configs.gnn import GNNModelConfig, PlatformConfig
    from repro.data.graphs import synthetic_graph
    from repro.gnn import train

    graph = synthetic_graph(scale=args.scale, edge_factor=8,
                            feat_dim=args.feat_dim, num_classes=8, seed=0)
    cfg = GNNModelConfig("graphsage", num_layers=2, hidden=32,
                         fanouts=(5, 5), batch_targets=args.batch_targets)

    out = {"nvtps": {}, "losses": {}, "iterations": {}, "device_counts": counts}
    for p in counts:
        platform = PlatformConfig(num_devices=p, data_parallel=True)
        # loss trajectory (one run, fixed seed) — the loss-equivalence data
        res = train(cfg, platform, algorithm=args.algorithm, graph=graph,
                    epochs=args.epochs, seed=0)
        out["losses"][str(p)] = [m["loss"] for m in res.epochs]
        out["iterations"][str(p)] = res.final["iterations"]
        # NVTPS: epoch 0 above compiled the step; per-round fresh epochs on
        # the SAME trainer measure steady-state dispatch+compute. Best of
        # rounds — the scaling signal on a timeshared host is the fastest
        # round, not the mean of noisy ones.
        best = 0.0
        for _ in range(args.rounds):
            best = max(best, res.trainer.run_epoch()["nvtps"])
        out["nvtps"][str(p)] = best
        res.close()

    if args.check_vmap:
        # the mesh step must train equivalently to the single-device vmap
        # step at the same device count (it is bitwise on CPU, but the
        # contract we pin is allclose)
        p = max(counts)
        platform = PlatformConfig(num_devices=p, data_parallel=True)
        mesh_res = train(cfg, platform, algorithm=args.algorithm,
                         graph=graph, epochs=args.epochs, seed=0)
        vmap_res = train(cfg, PlatformConfig(num_devices=p),
                         algorithm=args.algorithm, graph=graph,
                         epochs=args.epochs, seed=0)
        ml = [m["loss"] for m in mesh_res.epochs]
        vl = [m["loss"] for m in vmap_res.epochs]
        out["vmap_equal"] = all(
            abs(a - b) <= 1e-4 * max(abs(b), 1.0) for a, b in zip(ml, vl))
        out["vmap_losses"] = vl
        mesh_res.close()
        vmap_res.close()

    print(json.dumps(out))


if __name__ == "__main__":
    main()
