#!/usr/bin/env python3
"""On-chip smoke run of the GNN training path, at the paper's full width.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the data-parallel mesh on four

One chip: GraphSAGE (hidden 128, fanouts (25, 10), 1,024 targets per
batch) on ``scaled_dataset("ogbn-products", scale=19)`` (524,288 vertices,
100 features, 47 classes) trains one epoch through ``repro.gnn.train``
(DistDGL, ``PlatformConfig(num_devices=1, data_parallel=True)``, a spawned
sampler pool feeding the chip) with ``aggregate_backend="reference"`` and
one with ``"pallas_fused"`` from the same seed. The fused step must hold
Mosaic kernels (``tpu_custom_call``) and its epoch loss must agree with the
reference's within LOSS_RTOL. The fused-trained parameters then answer a
few requests through ``repro.gnn.serve``, on both backends, whose logits
must agree within LOGIT_RTOL.

Four chips (``--chips 4``): DistDGL and P3 each train one epoch on a
4-device mesh (shard_map step, per-device feature shards, gradient psum,
P3's all_to_all) and, from the same seed, with the single-device step on
one chip over the same four partitions. The losses must agree within
MESH_RTOL, the feature shard must sit on four distinct devices, and the
compiled mesh step must hold the collectives.

Every phase prints one line. The script exits non-zero, printing no
result, when a phase fails, a loss is not finite, or JAX finds no TPU;
otherwise its last line is ``{"ok": true, "device": {...}}``. The
compile cache follows ``repro.compile_cache``, so a second run compiles
less.
"""
import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# reference vs pallas_fused epoch-mean loss: TPU f32 matmuls in XLA run at
# the default (bf16-pass) precision, the Mosaic kernel's differently, so
# the trajectories agree to rounding, not bit for bit
LOSS_RTOL = 2e-2
# served logits of the two backends, relative to the largest |logit|
LOGIT_RTOL = 5e-2
# 4-device mesh vs the single-device step on the same partitions
MESH_RTOL = 1e-2

DATASET, SCALE = "ogbn-products", 19


class Fail(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise Fail(msg)


def tpu_device() -> dict:
    import jax
    devices = jax.devices()
    d = devices[0]
    check(d.platform == "tpu",
          f"no TPU: JAX found {len(devices)} {d.platform} device(s) "
          f"({d.device_kind}); this smoke runs on the chip only")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


class CompileClock:
    """Seconds JAX spends in backend compiles (persistent-cache lookups
    included) and the persistent-cache hits, from JAX's own events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def model_cfg(backend: str):
    from repro.configs.gnn import GNNModelConfig
    return GNNModelConfig("graphsage", num_layers=2, hidden=128,
                          fanouts=(25, 10), batch_targets=1024,
                          aggregate_backend=backend)


def train_epoch(graph, backend, platform, algorithm, clock, workers,
                seed=0):
    """One epoch through repro.gnn.train; returns (metrics line, result)."""
    from repro.gnn import train
    from repro.kernels.aggregate import resolve_interpret
    cfg = model_cfg(backend)
    check(not resolve_interpret(cfg.kernel_interpret),
          "Pallas would run in interpret mode")
    c0, t0 = clock.seconds, time.perf_counter()
    res = train(cfg, platform, algorithm=algorithm, graph=graph, epochs=1,
                seed=seed, num_sampler_workers=workers)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    m = res.final
    text = res.trainer.compiled_step_text()
    line = {"phase": "train", "algorithm": algorithm, "backend": backend,
            "devices": platform.num_devices,
            "mesh": platform.data_parallel, "loss": m["loss"],
            "acc": m["acc"], "steps": res.trainer.step_no,
            "batches": m["batches"], "sampler_workers": workers,
            "compile_s": compile_s, "wall_s": wall,
            "tpu_custom_call": text.count("tpu_custom_call"),
            "all_reduce": text.count("all-reduce("),
            "all_to_all": text.count("all-to-all(")}
    print(json.dumps(line), flush=True)
    check(math.isfinite(m["loss"]), f"non-finite loss: {line}")
    check(res.trainer.step_no > 0, f"no step ran: {line}")
    return line, res


def one_chip(graph, clock) -> None:
    from repro.configs.gnn import PlatformConfig
    from repro.gnn import serve
    platform = PlatformConfig(num_devices=1, data_parallel=True)
    ref, r_ref = train_epoch(graph, "reference", platform, "distdgl", clock,
                             workers=4)
    r_ref.close()
    fus, r_fus = train_epoch(graph, "pallas_fused", platform, "distdgl",
                             clock, workers=4)
    with r_fus:
        check(fus["tpu_custom_call"] > 0,
              "the pallas_fused step holds no Mosaic kernel")
        check(fus["steps"] == ref["steps"], "the epochs ran different steps")
        gap = abs(fus["loss"] - ref["loss"]) / ref["loss"]
        print(json.dumps({"phase": "loss_gap", "reference": ref["loss"],
                          "pallas_fused": fus["loss"], "rel_gap": gap,
                          "tolerance": LOSS_RTOL}), flush=True)
        check(gap <= LOSS_RTOL, f"loss gap {gap} > {LOSS_RTOL}")

        requests = [graph.train_ids[:5], graph.train_ids[5:25],
                    graph.train_ids[25:26]]
        t0 = time.perf_counter()
        with serve(model_cfg("reference"), graph=graph,
                   params=r_fus.params, warmup=False) as s_ref, \
                serve(model_cfg("pallas_fused"), graph=graph,
                      params=r_fus.params, warmup=False) as s_fus:
            worst = 0.0
            for ids in requests:
                a, b = s_ref.predict(ids), s_fus.predict(ids)
                check(a.shape == b.shape == (len(ids), graph.num_classes),
                      f"logits shape {a.shape} / {b.shape}")
                check(bool((abs(b) < float("inf")).all()),
                      "non-finite logits")
                scale = max(1.0, float(abs(a).max()))
                worst = max(worst, float(abs(a - b).max()) / scale)
        print(json.dumps({"phase": "serve", "requests": len(requests),
                          "targets": int(sum(len(r) for r in requests)),
                          "rel_logit_gap": worst, "tolerance": LOGIT_RTOL,
                          "wall_s": time.perf_counter() - t0}), flush=True)
        check(worst <= LOGIT_RTOL, f"logit gap {worst} > {LOGIT_RTOL}")


def four_chips(graph, clock) -> None:
    from repro.configs.gnn import PlatformConfig
    for algorithm in ("distdgl", "p3"):
        mesh, r_mesh = train_epoch(
            graph, "pallas_fused",
            PlatformConfig(num_devices=4, data_parallel=True), algorithm,
            clock, workers=8)
        with r_mesh:
            shard_devices = {s.device.id for s in
                             r_mesh.trainer._shard.addressable_shards}
        one, r_one = train_epoch(
            graph, "pallas_fused",
            PlatformConfig(num_devices=4, data_parallel=False), algorithm,
            clock, workers=8)
        r_one.close()
        gap = abs(mesh["loss"] - one["loss"]) / one["loss"]
        print(json.dumps({"phase": "mesh_check", "algorithm": algorithm,
                          "shard_devices": sorted(shard_devices),
                          "rel_loss_gap": gap, "tolerance": MESH_RTOL}),
              flush=True)
        check(len(shard_devices) == 4,
              f"feature shard on devices {sorted(shard_devices)}")
        check(mesh["all_reduce"] > 0, "mesh step holds no all-reduce")
        check(algorithm != "p3" or mesh["all_to_all"] > 0,
              "P3 mesh step holds no all-to-all")
        check(gap <= MESH_RTOL, f"mesh loss gap {gap} > {MESH_RTOL}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    try:
        from repro.compile_cache import enable_compile_cache
        from repro.data.graphs import scaled_dataset
    except ImportError as e:
        print(f"chip_smoke: FAIL: the repro package is not next to this "
              f"script ({e})", file=sys.stderr)
        return 1
    try:
        device = tpu_device()
        check(device["count"] >= args.chips,
              f"--chips {args.chips} but JAX sees {device['count']}")
        cache = enable_compile_cache()
        clock = CompileClock()
        t0 = time.perf_counter()
        graph = scaled_dataset(DATASET, scale=SCALE)
        print(json.dumps({"phase": "setup", "device": device,
                          "compile_cache": cache, "graph": graph.name,
                          "vertices": int(graph.num_vertices),
                          "edges": int(graph.num_edges),
                          "features": int(graph.features.shape[1]),
                          "classes": int(graph.num_classes),
                          "train_ids": len(graph.train_ids),
                          "wall_s": time.perf_counter() - t0}), flush=True)
        (four_chips if args.chips == 4 else one_chip)(graph, clock)
        print(json.dumps({"phase": "compile", "compile_s": clock.seconds,
                          "cache_hits": clock.cache_hits}), flush=True)
    except Fail as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
