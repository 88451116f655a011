#!/usr/bin/env python3
"""The control of a cell's ``correct``: the reference in bfloat16 in the
program's place, read by the same comparison, on several seeds.

    python bench/control.py --workload sage-products.train --seeds 1,2,3

For a training cell, each seed builds the batches the cell's run checks
(the same targets from ``--seed``, the trainer's sampler at the same
coordinates) and runs ``bench/reference.train_steps`` in float32 at
``highest`` precision and in bfloat16; the numbers are those
``bench/compare.training_numbers`` gives the bfloat16 steps against the
float32 ones. For a serving cell, each seed packs the cell's first
requests into micro-batches of about ``--pack`` targets, samples them as
the server does, and compares ``bench/reference.logits`` in bfloat16 with
float32 by ``bench/compare.logit_gap``, all micro-batches in one pass
(``bench/reference.stack_batches``) as the cell's run checks them. One
JSON line per seed, with the readings of the faults planted in the
reference put in the program's place (training: the loss over half of
each batch). A limit has to fail this control. The benchmark's own runs
do not run it; it runs on the chip, at the cell's size.
"""
import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import compare, graphs, reference  # noqa: E402


def _leaves(tree):
    import jax
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def training_control(config: dict, traffic: dict, seed: int,
                     arrays: dict) -> dict:
    import jax.numpy as jnp
    from repro.core.sampler import NeighborSampler
    from repro.data.graphs import Graph

    from bench.drivers.train_epochs import model_config_from, plain_batch
    m, opt = config["model"], config["optimizer"]
    per_epoch = int(traffic["batches_per_device"]) * int(m["batch_targets"])
    rng = np.random.default_rng(np.random.SeedSequence((seed, 1)))
    targets = np.sort(rng.choice(arrays["train_ids"], per_epoch,
                                 replace=False)).astype(np.int32)
    graph = Graph(arrays["indptr"], arrays["indices"], arrays["features"],
                  arrays["labels"], targets,
                  int(config["dataset"]["num_classes"]))
    sampler = NeighborSampler(graph, model_config_from(config), targets, 0,
                              seed)
    steps = [[plain_batch(sampler.batch_at(1, i))]
             for i in range(int(traffic["check_steps"]))]
    feats = [[np.asarray(arrays["features"][b["nodes"][0]]) for b in g]
             for g in steps]
    f_in = arrays["features"].shape[1]
    params0 = reference.make_params(
        m, f_in, int(config["dataset"]["num_classes"]), seed)
    ref = reference.train_steps(m, params0, steps, feats, opt)
    low = reference.train_steps(m, params0, steps, feats, opt,
                                dtype=jnp.bfloat16)
    half = reference.train_steps(m, params0, steps, feats, opt,
                                 target_share=0.5)

    def numbers(run):
        return compare.training_numbers(
            run["loss"], ref["loss"], _leaves(run["grad1"]),
            _leaves(ref["grad1"]), _leaves(params0),
            _leaves(run["params"][-1]), _leaves(ref["params"][-1]))

    return numbers(low), {"half_batch": numbers(half)}


def serving_control(config: dict, traffic: dict, seed: int, arrays: dict,
                    pack: int, batches: int) -> dict:
    import jax.numpy as jnp
    from repro.core.sampler import NeighborSampler
    from repro.core.serving import SERVE_EPOCH, bucket_ladder
    from repro.data.graphs import Graph

    from bench.drivers.serve_open_loop import schedule
    from bench.drivers.train_epochs import model_config_from, plain_batch
    m = config["model"]
    graph = Graph(arrays["indptr"], arrays["indices"], arrays["features"],
                  arrays["labels"], arrays["train_ids"],
                  int(config["dataset"]["num_classes"]))
    sampler = NeighborSampler(graph, model_config_from(config),
                              graph.train_ids, 0, seed)
    ladder = bucket_ladder(int(m["batch_targets"]))
    _, ids = schedule(traffic, 30.0, seed, graph.num_vertices)
    params = reference.make_params(m, graph.features.shape[1],
                                   graph.num_classes, seed)
    sampled, counts, lo = [], [], 0
    for rid in range(batches):
        group, n = [], 0
        while lo < len(ids) and (not group or n + len(ids[lo]) <= pack):
            group.append(ids[lo])
            n += len(ids[lo])
            lo += 1
        flat = np.concatenate(group)
        bucket = next(b for b in ladder if b >= len(flat))
        mb = sampler.request_batch(SERVE_EPOCH, rid,
                                   flat[np.arange(bucket) % len(flat)])
        sampled.append(plain_batch(mb))
        counts.append(len(flat))
    union, starts = reference.stack_batches(sampled)
    feats = np.asarray(arrays["features"][union["nodes"][0]])
    ref = reference.logits(m, params, union, feats)
    low = reference.logits(m, params, union, feats, jnp.bfloat16)
    worst = max(compare.logit_gap(low[a:a + n], ref[a:a + n])
                for a, n in zip(starts, counts))
    return {"logit_gap": worst}, {}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--pack", type=int, default=64)
    ap.add_argument("--batches", type=int, default=64)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(c for c in bench["workloads"] if c["name"] == args.workload)
    config = json.loads((BENCH / "configs"
                         / f"{cell['config']}.json").read_text())
    traffic = json.loads((BENCH / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: FAIL: no TPU", file=sys.stderr)
        return 1
    arrays, _ = graphs.load(config["dataset"],
                            workers=int(config["host"]["num_sampler_workers"]))
    limits = compare.load_limits(BENCH, cell["name"])
    for seed in (int(s) for s in args.seeds.split(",")):
        if traffic["driver"] == "serve_open_loop":
            numbers = serving_control(config, traffic, seed, arrays,
                                      args.pack, args.batches)
        else:
            numbers = training_control(config, traffic, seed, arrays)
        numbers, faults = numbers
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "control": numbers, "faults": faults,
                          "limits": limits,
                          "fails": compare.judge(numbers, limits)[0]
                          is False}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
