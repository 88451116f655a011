"""Driver ``train_epochs``: whole training epochs back to back.

Traffic parameters: ``batches_per_device`` (batches of the configuration's
``batch_targets`` per device per epoch), ``warmup_epochs`` and
``check_steps``.

Set-up: the configuration's graph (``bench/graphs.py``), an epoch's
targets drawn by ``--seed`` from its train split, the trainer through
``repro.gnn.train`` (``epochs=0``) on a ``data_parallel`` platform of the
cell's chips, the benchmark's weights (``bench/reference.make_params``)
put in place of the trainer's own, then the warm-up epochs through
``SyncGNNTrainer.run_epoch``. The first ``check_steps`` steps of the
warm-up are recorded as the step returns them (parameters, optimizer
state, loss).

Window: ``run_epoch`` again and again; no epoch starts once ``--seconds``
have passed. ``train_targets_per_s`` is the targets of the whole epochs
run over the wall time from the window's start to the end of its last
epoch.

Check: the recorded steps against ``bench/reference.train_steps`` over
the same sampled batches, after the batches themselves are held to the
graph (``bench/reference.check_batch``).
"""
from __future__ import annotations

import time

import numpy as np

from bench import compare, graphs, reference, trace as tr, work


class StepRecorder:
    """Keeps what the trainer's jitted step returns for its first ``n``
    calls; :meth:`restore` puts the step back untouched."""

    def __init__(self, trainer, n: int):
        self.trainer, self.n = trainer, n
        self.step = trainer._jit_step
        self.outs = []

        def recorded(*args):
            out = self.step(*args)
            if len(self.outs) < self.n:
                self.outs.append(out)
            return out

        trainer._jit_step = recorded

    def restore(self):
        self.trainer._jit_step = self.step
        return [jax_to_numpy(o) for o in self.outs]


def jax_to_numpy(tree):
    import jax
    return jax.tree.map(lambda x: np.asarray(x), tree)


def _leaves(tree):
    import jax
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def build_graph(run):
    from repro.data.graphs import Graph
    ds = run.config["dataset"]
    arrays, meta = graphs.load(
        ds, workers=int(run.config["host"]["num_sampler_workers"]))
    run.log(phase="graph", **{k: meta[k] for k in (
        "vertices", "directed_edges", "mean_in_degree", "isolated_share",
        "share_in_degree_ge_25", "generation_s")})
    return arrays, meta, Graph


# keys of a configuration's ``model`` block that are the benchmark's and
# not the program's: the precision the configuration states
BENCH_MODEL_KEYS = ("precision",)


def model_config_from(config: dict):
    """The program's model configuration for a benchmark configuration:
    every key of its ``model`` block but :data:`BENCH_MODEL_KEYS`, so a key
    the program does not know fails here (``TypeError``)."""
    from repro.configs.gnn import GNNModelConfig, HostConfig
    m = {k: v for k, v in config["model"].items()
         if k not in BENCH_MODEL_KEYS}
    return GNNModelConfig(**m, host=HostConfig(num_sampler_workers=int(
        config["host"]["num_sampler_workers"])))


def epoch_assignments(trainer):
    """The (partition, batch index) of every batch of one epoch, grouped
    by synchronous step, as the trainer schedules them."""
    from repro.core import scheduler as sched
    counts = [s.epoch_batches() for s in trainer.samplers]
    return [[(a.partition, a.batch_index) for a in group]
            for group in sched.iterations(sched.two_stage_schedule(counts))]


def plain_batch(mb) -> dict:
    return reference.as_batch(mb.nodes, mb.node_mask, mb.edge_src,
                              mb.edge_dst, mb.edge_mask, mb.self_idx,
                              mb.labels)


def run(run) -> dict:
    import jax
    from repro.configs.gnn import PlatformConfig
    from repro.gnn import train

    cfg, traffic = run.config, run.traffic
    m, opt = cfg["model"], cfg["optimizer"]
    arrays, meta, Graph = build_graph(run)
    chips = run.chips
    per_epoch = (int(traffic["batches_per_device"]) * int(m["batch_targets"])
                 * chips)
    rng = np.random.default_rng(np.random.SeedSequence((run.seed, 1)))
    targets = np.sort(rng.choice(arrays["train_ids"], per_epoch,
                                 replace=False)).astype(np.int32)
    graph = Graph(arrays["indptr"], arrays["indices"], arrays["features"],
                  arrays["labels"], targets,
                  int(cfg["dataset"]["num_classes"]), cfg["dataset"]["name"])
    model_cfg = model_config_from(cfg)
    result = train(model_cfg, PlatformConfig(num_devices=chips,
                                             data_parallel=True),
                   algorithm=cfg["algorithm"], graph=graph, epochs=0,
                   lr=float(opt["lr"]), seed=run.seed)
    trainer = result.trainer
    run.log(phase="trainer")
    try:
        f_in, n_cls = graph.features.shape[1], graph.num_classes
        params0 = reference.make_params(m, f_in, n_cls, run.seed)
        trainer.params = params0
        trainer.opt_state = trainer.optimizer.init(params0)
        params0 = jax_to_numpy(params0)
        n_check = int(traffic["check_steps"])
        recorder = StepRecorder(trainer, n_check)
        check_epoch = trainer.samplers[0].epoch + 1
        for _ in range(int(traffic["warmup_epochs"])):
            trainer.run_epoch()
        steps = recorder.restore()
        if len(steps) < n_check:
            raise RuntimeError(f"the warm-up ran {len(steps)} steps; "
                               f"{n_check} are checked")
        schedule = epoch_assignments(trainer)
        run.log(phase="warmup", steps=trainer.step_no)

        if run.trace:
            run.start_trace()
        window = []
        t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/window"):
            while time.perf_counter() - t_start < run.seconds:
                epoch = trainer.samplers[0].epoch + 1
                with jax.profiler.TraceAnnotation("bench/epoch"):
                    mt = trainer.run_epoch()
                window.append((epoch, mt))
        t_end = time.perf_counter()
        if run.trace:
            jax.profiler.stop_trace()
        setup_s = t_start - run.t0
        wall = t_end - t_start
        mem = run.memory_peak_bytes()
        batches = sum(int(mt["batches"]) for _, mt in window)
        run.log(phase="window", epochs=len(window), batches=batches,
                wall_s=wall, setup_s=setup_s,
                epoch_s=[mt["epoch_time_s"] for _, mt in window],
                host_wait_s=[mt["host_wait_s"] for _, mt in window],
                loss=[mt["loss"] for _, mt in window])
        record = {"window_s": wall, "epochs": len(window),
                  "batches": batches,
                  "host_wait_s": sum(mt["host_wait_s"] for _, mt in window),
                  "epoch_time_s": sum(mt["epoch_time_s"]
                                      for _, mt in window),
                  "model": m,
                  "dims": work.model_widths(m, f_in, n_cls)}
        if run.trace:
            # the window's real work, batch by batch, from the trainer's
            # pure batch_at (seed, partition, epoch, index)
            sizes = []
            for epoch, _ in window:
                for group in schedule:
                    for part, idx in group:
                        mb = trainer.samplers[part].batch_at(epoch, idx)
                        sizes.append(work.layer_sizes(
                            [int(x.sum()) for x in mb.node_mask],
                            [int(x.sum()) for x in mb.edge_mask]))
            record["batch_sizes"] = sizes
        fanouts = list(m["fanouts"])[::-1]  # bottom-up
        check = [[plain_batch(trainer.samplers[p].batch_at(check_epoch, i))
                  for p, i in group] for group in schedule[:n_check]]
    finally:
        result.close()
    del trainer, result
    summary = None
    if run.trace:
        summary = tr.summarize(*tr.load_events(tr.find_xplane(
            run.trace_dir)), devices=chips)

    # the check: batches held to the graph, then the reference's steps
    problems = [f"step {k + 1}: {p}" for k, group in enumerate(check)
                for b in group for p in reference.check_batch(
                    arrays, fanouts, b)]
    feats = [[np.asarray(arrays["features"][b["nodes"][0]]) for b in group]
             for group in check]
    ref = reference.train_steps(m, params0, check, feats, opt)
    b1 = float(opt["b1"])
    numbers = compare.training_numbers(
        prog_losses=[float(s[3]["loss"]) for s in steps],
        ref_losses=ref["loss"],
        prog_grad1=[x / (1 - b1) for x in _leaves(steps[0][1]["m"])],
        ref_grad1=_leaves(ref["grad1"]),
        params0=_leaves(params0),
        prog_last=_leaves(steps[-1][0]),
        ref_last=_leaves(ref["params"][-1]))
    correct, checks = compare.judge(
        numbers, compare.load_limits(run.bench_dir, run.cell["name"]),
        problems)
    for p in problems[:20]:
        run.log(problem=p)
    run.log(phase="check", **numbers)
    return {"correct": correct, "attempted": batches, "failed": 0,
            "checks": checks, "memory_peak_bytes": mem, "trace": summary,
            "record": record,
            "end_to_end": {"train_targets_per_s": len(window) * per_epoch
                           / wall,
                           "setup_s": setup_s}}
