"""Driver ``serve_open_loop``: requests sent on a fixed schedule.

Traffic parameters: ``rate_per_s`` (offered requests per second),
``targets_min`` / ``targets_max`` (targets per request, log-uniform),
``zipf_s`` (target popularity over all vertices), ``slo_ms`` and
``num_workers`` (the server's), ``traffic_seed``, ``check_batches`` and
``drain_s``.

The schedule: ``round(rate_per_s * seconds)`` requests whose sizes and
inter-arrival gaps (exponential, so Poisson arrivals) are one fixed
multiset drawn from ``traffic_seed``, put in an order drawn from
``--seed`` and scaled so the last request is due at ``--seconds``; every
seed offers the same work in another order. Targets are Zipf ranks drawn
from ``--seed``, mapped to vertices by a permutation fixed by
``traffic_seed``.

Set-up: the configuration's graph, the benchmark's weights, and the
server through ``repro.gnn.serve`` (default bucket ladder, every bucket
compiled by its warm-up). Window: one thread submits each request when it
is due; a request's latency runs from its due time to its result, and the
sender's lateness is logged apart. After the window every request is
waited for, ``drain_s`` at most; one that never comes counts in
``failed`` and makes the run not correct.

Check: a sample of micro-batches drawn from ``--seed`` (the one with the
most targets always in it); each one's sampled neighbourhood is held to
the graph; the sample goes through ``bench/reference.logits`` as one
batch (``bench/reference.stack_batches``), and every request in it is
compared with what its future returned.
"""
from __future__ import annotations

import time

import numpy as np

from bench import compare, graphs, reference, trace as tr


def schedule(traffic: dict, seconds: float, seed: int, num_vertices: int):
    """(due seconds from the window's start, target id arrays)."""
    n = max(1, int(round(float(traffic["rate_per_s"]) * seconds)))
    base = int(traffic["traffic_seed"])
    fixed = np.random.default_rng(np.random.SeedSequence((base, 1)))
    lo, hi = int(traffic["targets_min"]), int(traffic["targets_max"])
    sizes = np.minimum(np.exp(fixed.uniform(np.log(lo), np.log(hi + 1), n))
                       .astype(np.int64), hi)
    gaps = fixed.exponential(1.0, n)
    order = np.random.default_rng(np.random.SeedSequence((seed, 2)))
    sizes = sizes[order.permutation(n)]
    gaps = gaps[order.permutation(n)]
    due = np.cumsum(gaps) * (seconds / gaps.sum())
    ranks = zipf_ranks(float(traffic["zipf_s"]), num_vertices,
                       int(sizes.sum()), seed)
    vertex_of_rank = np.random.default_rng(np.random.SeedSequence(
        (base, 3))).permutation(num_vertices).astype(np.int32)
    ids = np.split(vertex_of_rank[ranks], np.cumsum(sizes)[:-1])
    return due, ids


def zipf_ranks(s: float, n: int, k: int, seed: int) -> np.ndarray:
    """``k`` ranks in [0, n) with P(rank r) proportional to (r + 1)^-s."""
    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -s)
    u = np.random.default_rng(np.random.SeedSequence((seed, 4))).random(k)
    return np.minimum(np.searchsorted(cdf, u * cdf[-1]), n - 1)


def offer(server, due, ids, seconds: float, drain_s: float):
    """Submit each request when it is due, then wait for every answer
    (``drain_s`` past the window at most). Returns the window's start and
    end, each request's completion time (nan if none), the sender's
    lateness, and the futures."""
    import jax
    done = np.full(len(due), np.nan)
    late = np.empty(len(due))
    futures = []

    def finisher(i):
        def cb(_):
            done[i] = time.perf_counter()
        return cb

    t_start = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench/window"):
        for i, (t_due, req) in enumerate(zip(due, ids)):
            wait = t_start + t_due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late[i] = time.perf_counter() - (t_start + t_due)
            fut = server.submit(req)
            fut.add_done_callback(finisher(i))
            futures.append(fut)
        deadline = t_start + seconds + drain_s
        for fut in futures:
            try:
                fut.result(timeout=max(0.0, deadline - time.perf_counter()))
            except Exception:  # counted as failed from the future itself
                pass
    return t_start, time.perf_counter(), done, late, futures


def latencies_ms(due, done, t_start, t_end) -> np.ndarray:
    """Due time to result; a request with no result counts to the end."""
    return 1e3 * (np.where(np.isnan(done), t_end, done) - (t_start + due))


class FlushRecorder:
    """Keeps, for every micro-batch the server flushes, its request index
    and its requests (ids and future)."""

    def __init__(self, server):
        self.server = server
        self.flush = server._flush
        self.batches = []

        def recorded(requests):
            rid = server._next_rid
            self.batches.append((rid, [(r.ids, r.future) for r in requests]))
            return self.flush(requests)

        server._flush = recorded


def run(run) -> dict:
    import jax
    from repro.core.serving import SERVE_EPOCH
    from repro.data.graphs import Graph
    from repro.gnn import serve

    from bench.drivers.train_epochs import model_config_from, plain_batch
    cfg, traffic = run.config, run.traffic
    m = cfg["model"]
    arrays, _ = graphs.load(
        cfg["dataset"], workers=int(cfg["host"]["num_sampler_workers"]))
    graph = Graph(arrays["indptr"], arrays["indices"], arrays["features"],
                  arrays["labels"], arrays["train_ids"],
                  int(cfg["dataset"]["num_classes"]), cfg["dataset"]["name"])
    model_cfg = model_config_from(cfg)
    params = reference.make_params(m, graph.features.shape[1],
                                   graph.num_classes, run.seed)
    params_np = jax.tree.map(np.asarray, params)
    due, ids = schedule(traffic, run.seconds, run.seed, graph.num_vertices)
    server = serve(model_cfg, graph=graph, params=params,
                   algorithm=cfg["algorithm"],
                   slo_ms=float(traffic["slo_ms"]),
                   num_workers=int(traffic["num_workers"]), seed=run.seed)
    run.log(phase="server", buckets=list(server.buckets))
    try:
        server.start()
        recorder = FlushRecorder(server)
        if run.trace:
            run.start_trace()
        t_start, t_end, done, late, futures = offer(
            server, due, ids, run.seconds, float(traffic["drain_s"]))
        if run.trace:
            jax.profiler.stop_trace()
        setup_s = t_start - run.t0
        mem = run.memory_peak_bytes()
        failed = [i for i, f in enumerate(futures)
                  if not f.done() or f.exception() is not None]
        latency_ms = latencies_ms(due, done, t_start, t_end)
        batches = list(recorder.batches)
        run.log(phase="generator", requests=len(due),
                lateness_p50_ms=1e3 * float(np.percentile(late, 50)),
                lateness_p99_ms=1e3 * float(np.percentile(late, 99)),
                lateness_max_ms=1e3 * float(late.max()))
        run.log(phase="window", requests=len(due), failed=len(failed),
                micro_batches=len(batches), setup_s=setup_s,
                wall_s=t_end - t_start,
                targets_per_batch=float(np.mean(
                    [sum(len(r[0]) for r in b[1]) for b in batches]))
                if batches else 0.0)
        sampler = server._sampler
        bucket_for = server.batcher.bucket_for
        rng = np.random.default_rng(np.random.SeedSequence((run.seed, 5)))
        k = min(int(traffic["check_batches"]), len(batches))
        pick = set(rng.choice(len(batches), k, replace=False).tolist())
        if batches:
            pick.add(int(np.argmax([sum(len(r[0]) for r in b[1])
                                    for b in batches])))
        check = []
        for j in sorted(pick):
            rid, reqs = batches[j]
            flat = np.concatenate([r[0] for r in reqs])
            padded = flat[np.arange(bucket_for(len(flat))) % len(flat)]
            mb = sampler.request_batch(SERVE_EPOCH, rid, padded)
            answers = [f.result() if f.done() and f.exception() is None
                       else None for _, f in reqs]
            check.append((plain_batch(mb), [len(r[0]) for r in reqs],
                          answers))
    finally:
        server.close()
    del server, params
    summary = None
    if run.trace:
        summary = tr.summarize(*tr.load_events(tr.find_xplane(
            run.trace_dir)), devices=run.chips)

    t_check = time.perf_counter()
    fanouts = list(m["fanouts"])[::-1]
    problems = [f"request {j}: never answered" for j in failed]
    worst = 0.0
    for b, _, _ in check:
        problems += reference.check_batch(arrays, fanouts, b)
    if check:
        union, starts = reference.stack_batches([b for b, _, _ in check])
        ref = reference.logits(m, params_np, union, np.asarray(
            arrays["features"][union["nodes"][0]]))
        for (_, lengths, answers), lo in zip(check, starts):
            for n, ans in zip(lengths, answers):
                if ans is not None:
                    worst = max(worst, compare.logit_gap(ans,
                                                         ref[lo:lo + n]))
                lo += n
    correct, checks = compare.judge(
        {"logit_gap": worst},
        compare.load_limits(run.bench_dir, run.cell["name"]), problems)
    for p in problems[:20]:
        run.log(problem=p)
    run.log(phase="check", logit_gap=worst, batches_checked=len(check),
            check_s=time.perf_counter() - t_check)
    return {"correct": correct, "attempted": len(due),
            "failed": len(failed), "checks": checks,
            "memory_peak_bytes": mem, "trace": summary,
            "record": {"micro_batches": len(batches),
                       "window_s": t_end - t_start},
            "end_to_end": {
                "serve_p50_ms": float(np.percentile(latency_ms, 50)),
                "serve_p95_ms": float(np.percentile(latency_ms, 95)),
                "setup_s": setup_s}}
