"""The training feed's counters and spans, and the process's compile count.

Every stage of the host feed is timed into a counter and, over the same
interval, a profiler span of the same name (``core/pipeline.timed``): the
prefetch thread's wait for the sampling service (``feed/pool_wait``), the
ring decode (``feed/decode``), the assembly (``feed/assemble``), the main
thread's dispatch (``step/dispatch``); the sampler workers' stages ride
back with their results and are counted once per delivered batch.
"""
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.compile_cache import compile_counter
from repro.configs.gnn import GNNModelConfig
from repro.core.pipeline import PipelineStats, timed
from repro.core.sampler_pool import SamplerPool
from repro.core.trainer import SyncGNNTrainer
from repro.data.graphs import synthetic_graph

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import trace as tr  # noqa: E402

G = synthetic_graph(scale=8, edge_factor=5, feat_dim=8, num_classes=4)
CFG = GNNModelConfig("graphsage", num_layers=2, hidden=8, fanouts=(3, 2),
                     batch_targets=16, aggregate_backend="pallas_fused")

# four batches an epoch for the pool's fault injection to target
POOL_CFG = GNNModelConfig("graphsage", num_layers=2, hidden=8,
                          fanouts=(3, 2), batch_targets=4)

FEED_COUNTERS = ("host_source_wait_s", "host_produce_s", "host_dispatch_s",
                 "pool_sample_s", "pool_layout_s")


@pytest.mark.parametrize("workers", [0, 2])
def test_epoch_reports_every_feed_counter(workers):
    t0 = time.perf_counter()
    with SyncGNNTrainer(G, CFG, num_devices=1, seed=0,
                        num_sampler_workers=workers) as tr_:
        first = tr_.run_epoch()
        m = tr_.run_epoch()
        lifetime = time.perf_counter() - t0
        phases = dict(tr_.setup_phase_s)
    for k in FEED_COUNTERS:
        assert m[k] > 0, k
    if workers:
        # the ring decode and the workers' encode happen only with a pool
        assert m["host_decode_s"] > 0 and m["pool_ship_s"] > 0
        assert phases["pool_spawn"] > 0
        busy = sum(first[k] + m[k] for k in ("pool_sample_s",
                                              "pool_layout_s",
                                              "pool_ship_s"))
        assert busy <= workers * lifetime
    else:
        assert m["host_decode_s"] == 0 and m["pool_ship_s"] == 0
        assert "pool_spawn" not in phases
        # in-process sampling runs inside the assembly
        assert m["pool_sample_s"] + m["pool_layout_s"] <= m["host_produce_s"]
    # one thread's own time cannot exceed the epoch's wall time
    assert (m["host_source_wait_s"] + m["host_produce_s"]
            + m["host_decode_s"]) <= m["epoch_time_s"]
    assert m["host_dispatch_s"] <= m["epoch_time_s"]
    assert first["compiles"] > 0 and m["compiles"] == 0
    assert phases["partition"] > 0 and phases["store"] > 0
    assert phases["compile"] > 0


def test_speculative_duplicate_stage_seconds_count_once():
    counted = []
    with SamplerPool(G, POOL_CFG, [G.train_ids], seed=3, num_workers=2,
                     straggler_timeout_s=0.3,
                     fault_spec="hang:1.2@0.0.0") as pool:
        count = pool._count_stages

        def spy(*stage_s):
            counted.append(stage_s)
            count(*stage_s)

        pool._count_stages = spy
        outs = list(pool.map_tasks([(0, 0, i) for i in range(4)],
                                   fetch_timeout=120.0))
        launches = pool.stats["speculative"]
        assert launches >= 1
        deadline = time.time() + 8.0  # the hung worker's late copy
        while (pool.stats["duplicates_dropped"] < launches
               and time.time() < deadline):
            pool._drain_results()
            time.sleep(0.02)
        assert pool.stats["duplicates_dropped"] == launches
        stats = dict(pool.stats)
    # four batches delivered, four counted: the losing copy is not
    assert len(outs) == 4 and len(counted) == 4
    assert stats["sample_s"] == pytest.approx(sum(s[0] for s in counted))
    assert stats["sample_s"] > 0 and stats["ship_s"] > 0


def test_compile_counter_counts_new_shapes_only():
    counter = compile_counter()
    assert compile_counter() is counter  # registered once per process
    f = jax.jit(lambda x: x * 2.0 + 1.0)
    a3, b3, a5 = (jax.device_put(np.full(n, v, np.float32))
                  for n, v in ((3, 1.0), (3, 2.0), (5, 1.0)))
    n0, s0 = counter.compiles, counter.seconds
    f(a3).block_until_ready()
    assert counter.compiles == n0 + 1 and counter.seconds > s0
    f(b3).block_until_ready()
    assert counter.compiles == n0 + 1
    f(a5).block_until_ready()
    assert counter.compiles == n0 + 2


def test_timed_counts_self_time_and_nests():
    stats = PipelineStats()
    with timed("feed/pool_wait", stats, "source_wait_s", self_time=True,
               iteration=4):
        time.sleep(0.02)
        with timed("feed/decode", stats, "produce_s"):
            time.sleep(0.03)
    assert stats.produce_s >= 0.03
    assert 0.02 <= stats.source_wait_s < 0.03
    counters = {}
    with timed("feed/decode", counters, "decode_s"):
        pass
    assert counters["decode_s"] >= 0


def test_feed_spans_land_on_the_host_plane(tmp_path):
    with SyncGNNTrainer(G, CFG, num_devices=1, seed=0,
                        num_sampler_workers=2) as tr_:
        tr_.run_epoch()  # compile outside the trace
        with jax.profiler.trace(str(tmp_path)):
            with jax.profiler.TraceAnnotation("bench/window"):
                m = tr_.run_epoch()
    assert m["batches"] >= 2
    _, _, host = tr.load_events(tr.find_xplane(str(tmp_path)))
    names = [e.name for e in host]
    for span in ("feed/pool_wait", "feed/decode", "feed/assemble",
                 "step/dispatch", "feed/stages"):
        assert span in names, span
    assert names.count("step/dispatch") == m["iterations"]
    # a span and its counter time the same interval
    dispatch = sum(e.dur for e in host if e.name == "step/dispatch")
    assert dispatch == pytest.approx(m["host_dispatch_s"], rel=0.05,
                                     abs=2e-3)
