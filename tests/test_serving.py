"""Request-driven serving runtime (core/serving.py + the repro.gnn.serve
facade).

Contracts under test:

* request batches are pure functions of (epoch, index, targets) — the
  serving RNG coordinates — so any process re-materializes them bitwise;
* pad_minibatch/slice_minibatch round-trip exactly (the pool ships every
  request batch at the codec's fixed geometry and the consumer slices the
  real prefix back out);
* the bucket ladder absorbs every request size: after one warmup trace
  per bucket the forward NEVER recompiles, whatever sizes arrive;
* the pool-backed runtime answers bitwise-identically to the in-process
  one (and, under injected faults, to the fault-free run — requests
  complete PAST the SLO, they never error and never change value);
* the MicroBatcher flushes on bucket-full or SLO pressure, never before.
"""
import numpy as np
import pytest

from repro.configs.gnn import FaultConfig, GNNModelConfig
from repro.core.sampler import (NeighborSampler, layer_capacities,
                                layer_capacities_for, pad_minibatch,
                                slice_minibatch)
from repro.core.serving import (MicroBatcher, ServeConfig, ServingRuntime,
                                bucket_ladder, closed_loop_load)
from repro.data.graphs import synthetic_graph

G = synthetic_graph(scale=8, edge_factor=5, feat_dim=8, num_classes=4)
CFG = GNNModelConfig("graphsage", num_layers=2, hidden=8, fanouts=(3, 2),
                     batch_targets=16)


def _params(cfg=CFG, seed=0):
    import jax

    from repro.gnn import models as gnn_models
    from repro.nn.param import materialize
    spec = gnn_models.param_spec(cfg, G.features.shape[1], G.num_classes)
    return materialize(spec, jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# bucket ladder
# ---------------------------------------------------------------------------

def test_bucket_ladder_default_geometric_and_capped():
    assert bucket_ladder(16) == (8, 16)
    assert bucket_ladder(1024) == (8, 32, 128, 512, 1024)
    assert bucket_ladder(8) == (8,)
    assert bucket_ladder(4) == (4,)


def test_bucket_ladder_explicit_validated():
    assert bucket_ladder(64, [16, 4, 16]) == (4, 16)
    with pytest.raises(ValueError):
        bucket_ladder(64, [])
    with pytest.raises(ValueError):
        bucket_ladder(64, [128])  # above batch_targets
    with pytest.raises(ValueError):
        bucket_ladder(64, [0])


# ---------------------------------------------------------------------------
# request batches: determinism + pad/slice round trip
# ---------------------------------------------------------------------------

def test_request_batch_pure_function_of_coordinates():
    s1 = NeighborSampler(G, CFG, G.train_ids, 0, seed=3)
    s2 = NeighborSampler(G, CFG, G.train_ids, 0, seed=3)
    tgt = np.asarray(G.train_ids[:5], np.int32)
    a = s1.request_batch(1 << 30, 7, tgt)
    b = s2.request_batch(1 << 30, 7, tgt)
    assert (a.targets == b.targets).all()
    for l in range(len(a.nodes)):
        assert (a.nodes[l] == b.nodes[l]).all()
    for l in range(len(a.edge_src)):
        assert (a.edge_src[l] == b.edge_src[l]).all()
        assert (a.edge_dst[l] == b.edge_dst[l]).all()
    # a different index is a different stream
    c = s1.request_batch(1 << 30, 8, tgt)
    assert not all(a.nodes[l].shape == c.nodes[l].shape
                   and (a.nodes[l] == c.nodes[l]).all()
                   for l in range(len(a.nodes)))


def test_request_batch_validates_target_count():
    s = NeighborSampler(G, CFG, G.train_ids, 0, seed=3)
    with pytest.raises(ValueError):
        s.request_batch(0, 0, np.asarray([], np.int32))
    with pytest.raises(ValueError):
        s.request_batch(0, 0, np.asarray(G.train_ids[:17], np.int32))


def test_pad_slice_round_trip_bitwise():
    s = NeighborSampler(G, CFG, G.train_ids, 0, seed=3)
    tgt = np.asarray(G.train_ids[:8], np.int32)
    mb = s.request_batch(5, 2, tgt)
    n_caps, e_caps = layer_capacities(CFG)
    padded = pad_minibatch(mb, n_caps, e_caps)
    assert len(padded.targets) == CFG.batch_targets
    assert not padded.node_mask[0][len(mb.nodes[0]):].any()
    b_caps = layer_capacities_for(8, CFG.fanouts)
    back = slice_minibatch(padded, *b_caps)
    assert (back.targets == mb.targets).all()
    assert (back.labels == mb.labels).all()
    for l in range(len(mb.nodes)):
        assert (back.nodes[l] == mb.nodes[l]).all()
        assert (back.node_mask[l] == mb.node_mask[l]).all()
    for l in range(len(mb.edge_src)):
        assert (back.edge_src[l] == mb.edge_src[l]).all()
        assert (back.edge_dst[l] == mb.edge_dst[l]).all()
        assert (back.edge_mask[l] == mb.edge_mask[l]).all()
        assert (back.self_idx[l] == mb.self_idx[l]).all()


# ---------------------------------------------------------------------------
# MicroBatcher policy
# ---------------------------------------------------------------------------

def test_microbatcher_bucket_for():
    mb = MicroBatcher([8, 32, 128], slo_s=0.05)
    assert mb.bucket_for(1) == 8
    assert mb.bucket_for(8) == 8
    assert mb.bucket_for(9) == 32
    assert mb.bucket_for(500) == 128  # oversized -> largest (caller chunks)


def test_microbatcher_flushes_when_largest_bucket_full():
    mb = MicroBatcher([4, 8], slo_s=10.0)
    mb.add("a", 4, deadline=1e9)
    assert not mb.due(now=0.0)  # huge SLO, not full: hold
    mb.add("b", 4, deadline=1e9)
    assert mb.due(now=0.0)
    assert mb.take() == ["a", "b"]
    assert mb.pending == 0


def test_microbatcher_flushes_on_slo_pressure():
    mb = MicroBatcher([8], slo_s=0.1, safety_frac=0.1)
    mb.observe(8, 0.02)
    mb.add("a", 1, deadline=100.0)
    # flush_at = deadline - est(0.02) - safety(0.01) = 99.97
    assert mb.flush_at() == pytest.approx(99.97)
    assert not mb.due(now=99.9)
    assert mb.due(now=99.98)


def test_microbatcher_take_leaves_overflow_pending():
    mb = MicroBatcher([4], slo_s=0.1)
    mb.add("a", 3, deadline=1.0)
    mb.add("b", 3, deadline=2.0)
    assert mb.take() == ["a"]  # b would overflow the 4-bucket
    assert mb.pending == 1
    assert mb.take() == ["b"]


def test_microbatcher_ewma_tracks_service_time():
    mb = MicroBatcher([8], slo_s=0.1)
    mb.observe(8, 0.10)
    mb.observe(8, 0.20)
    assert mb.estimate(8) == pytest.approx(0.7 * 0.10 + 0.3 * 0.20)


# ---------------------------------------------------------------------------
# the runtime
# ---------------------------------------------------------------------------

def test_runtime_predict_zero_steady_state_recompiles():
    params = _params()
    with ServingRuntime(G, CFG, params,
                        serve_cfg=ServeConfig(num_workers=0)) as rt:
        n = rt.warmup()
        assert n == len(rt.buckets)
        for m in (1, 3, 8, 11, 16):  # every bucket, odd sizes included
            out = rt.predict(np.asarray(G.train_ids[:m], np.int32))
            assert out.shape == (m, G.num_classes)
        big = np.asarray(G.train_ids[:23], np.int32)  # > largest bucket
        assert rt.predict(big).shape == (23, G.num_classes)
        assert rt.forward_compiles == n, "steady-state serving recompiled"


def test_runtime_predict_matches_ground_truth_forward():
    """predict() equals running the reference forward over the request
    batch directly — the frontend adds padding and plumbing, no math."""
    import jax

    from repro.core.trainer import batch_to_arrays
    from repro.gnn import models as gnn_models
    params = _params()
    with ServingRuntime(G, CFG, params,
                        serve_cfg=ServeConfig(num_workers=0)) as rt:
        ids = np.asarray(G.train_ids[:6], np.int32)
        got = rt.predict(ids)
        # ground truth: same RNG coordinates, bucket-8 cyclic pad
        s = NeighborSampler(G, CFG, G.train_ids, 0, seed=0)
        padded = ids[np.arange(8) % 6]
        mb = s.request_batch(1 << 30, rt._next_rid - 1, padded)
        feats = rt.store.gather(0, mb.nodes[0], mb.node_mask[0])
        logits = gnn_models.forward(CFG, params,
                                    batch_to_arrays(mb, feats))
        want = np.asarray(jax.block_until_ready(logits))[:6]
    assert (got == want).all()


@pytest.mark.parametrize("backend", ["pallas_edges", "pallas_fused"])
def test_runtime_kernel_backend_matches_reference(backend):
    """A kernel backend serves through its own Pallas path (bucket-shaped
    layouts built per micro-batch), equal to the reference backend's
    logits on the same request stream."""
    params = _params()
    ids = [np.asarray(G.train_ids[:m], np.int32) for m in (3, 11)]
    with ServingRuntime(G, CFG, params,
                        serve_cfg=ServeConfig(num_workers=0)) as rt:
        want = [rt.predict(i) for i in ids]
    cfg = GNNModelConfig("graphsage", num_layers=2, hidden=8,
                         fanouts=(3, 2), batch_targets=16,
                         aggregate_backend=backend)
    with ServingRuntime(G, cfg, params,
                        serve_cfg=ServeConfig(num_workers=0)) as rt:
        got = [rt.predict(i) for i in ids]
    for w, g in zip(want, got):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


def test_runtime_pool_path_bitwise_equals_in_process():
    params = _params()
    ids_a = np.asarray(G.train_ids[:5], np.int32)
    ids_b = np.asarray(G.train_ids[5:17], np.int32)
    with ServingRuntime(G, CFG, params,
                        serve_cfg=ServeConfig(num_workers=0)) as r0:
        want = [r0.predict(ids_a), r0.predict(ids_b)]
    with ServingRuntime(G, CFG, params,
                        serve_cfg=ServeConfig(num_workers=2)) as r2:
        got = [r2.predict(ids_a), r2.predict(ids_b)]
    for w, g in zip(want, got):
        assert (w == g).all()


def test_runtime_submit_futures_coalesce_and_match_predict_values():
    params = _params()
    with ServingRuntime(G, CFG, params,
                        serve_cfg=ServeConfig(num_workers=0,
                                              slo_ms=30.0)) as rt:
        rt.warmup()
        futs = [rt.submit([int(v)]) for v in G.train_ids[:6]]
        outs = [f.result(timeout=60.0) for f in futs]
        assert all(o.shape == (1, G.num_classes) for o in outs)
        stats = rt.stats()
        assert stats["completed"] == 6  # warmup batches are not requests
        assert rt.forward_compiles == len(rt.buckets)
        assert all(np.isfinite(o).all() for o in outs)


def test_closed_loop_load_reports_point():
    params = _params()
    with ServingRuntime(G, CFG, params,
                        serve_cfg=ServeConfig(num_workers=0)) as rt:
        rt.warmup()
        pt = closed_loop_load(rt, G.train_ids, clients=2,
                              requests_per_client=3, ids_per_request=2)
        assert pt["requests"] == 6
        assert pt["offered_rps"] > 0
        assert pt["p99_ms"] >= pt["p50_ms"] >= 0
        assert 0.0 <= pt["slo_miss_rate"] <= 1.0
        assert rt.forward_compiles == len(rt.buckets)


def test_predict_after_close_raises():
    rt = ServingRuntime(G, CFG, _params(),
                        serve_cfg=ServeConfig(num_workers=0))
    rt.close()
    with pytest.raises(RuntimeError):
        rt.predict(np.asarray([0], np.int32))
    rt.close()  # idempotent


# ---------------------------------------------------------------------------
# the facade
# ---------------------------------------------------------------------------

def test_serve_facade_materializes_params_and_warms_up():
    from repro.gnn import serve
    with serve(CFG, graph=G, params=None, num_workers=0,
               buckets=(4, 16)) as server:
        assert server.buckets == (4, 16)
        assert server.forward_compiles == 2  # warmed up
        out = server.predict(np.asarray(G.train_ids[:2], np.int32))
        assert out.shape == (2, G.num_classes)


def test_serve_facade_rejects_unknown_algorithm():
    from repro.gnn import serve
    with pytest.raises(ValueError):
        serve(CFG, graph=G, algorithm="nope")


# ---------------------------------------------------------------------------
# chaos: the request path under fault injection (satellite)
# ---------------------------------------------------------------------------

def _chaos_run(fault_cfg):
    """Same request sequence against a fault-free and a faulted runtime;
    returns (clean_logits, faulted_logits, faulted_stats)."""
    params = _params()
    reqs = [np.asarray(G.train_ids[i:i + 3], np.int32) for i in range(4)]
    with ServingRuntime(G, CFG, params,
                        serve_cfg=ServeConfig(num_workers=1)) as clean:
        want = [clean.predict(r) for r in reqs]
    with ServingRuntime(G, fault_cfg, params,
                        serve_cfg=ServeConfig(num_workers=1)) as rt:
        got = [rt.predict(r) for r in reqs]
        stats = rt.stats()
    return want, got, stats


def test_serving_survives_worker_kill_bitwise():
    """A killed sampler worker mid-request: the pool respawns and
    resubmits, the request completes (late, not lost), and every response
    is bitwise equal to the fault-free run."""
    cfg = GNNModelConfig("graphsage", num_layers=2, hidden=8,
                         fanouts=(3, 2), batch_targets=16,
                         fault=FaultConfig(fault_spec="kill#1"))
    want, got, stats = _chaos_run(cfg)
    for w, g in zip(want, got):
        assert (w == g).all()
    assert stats["pool"]["respawns"] == 1
    assert stats["completed"] == len(want)  # every request completed
    assert not stats["pool_degraded"]


def test_serving_survives_straggler_with_speculation_bitwise():
    """A hung worker mid-request: speculation re-executes on the healthy
    path; responses stay bitwise equal and no request errors."""
    cfg = GNNModelConfig("graphsage", num_layers=2, hidden=8,
                         fanouts=(3, 2), batch_targets=16,
                         fault=FaultConfig(fault_spec="hang:0.8#1",
                                           straggler_timeout_s=0.2))
    want, got, stats = _chaos_run(cfg)
    for w, g in zip(want, got):
        assert (w == g).all()
    assert stats["pool"]["speculative"] >= 1
    assert not stats["pool_degraded"]
