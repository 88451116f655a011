"""Multi-process sampling service (core/sampler_pool.py).

Covers the PR's contracts: (1) the shared-memory graph store is a zero-copy
attach with owner-only unlink; (2) a SamplerPool worker materializes batches
and stage-2b layouts BIT-IDENTICAL to the in-process sampler for the same
(partition, epoch, index) coordinates, delivered in submission order through
the reorder buffer; (3) worker exceptions re-raise in the consumer with the
worker's traceback attached, and shutdown releases/unlinks every shared
segment on error paths; (4) training with workers=N is bit-identical to
workers=0 per seed — batch order, contents, and final model parameters —
including zero-edge layers and the last ragged batch.
"""
import numpy as np
import pytest
from multiprocessing import shared_memory

from repro.configs.gnn import GNNModelConfig
from repro.core.pipeline import ReorderBuffer
from repro.core.sampler import NeighborSampler
from repro.core.sampler_pool import SamplerPool
from repro.data.graphs import Graph, build_graph, synthetic_graph
from repro.kernels.layout import block_capacities, build_layer_layouts

G = synthetic_graph(scale=8, edge_factor=5, feat_dim=8, num_classes=4)
CFG = GNNModelConfig("graphsage", num_layers=2, hidden=8, fanouts=(3, 2),
                     batch_targets=16)


def _segment_names(pool):
    names = [a.name for a in pool._shared.spec.arrays.values()]
    if pool._ring is not None:
        names.append(pool._ring.name)
    return names


def _assert_all_unlinked(names):
    for name in names:
        with pytest.raises(FileNotFoundError):
            shared_memory.SharedMemory(name=name)


# ---------------------------------------------------------------------------
# shared-memory graph store
# ---------------------------------------------------------------------------

def test_shared_graph_roundtrip_zero_copy_and_unlink():
    sg = G.to_shared()
    g2 = Graph.from_shared(sg.spec)
    assert (g2.indptr == G.indptr).all()
    assert (g2.indices == G.indices).all()
    assert (g2.features == G.features).all()
    assert (g2.labels == G.labels).all()
    assert (g2.train_ids == G.train_ids).all()
    assert g2.num_classes == G.num_classes and g2.name == G.name
    # zero-copy: a second attachment sees writes through the first
    g3 = Graph.from_shared(sg.spec)
    g2.features[0, 0] = 42.0
    assert g3.features[0, 0] == 42.0
    names = [a.name for a in sg.spec.arrays.values()]
    del g2, g3
    sg.close()
    _assert_all_unlinked(names)
    sg.close()  # idempotent


def test_shared_graph_context_manager_unlinks_on_error():
    with pytest.raises(RuntimeError, match="boom"):
        with G.to_shared() as sg:
            names = [a.name for a in sg.spec.arrays.values()]
            raise RuntimeError("boom")
    _assert_all_unlinked(names)


# ---------------------------------------------------------------------------
# reorder buffer
# ---------------------------------------------------------------------------

def test_reorder_buffer_orders_out_of_order_completions():
    rob = ReorderBuffer()
    rob.put(2, "c")
    rob.put(0, "a")
    assert rob.pop() == "a"
    assert rob.pop() is None  # seq 1 not arrived
    rob.put(1, "b")
    assert rob.pop() == "b"
    assert rob.pop() == "c"
    assert len(rob) == 0


def test_reorder_buffer_handles_none_items():
    """A legitimately-None item must advance the sequence, not wedge it."""
    rob = ReorderBuffer()
    rob.put(0, None)
    rob.put(1, "b")
    assert rob.pop() is None and len(rob) == 1
    assert rob.pop() == "b"


def test_reorder_buffer_drops_duplicates():
    """Speculative resubmission means a task can legitimately complete
    twice: the first result wins, the loser is dropped (False), and stale
    completions of already-consumed sequence numbers are dropped too."""
    rob = ReorderBuffer()
    assert rob.put(0, "a") is True
    assert rob.put(0, "again") is False  # pending duplicate
    assert rob.pop() == "a"
    assert rob.put(0, "stale") is False  # already consumed
    assert rob.pop() is None and rob.next_seq == 1


# ---------------------------------------------------------------------------
# pool == in-process sampler, bit for bit
# ---------------------------------------------------------------------------

def test_pool_batches_and_layouts_bit_identical_to_inprocess():
    caps = block_capacities(CFG)
    ref = NeighborSampler(G, CFG, G.train_ids, 0, seed=3)
    n_b = ref.epoch_batches()
    # interleave epochs and include the last (ragged) batch of each epoch
    coords = [(0, 0), (0, n_b - 1), (1, 0), (0, 1), (1, n_b - 1)]
    with SamplerPool(G, CFG, [G.train_ids], seed=3, num_workers=2,
                     agg_kind="mean", blk_caps=caps) as pool:
        outs = list(pool.map_tasks([(0, e, i) for e, i in coords]))
    for (e, i), out in zip(coords, outs):
        want = ref.batch_at(e, i)
        mb = out["minibatch"]
        assert mb.partition_id == 0 and mb.seq_no == i
        assert (mb.targets == want.targets).all()
        assert (mb.labels == want.labels).all()
        for l in range(CFG.num_layers):
            for f in ("nodes", "node_mask", "edge_src", "edge_dst",
                      "edge_mask", "self_idx"):
                got = getattr(mb, f)[l]
                exp = getattr(want, f)[l]
                assert (got == exp).all(), (f, l, e, i)
        assert (mb.nodes[-1] == want.nodes[-1]).all()
        assert out["load"] == want.work_estimate()
        want_layout = build_layer_layouts(want.edge_src, want.edge_dst,
                                          want.edge_mask, caps, "mean")
        for k, layers in want_layout.items():
            for l, exp in enumerate(layers):
                assert (out["layout"][k][l] == exp).all(), (k, l)


def test_pool_results_arrive_in_submission_order():
    with SamplerPool(G, CFG, [G.train_ids], seed=0, num_workers=2) as pool:
        tasks = [(0, i % 3, i % 2) for i in range(24)]
        outs = list(pool.map_tasks(tasks))
    assert [o["minibatch"].seq_no for o in outs] == [i % 2 for i in range(24)]


def test_pool_without_layout_caps_ships_no_layout():
    with SamplerPool(G, CFG, [G.train_ids], seed=0, num_workers=1) as pool:
        out = next(pool.map_tasks([(0, 0, 0)]))
    assert out["layout"] is None


# ---------------------------------------------------------------------------
# failure paths: worker exceptions, shutdown, shared-memory release
# ---------------------------------------------------------------------------

def test_worker_error_reraises_with_worker_traceback():
    with SamplerPool(G, CFG, [G.train_ids], seed=0, num_workers=1) as pool:
        names = _segment_names(pool)
        pool.submit(5, 0, 0)  # partition 5 does not exist
        with pytest.raises(IndexError) as ei:
            pool.fetch()
        attached = (getattr(ei.value, "__notes__", None)
                    or [getattr(ei.value, "sampler_worker_traceback", "")])
        joined = "\n".join(attached)
        assert "Traceback" in joined and "_worker_main" in joined
        # the pool stays serviceable after a task-level error
        out = next(pool.map_tasks([(0, 0, 0)]))
        assert out["minibatch"].seq_no == 0
    _assert_all_unlinked(names)


def test_pool_context_manager_unlinks_on_consumer_exception():
    with pytest.raises(KeyboardInterrupt):
        with SamplerPool(G, CFG, [G.train_ids], seed=0,
                         num_workers=1) as pool:
            names = _segment_names(pool)
            next(pool.map_tasks([(0, 0, 0)]))
            raise KeyboardInterrupt  # ctrl-C mid-epoch
    _assert_all_unlinked(names)


def test_pools_can_share_one_graph_store():
    """Pools given a borrowed SharedGraph reuse its segments and never
    unlink them; the owner's close still does."""
    sg = G.to_shared()
    names = [a.name for a in sg.spec.arrays.values()]
    with SamplerPool(G, CFG, [G.train_ids], seed=0, num_workers=1,
                     shared=sg) as p1:
        out1 = next(p1.map_tasks([(0, 0, 0)]))
    # segments survive the borrowing pool's close
    for name in names:
        shared_memory.SharedMemory(name=name).close()
    with SamplerPool(G, CFG, [G.train_ids], seed=0, num_workers=1,
                     shared=sg) as p2:
        out2 = next(p2.map_tasks([(0, 0, 0)]))
    assert (out1["minibatch"].targets == out2["minibatch"].targets).all()
    sg.close()
    _assert_all_unlinked(names)


def test_pool_close_is_idempotent_and_rejects_submit():
    pool = SamplerPool(G, CFG, [G.train_ids], seed=0, num_workers=1)
    names = _segment_names(pool)
    pool.close()
    pool.close()
    _assert_all_unlinked(names)
    with pytest.raises(RuntimeError, match="closed"):
        pool.submit(0, 0, 0)


# ---------------------------------------------------------------------------
# trainer integration: workers=N epochs bit-identical to workers=0
# ---------------------------------------------------------------------------

def _zero_edge_graph():
    """All train vertices are isolated: every sampled layer has ZERO edges.
    |train| = 48 with batch_targets=16*3 -> also exercises epoch tails."""
    rng = np.random.default_rng(0)
    edges = np.stack([rng.integers(0, 64, 600),
                      rng.integers(0, 64, 600)], axis=1)
    g = build_graph(edges, 110, feat_dim=8, num_classes=4, rng=rng)
    g.train_ids = np.arange(64, 110, dtype=np.int32)  # isolated vertices
    return g


@pytest.mark.parametrize("seed", [0, 3])
def test_training_with_workers_bit_identical_to_inprocess(seed):
    """The property the whole service rests on: same seed => workers=N and
    workers=0 produce the same batch stream (order AND contents), hence the
    same losses and BIT-IDENTICAL final parameters. |train_ids| is not a
    multiple of batch_targets, so every epoch ends in a ragged batch."""
    import jax
    from repro.core.trainer import SyncGNNTrainer
    assert len(G.train_ids) % CFG.batch_targets != 0
    t_in = SyncGNNTrainer(G, CFG, num_devices=2, seed=seed)
    t_mp = SyncGNNTrainer(G, CFG, num_devices=2, seed=seed,
                          num_sampler_workers=2)
    try:
        for _ in range(2):
            m_in = t_in.run_epoch()
            m_mp = t_mp.run_epoch()
            assert m_in["loss"] == m_mp["loss"]
            assert m_in["acc"] == m_mp["acc"]
            assert m_in["batches"] == m_mp["batches"]
        for a, b in zip(jax.tree.leaves(t_in.params),
                        jax.tree.leaves(t_mp.params)):
            assert (np.asarray(a) == np.asarray(b)).all()
    finally:
        t_mp.close()
        t_in.close()


def test_training_with_workers_handles_zero_edge_layers():
    import jax
    from repro.core.trainer import SyncGNNTrainer
    g = _zero_edge_graph()
    t_in = SyncGNNTrainer(g, CFG, num_devices=2, seed=1)
    t_mp = SyncGNNTrainer(g, CFG, num_devices=2, seed=1,
                          num_sampler_workers=2)
    try:
        mb = t_in.samplers[0].batch_at(0, 0)
        assert mb.edges_traversed() == 0  # the frontier really is isolated
        m_in = t_in.run_epoch()
        m_mp = t_mp.run_epoch()
        assert m_in["loss"] == m_mp["loss"]
        for a, b in zip(jax.tree.leaves(t_in.params),
                        jax.tree.leaves(t_mp.params)):
            assert (np.asarray(a) == np.asarray(b)).all()
    finally:
        t_mp.close()
        t_in.close()


def test_load_balance_policy_identical_across_worker_counts():
    """balance_policy="load" re-maps batches to devices by the Eq. 5 work
    estimate; the mapping is a pure function of the batch stream, so it too
    is bit-identical between workers=0 and workers=N."""
    import jax
    from repro.core.trainer import SyncGNNTrainer
    t_in = SyncGNNTrainer(G, CFG, num_devices=2, seed=4,
                          balance_policy="load")
    t_mp = SyncGNNTrainer(G, CFG, num_devices=2, seed=4,
                          balance_policy="load", num_sampler_workers=2)
    try:
        m_in = t_in.run_epoch()
        m_mp = t_mp.run_epoch()
        assert m_in["loss"] == m_mp["loss"]
        assert m_in["load_imbalance"] == m_mp["load_imbalance"]
        for a, b in zip(jax.tree.leaves(t_in.params),
                        jax.tree.leaves(t_mp.params)):
            assert (np.asarray(a) == np.asarray(b)).all()
    finally:
        t_mp.close()
        t_in.close()


def test_trainer_validates_knobs():
    from repro.core.trainer import SyncGNNTrainer
    with pytest.raises(ValueError, match="balance_policy"):
        SyncGNNTrainer(G, CFG, num_devices=2, balance_policy="fastest")
    with pytest.raises(ValueError, match="num_sampler_workers"):
        SyncGNNTrainer(G, CFG, num_devices=2, num_sampler_workers=-1)


def test_worker_modules_never_import_jax():
    """A spawned sampler worker imports only these modules: were one of
    them to pull in jax, every worker of a pool feeding an accelerator
    could try to claim the chip the training process already holds."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = ("import sys, repro.core.sampler_pool; "
            "sys.exit('jax' in sys.modules)")
    env = dict(os.environ, PYTHONPATH=src)
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=120).returncode == 0
