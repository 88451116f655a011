"""The benchmark's yardstick pieces on hand-built inputs: the trace
reduction (bench/trace.py), the work counts (bench/work.py), the sampled
batch check and the plain reference (bench/reference.py), the comparison
(bench/compare.py), and the serving schedule."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import compare, reference, work  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.drivers import serve_open_loop  # noqa: E402

E = tr.Event


# -- trace reduction -----------------------------------------------------------

def test_union_and_busy_intervals_merge_overlaps():
    assert tr.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    evs = [E("a", 0.0, 2.0), E("b", 1.0, 2.0), E("c", 5.0, 1.0),
           E("d", 9.0, 5.0)]
    assert tr.busy_intervals(evs, (0.5, 10.0)) == [(0.5, 3.0), (5.0, 6.0),
                                                  (9.0, 10.0)]
    assert tr.idle_gaps([(0.5, 3.0), (5.0, 6.0)], (0.0, 8.0)) == [
        (0.0, 0.5), (3.0, 5.0), (6.0, 8.0)]


def test_summary_of_a_hand_built_trace():
    # two chips; the window is the host span bench/window, 0..10 s
    ops = [[E("fusion.1", 1.0, 2.0), E("_fused_kernel", 4.0, 1.0),
            E("all-reduce.3", 5.0, 0.5), E("fusion.1", 12.0, 1.0)],
           [E("fusion.1", 1.0, 4.0), E("custom-call", 6.0, 1.0,
                                       "kernel _fused_bwd_kernel")]]
    modules = [[E("jit_fwd(1)", 1.0, 3.0)], [E("jit_step(2)", 1.0, 6.0)]]
    host = [E("bench/window", 0.0, 10.0), E("run_epoch", 0.0, 10.0),
            E("sample", 3.0, 1.0), E("dispatch", 5.5, 0.1)]
    s = tr.summarize(ops, modules, host)
    assert s.window == (0.0, 10.0) and s.devices == 2
    assert s.busy_s == [3.5, 5.0]
    assert s.mean_busy_s == 4.25
    assert s.op_seconds(r"_fused") == 2.0  # name on one, stats on the other
    assert s.op_seconds(r"all-reduce") == 0.5
    assert [e.name for e in s.module_events("fwd")] == ["jit_fwd(1)"]
    assert s.top_ops(2) == [("fusion.1", 6.0), ("_fused_kernel", 1.0)]
    # device 0 idles 0-1, 3-4 (sampling on the host) and 5.5-10; device 1
    # idles 0-1, 5-6 and 7-10; the longest first
    assert s.gaps == [("run_epoch", 4.5), ("run_epoch", 3.0),
                      ("run_epoch", 1.0), ("sample", 1.0),
                      ("run_epoch", 1.0), ("run_epoch", 1.0)]


def test_summary_keeps_the_cells_chips():
    ops = [[E("x", 0.0, 1.0)], [E("x", 0.0, 2.0)]]
    s = tr.summarize(ops, [[], []], [E("bench/window", 0.0, 4.0)],
                     devices=1)
    assert s.devices == 1 and s.busy_s == [1.0]


# -- work counts -----------------------------------------------------------------

def test_work_counts_by_hand():
    # layer 0: 10 sources, 4 destinations, 12 edges; layer 1: 4 -> 2, 5 edges
    sizes = work.layer_sizes([10, 4, 2], [12, 5])
    dims = work.widths(f_in=3, hidden=8, n_classes=5, num_layers=2)
    assert sizes == [(10, 4, 12), (4, 2, 5)] and dims == [(3, 8), (8, 5)]
    # aggregation: fwd 2*12*3 + 2*5*8; bwd only at layer 1: 2*5*8
    assert work.agg_flops(sizes, dims) == 72 + 80 + 80
    assert work.agg_flops(sizes, dims, train=False) == 72 + 80
    # bytes: 4*f*(n_src+n_dst) + 8*edges, layer 1 twice in training
    assert work.agg_bytes(sizes, dims) == (4 * 3 * 14 + 96) \
        + 2 * (4 * 8 * 6 + 40)
    # graphsage: two matmuls per layer; fwd + dW everywhere, dX above 0
    mm0, mm1 = 2 * 2 * 4 * 3 * 8, 2 * 2 * 2 * 8 * 5
    assert work.step_flops({"name": "graphsage"}, sizes, dims) == \
        work.agg_flops(sizes, dims) + 2 * mm0 + 3 * mm1
    mm0, mm1 = 2 * 4 * 3 * 8, 2 * 2 * 8 * 5
    assert work.step_flops({"name": "gcn"}, sizes, dims, train=False) == \
        work.agg_flops(sizes, dims, train=False) + mm0 + mm1


# -- batches and the reference ----------------------------------------------------

def _tiny_graph():
    # in-edges: 0<-{1,2,3}, 1<-{0}, 2<-{0,3}, 3<-{0,2}, 4<-{} (sources sorted)
    indptr = np.array([0, 3, 4, 6, 8, 8])
    indices = np.array([1, 2, 3, 0, 0, 3, 0, 2], np.int32)
    return {"indptr": indptr, "indices": indices,
            "labels": np.array([0, 1, 2, 0, 1], np.int32)}


def _tiny_batch():
    # targets [0, 2]; layer 1 keeps all of 0's and 2's in-edges (fanout 3)
    # layer 0 frontier [0,1,2,3] with fanout 1 each
    return {
        "nodes": [np.array([0, 1, 2, 3]), np.array([0, 1, 2, 3]),
                  np.array([0, 2])],
        "edge_src": [np.array([1, 0, 0, 0]), np.array([1, 2, 3, 0, 3])],
        "edge_dst": [np.array([0, 1, 2, 3]), np.array([0, 0, 0, 1, 1])],
        "self_idx": [np.array([0, 1, 2, 3]), np.array([0, 2])],
        "labels": np.array([0, 2])}


def test_check_batch_accepts_a_sound_sample():
    assert reference.check_batch(_tiny_graph(), [1, 3], _tiny_batch()) == []


@pytest.mark.parametrize("fault, words", [
    (lambda b: b["edge_src"][1].__setitem__(0, 0), "not in the graph"),
    (lambda b: b["edge_src"][1].__setitem__(1, 1), "repeats a source"),
    (lambda b: b.update(labels=np.array([1, 2])), "labels"),
    (lambda b: b["self_idx"][1].__setitem__(1, 3), "self index"),
])
def test_check_batch_catches_a_wrong_sample(fault, words):
    b = _tiny_batch()
    fault(b)
    assert any(words in p for p in reference.check_batch(
        _tiny_graph(), [1, 3], b))


def test_check_batch_catches_a_fanout_overrun():
    # fanout 2 at the targets' layer, but vertex 0 kept all three sources
    assert any("min(in-degree, 2)" in p for p in reference.check_batch(
        _tiny_graph(), [1, 2], _tiny_batch()))


def test_reference_forward_by_hand():
    b = _tiny_batch()
    feats = np.arange(8, dtype=np.float32).reshape(4, 2)
    p = {"layers": [{"w": np.eye(2, dtype=np.float32),
                     "b": np.zeros(2, np.float32)},
                    {"w": np.ones((2, 3), np.float32),
                     "b": np.array([0.0, 1.0, 2.0], np.float32)}]}
    out = reference.logits({"name": "gcn"}, p, b, feats)
    h = feats
    agg0 = np.stack([h[1], h[0], h[0], h[0]])
    h1 = np.maximum((agg0 + h) * 0.5, 0)
    agg1 = np.stack([h1[[1, 2, 3]].mean(0), h1[[0, 3]].mean(0)])
    want = (agg1 + h1[[0, 2]]) @ np.ones((2, 3)) * 0.5 + [0, 1, 2]
    np.testing.assert_allclose(out, want, rtol=1e-6)


def test_reference_training_steps_move_every_leaf():
    b = _tiny_batch()
    feats = np.random.default_rng(0).standard_normal((4, 2)).astype(
        np.float32)
    model = {"name": "graphsage", "hidden": 4, "num_layers": 2}
    params = reference.make_params(model, 2, 3, seed=2**33 + 5)
    opt = {"lr": 0.01, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
           "weight_decay": 0.0, "grad_clip": 1.0, "warmup": 10,
           "total": 1000, "min_ratio": 0.1}
    out = reference.train_steps(model, params, [[b], [b]],
                                [[feats], [feats]], opt)
    assert len(out["loss"]) == 2 and out["loss"][1] < out["loss"][0]
    p0 = [np.asarray(x) for x in __import__("jax").tree.leaves(params)]
    p2 = __import__("jax").tree.leaves(out["params"][-1])
    assert all(np.abs(a - b).max() > 0 for a, b in zip(p0, p2))


# -- the comparison -------------------------------------------------------------

def test_training_numbers_and_judge():
    g = [np.ones(4), np.full(3, 2.0), np.full(2, 1e-9)]
    p0 = [np.zeros(4), np.zeros(3), np.zeros(2)]
    same = compare.training_numbers([1.0], [1.0], g, g, p0, g, g)
    assert same == {"loss_gap": 0.0, "grad_gap": 0.0, "change_gap": 0.0}
    frozen = compare.training_numbers([1.0], [1.0], g, g, p0, p0, g)
    assert frozen["change_gap"] == 1.0  # a step that leaves the state
    # the near-zero leaf is left out even when it is far off
    off = compare.training_numbers([1.1], [1.0],
                                   [g[0], g[1], np.full(2, 1.0)], g, p0, g, g)
    assert off["grad_gap"] == 0.0 and off["loss_gap"] == pytest.approx(0.1)
    limits = {"loss_gap": 0.05, "grad_gap": 0.05, "change_gap": 0.05}
    assert compare.judge(same, limits)[0]
    assert not compare.judge(off, limits)[0]
    assert not compare.judge(same, {"loss_gap": 1.0})[0]  # a missing limit
    assert not compare.judge(same, limits, ["a problem"])[0]
    ok, checks = compare.judge(same, limits)
    assert checks["loss_gap"] == {"value": 0.0, "limit": 0.05}


def test_logit_gap_is_relative_to_each_row():
    ref = np.array([[1.0, -4.0], [0.1, 0.2]])
    assert compare.logit_gap(ref, ref) == 0.0
    assert compare.logit_gap(ref + [[0.4, 0], [0, 0]], ref) == \
        pytest.approx(0.1)


# -- the serving schedule ----------------------------------------------------------

TRAFFIC = {"rate_per_s": 50, "targets_min": 1, "targets_max": 32,
           "zipf_s": 0.99, "traffic_seed": 0}


def test_schedule_offers_the_same_work_for_every_seed():
    due_a, ids_a = serve_open_loop.schedule(TRAFFIC, 4.0, 2**40 + 1, 1000)
    due_b, ids_b = serve_open_loop.schedule(TRAFFIC, 4.0, 7, 1000)
    assert len(due_a) == len(due_b) == 200
    assert due_a[-1] == pytest.approx(4.0) and due_b[-1] == pytest.approx(4.0)
    assert np.all(np.diff(due_a) > 0)
    assert sorted(map(len, ids_a)) == sorted(map(len, ids_b))
    assert not np.array_equal(due_a, due_b)
    assert all(1 <= len(x) <= 32 for x in ids_a)
    again = serve_open_loop.schedule(TRAFFIC, 4.0, 7, 1000)
    assert np.array_equal(again[0], due_b)
    assert all(np.array_equal(x, y) for x, y in zip(again[1], ids_b))


def test_zipf_ranks_favour_the_head():
    r = serve_open_loop.zipf_ranks(0.99, 100_000, 50_000, seed=1)
    assert r.min() >= 0 and r.max() < 100_000
    assert np.mean(r == 0) > 0.05 and np.mean(r < 100) > 0.3


def test_stacked_batches_give_each_batch_its_own_logits():
    b1 = _tiny_batch()
    b2 = {"nodes": [np.array([0, 2, 3]), np.array([0, 3]), np.array([3])],
          "edge_src": [np.array([1, 2, 0]), np.array([0])],
          "edge_dst": [np.array([0, 0, 1]), np.array([0])],
          "self_idx": [np.array([0, 2]), np.array([1])],
          "labels": np.array([0])}
    union, starts = reference.stack_batches([b1, b2, b1])
    # rows and edges padded to powers of two, with a spare row per layer
    assert [len(n) for n in union["nodes"]] == [16, 16, 8]
    assert [len(e) for e in union["edge_src"]] == [16, 16]
    assert list(starts) == [0, 2, 3]
    model = {"name": "graphsage", "hidden": 4, "num_layers": 2}
    params = reference.make_params(model, 2, 3, seed=11)
    feats = np.random.default_rng(1).standard_normal((5, 2)).astype(
        np.float32)
    got = reference.logits(model, params, union, feats[union["nodes"][0]])
    for b, lo in zip([b1, b2, b1], starts):
        want = reference.logits(model, params, b, feats[b["nodes"][0]])
        np.testing.assert_allclose(got[lo:lo + len(want)], want, rtol=1e-6,
                                   atol=1e-7)
