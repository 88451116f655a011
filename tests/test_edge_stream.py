"""Edge-streaming Pallas aggregation (tile densification in VMEM) + the
aggregate-kernel bug sweep.

Covers the PR's contracts: (1) the layout builder's ``edge_stream`` mode
re-sorts the compact triples into per-tile contiguous segments with
CSR-style ``tile_seg`` offsets, and the sorted triples densify bit-identical
to the unsorted ones; (2) ``aggregate_edges`` — which densifies each 128x128
tile in a VMEM scratch inside the grid step, never materializing the dense
tile tensor in HBM — matches the densify+SpMM path BITWISE on sampler-style
(distinct-pair) data and to fp tolerance on multi-edge data, including
zero-edge layers, fully-masked edges, and ragged tail batches; (3) the
``aggregate_edges_vjp`` backward over the A^T segments matches the compact
VJP bitwise; (4) training with ``aggregate_backend="pallas_edges"`` is
bit-identical per seed to the ``"pallas"`` backend, in-process and through
the sampler pool (ring fields reused + the new segment fields); (5) the
bug sweep: ``densify_tiles``'s flat scatter index no longer overflows int32
past 131072 tile slots, ``_agg_bwd``/``_agg_compact_bwd`` return the
cotangent in the primal dtype (bf16-safe), and ``aggregate_blockcsr`` pads
odd feature widths up to a lane-aligned block instead of serializing the
grid at fb=1.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.gnn import GNNModelConfig
from repro.core.sampler import NeighborSampler
from repro.core.trainer import SyncGNNTrainer
from repro.data.graphs import synthetic_graph
from repro.gnn import models as gnn_models
from repro.kernels.aggregate import (BLK, _pad_feature_dim, aggregate_edges,
                                     aggregate_edges_vjp,
                                     aggregate_blockcsr,
                                     aggregate_compact_vjp, densify_tiles,
                                     densify_tiles_np, build_block_coo_pair,
                                     build_block_csr, build_layer_layouts,
                                     block_capacities,
                                     edge_stream_layout_bytes)

G = synthetic_graph(scale=9, edge_factor=6, feat_dim=16, num_classes=4)
CFG = GNNModelConfig("graphsage", num_layers=2, hidden=16, fanouts=(4, 3),
                     batch_targets=32)


def _distinct_edges(rng, n_src, n_dst, n_edges):
    """Distinct (src, dst) pairs — the sampler's per-layer contract, under
    which every tile cell is single-edge and the VMEM densification is
    bit-identical to the HBM scatter-add."""
    n_edges = min(n_edges, n_src * n_dst)
    pairs = rng.choice(n_src * n_dst, n_edges, replace=False)
    return ((pairs % n_src).astype(np.int32),
            (pairs // n_src).astype(np.int32))


def _edges_agg(coo, h):
    return aggregate_edges(jnp.asarray(coo["tile_off"]),
                           jnp.asarray(coo["val"]),
                           jnp.asarray(coo["tile_seg"]),
                           jnp.asarray(coo["cols"]), h)


# ---------------------------------------------------------------------------
# layout builder: per-tile segments + CSR offsets
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,mask_p", [(0, 0.85), (1, 0.5), (2, 1.0),
                                         (3, 0.0)])
def test_edge_stream_sort_is_consistent_and_densifies_identically(seed,
                                                                  mask_p):
    rng = np.random.default_rng(seed)
    n_src = int(rng.integers(30, 400))
    n_dst = int(rng.integers(30, 300))
    E = int(rng.integers(50, 3000))
    es = rng.integers(0, n_src, E).astype(np.int32)
    ed = rng.integers(0, n_dst, E).astype(np.int32)
    em = rng.random(E) < mask_p
    vals = rng.standard_normal(E).astype(np.float32)

    plain = build_block_coo_pair(es, ed, em, n_src, n_dst, vals)
    coo = build_block_coo_pair(es, ed, em, n_src, n_dst, vals,
                               max_blk=plain["cols"].shape[1],
                               max_blk_t=plain["cols_t"].shape[1],
                               edge_stream=True)
    np.testing.assert_array_equal(coo["cols"], plain["cols"])
    np.testing.assert_array_equal(coo["cols_t"], plain["cols_t"])
    for suffix, cols_key in (("", "cols"), ("_t", "cols_t")):
        seg = coo[f"tile_seg{suffix}"]
        tid = coo[f"tile_id{suffix}"]
        n_tiles = coo[cols_key].shape[0] * coo[cols_key].shape[1]
        assert seg.shape == (n_tiles + 1,) and seg.dtype == np.int32
        assert seg[0] == 0 and seg[-1] == int(em.sum())
        assert (np.diff(seg) >= 0).all(), "offsets must be monotone"
        # segment t holds exactly the edges whose tile is t, in tile order
        for t in rng.choice(n_tiles, min(n_tiles, 8), replace=False):
            assert (tid[seg[t]:seg[t + 1]] == t).all()
        assert (tid[:seg[-1]] == np.sort(tid[:seg[-1]])).all()
    # the sorted triples densify bit-identical to the unsorted ones
    for suffix, cols_key in (("", "cols"), ("_t", "cols_t")):
        val_key = "val" if suffix == "" else "val_t"
        a = densify_tiles_np(plain[f"tile_id{suffix}"],
                             plain[f"tile_off{suffix}"], plain["val"],
                             *plain[cols_key].shape)
        b = densify_tiles_np(coo[f"tile_id{suffix}"],
                             coo[f"tile_off{suffix}"], coo[val_key],
                             *coo[cols_key].shape)
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_edge_stream_layout_bytes_leaner_than_compact():
    """The device consumes 16 B/edge (no tile_id) + offsets under the
    edge-streaming layout vs 20 B/edge for the densify layout."""
    from repro.kernels.layout import compact_layout_bytes
    assert edge_stream_layout_bytes(10_000, 8, 4, 16, 8) < \
        compact_layout_bytes(10_000, 8, 4, 16, 8)


# ---------------------------------------------------------------------------
# kernel: VMEM densification == HBM densify + SpMM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_edges_kernel_bitwise_matches_densify_path(seed):
    rng = np.random.default_rng(seed)
    n_src = int(rng.integers(100, 500))
    n_dst = int(rng.integers(80, 400))
    E = int(rng.integers(200, 4000))
    f = int(rng.choice([16, 64, 160]))
    es, ed = _distinct_edges(rng, n_src, n_dst, E)
    em = rng.random(len(es)) < 0.85
    coo = build_block_coo_pair(es, ed, em, n_src, n_dst, edge_stream=True)
    b, c, pad = build_block_csr(es, ed, em, n_src, n_dst,
                                max_blk=coo["cols"].shape[1])
    h = rng.standard_normal((pad, f)).astype(np.float32)
    out_dense = aggregate_blockcsr(jnp.asarray(b), jnp.asarray(c),
                                   jnp.asarray(h))
    out_edges = _edges_agg(coo, jnp.asarray(h))
    assert (np.asarray(out_dense) == np.asarray(out_edges)).all(), \
        "single-edge cells must densify bit-identically in VMEM"


def test_edges_kernel_multi_edge_allclose():
    """Duplicate (src, dst) pairs accumulate in possibly different fp order
    than the scatter-add — equal to tolerance, not necessarily bitwise."""
    rng = np.random.default_rng(5)
    E = 2000
    es = rng.integers(0, 60, E).astype(np.int32)
    ed = rng.integers(0, 50, E).astype(np.int32)
    em = rng.random(E) < 0.9
    vals = rng.standard_normal(E).astype(np.float32)
    coo = build_block_coo_pair(es, ed, em, 60, 50, vals, edge_stream=True)
    b, c, pad = build_block_csr(es, ed, em, 60, 50, vals,
                                max_blk=coo["cols"].shape[1])
    h = rng.standard_normal((pad, 32)).astype(np.float32)
    out_dense = aggregate_blockcsr(jnp.asarray(b), jnp.asarray(c),
                                   jnp.asarray(h))
    out_edges = _edges_agg(coo, jnp.asarray(h))
    np.testing.assert_allclose(np.asarray(out_edges), np.asarray(out_dense),
                               atol=1e-4, rtol=1e-4)


def test_edges_kernel_fully_masked_and_zero_edges():
    rng = np.random.default_rng(7)
    E = 64
    es = rng.integers(0, 100, E).astype(np.int32)
    ed = rng.integers(0, 90, E).astype(np.int32)
    coo = build_block_coo_pair(es, ed, np.zeros(E, bool), 100, 90,
                               max_blk=2, max_blk_t=1, edge_stream=True)
    h = jnp.asarray(rng.standard_normal((coo["n_src_pad"], 16)), jnp.float32)
    assert not np.asarray(_edges_agg(coo, h)).any()
    # zero-LENGTH edge arrays (a layer whose capacity itself is zero)
    coo0 = build_block_coo_pair(np.empty(0, np.int32), np.empty(0, np.int32),
                                np.empty(0, bool), 200, 150,
                                max_blk=3, max_blk_t=2, edge_stream=True)
    out0 = _edges_agg(coo0, jnp.ones((256, 8), jnp.float32))
    assert out0.shape == (256, 8) and not np.asarray(out0).any()


def test_edges_kernel_ragged_tail_batch():
    """The last ragged batch of an epoch (fewer real targets than the static
    capacity, heavy padding) streams identically to the densify path."""
    cfg = GNNModelConfig("graphsage", num_layers=2, hidden=16,
                         fanouts=(4, 3), batch_targets=48)
    s = NeighborSampler(G, cfg, G.train_ids[:50], 0, seed=1)  # 50 % 48 != 0
    caps = block_capacities(cfg)
    mb = s.batch_at(0, 1)  # tail batch: 2 real targets + drawn padding
    lo_e = build_layer_layouts(mb.edge_src, mb.edge_dst, mb.edge_mask, caps,
                               "mean", edge_stream=True)
    lo_d = build_layer_layouts(mb.edge_src, mb.edge_dst, mb.edge_mask, caps,
                               "mean")
    rng = np.random.default_rng(0)
    for l in range(cfg.num_layers):
        cols = lo_d["agg_cols"][l]
        tiles = densify_tiles(jnp.asarray(lo_d["agg_tile_id"][l]),
                              jnp.asarray(lo_d["agg_tile_off"][l]),
                              jnp.asarray(lo_d["agg_val"][l]), *cols.shape)
        n_src_pad = lo_d["agg_cols_t"][l].shape[0] * BLK
        h = jnp.asarray(rng.standard_normal((n_src_pad, 16)), jnp.float32)
        out_d = aggregate_blockcsr(tiles, jnp.asarray(cols), h)
        out_e = aggregate_edges(jnp.asarray(lo_e["agg_tile_off"][l]),
                                jnp.asarray(lo_e["agg_val"][l]),
                                jnp.asarray(lo_e["agg_tile_seg"][l]),
                                jnp.asarray(cols), h)
        assert (np.asarray(out_d) == np.asarray(out_e)).all()


def test_edges_hypothesis_sweep():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @given(n_src=st.integers(60, 400), n_dst=st.integers(50, 300),
           n_edges=st.integers(0, 3000),
           mask_p=st.sampled_from([0.0, 0.6, 1.0]),
           f=st.sampled_from([16, 48]))
    @settings(deadline=None, max_examples=12)
    def run(n_src, n_dst, n_edges, mask_p, f):
        rng = np.random.default_rng(n_src * n_dst + n_edges)
        es, ed = _distinct_edges(rng, n_src, n_dst, n_edges)
        em = rng.random(len(es)) < mask_p
        coo = build_block_coo_pair(es, ed, em, n_src, n_dst,
                                   edge_stream=True)
        b, c, pad = build_block_csr(es, ed, em, n_src, n_dst,
                                    max_blk=coo["cols"].shape[1])
        h = rng.standard_normal((pad, f)).astype(np.float32)
        out_d = aggregate_blockcsr(jnp.asarray(b), jnp.asarray(c),
                                   jnp.asarray(h))
        out_e = _edges_agg(coo, jnp.asarray(h))
        assert (np.asarray(out_d) == np.asarray(out_e)).all()

    run()


# ---------------------------------------------------------------------------
# custom VJP over the A^T segments
# ---------------------------------------------------------------------------

def _vjp_layouts(rng, n_src=220, n_dst=180, E=1500):
    es, ed = _distinct_edges(rng, n_src, n_dst, E)
    em = rng.random(len(es)) < 0.85
    deg = np.bincount(ed[em], minlength=n_dst)
    vals = (1.0 / np.maximum(deg[ed], 1.0)).astype(np.float32)
    coo_e = build_block_coo_pair(es, ed, em, n_src, n_dst, vals,
                                 edge_stream=True)
    coo_c = build_block_coo_pair(es, ed, em, n_src, n_dst, vals)
    return coo_e, coo_c


def _edges_vjp_call(coo, h):
    return aggregate_edges_vjp(
        jnp.asarray(coo["tile_off"]), jnp.asarray(coo["val"]),
        jnp.asarray(coo["tile_seg"]), jnp.asarray(coo["cols"]),
        jnp.asarray(coo["tile_off_t"]), jnp.asarray(coo["val_t"]),
        jnp.asarray(coo["tile_seg_t"]), jnp.asarray(coo["cols_t"]), h)


def test_edges_vjp_gradient_bitwise_matches_compact_vjp():
    rng = np.random.default_rng(11)
    coo_e, coo_c = _vjp_layouts(rng)
    h = jnp.asarray(rng.standard_normal((coo_e["n_src_pad"], 32)),
                    jnp.float32)
    w = jnp.asarray(
        rng.standard_normal((coo_e["cols"].shape[0] * BLK, 32)), jnp.float32)

    def loss_e(hh):
        return (_edges_vjp_call(coo_e, hh) * w).sum()

    def loss_c(hh):
        layout = tuple(jnp.asarray(coo_c[k]) for k in
                       ("tile_id", "tile_off", "val", "cols",
                        "tile_id_t", "tile_off_t", "cols_t"))
        return (aggregate_compact_vjp(*layout, hh) * w).sum()

    v_e, g_e = jax.value_and_grad(loss_e)(h)
    v_c, g_c = jax.value_and_grad(loss_c)(h)
    assert float(v_e) == float(v_c)
    assert (np.asarray(g_e) == np.asarray(g_c)).all()


@pytest.mark.parametrize("call", ["compact", "edges"])
def test_bwd_cotangent_keeps_bf16_primal_dtype(call):
    """Regression (bug sweep): the backward kernels computed dh in fp32
    unconditionally, mismatching a bf16 primal's cotangent dtype."""
    rng = np.random.default_rng(3)
    coo_e, coo_c = _vjp_layouts(rng, E=600)
    h = jnp.asarray(rng.standard_normal((coo_e["n_src_pad"], 32)),
                    jnp.bfloat16)

    if call == "edges":
        def loss(hh):
            return _edges_vjp_call(coo_e, hh).astype(jnp.float32).sum()
    else:
        def loss(hh):
            layout = tuple(jnp.asarray(coo_c[k]) for k in
                           ("tile_id", "tile_off", "val", "cols",
                            "tile_id_t", "tile_off_t", "cols_t"))
            return aggregate_compact_vjp(
                *layout, hh).astype(jnp.float32).sum()

    g = jax.grad(loss)(h)
    assert g.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(g, np.float32)).all()


# ---------------------------------------------------------------------------
# bug sweep: densify_tiles int32 overflow past 131072 tile slots
# ---------------------------------------------------------------------------

OVERFLOW_TILES = (1 << 31) // (BLK * BLK) + 2  # flat index crosses 2**31


def test_densify_np_no_int32_overflow_past_2_31():
    """131074 tile slots put the old flat index (tile_id * BLK*BLK +
    tile_off) past 2**31; the 2-D scatter must land both edges exactly.
    np.zeros is virtual (calloc), so the 8.6 GB tensor costs only the
    touched pages."""
    tile_id = np.array([OVERFLOW_TILES - 1, 0], np.int32)
    tile_off = np.array([BLK * BLK - 1, 5], np.int32)
    val = np.array([2.5, 1.5], np.float32)
    tiles = densify_tiles_np(tile_id, tile_off, val, OVERFLOW_TILES, 1)
    assert tiles.shape == (OVERFLOW_TILES, 1, BLK, BLK)
    assert tiles[OVERFLOW_TILES - 1, 0, BLK - 1, BLK - 1] == 2.5
    assert tiles[0, 0, 0, 5] == 1.5


def _mem_available_gb() -> float:
    try:
        with open("/proc/meminfo") as fh:
            for line in fh:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) / 2**20
    except OSError:
        pass
    return 0.0


@pytest.mark.skipif(_mem_available_gb() < 24,
                    reason="jax materializes the >2**31-element tile tensor"
                           " (~17 GB transient); needs a big host")
def test_densify_jax_no_int32_overflow_past_2_31():
    """Same boundary through the jax scatter (which, unlike numpy, has no
    int64 escape hatch without x64 mode — the 2-D index IS the fix)."""
    tile_id = jnp.asarray([OVERFLOW_TILES - 1, 0], jnp.int32)
    tile_off = jnp.asarray([BLK * BLK - 1, 5], jnp.int32)
    val = jnp.asarray([2.5, 1.5], jnp.float32)
    tiles = densify_tiles(tile_id, tile_off, val, OVERFLOW_TILES, 1)
    assert float(tiles[OVERFLOW_TILES - 1, 0, BLK - 1, BLK - 1]) == 2.5
    assert float(tiles[0, 0, 0, 5]) == 1.5
    del tiles


def test_densify_jax_matches_np_bitwise():
    rng = np.random.default_rng(9)
    E = 500
    n_tiles, max_blk = 3, 4
    tile_id = rng.integers(0, n_tiles * max_blk, E).astype(np.int32)
    tile_off = rng.integers(0, BLK * BLK, E).astype(np.int32)
    val = rng.standard_normal(E).astype(np.float32)
    a = densify_tiles_np(tile_id, tile_off, val, n_tiles, max_blk)
    b = densify_tiles(jnp.asarray(tile_id), jnp.asarray(tile_off),
                      jnp.asarray(val), n_tiles, max_blk)
    np.testing.assert_allclose(np.asarray(b), a, atol=1e-5)


# ---------------------------------------------------------------------------
# bug sweep: odd feature widths pad up instead of serializing the grid
# ---------------------------------------------------------------------------

def test_pad_feature_dim_never_degrades_to_fb_1():
    for F, feat_block in ((331, 256), (101, 64), (330, 256)):
        h = jnp.zeros((BLK, F), jnp.float32)
        h_pad, F_pad, fb = _pad_feature_dim(h, feat_block)
        assert fb == min(feat_block, F), \
            "fb must stay the requested block, not a degenerate divisor"
        assert F_pad % fb == 0 and F_pad >= F
        assert h_pad.shape == (BLK, F_pad)


@pytest.mark.parametrize("F", [101, 331])
def test_blockcsr_odd_feature_width_matches_reference(F):
    rng = np.random.default_rng(F)
    n_src, n_dst, E = 200, 150, 1200
    es = rng.integers(0, n_src, E).astype(np.int32)
    ed = rng.integers(0, n_dst, E).astype(np.int32)
    em = rng.random(E) < 0.9
    b, c, pad = build_block_csr(es, ed, em, n_src, n_dst)
    h = rng.standard_normal((pad, F)).astype(np.float32)
    out = aggregate_blockcsr(jnp.asarray(b), jnp.asarray(c), jnp.asarray(h),
                             feat_block=64)
    exp = gnn_models.aggregate(jnp.asarray(h[:n_src]), jnp.asarray(es),
                               jnp.asarray(ed), jnp.asarray(em), n_dst,
                               "sum")
    assert out.shape == (b.shape[0] * BLK, F)
    np.testing.assert_allclose(np.asarray(out)[:n_dst], np.asarray(exp),
                               atol=1e-3, rtol=1e-4)


# ---------------------------------------------------------------------------
# end-to-end: pallas_edges trains bit-identical to pallas, per seed
# ---------------------------------------------------------------------------

def _params_equal(a, b) -> bool:
    return all((np.asarray(x) == np.asarray(y)).all()
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("model", ["graphsage", "gin"])
def test_pallas_edges_trains_bitwise_identical_to_pallas(model):
    cfg = GNNModelConfig(model, num_layers=2, hidden=16, fanouts=(4, 3),
                         batch_targets=32)
    t_pal = SyncGNNTrainer(G, cfg, num_devices=2, seed=3,
                           aggregate_backend="pallas")
    t_edg = SyncGNNTrainer(G, cfg, num_devices=2, seed=3,
                           aggregate_backend="pallas_edges")
    assert t_edg.densified_hbm_bytes() == 0
    assert t_pal.densified_hbm_bytes() > 0
    for _ in range(2):
        m_pal = t_pal.run_epoch()
        m_edg = t_edg.run_epoch()
        assert m_pal["loss"] == m_edg["loss"], model
    assert _params_equal(t_pal.params, t_edg.params)


def test_pallas_edges_through_sampler_pool_bitwise():
    """Worker-built edge-stream payloads (ring fields + the new segment
    fields) train bit-identical to the in-process path, including with the
    stage-2 gather offload."""
    t_in = SyncGNNTrainer(G, CFG, num_devices=2, seed=5,
                          aggregate_backend="pallas_edges")
    m_in = t_in.run_epoch()
    with SyncGNNTrainer(G, CFG, num_devices=2, seed=5,
                        aggregate_backend="pallas_edges",
                        num_sampler_workers=2,
                        gather_in_workers=True) as t_w:
        m_w = t_w.run_epoch()
        assert m_in["loss"] == m_w["loss"]
        assert _params_equal(t_in.params, t_w.params)


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="aggregate_backend"):
        SyncGNNTrainer(G, CFG, num_devices=1,
                       aggregate_backend="pallas_vmem")


@pytest.mark.parametrize("backend", ["pallas", "pallas_edges",
                                     "pallas_fused"])
def test_kernel_backend_without_layout_raises(backend):
    """A kernel backend never quietly runs the reference aggregation: a
    batch without the stage-2b layout arrays is an error. GAT has no kernel
    path and always runs the reference edge list."""
    from repro.core.trainer import batch_to_arrays
    from repro.nn.param import materialize
    mb = NeighborSampler(G, CFG, G.train_ids, 0, seed=0).batch_at(0, 0)
    batch = batch_to_arrays(mb, G.features[np.asarray(mb.nodes[0])])
    for name, ok in (("graphsage", False), ("gat", True)):
        cfg = GNNModelConfig(name, num_layers=2, hidden=16, fanouts=(4, 3),
                             batch_targets=32, aggregate_backend=backend)
        params = materialize(gnn_models.param_spec(
            cfg, G.features.shape[1], G.num_classes), jax.random.PRNGKey(0))
        if ok:
            assert np.isfinite(np.asarray(
                gnn_models.forward(cfg, params, batch))).all()
        else:
            with pytest.raises(ValueError, match="layout"):
                gnn_models.forward(cfg, params, batch)
