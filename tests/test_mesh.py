"""Multi-device mesh trainer: the shard_map step, the sharded feature
store, on-device P3 all-to-all, and device-count validation.

The pytest process owns a single real CPU device, so in-process tests run
the p=1 mesh (shard_map machinery, bit-identical contract) and unit-test
the on-device feature assembly against the host-side gather; the 1/2/4
simulated-device scaling + loss-equivalence property runs in a subprocess
(``benchmarks/mesh_child.py``) where
``XLA_FLAGS=--xla_force_host_platform_device_count`` can be set before jax
imports."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs.gnn import GNNModelConfig, PlatformConfig
from repro.core.feature_store import FeatureStore
from repro.core.partition import get_partitioner
from repro.core.residency import ResidencyCore
from repro.core.trainer import SyncGNNTrainer
from repro.data.graphs import synthetic_graph
from repro.distributed.sharding import make_data_mesh, require_data_axis
from repro.gnn import models as gnn_models

G = synthetic_graph(scale=9, edge_factor=8, feat_dim=24, num_classes=5)
CFG = GNNModelConfig("graphsage", num_layers=2, hidden=16, fanouts=(4, 4),
                     batch_targets=16)
ROOT = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# validation (satellite fix: no more phantom devices)
# ---------------------------------------------------------------------------

class TestDeviceValidation:
    def test_data_parallel_too_many_devices_raises(self):
        with pytest.raises(ValueError, match="xla_force_host_platform"):
            SyncGNNTrainer(G, CFG, num_devices=jax.device_count() + 1,
                           data_parallel=True)

    def test_too_many_devices_on_accelerator_has_no_cpu_hint(
            self, monkeypatch):
        # simulated CPU devices cannot stand in for missing chips
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(ValueError, match="tpu device") as err:
            make_data_mesh(jax.device_count() + 1)
        assert "xla_force_host_platform" not in str(err.value)

    def test_mesh_axis_extent_mismatch_raises(self):
        mesh = make_data_mesh(1)
        with pytest.raises(ValueError, match="does not match"):
            SyncGNNTrainer(G, CFG, num_devices=2, mesh=mesh)

    def test_mesh_without_data_axis_raises(self):
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
        with pytest.raises(ValueError, match="'data' axis"):
            SyncGNNTrainer(G, CFG, num_devices=1, mesh=mesh)

    def test_require_data_axis_ok(self):
        require_data_axis(make_data_mesh(1), 1)

    def test_mesh_plus_midepoch_cache_refresh_raises(self):
        with pytest.raises(ValueError, match="epoch-boundary"):
            SyncGNNTrainer(
                G, CFG.replace_flat(cache_capacity=64,
                                    cache_refresh_every=2),
                num_devices=1, data_parallel=True)


# ---------------------------------------------------------------------------
# sharded feature store
# ---------------------------------------------------------------------------

def _store(algorithm: str, p: int) -> FeatureStore:
    from repro.core.trainer import ALGORITHMS
    part_name, store_name = ALGORITHMS[algorithm]
    part = get_partitioner(part_name)(G, p, 0)
    return FeatureStore(G, part, store_name)


class TestShardMatrix:
    def test_shard_rows_match_residency(self):
        st = _store("distdgl", 4)
        mat = st.build_shard_matrix()
        assert mat.shape[0] == 4
        for d in range(4):
            rid = st.resident_ids(d)
            np.testing.assert_array_equal(mat[d, :len(rid)],
                                          G.features[rid])
            assert not mat[d, len(rid):].any()

    def test_p3_shard_is_feature_slices(self):
        st = _store("p3", 4)
        mat = st.build_shard_matrix()
        assert mat.shape[:2] == (4, G.num_vertices)
        for d in range(4):
            w = st.core.slice_width(d)
            np.testing.assert_array_equal(
                mat[d, :, :w], G.features[:, st.core.feature_slice(d)])

    def test_resident_positions_roundtrip(self):
        st = _store("pagraph", 3)
        ids = np.random.default_rng(0).integers(
            0, G.num_vertices, 64).astype(np.int32)
        mask = np.ones(64, bool)
        mask[50:] = False
        for d in range(3):
            pos, hit = st.core.resident_positions(d, ids, mask)
            rid = st.core.resident_ids(d)
            expect_hit = st.core.is_resident(d, ids) & mask
            np.testing.assert_array_equal(hit, expect_hit)
            np.testing.assert_array_equal(rid[pos[hit]], ids[hit])

    def test_device_feats_assembly_bitwise_vs_gather(self):
        # the on-device scatter assembly must reproduce the host-side
        # FeatureStore.gather block exactly, device by device
        st = _store("distdgl", 4)
        rng = np.random.default_rng(1)
        ids = rng.integers(0, G.num_vertices, 48).astype(np.int32)
        mask = np.ones(48, bool)
        mask[40:] = False
        mat = st.build_shard_matrix()
        for d in range(4):
            want = st.gather(d, ids, mask)
            pos, hit = st.core.resident_positions(d, ids, mask)
            mpos, mrows = st.core.select_ship_rows(d, G.features, ids, mask)
            cap = 64
            mp = np.full(cap, len(ids), np.int32)
            mp[:len(mpos)] = mpos
            mr = np.zeros((cap, G.features.shape[1]), np.float32)
            mr[:len(mrows)] = mrows
            batch = {"shard_pos": pos, "shard_hit": hit.astype(np.float32),
                     "miss_pos": mp, "miss_rows": mr}
            got = np.asarray(
                gnn_models.assemble_device_feats(jax.numpy.asarray(mat[d]),
                                                 batch))
            np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# p=1 mesh: the full shard_map step in-process
# ---------------------------------------------------------------------------

class TestSingleDeviceMesh:
    @pytest.mark.parametrize("algorithm", ["distdgl", "p3"])
    def test_mesh_p1_trains_and_decreases(self, algorithm):
        tr = SyncGNNTrainer(G, CFG, num_devices=1, algorithm=algorithm,
                            data_parallel=True, pipeline=False)
        assert tr.mesh is not None
        losses = [tr.run_epoch()["loss"] for _ in range(3)]
        tr.close()
        assert losses[-1] < losses[0]

    def test_mesh_p1_loss_close_to_vmap(self):
        def run(**kw):
            tr = SyncGNNTrainer(G, CFG, num_devices=1, pipeline=False,
                                seed=7, **kw)
            out = [tr.run_epoch()["loss"] for _ in range(2)]
            tr.close()
            return out
        mesh_losses = run(data_parallel=True)
        vmap_losses = run()
        np.testing.assert_allclose(mesh_losses, vmap_losses, rtol=1e-5)

    def test_mesh_metrics_report_devices(self):
        tr = SyncGNNTrainer(G, CFG, num_devices=1, data_parallel=True,
                            pipeline=False)
        m = tr.run_epoch()
        tr.close()
        assert m["mesh_devices"] == 1
        assert "fill_slots" in m


# ---------------------------------------------------------------------------
# 1/2/4 simulated devices (subprocess: XLA_FLAGS before jax import)
# ---------------------------------------------------------------------------

class TestSimulatedDeviceScaling:
    @pytest.fixture(scope="class")
    def child(self):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(ROOT, "src")
        out = subprocess.run(
            [sys.executable,
             os.path.join(ROOT, "benchmarks", "mesh_child.py"),
             "--device-counts", "1,2,4", "--epochs", "3", "--rounds", "1",
             "--scale", "10", "--batch-targets", "32", "--check-vmap"],
            capture_output=True, text=True, env=env, timeout=900)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout)

    def test_losses_decrease_at_every_device_count(self, child):
        for p, losses in child["losses"].items():
            assert losses[-1] < losses[0], (p, losses)

    def test_losses_equivalent_across_device_counts(self, child):
        finals = [l[-1] for l in child["losses"].values()]
        mean = sum(finals) / len(finals)
        assert (max(finals) - min(finals)) / mean < 0.5, finals

    def test_mesh_step_matches_vmap_step(self, child):
        assert child["vmap_equal"], (child["losses"], child["vmap_losses"])

    def test_iterations_shrink_with_devices(self, child):
        it = child["iterations"]
        assert it["1"] >= it["2"] >= it["4"]
