"""The benchmark's graph generator (bench/graphs.py) at a small scale."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import graphs  # noqa: E402

DS = {"name": "small", "num_vertices": 5000, "mean_in_degree": 24.0,
      "feat_dim": 12, "num_classes": 7, "train_frac": 0.1, "graph_seed": 3}


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    return graphs.load(DS, workers=1,
                       cache_dir=tmp_path_factory.mktemp("graphs"))


def _edges(arrays):
    indptr = arrays["indptr"]
    dst = np.repeat(np.arange(len(indptr) - 1), np.diff(indptr))
    return np.asarray(arrays["indices"], np.int64), dst


def test_mean_degree_is_the_published_one(small):
    arrays, meta = small
    V = DS["num_vertices"]
    assert arrays["indptr"][-1] == 2 * graphs.target_edges(DS)
    assert abs(meta["mean_in_degree"] - DS["mean_in_degree"]) \
        <= 0.01 * DS["mean_in_degree"]
    assert meta["vertices"] == V == len(arrays["indptr"]) - 1


def test_no_self_loops_duplicates_and_both_directions(small):
    arrays, _ = small
    V = DS["num_vertices"]
    src, dst = _edges(arrays)
    assert not np.any(src == dst)
    key = dst * V + src
    assert np.all(np.diff(key) > 0)  # rows by dst, sources ascending, unique
    assert np.array_equal(np.sort(src * V + dst), key)  # symmetric


def test_degrees_are_skewed(small):
    arrays, meta = small
    deg = np.diff(arrays["indptr"])
    assert deg.max() > 10 * DS["mean_in_degree"]
    assert meta["isolated_share"] == pytest.approx(np.mean(deg == 0))


def test_features_labels_and_split_as_build_graph(small):
    arrays, _ = small
    V, F = DS["num_vertices"], DS["feat_dim"]
    feats, labels = np.asarray(arrays["features"]), arrays["labels"]
    assert feats.shape == (V, F) and feats.dtype == np.float32
    assert labels.min() >= 0 and labels.max() < DS["num_classes"]
    marked = feats[np.arange(V), labels % F]
    assert abs(marked.mean() - 2.0) < 0.1
    assert len(arrays["train_ids"]) == int(V * DS["train_frac"])
    assert np.all(np.diff(arrays["train_ids"]) > 0)


def test_deterministic_per_config_seed_and_worker_count(small, tmp_path):
    arrays, _ = small
    again, _ = graphs.load(DS, workers=2, cache_dir=tmp_path / "a")
    for name in graphs.ARRAYS:
        assert np.array_equal(np.asarray(arrays[name]),
                              np.asarray(again[name])), name
    other, _ = graphs.load(dict(DS, graph_seed=4), workers=1,
                           cache_dir=tmp_path / "b")
    assert not np.array_equal(np.asarray(arrays["indices"][:1000]),
                              np.asarray(other["indices"][:1000]))


def test_cache_is_reused(small, tmp_path):
    graphs.load(DS, workers=1, cache_dir=tmp_path)
    path = graphs.cache_path(DS, tmp_path)
    stamp = (path / "indices.npy").stat().st_mtime_ns
    graphs.load(DS, workers=1, cache_dir=tmp_path)
    assert (path / "indices.npy").stat().st_mtime_ns == stamp
