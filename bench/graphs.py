"""The benchmark's own graphs: RMAT topology at a dataset's published degree.

A configuration names its dataset's published statistics (vertex count,
mean in-degree, feature width, classes). This module draws a graph with
those statistics from the configuration's own fixed seed, so the graph
belongs to the configuration the way a downloaded dataset would:

* skewed degrees from the Graph500 RMAT recursion (a=0.57, b=0.19, c=0.19)
  over 2**ceil(log2 V) ids, relabelled by a seeded permutation and folded
  onto the V published vertices (id * V >> scale, so every vertex takes one
  or two RMAT ids, spread at random);
* undirected and stored in both directions, no self loops, no duplicate
  edges, exactly round(V * mean_in_degree / 2) undirected edges (pairs are
  drawn in seeded chunks until enough distinct ones exist, and a seeded
  subset of the surplus is dropped);
* CSR over in-edges with each row's sources ascending, features and labels
  built as ``repro.data.graphs.build_graph`` builds them (standard normal
  features plus 2.0 at column ``label % F``, uniform labels, a 10% train
  split).

The RMAT chunks and the feature rows are drawn in worker processes, each
chunk from its own ``SeedSequence``, so the graph is the same whatever the
worker count. The arrays are cached under ``bench/.cache/graphs`` (listed
in ``.gitignore``); later runs map them from disk.

This module is numpy-only: it imports nothing of the system under test.
"""
from __future__ import annotations

import hashlib
import json
import multiprocessing
import os
import shutil
import time
from pathlib import Path
from typing import Dict, Optional, Tuple

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
CACHE_DIR = BENCH_DIR / ".cache" / "graphs"
GEN_VERSION = 1
CHUNK = 1 << 22          # RMAT pairs per seeded chunk
ROW_CHUNK = 1 << 17      # feature rows per seeded chunk
ARRAYS = ("indptr", "indices", "features", "labels", "train_ids")


def _scale(num_vertices: int) -> int:
    return max(1, int(num_vertices - 1).bit_length())


def _rmat_keys(ds: dict, idx: int) -> np.ndarray:
    """Undirected keys ``min * V + max`` of RMAT chunk ``idx`` (self loops
    dropped). 16-bit draws per level: the quadrant probabilities are met to
    1/65536."""
    V = int(ds["num_vertices"])
    scale = _scale(V)
    a, b, c = ds.get("rmat", (0.57, 0.19, 0.19))
    ta, tab, tabc = (int(round(x * 65536)) for x in (a, a + b, a + b + c))
    rng = np.random.default_rng(np.random.SeedSequence(
        (int(ds["graph_seed"]), 1, idx)))
    n = chunk_pairs(ds)
    src = np.zeros(n, np.int64)
    dst = np.zeros(n, np.int64)
    for bit in range(scale):
        r = rng.integers(0, 65536, n, dtype=np.uint16)
        s_bit = r >= tab
        d_bit = ((r >= ta) & (r < tab)) | (r >= tabc)
        src |= s_bit.astype(np.int64) << bit
        dst |= d_bit.astype(np.int64) << bit
    perm = np.random.default_rng(np.random.SeedSequence(
        (int(ds["graph_seed"]), 0))).permutation(1 << scale)
    u = (perm[src] * V) >> scale
    v = (perm[dst] * V) >> scale
    keep = u != v
    u, v = u[keep], v[keep]
    return np.minimum(u, v) * V + np.maximum(u, v)


def _feature_rows(args) -> None:
    """Fill rows [lo, hi) of the cached feature matrix in place."""
    path, labels_path, seed, lo, hi, idx = args
    feats = np.load(path, mmap_mode="r+")
    labels = np.load(labels_path, mmap_mode="r")[lo:hi]
    rng = np.random.default_rng(np.random.SeedSequence((seed, 2, idx)))
    x = rng.standard_normal((hi - lo, feats.shape[1]), dtype=np.float32)
    x[np.arange(hi - lo), labels % feats.shape[1]] += 2.0
    feats[lo:hi] = x
    feats.flush()


def chunk_pairs(ds: dict) -> int:
    """RMAT pairs per chunk: ``CHUNK``, or fewer for a small graph, so that
    little surplus is dropped."""
    return min(CHUNK, 1 << max(10, target_edges(ds).bit_length() - 3))


def target_edges(ds: dict) -> int:
    """Undirected edges that give the published mean in-degree."""
    return int(round(ds["num_vertices"] * ds["mean_in_degree"] / 2))


def _pool(workers: int):
    return multiprocessing.get_context("spawn").Pool(workers)


def _map(fn, items, workers: int):
    if workers <= 1 or len(items) <= 1:
        return [fn(it) for it in items]
    with _pool(min(workers, len(items))) as pool:
        return pool.map(fn, items, chunksize=1)


def _rmat_job(args):
    ds, idx = args
    return _rmat_keys(ds, idx)


def generate(ds: dict, out_dir: Path, workers: int) -> dict:
    """Draw the graph of dataset statistics ``ds`` into ``out_dir``
    (``.npy`` arrays plus ``meta.json``); returns the meta record."""
    t0 = time.perf_counter()
    V = int(ds["num_vertices"])
    want = target_edges(ds)
    keys = np.empty(0, np.int64)
    next_chunk = 0
    while len(keys) < want:
        # chunks still to draw, assuming the distinct share seen so far
        got_per_chunk = (len(keys) / next_chunk if next_chunk
                         else chunk_pairs(ds) * 0.9)
        n_new = max(1, int(np.ceil((want - len(keys)) * 1.05
                                   / max(got_per_chunk, 1.0))))
        new = _map(_rmat_job, [(ds, next_chunk + i) for i in range(n_new)],
                   workers)
        next_chunk += n_new
        keys = np.unique(np.concatenate([keys] + new))
    rng = np.random.default_rng(np.random.SeedSequence(
        (int(ds["graph_seed"]), 3)))
    surplus = len(keys) - want
    if surplus:
        keys = np.delete(keys, rng.choice(len(keys), surplus, replace=False))
    u, v = np.divmod(keys, V)
    del keys
    # both directions, keyed by (dst, src): one sort gives the CSR rows
    # with each row's sources ascending
    dkey = np.concatenate([v * V + u, u * V + v])
    del u, v
    dkey.sort()
    dst, src = np.divmod(dkey, V)
    del dkey
    counts = np.bincount(dst, minlength=V)
    del dst
    indptr = np.zeros(V + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    indices = src.astype(np.int32)
    del src
    labels = rng.integers(0, int(ds["num_classes"]), V).astype(np.int32)
    n_train = max(1, int(V * float(ds.get("train_frac", 0.1))))
    train_ids = np.sort(rng.choice(V, n_train, replace=False)).astype(
        np.int32)

    out_dir.mkdir(parents=True)
    for name, arr in (("indptr", indptr), ("indices", indices),
                      ("labels", labels), ("train_ids", train_ids)):
        np.save(out_dir / f"{name}.npy", arr)
    feat_path = out_dir / "features.npy"
    feats = np.lib.format.open_memmap(feat_path, mode="w+", dtype=np.float32,
                                      shape=(V, int(ds["feat_dim"])))
    del feats
    jobs = [(str(feat_path), str(out_dir / "labels.npy"),
             int(ds["graph_seed"]), lo, min(lo + ROW_CHUNK, V), i)
            for i, lo in enumerate(range(0, V, ROW_CHUNK))]
    _map(_feature_rows, jobs, workers)
    meta = {"generator_version": GEN_VERSION, "dataset": ds,
            "vertices": V, "directed_edges": int(indptr[-1]),
            "mean_in_degree": float(indptr[-1] / V),
            "isolated_share": float(np.mean(counts == 0)),
            "max_in_degree": int(counts.max()),
            "share_in_degree_ge_25": float(np.mean(counts >= 25)),
            "rmat_chunks": next_chunk,
            "generation_s": time.perf_counter() - t0}
    (out_dir / "meta.json").write_text(json.dumps(meta, indent=1))
    return meta


def cache_path(ds: dict, cache_dir: Optional[Path] = None) -> Path:
    cache_dir = CACHE_DIR if cache_dir is None else cache_dir
    digest = hashlib.sha256(json.dumps(
        [GEN_VERSION, CHUNK, ROW_CHUNK, ds], sort_keys=True).encode()
    ).hexdigest()[:12]
    return cache_dir / f"{ds['name']}-{digest}"


def load(ds: dict, workers: int = 1, cache_dir: Optional[Path] = None
         ) -> Tuple[Dict[str, np.ndarray], dict]:
    """The graph's arrays (``indices`` and ``features`` mapped from disk)
    and its meta record, generating and caching it on first use."""
    path = cache_path(ds, cache_dir)
    if not (path / "meta.json").exists():
        tmp = path.with_name(path.name + ".tmp")
        shutil.rmtree(tmp, ignore_errors=True)
        generate(ds, tmp, workers)
        os.replace(tmp, path)
    meta = json.loads((path / "meta.json").read_text())
    arrays = {name: np.load(path / f"{name}.npy",
                            mmap_mode="r" if name in ("indices", "features")
                            else None)
              for name in ARRAYS}
    return arrays, meta
