"""Host-side block-CSR / compact-COO layout builders (pure numpy, NO jax).

These are the stage-2b preprocessing routines that feed the Pallas SpMM in
``kernels/aggregate.py``. They live in their own jax-free module because the
multi-process sampling service (``core/sampler_pool.py``) runs them inside
sampler WORKER processes: a worker imports only numpy + this module + the
sampler, so spawning N workers never pays (or races on) jax initialization.
``kernels/aggregate.py`` re-exports every name for its existing importers.

Two builders feed the kernel:

* ``build_block_csr`` / ``build_block_csr_pair`` — the original DENSE path:
  materializes the (Nd, max_blk, 128, 128) tiles in numpy, ~64 KB per block
  slot. Kept for tests and as the reference the compact path must match
  bit-for-bit.
* ``build_block_coo_pair`` — the COMPACT edge-centric path (the hot path):
  emits only per-edge (tile_id, tile_off, value) triples — 12 B per edge for
  A, 20 B with the A^T coordinates (values shared) — derived from ONE sort
  of the edge block keys; tiles are densified ON DEVICE right before the
  SpMM (``kernels/aggregate.densify_tiles``). With ``edge_stream=True`` the
  triples are additionally RE-SORTED into per-tile contiguous segments with
  CSR-style ``tile_seg`` offsets over the tile slots, so the edge-streaming
  Pallas kernel (``kernels/aggregate.aggregate_edges``) can densify each
  128x128 tile in a VMEM scratch inside the grid step — no dense tile
  tensor is ever materialized in device HBM.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

BLK = 128
# the streaming kernels DMA the edge stream in windows of this many edges,
# starting on multiples of it (Mosaic slices lanes at tile boundaries), so
# every per-layer edge capacity is rounded up to a multiple of it
EDGE_CHUNK = 128

# aggregate_backend values that consume the per-tile SEGMENT layout (the
# ``edge_stream=True`` builder output) instead of the scatter-densify
# triples. Lives here — not in gnn/models.py — because the sampler-pool
# codec and its worker processes must branch on it without importing jax.
EDGE_STREAM_BACKENDS = ("pallas_edges", "pallas_fused")


def build_block_csr(edge_src: np.ndarray, edge_dst: np.ndarray,
                    edge_mask: np.ndarray, n_src: int, n_dst: int,
                    values: np.ndarray | None = None,
                    max_blk: int | None = None):
    """Edge list -> padded block-CSR (numpy, host-side preprocessing).

    Returns (blocks (Nd, max_blk, BLK, BLK) f32, cols (Nd, max_blk) i32,
    padded src row count). A[dst, src] = value (default 1).

    ``max_blk`` pins the nonzero-blocks-per-row capacity to a STATIC value so
    every mini-batch of a fixed sampler config produces identically-shaped
    arrays (one compiled executable, no per-batch re-jit). Unused slots keep
    all-zero tiles pointing at source block 0 and contribute nothing."""
    n_srcb = (n_src + BLK - 1) // BLK
    n_dstb = (n_dst + BLK - 1) // BLK
    src = np.asarray(edge_src)[np.asarray(edge_mask)]
    dst = np.asarray(edge_dst)[np.asarray(edge_mask)]
    val = (np.ones(len(src), np.float32) if values is None
           else np.asarray(values)[np.asarray(edge_mask)].astype(np.float32))
    bs, bd = src // BLK, dst // BLK
    keys = bd.astype(np.int64) * n_srcb + bs
    uniq, inv = np.unique(keys, return_inverse=True)
    # per dst block: which src blocks are nonzero
    blk_dst = (uniq // n_srcb).astype(np.int32)
    blk_src = (uniq % n_srcb).astype(np.int32)
    counts = np.bincount(blk_dst, minlength=n_dstb)
    need = max(1, int(counts.max()) if len(uniq) else 0)
    if max_blk is None:
        max_blk = need
    elif need > max_blk:
        raise ValueError(f"max_blk={max_blk} < required {need}")
    blocks = np.zeros((n_dstb, max_blk, BLK, BLK), np.float32)
    cols = np.zeros((n_dstb, max_blk), np.int32)
    # uniq is sorted, so entries are grouped by dst block: the slot of entry
    # u is its rank within its group (vectorized cursor).
    group_start = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot_of = (np.arange(len(uniq)) - group_start[blk_dst]).astype(np.int32)
    cols[blk_dst, slot_of] = blk_src
    np.add.at(blocks,
              (bd.astype(np.int32), slot_of[inv], dst % BLK, src % BLK), val)
    return blocks, cols, n_srcb * BLK


def build_block_csr_pair(edge_src: np.ndarray, edge_dst: np.ndarray,
                         edge_mask: np.ndarray, n_src: int, n_dst: int,
                         values: np.ndarray | None = None,
                         max_blk: int | None = None,
                         max_blk_t: int | None = None):
    """Forward layout A plus the transposed layout A^T in one call.

    The backward pass of ``out = A @ h`` is ``dh = A^T @ dout`` — on the
    FPGA the same scatter-gather array streams the transposed adjacency; here
    the transpose is a second block-CSR built over the PADDED dimensions so
    the cotangent shapes line up exactly with the primal shapes.

    Returns (blocks, cols, blocks_t, cols_t, n_src_pad)."""
    blocks, cols, n_src_pad = build_block_csr(
        edge_src, edge_dst, edge_mask, n_src, n_dst, values, max_blk)
    n_dst_pad = blocks.shape[0] * BLK
    blocks_t, cols_t, _ = build_block_csr(
        edge_dst, edge_src, edge_mask, n_dst_pad, n_src_pad, values, max_blk_t)
    return blocks, cols, blocks_t, cols_t, n_src_pad


# ---------------------------------------------------------------------------
# Compact edge-centric layout (host side)
# ---------------------------------------------------------------------------

def build_block_coo_pair(edge_src: np.ndarray, edge_dst: np.ndarray,
                         edge_mask: np.ndarray, n_src: int, n_dst: int,
                         values: np.ndarray | None = None,
                         max_blk: int | None = None,
                         max_blk_t: int | None = None,
                         edge_stream: bool = False) -> dict:
    """Single-pass compact layout for A AND A^T from one edge-key sort.

    Instead of materializing dense (Nd, max_blk, BLK, BLK) tiles host-side,
    emit per-edge coordinates into the tile array:

      tile_id[e]  = dst_block(e) * max_blk + slot(e)      (which tile)
      tile_off[e] = (dst % BLK) * BLK + (src % BLK)       (cell within tile)
      val[e]      = edge value (0.0 for masked/padded edges)

    plus the ``cols`` scalar-prefetch table the kernel already consumes.
    Masked edges keep tile_id = tile_off = 0 with val 0.0 — a zero add into
    an existing cell — so every array keeps its STATIC padded length.

    The transposed layout (``*_t`` keys, consumed by the custom VJP) is
    derived from the SAME ``np.unique`` over the E-length block keys: the
    unique (dst_blk, src_blk) pairs are re-ranked by (src_blk, dst_blk) — an
    O(U log U) argsort over the U unique blocks, U << E — instead of paying a
    second full E-length sort as ``build_block_csr_pair`` does. Densifying
    the result is bit-identical to two independent ``build_block_csr`` calls
    (tests/test_pipeline.py property test).

    Returns a dict with keys ``tile_id, tile_off, val, cols, tile_id_t,
    tile_off_t, cols_t, n_src_pad``.

    ``edge_stream=True`` (the ``aggregate_backend="pallas_edges"`` layout)
    re-sorts the per-edge arrays into PER-TILE CONTIGUOUS SEGMENTS — a
    stable sort by ``tile_id`` (and, independently, by ``tile_id_t`` for the
    transpose, which therefore needs its own ``val_t`` copy) with masked
    edges pushed past the last real segment — and adds the CSR-style offsets
    ``tile_seg`` (``n_dstb * max_blk + 1``) / ``tile_seg_t``
    (``n_srcb * max_blk_t + 1``): tile ``t``'s edges occupy
    ``sorted_arrays[tile_seg[t]:tile_seg[t + 1]]``. The edge-streaming
    Pallas kernel consumes exactly these segments, one VMEM tile
    densification per grid step, and never touches ``tile_id`` itself.
    Within a cell, multi-edges keep their original edge order (stable sort),
    so densifying the sorted triples stays bit-identical to densifying the
    unsorted ones whenever cells are single-edge (the sampler's contract:
    distinct (src, dst) pairs per layer).
    """
    n_srcb = (n_src + BLK - 1) // BLK
    n_dstb = (n_dst + BLK - 1) // BLK
    src = np.asarray(edge_src).astype(np.int64)
    dst = np.asarray(edge_dst).astype(np.int64)
    mask = np.asarray(edge_mask).astype(bool)
    E = len(src)
    if values is None:
        val = mask.astype(np.float32)
    else:
        val = np.where(mask, np.asarray(values), 0.0).astype(np.float32)
    src = np.where(mask, src, 0)
    dst = np.where(mask, dst, 0)
    bs, bd = src // BLK, dst // BLK

    # THE single sort: unique (dst_blk, src_blk) keys over the real edges.
    keys = bd * n_srcb + bs
    uniq, inv = np.unique(keys[mask], return_inverse=True)
    U = len(uniq)
    blk_dst = uniq // n_srcb
    blk_src = uniq % n_srcb

    # forward slots: uniq is sorted by (dst_blk, src_blk), so the slot of a
    # block is its rank within its dst group (vectorized cursor).
    counts = np.bincount(blk_dst, minlength=n_dstb)
    need = int(counts.max()) if U else 0
    if max_blk is None:
        max_blk = max(1, need)
    elif need > max_blk:
        raise ValueError(f"max_blk={max_blk} < required {need}")
    group_start = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot_of = np.arange(U) - group_start[blk_dst]
    cols = np.zeros((n_dstb, max_blk), np.int32)
    cols[blk_dst, slot_of] = blk_src.astype(np.int32)
    tile_id = np.zeros(E, np.int32)
    tile_id[mask] = (blk_dst[inv] * max_blk + slot_of[inv]).astype(np.int32)
    tile_off = np.where(mask, (dst % BLK) * BLK + src % BLK,
                        0).astype(np.int32)

    # transpose slots: re-rank the SAME U blocks by (src_blk, dst_blk).
    order_t = np.argsort(blk_src * n_dstb + blk_dst)
    bs_t, bd_t = blk_src[order_t], blk_dst[order_t]
    counts_t = np.bincount(bs_t, minlength=n_srcb)
    need_t = int(counts_t.max()) if U else 0
    if max_blk_t is None:
        max_blk_t = max(1, need_t)
    elif need_t > max_blk_t:
        raise ValueError(f"max_blk_t={max_blk_t} < required {need_t}")
    group_start_t = np.concatenate([[0], np.cumsum(counts_t)[:-1]])
    slot_of_t = np.arange(U) - group_start_t[bs_t]
    cols_t = np.zeros((n_srcb, max_blk_t), np.int32)
    cols_t[bs_t, slot_of_t] = bd_t.astype(np.int32)
    slot_by_uniq = np.empty(U, np.int64)
    slot_by_uniq[order_t] = slot_of_t
    tile_id_t = np.zeros(E, np.int32)
    tile_id_t[mask] = (blk_src[inv] * max_blk_t
                       + slot_by_uniq[inv]).astype(np.int32)
    tile_off_t = np.where(mask, (src % BLK) * BLK + dst % BLK,
                          0).astype(np.int32)

    out = {"tile_id": tile_id, "tile_off": tile_off, "val": val,
           "cols": cols, "tile_id_t": tile_id_t, "tile_off_t": tile_off_t,
           "cols_t": cols_t, "n_src_pad": n_srcb * BLK}
    if edge_stream:
        out.update(_edge_stream_sort(out, mask, n_dstb * max_blk,
                                     n_srcb * max_blk_t))
    return out


def _edge_stream_sort(coo: dict, mask: np.ndarray, n_tiles: int,
                      n_tiles_t: int) -> dict:
    """Re-sort the compact triples into per-tile contiguous segments.

    Masked/padded edges sort past every real segment (key = n_tiles), so the
    static E-length arrays keep their shape while ``tile_seg[-1]`` — the
    number of real edges — never points at them. The sort is STABLE: edges
    of one tile (and of one cell) keep their original relative order."""
    sorted_fields = {}
    for suffix, n_t in (("", n_tiles), ("_t", n_tiles_t)):
        tid = coo[f"tile_id{suffix}"]
        order = np.argsort(np.where(mask, tid, n_t), kind="stable")
        seg = np.zeros(n_t + 1, np.int32)
        np.cumsum(np.bincount(tid[mask], minlength=n_t), out=seg[1:])
        sorted_fields[f"tile_id{suffix}"] = tid[order]
        sorted_fields[f"tile_off{suffix}"] = coo[f"tile_off{suffix}"][order]
        sorted_fields[f"val{suffix}"] = coo["val"][order]
        sorted_fields[f"tile_seg{suffix}"] = seg
    return sorted_fields


def compact_layout_bytes(n_edges: int, n_dstb: int, max_blk: int,
                         n_srcb: int, max_blk_t: int) -> int:
    """Host->device bytes per batch for one layer's compact layout: three
    4-byte per-edge arrays for A (tile_id, tile_off, val), two more for A^T
    (the values are shared), plus the two cols tables."""
    return 5 * 4 * n_edges + 4 * (n_dstb * max_blk + n_srcb * max_blk_t)


def edge_stream_layout_bytes(n_edges: int, n_dstb: int, max_blk: int,
                             n_srcb: int, max_blk_t: int) -> int:
    """Host->device bytes per batch for one layer's EDGE-STREAMING layout
    (``aggregate_backend="pallas_edges"``): the device consumes two 4-byte
    per-edge arrays per direction — (tile_off, val) for A and an
    independently-sorted (tile_off_t, val_t) for A^T; ``tile_id`` never
    crosses, the CSR-style tile_seg offsets replace it — plus the two
    offsets arrays and the two cols tables."""
    return (4 * 4 * n_edges
            + 4 * (n_dstb * max_blk + 1 + n_srcb * max_blk_t + 1)
            + 4 * (n_dstb * max_blk + n_srcb * max_blk_t))


def dense_layout_bytes(n_edges: int, n_dstb: int, max_blk: int,
                       n_srcb: int, max_blk_t: int) -> int:
    """Host->device bytes per batch for one layer's DENSE layout (the
    pre-compact path): full 64 KB tiles for A and A^T plus cols tables."""
    return (4 * (n_dstb * max_blk + n_srcb * max_blk_t) * BLK * BLK
            + 4 * (n_dstb * max_blk + n_srcb * max_blk_t))


def densify_tiles_np(tile_id: np.ndarray, tile_off: np.ndarray,
                     val: np.ndarray, n_tile_rows: int, max_blk: int
                     ) -> np.ndarray:
    """Numpy twin of ``aggregate.densify_tiles`` (same accumulation order as
    the dense builder's ``np.add.at``) — used by tests for bit-identity.

    The scatter indexes 2-D ``(tile, cell)`` — NEVER the flattened
    ``tile_id * BLK*BLK + tile_off`` product, which overflows int32 once the
    layout exceeds 2**31 / BLK**2 = 131072 tile slots (large fanout/batch
    configs). Each coordinate stays well inside int32 on its own."""
    tiles = np.zeros((n_tile_rows * max_blk, BLK * BLK), np.float32)
    np.add.at(tiles, (tile_id, tile_off), val)
    return tiles.reshape(n_tile_rows, max_blk, BLK, BLK)


# ---------------------------------------------------------------------------
# Shared per-config capacity planning + per-batch layout build
# ---------------------------------------------------------------------------
# The trainer AND the sampler-pool workers must agree exactly on the static
# block-CSR capacities and on how a MiniBatch's edge lists turn into layout
# arrays, so both paths call the two functions below (bit-identical layouts
# wherever the batch is built).

def block_capacities(cfg) -> List[Tuple[int, int, int, int, int]]:
    """Static per-layer block-CSR capacities for a sampler config.

    Returns one ``(n_src, n_dst, max_blk, max_blk_t, e_cap)`` tuple per
    layer. A dst block holds <= BLK * fanout edges, so it can touch at most
    that many distinct src blocks; the transpose has no fanout bound on its
    rows (a source may feed arbitrarily many destinations). ``e_cap`` is
    the layer's edge capacity rounded up to a multiple of EDGE_CHUNK;
    ``build_layer_layouts`` pads the edge lists to it. One shape per
    config => one compiled executable across the epoch."""
    from repro.core.sampler import layer_capacities  # local: no jax either
    n_caps, e_caps = layer_capacities(cfg)
    fans = cfg.fanouts[::-1]  # layer order matches n_caps
    caps = []
    for l in range(cfg.num_layers):
        n_srcb = (n_caps[l] + BLK - 1) // BLK
        n_dstb = (n_caps[l + 1] + BLK - 1) // BLK
        max_blk = min(n_srcb, BLK * fans[l])
        max_blk_t = n_dstb
        caps.append((n_caps[l], n_caps[l + 1], max_blk, max_blk_t,
                     -(-e_caps[l] // EDGE_CHUNK) * EDGE_CHUNK))
    return caps


def densified_tile_bytes(caps: List[Tuple[int, int, int, int, int]]) -> int:
    """Transient DEVICE bytes per batch once the compact triples are
    densified into (Nd, max_blk, BLK, BLK) + transpose tiles on device."""
    total = 0
    for n_src, n_dst, max_blk, max_blk_t, _ in caps:
        n_srcb = (n_src + BLK - 1) // BLK
        n_dstb = (n_dst + BLK - 1) // BLK
        total += (n_dstb * max_blk + n_srcb * max_blk_t) * BLK * BLK * 4
    return total


LAYOUT_KEYS = ("tile_id", "tile_off", "val", "cols",
               "tile_id_t", "tile_off_t", "cols_t")
# the edge-streaming kernel never reads tile_id — the CSR-style segment
# offsets replace it — so the payload drops both (e_cap,) i32 arrays and
# gains val_t + the two (n_tiles + 1,) offsets instead (16 B/edge on the
# wire vs the densify path's 20)
EDGE_STREAM_KEYS = ("tile_off", "val", "cols", "tile_off_t", "cols_t",
                    "val_t", "tile_seg", "tile_seg_t")


def build_layer_layouts(edge_src: List[np.ndarray],
                        edge_dst: List[np.ndarray],
                        edge_mask: List[np.ndarray],
                        caps: List[Tuple[int, int, int, int, int]],
                        kind: Optional[str],
                        edge_stream: bool = False) -> dict:
    """Per-layer COMPACT block-CSR layout build for one mini-batch (fwd +
    transpose from one sort — ``build_block_coo_pair``). ``kind`` is the
    aggregation semantic ("mean" bakes 1/deg into the edge values; "sum"
    ships raw 1.0 weights). Shapes are pinned by ``caps``, so every batch of
    a config reuses one compiled executable. ``edge_stream`` adds the
    per-tile segment ordering + CSR offsets the edge-streaming kernel
    consumes (``aggregate_backend="pallas_edges"``)."""
    keys = EDGE_STREAM_KEYS if edge_stream else LAYOUT_KEYS
    out: dict = {f"agg_{k}": [] for k in keys}
    for l, (n_src, n_dst, max_blk, max_blk_t, e_cap) in enumerate(caps):
        pad = (0, e_cap - len(edge_src[l]))  # masked tail up to e_cap
        src, dst = np.pad(edge_src[l], pad), np.pad(edge_dst[l], pad)
        mask = np.pad(edge_mask[l], pad)
        vals = None
        if kind == "mean":
            deg = np.bincount(dst[mask], minlength=n_dst)
            vals = 1.0 / np.maximum(deg[dst], 1.0)
        coo = build_block_coo_pair(src, dst, mask, n_src, n_dst, vals,
                                   max_blk=max_blk, max_blk_t=max_blk_t,
                                   edge_stream=edge_stream)
        for k in keys:
            out[f"agg_{k}"].append(coo[k])
    return out
