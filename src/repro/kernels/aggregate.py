"""Aggregate kernel: block-CSR SpMM on the MXU (the paper's scatter-gather
PE array, re-thought for the TPU memory hierarchy — DESIGN.md §3).

FPGA original: n scatter-gather PEs stream edges, route messages through an
n-lane network (the n*log n LUT term of Eq. 2), accumulate per-dst in BRAM.
TPU adaptation: the sampled adjacency is tiled into 128x128 blocks; per-edge
routing becomes per-BLOCK gathers driven by a scalar-prefetched block-column
index (the BlockSpec index_map reads it BEFORE the grid step, so the DMA of
the source feature tile overlaps compute — the paper's pipelined
load/compute, Eq. 6). Each nonzero block is one MXU matmul; padding blocks
are all-zero and contribute nothing.

Layout (built by ``kernels/layout.build_block_csr``):
  blocks  (n_dst_blocks, max_blk, 128, 128)  dense adjacency tiles
  cols    (n_dst_blocks, max_blk) int32      source block index (0-padded)
  h_in    (n_src_blocks*128, F)              source features

Grid: (n_dst_blocks, F/fb, max_blk); the last axis is sequential with an
fp32 VMEM accumulator.

The host-side layout builders (dense ``build_block_csr`` / compact
``build_block_coo_pair``) live in ``kernels/layout.py`` — a PURE-NUMPY
module, because the multi-process sampling service runs them inside sampler
worker processes that must never import jax. They are re-exported here for
existing importers. The compact path ships only ~20 B/edge; the dense tiles
are densified ON DEVICE by ``densify_tiles`` (a jit'd scatter-add) right
before the Pallas SpMM.
"""
from __future__ import annotations

import functools
import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import (  # noqa: F401  (re-exported host builders)
    BLK, EDGE_CHUNK, EDGE_STREAM_BACKENDS, block_capacities,
    build_block_coo_pair, build_block_csr, build_block_csr_pair,
    build_layer_layouts, compact_layout_bytes, dense_layout_bytes,
    densified_tile_bytes, densify_tiles_np, edge_stream_layout_bytes)
from repro.kernels.update_mlp import update_epilogue


def densify_tiles(tile_id: jax.Array, tile_off: jax.Array, val: jax.Array,
                  n_tile_rows: int, max_blk: int) -> jax.Array:
    """Device-side tile densification: scatter-add the compact per-edge
    triples into (n_tile_rows, max_blk, BLK, BLK) dense tiles. Runs inside
    the jit'd step (XLA scatter), so the host ships ~20 B/edge instead of
    64 KB per block slot. Masked edges carry val = 0 at cell (0, 0).

    The scatter indexes 2-D ``(tile, cell)``: the flattened
    ``tile_id * BLK*BLK + tile_off`` form silently overflowed int32 past
    2**31 / BLK**2 = 131072 tile slots (and int64 is unavailable without
    jax x64), whereas each 2-D coordinate stays int32-safe on its own for
    any layout whose tile COUNT fits int32."""
    tiles = jnp.zeros((n_tile_rows * max_blk, BLK * BLK), jnp.float32)
    tiles = tiles.at[tile_id, tile_off].add(val.astype(jnp.float32))
    return tiles.reshape(n_tile_rows, max_blk, BLK, BLK)


def resolve_interpret(override: bool | None = None) -> bool:
    """Pallas execution mode: compiled Mosaic on real TPU, interpret mode
    elsewhere. ``override`` (e.g. ``GNNModelConfig.kernel_interpret``) pins
    the mode explicitly — set False to force compilation, True to force the
    interpreter even on hardware.

    ``HITGNN_COMPILED_KERNELS=1`` in the environment is the explicit
    compiled-shakedown opt-in: it forces compiled mode everywhere an
    ``override`` hasn't pinned one, so the compiled-vs-interpret smoke test
    (tests/test_compiled_kernels.py, auto-skipped off-TPU) and ad-hoc runs
    on real hardware exercise the Mosaic lowering of every kernel."""
    if override is not None:
        return bool(override)
    if os.environ.get("HITGNN_COMPILED_KERNELS", "") == "1":
        return False
    return jax.default_backend() != "tpu"


def _kernel(cols_ref, a_ref, h_ref, o_ref, acc_ref, *, n_blk: int):
    del cols_ref  # consumed by the index_map (scalar prefetch)
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[0, 0], h_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_blk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad_feature_dim(h_in: jax.Array, feat_block: int):
    """Pick the feature-block width and zero-pad F up to a multiple of it.

    The old fallback (``while F % fb: fb -= 1``) degraded to fb = 1 for
    prime/odd F — a silently SERIALIZED grid of lane-width-1 steps. Instead
    keep fb = min(feat_block, F) and pad F up to the next multiple (the
    padded columns are zeros; callers slice the output back to F), so an
    odd feature width costs one pad/slice, never a degenerate grid.
    Returns (h_padded, F_pad, fb)."""
    F = h_in.shape[1]
    fb = min(feat_block, F)
    F_pad = -(-F // fb) * fb
    if F_pad != F:
        h_in = jnp.pad(h_in, ((0, 0), (0, F_pad - F)))
    return h_in, F_pad, fb


def aggregate_blockcsr(blocks: jax.Array, cols: jax.Array, h_in: jax.Array,
                       *, feat_block: int = 256, interpret: bool = True
                       ) -> jax.Array:
    """out = A @ h_in with A in padded block-CSR form.

    blocks: (Nd, max_blk, BLK, BLK); cols: (Nd, max_blk) i32;
    h_in: (n_src_pad, F). Returns (Nd*BLK, F)."""
    n_dstb, max_blk = cols.shape
    n_src_pad, F = h_in.shape
    h_in, F_pad, fb = _pad_feature_dim(h_in, feat_block)
    grid = (n_dstb, F_pad // fb, max_blk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, BLK, BLK), lambda i, j, k, cols: (i, k, 0, 0)),
            pl.BlockSpec((BLK, fb), lambda i, j, k, cols: (cols[i, k], j)),
        ],
        out_specs=pl.BlockSpec((BLK, fb), lambda i, j, k, cols: (i, j)),
        scratch_shapes=[pltpu.VMEM((BLK, fb), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, n_blk=max_blk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_dstb * BLK, F_pad), h_in.dtype),
        interpret=interpret,
        name="agg_blockcsr",
    )(cols, blocks, h_in)
    return out[:, :F] if F_pad != F else out


# ---------------------------------------------------------------------------
# Differentiable wrapper (training path)
# ---------------------------------------------------------------------------
# ``pallas_call`` has no JVP rule, so the training forward routes through a
# custom VJP: the cotangent of ``A @ h`` w.r.t. ``h`` is ``A^T @ dout``, i.e.
# the SAME kernel over the transposed block-CSR built host-side by
# ``build_block_csr_pair``. The adjacency (blocks/cols) is sampled data, not
# a parameter — its cotangents are symbolic zeros.

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def aggregate_blockcsr_vjp(blocks: jax.Array, cols: jax.Array,
                           blocks_t: jax.Array, cols_t: jax.Array,
                           h_in: jax.Array, feat_block: int = 256,
                           interpret: bool = True) -> jax.Array:
    """Differentiable ``A @ h_in``; backward runs the kernel on (A^T)."""
    return aggregate_blockcsr(blocks, cols, h_in,
                              feat_block=feat_block, interpret=interpret)


def _agg_fwd(blocks, cols, blocks_t, cols_t, h_in, feat_block, interpret):
    out = aggregate_blockcsr(blocks, cols, h_in,
                             feat_block=feat_block, interpret=interpret)
    return out, (blocks, cols, blocks_t, cols_t)


def _agg_bwd(feat_block, interpret, res, g):
    blocks, cols, blocks_t, cols_t = res
    # the kernel computes in fp32; the cotangent of h must come back in the
    # PRIMAL dtype (== the out dtype g carries) or bf16/f16 training breaks
    dh = aggregate_blockcsr(blocks_t, cols_t, g.astype(jnp.float32),
                            feat_block=feat_block,
                            interpret=interpret).astype(g.dtype)
    return (jnp.zeros_like(blocks),
            np.zeros(cols.shape, jax.dtypes.float0),
            jnp.zeros_like(blocks_t),
            np.zeros(cols_t.shape, jax.dtypes.float0),
            dh)


aggregate_blockcsr_vjp.defvjp(_agg_fwd, _agg_bwd)


# ---------------------------------------------------------------------------
# Compact-layout differentiable wrapper (the training hot path)
# ---------------------------------------------------------------------------
# Same contract as ``aggregate_blockcsr_vjp`` but fed by the COMPACT
# edge-centric layout of ``build_block_coo_pair``: the forward densifies A's
# tiles on device and runs the Pallas SpMM; the backward densifies A^T's
# tiles (from the residual compact triples — no dense transpose is ever kept
# live between forward and backward) and runs the same kernel on the
# cotangent. The adjacency is sampled data, not a parameter: every layout
# input gets a zero/float0 cotangent.

@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def aggregate_compact_vjp(tile_id: jax.Array, tile_off: jax.Array,
                          val: jax.Array, cols: jax.Array,
                          tile_id_t: jax.Array, tile_off_t: jax.Array,
                          cols_t: jax.Array, h_in: jax.Array,
                          feat_block: int = 256,
                          interpret: bool = True) -> jax.Array:
    """Differentiable ``A @ h_in`` with A in compact edge-centric form."""
    blocks = densify_tiles(tile_id, tile_off, val, *cols.shape)
    return aggregate_blockcsr(blocks, cols, h_in,
                              feat_block=feat_block, interpret=interpret)


def _agg_compact_fwd(tile_id, tile_off, val, cols, tile_id_t, tile_off_t,
                     cols_t, h_in, feat_block, interpret):
    out = aggregate_compact_vjp(tile_id, tile_off, val, cols, tile_id_t,
                                tile_off_t, cols_t, h_in,
                                feat_block, interpret)
    return out, (tile_id, tile_off, val, cols, tile_id_t, tile_off_t, cols_t)


def _agg_compact_bwd(feat_block, interpret, res, g):
    tile_id, tile_off, val, cols, tile_id_t, tile_off_t, cols_t = res
    blocks_t = densify_tiles(tile_id_t, tile_off_t, val, *cols_t.shape)
    # cast back to the primal dtype (g carries the out dtype == h_in.dtype)
    dh = aggregate_blockcsr(blocks_t, cols_t, g.astype(jnp.float32),
                            feat_block=feat_block,
                            interpret=interpret).astype(g.dtype)

    def f0(a):
        return np.zeros(a.shape, jax.dtypes.float0)

    return (f0(tile_id), f0(tile_off), jnp.zeros_like(val), f0(cols),
            f0(tile_id_t), f0(tile_off_t), f0(cols_t), dh)


aggregate_compact_vjp.defvjp(_agg_compact_fwd, _agg_compact_bwd)


# ---------------------------------------------------------------------------
# Edge-streaming aggregation (tile densification in VMEM)
# ---------------------------------------------------------------------------
# The compact path above still scatter-adds the FULL dense tile tensor in
# device HBM (``densify_tiles``) before the SpMM — the dense footprint the
# compact layout was built to avoid merely moved from PCIe to HBM. The
# paper's scatter-gather PEs stream edges and accumulate per-destination in
# on-chip BRAM (HitGNN §3, Eq. 2/6); these kernels are that datapath on the
# TPU memory hierarchy: the layout builder re-sorts the per-edge triples
# into per-tile contiguous segments (CSR-style ``tile_seg`` offsets over
# the tile slots), and each grid step (i, k) densifies tile (i, k) in VMEM
# — streaming its segment in EDGE_CHUNK-edge windows, turning each window
# into a (rows-one-hot * val)^T @ cols-one-hot MXU outer product — right
# before the tile's matmul. No (Nd, max_blk, 128, 128) tensor ever exists
# in HBM, forward or backward.
#
# Memory placement (what Mosaic accepts at the paper's layer-0 shapes,
# where a whole (n_dstb, max_blk) ``cols`` table or ``tile_seg`` overflows
# SMEM and a whole (1, E) edge stream overflows scoped VMEM):
#   * SMEM holds only the CURRENT dst row's scalar tables — its ``cols``
#     row and its tiles' segment bounds (``seg_lo``/``seg_hi``), blocked by
#     the row index;
#   * the source features stay in HBM (``memory_space=ANY``): each tile's
#     (BLK, F) source block is DMA'd by the kernel, keyed by ``cols``, into
#     a two-slot VMEM buffer, so tile k+1's fetch overlaps tile k's densify;
#   * the edge stream stays in HBM and is DMA'd in EDGE_CHUNK-edge windows
#     that start on a multiple of EDGE_CHUNK (E is padded to one); the
#     validity mask drops a window's edges outside the tile's segment;
#   * padded tile slots (empty segments) fetch and multiply nothing.
# Interpret mode runs the same windows and DMA schedule; only the
# per-window densify differs (a scatter-add for the MXU contraction — equal
# bit for bit under the sampler's distinct-pair contract, see
# ``_densify_scatter``).

def _densify_scatter(a_tile, off, v, valid):
    """Interpret-mode window densify: scatter the window's edges into the
    tile.

    ``off`` IS the flat cell offset inside the BLK x BLK tile, so the window
    densifies as a 1D scatter-add — O(chunk) work instead of the
    chunk x BLK x BLK one-hot contraction the MXU path uses.  Bitwise-equal
    to that contraction whenever tile cells are single-edge (the sampler's
    distinct-pair contract): around the one real product the contraction
    only ever adds +0.0 terms, which are fp32 addition identities for every
    value the cell can hold (a -0.0 edge value lands as +0.0 on the
    0.0-initialised cell under both formulations)."""
    n = off.shape[0]
    tgt = jnp.where(valid, off, BLK * BLK).reshape(n)
    contrib = jnp.where(valid, v, 0.0).reshape(n)
    return a_tile.reshape(-1).at[tgt].add(
        contrib, mode="drop").reshape(BLK, BLK)


def _densify_segment(start, end, off_hbm, val_hbm, win, *,
                     interpret: bool) -> jax.Array:
    """Densify the edge segment ``[start, end)`` into a (BLK, BLK) fp32
    tile.

    The segment streams from HBM through the two-slot VMEM scratch ``win``
    in EDGE_CHUNK-edge windows. The first window starts at ``start``
    rounded down to a multiple of EDGE_CHUNK (Mosaic slices the lane axis
    only at tile boundaries), and the validity mask drops the neighbouring
    segments' edges a window overlaps. The DMA for window c+1 is issued
    BEFORE the wait on window c, so the copy engine fills one slot while
    the MXU consumes the other (the double-buffer timeline in
    ARCHITECTURE.md). An empty segment issues no window."""
    obuf, vbuf, osem, vsem = win
    chunk = EDGE_CHUNK
    first = start // chunk * chunk
    n_win = jnp.where(end > start, (end - first + chunk - 1) // chunk, 0)

    def copies(c):
        slot = jax.lax.rem(c, 2)
        base = pl.multiple_of(first + c * chunk, chunk)
        return (pltpu.make_async_copy(off_hbm.at[0, pl.ds(base, chunk)],
                                      obuf.at[slot], osem.at[slot]),
                pltpu.make_async_copy(val_hbm.at[0, pl.ds(base, chunk)],
                                      vbuf.at[slot], vsem.at[slot]))

    @pl.when(n_win > 0)
    def _first_window():
        for cp in copies(0):
            cp.start()

    def window(c, a_tile):
        @pl.when(c + 1 < n_win)
        def _next_window():
            for cp in copies(c + 1):
                cp.start()

        for cp in copies(c):
            cp.wait()
        slot = jax.lax.rem(c, 2)
        off = obuf[slot].reshape(chunk, 1)
        v = vbuf[slot].reshape(chunk, 1)
        idx = (first + c * chunk
               + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0))
        valid = (idx >= start) & (idx < end)
        if interpret:
            return _densify_scatter(a_tile, off, v, valid)
        lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, BLK), 1)
        rv = jnp.where((off // BLK == lane) & valid, v, 0.0)
        cm = (off % BLK == lane).astype(jnp.float32)
        # a_tile[r, c] += sum_e v_e [row_e == r][col_e == c]: one MXU
        # contraction over the window axis densifies `chunk` edges at once
        return a_tile + jax.lax.dot_general(
            rv, cm, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    return jax.lax.fori_loop(0, n_win, window,
                             jnp.zeros((BLK, BLK), jnp.float32))


class _Stream(NamedTuple):
    """The refs every streaming kernel walks a dst row with: the row's SMEM
    tables, the HBM edge stream and source features, and the two VMEM
    double buffers. Kernels take them as the first six inputs and the last
    six scratch refs (``_stream_specs`` / ``_stream_scratch``)."""
    cols: Any
    seg_lo: Any
    seg_hi: Any
    off: Any
    val: Any
    h: Any
    hbuf: Any
    hsem: Any
    obuf: Any
    vbuf: Any
    osem: Any
    vsem: Any

    @classmethod
    def split(cls, refs):
        """(stream, the kernel's own refs in between)."""
        return cls(*refs[:6], *refs[-6:]), list(refs[6:-6])

    @property
    def win(self):
        return self.obuf, self.vbuf, self.osem, self.vsem

    def nonempty(self, k):
        return self.seg_hi[0, k] > self.seg_lo[0, k]

    def src_copy(self, k):
        slot = jax.lax.rem(k, 2)
        row = pl.multiple_of(self.cols[0, k] * BLK, BLK)
        return pltpu.make_async_copy(self.h.at[pl.ds(row, BLK)],
                                     self.hbuf.at[slot], self.hsem.at[slot])

    def accumulate(self, k, n_blk: int, acc_ref, *, interpret: bool):
        """Grid step (i, k): ``acc += A[i, k] @ h[cols[i, k]]`` for a
        non-empty tile. Issues tile k+1's source-block DMA first (step 0
        also issues its own), so the fetch overlaps this tile's densify;
        an empty tile skips the fetch and the matmul, which would only add
        +0.0 to the accumulator."""
        @pl.when((k == 0) & self.nonempty(0))
        def _fetch_first():
            self.src_copy(0).start()

        nxt = jnp.minimum(k + 1, n_blk - 1)

        @pl.when((k + 1 < n_blk) & self.nonempty(nxt))
        def _fetch_next():
            self.src_copy(nxt).start()

        @pl.when(self.nonempty(k))
        def _tile():
            a_tile = _densify_segment(self.seg_lo[0, k], self.seg_hi[0, k],
                                      self.off, self.val, self.win,
                                      interpret=interpret)
            self.src_copy(k).wait()
            acc_ref[...] += jnp.dot(a_tile, self.hbuf[jax.lax.rem(k, 2)],
                                    preferred_element_type=jnp.float32)


def _stream_specs(max_blk: int) -> list:
    # (n_rows, 1, max_blk) tables: a (1, max_blk) block spans the array's
    # last two dims, which Mosaic accepts for any max_blk
    row = pl.BlockSpec((None, 1, max_blk), lambda i, k: (i, 0, 0),
                       memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return [row, row, row, hbm, hbm, hbm]


def _edge_stream_hbm(tile_off, val) -> list:
    """The (1, E) HBM edge stream, padded to a multiple of EDGE_CHUNK with
    edges past ``seg[-1]`` (never valid) — a no-op for the layouts
    ``build_layer_layouts`` emits, whose capacity is already rounded."""
    pad = -tile_off.shape[0] % EDGE_CHUNK
    return [jnp.pad(tile_off.astype(jnp.int32), (0, pad)).reshape(1, -1),
            jnp.pad(val.astype(jnp.float32), (0, pad)).reshape(1, -1)]


def _stream_operands(tile_off, val, seg, cols, h) -> list:
    """Row tables (``seg`` split into per-tile lo/hi bounds shaped like
    ``cols``) + the HBM edge stream and source features."""
    n, m = cols.shape
    return [cols.reshape(n, 1, m), seg[:-1].reshape(n, 1, m),
            seg[1:].reshape(n, 1, m), *_edge_stream_hbm(tile_off, val), h]


def _stream_scratch(h) -> list:
    return [pltpu.VMEM((2, BLK, h.shape[1]), h.dtype),   # src blocks
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((2, EDGE_CHUNK), jnp.int32),      # edge windows
            pltpu.VMEM((2, EDGE_CHUNK), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,))]


def _pad_lanes(x: jax.Array, axis: int) -> jax.Array:
    """Zero-pad ``axis`` up to a multiple of BLK (MXU lane alignment)."""
    n = x.shape[axis]
    pad = -n % BLK
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _edges_kernel(*refs, n_blk: int, interpret: bool):
    st, (o_ref, acc_ref) = _Stream.split(refs)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    st.accumulate(k, n_blk, acc_ref, interpret=interpret)

    @pl.when(k == n_blk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def aggregate_edges(tile_off: jax.Array, val: jax.Array, seg: jax.Array,
                    cols: jax.Array, h_in: jax.Array, *,
                    interpret: bool = True) -> jax.Array:
    """out = A @ h_in with A streamed from per-tile edge segments.

    tile_off (E,) i32 cell offsets sorted into per-tile segments;
    val (E,) f32 matching edge values; seg (n_dstb * max_blk + 1,) i32
    CSR-style segment offsets over the tile slots (masked/padded edges live
    past seg[-1] and are never read as valid); cols (n_dstb, max_blk) i32
    source-block table; h_in (n_src_pad, F).
    Returns (n_dstb * BLK, F).

    Accumulator discipline matches ``aggregate_blockcsr`` (same tile order
    per dst row, same fp32 VMEM accumulator, same per-tile ``jnp.dot``),
    and a VMEM-densified tile is bit-identical to its scatter-added twin
    whenever tile cells are single-edge (the sampler's distinct-pair
    contract) — so the two backends train bit-identically per seed in
    interpret mode. The compiled path lane-pads F to a multiple of 128."""
    n_dstb, max_blk = cols.shape
    F = h_in.shape[1]
    if tile_off.shape[0] == 0:  # zero-capacity layer: A is empty
        return jnp.zeros((n_dstb * BLK, F), h_in.dtype)
    h_k = h_in if interpret else _pad_lanes(h_in, 1)
    F_pad = h_k.shape[1]
    out = pl.pallas_call(
        functools.partial(_edges_kernel, n_blk=max_blk, interpret=interpret),
        grid=(n_dstb, max_blk),
        in_specs=_stream_specs(max_blk),
        out_specs=pl.BlockSpec((BLK, F_pad), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_dstb * BLK, F_pad), h_in.dtype),
        scratch_shapes=[pltpu.VMEM((BLK, F_pad), jnp.float32),
                        *_stream_scratch(h_k)],
        interpret=interpret,
        name="agg_edges",
    )(*_stream_operands(tile_off, val, seg, cols, h_k))
    return out[:, :F] if F_pad != F else out


# Differentiable wrapper: the cotangent of ``A @ h`` w.r.t. ``h`` is
# ``A^T @ dout`` — the SAME edge-streaming kernel over the independently
# tile-sorted transpose segments (tile_off_t / val_t / seg_t / cols_t).
# The adjacency is sampled data, not a parameter: every layout input gets
# a zero/float0 cotangent, and no dense tile tensor exists in HBM in
# either direction.

@functools.partial(jax.custom_vjp, nondiff_argnums=(9,))
def aggregate_edges_vjp(tile_off: jax.Array, val: jax.Array,
                        seg: jax.Array, cols: jax.Array,
                        tile_off_t: jax.Array, val_t: jax.Array,
                        seg_t: jax.Array, cols_t: jax.Array,
                        h_in: jax.Array, interpret: bool = True
                        ) -> jax.Array:
    """Differentiable ``A @ h_in`` with A in edge-streaming segment form."""
    return aggregate_edges(tile_off, val, seg, cols, h_in,
                           interpret=interpret)


def _agg_edges_fwd(tile_off, val, seg, cols, tile_off_t, val_t, seg_t,
                   cols_t, h_in, interpret):
    out = aggregate_edges_vjp(tile_off, val, seg, cols, tile_off_t, val_t,
                              seg_t, cols_t, h_in, interpret)
    return out, (tile_off, val, seg, cols, tile_off_t, val_t, seg_t, cols_t)


def _agg_edges_bwd(interpret, res, g):
    tile_off, val, seg, cols, tile_off_t, val_t, seg_t, cols_t = res
    # cast back to the primal dtype (g carries the out dtype == h_in.dtype)
    dh = aggregate_edges(tile_off_t, val_t, seg_t, cols_t,
                         g.astype(jnp.float32),
                         interpret=interpret).astype(g.dtype)

    def f0(a):
        return np.zeros(a.shape, jax.dtypes.float0)

    return (f0(tile_off), jnp.zeros_like(val), f0(seg), f0(cols),
            f0(tile_off_t), jnp.zeros_like(val_t), f0(seg_t), f0(cols_t),
            dh)


aggregate_edges_vjp.defvjp(_agg_edges_fwd, _agg_edges_bwd)


# ---------------------------------------------------------------------------
# Fused single-pass datapath: densify + SpMM + update MLP in one grid
# ---------------------------------------------------------------------------
# ``pallas_edges`` holds the zero-densified-HBM record but still runs the
# layer as separate dispatches: aggregate kernel -> (Nd*BLK, F) intermediate
# in HBM -> XLA matmul against the update weights. This kernel is HitGNN's
# full on-chip datapath (and GenGNN's single-pass message passing) on the
# TPU memory hierarchy: each grid step (i, k) streams and densifies tile
# (i, k) exactly like ``_edges_kernel`` (``_Stream.accumulate``) into the
# fp32 row-block accumulator. On the FINAL k-step of each output row-block
# the update MLP runs right there with its weights resident in VMEM
# (``update_mlp.update_epilogue`` — the shared update-stage tail), so the
# aggregated intermediate ``(Nd, BLK, F)`` never exists in HBM.
#
# Bitwise contract (the property tests pin it): with ``act="none"`` and no
# bias — how the GNN layers call it, keeping their bias/activation epilogue
# in XLA, whose reduce strategy is M-dependent and therefore NOT
# bitwise-reproducible from padded shapes — the fused layer term is
# bit-identical in interpret mode to ``pallas_edges`` + the XLA matmul:
# the aggregation reuses the exact tile order and fp32 accumulator, and XLA
# CPU matmuls are row/column-independent and zero-padding-neutral (measured
# properties; see ARCHITECTURE.md "fused stage-2c datapath"). The backward
# ``dw`` contraction accumulates one partial per 128-row dst block, which
# matches the unfused single-dot order whenever the dst capacity fits one
# row block (zero-padded rows are bitwise-neutral); multi-block dst layers
# get allclose, not bitwise, ``dw``.

def _fused_kernel(*refs, n_blk: int, act: str, has_bias: bool,
                  has_self: bool, z_dtype, interpret: bool):
    st, rest = _Stream.split(refs)
    w_ref = rest.pop(0)
    b_ref = rest.pop(0) if has_bias else None
    s_ref = rest.pop(0) if has_self else None
    o_ref, acc_ref = rest
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    st.accumulate(k, n_blk, acc_ref, interpret=interpret)

    @pl.when(k == n_blk - 1)
    def _update():
        # the row-block's aggregate leaves VMEM only THROUGH the update MLP
        z = acc_ref[...].astype(z_dtype)
        if has_self:
            z = z + s_ref[...]
        y = jnp.dot(z, w_ref[...])
        b = b_ref[...] if has_bias else None
        o_ref[...] = update_epilogue(y, b, act).astype(o_ref.dtype)


def _fused_bwd_kernel(*refs, n_blk: int, act: str, has_bias: bool,
                      has_self: bool, z_dtype, interpret: bool):
    st, rest = _Stream.split(refs)
    g_ref = rest.pop(0)
    w_ref = rest.pop(0) if act != "none" else None
    b_ref = rest.pop(0) if act != "none" and has_bias else None
    s_ref = rest.pop(0) if has_self else None
    dw_ref = rest.pop(0)
    db_ref = rest.pop(0) if has_bias else None
    dy_ref = rest.pop(0) if act != "none" else None
    acc_ref, dw_acc = rest[:2]
    db_acc = rest[2] if has_bias else None
    i, k = pl.program_id(0), pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    st.accumulate(k, n_blk, acc_ref, interpret=interpret)

    @pl.when(k == n_blk - 1)
    def _grads():
        # recompute the MLP pre-activation from the VMEM aggregate — it was
        # never saved (and never touched HBM) in the forward
        z = acc_ref[...].astype(z_dtype)
        if has_self:
            z = z + s_ref[...]
        if act == "none":
            dy = g_ref[...]
        else:
            y = jnp.dot(z, w_ref[...])
            if has_bias:
                y = y + b_ref[...].astype(jnp.float32)[None, :]
            if act == "relu":
                dy = g_ref[...] * (y > 0.0).astype(g_ref.dtype)
            elif act == "gelu":
                dy = g_ref[...] * jax.grad(
                    lambda q: jax.nn.gelu(q).sum())(y).astype(g_ref.dtype)
            else:
                raise ValueError(f"unknown activation: {act!r}")
            dy_ref[...] = dy.astype(dy_ref.dtype)
        # dw partial for this row block; the first block ASSIGNS (so a
        # single-block dst — the bitwise-pinned case — is one contraction,
        # not 0 + partial)
        partial = jax.lax.dot_general(z, dy, (((0,), (0,)), ((), ())))

        @pl.when(i == 0)
        def _first():
            dw_acc[...] = partial.astype(jnp.float32)

        @pl.when(i != 0)
        def _accum():
            dw_acc[...] += partial.astype(jnp.float32)

        if has_bias:
            dbp = jnp.sum(dy.astype(jnp.float32), axis=0, keepdims=True)

            @pl.when(i == 0)
            def _db_first():
                db_acc[...] = dbp

            @pl.when(i != 0)
            def _db_accum():
                db_acc[...] += dbp

        @pl.when(i == pl.num_programs(0) - 1)
        def _emit():
            dw_ref[...] = dw_acc[...].astype(dw_ref.dtype)
            if has_bias:
                db_ref[...] = db_acc[...].astype(db_ref.dtype)


def _fused_operands(h_in, w, b, s, interpret):
    """Shared fwd/bwd operand prep: lane-pad the MLP operands.

    Lane padding exists only for Mosaic's 128-lane tiling; interpret mode
    accepts any block width, and the pad columns are all-zero (bitwise
    neutral in every contraction), so the CPU path skips them — at F=64
    that halves the per-grid-step copy and dot volume."""
    if interpret:
        return h_in, w, b, s
    return (_pad_lanes(h_in, 1), _pad_lanes(_pad_lanes(w, 0), 1),
            _pad_lanes(b, 0) if b is not None else None,
            _pad_lanes(s, 1) if s is not None else None)


def aggregate_fused(tile_off: jax.Array, val: jax.Array, seg: jax.Array,
                    cols: jax.Array, h_in: jax.Array, w: jax.Array,
                    b: jax.Array | None = None, s: jax.Array | None = None,
                    *, act: str = "none", z_dtype=None,
                    interpret: bool = True) -> jax.Array:
    """out = act((A @ h_in [+ s]) @ w [+ b]) in ONE Pallas grid.

    A streams from the per-tile edge segments (``tile_off``/``val``/``seg``
    as in ``aggregate_edges``); ``w`` (F, N) and optional ``b`` (N,) are the
    update-MLP parameters, resident in VMEM for the whole grid; optional
    ``s`` (n_dstb*BLK, F) is an additive self/skip term folded in before
    the MLP (GCN's ``agg + h_self``, GIN's ``(1+eps)*h_self + agg``).
    ``z_dtype`` is the dtype the row-block aggregate is cast to before the
    MLP matmul (default ``h_in.dtype``) — it mirrors the unfused path's
    ``agg.astype(h.dtype)`` so mixed-precision callers keep bitwise parity.
    Returns (n_dstb * BLK, N). The aggregated intermediate exists only as
    the kernel's fp32 VMEM accumulator — never in HBM."""
    n_dstb, max_blk = cols.shape
    F = h_in.shape[1]
    N = w.shape[1]
    if z_dtype is None:
        z_dtype = h_in.dtype
    out_dtype = jnp.result_type(z_dtype, w.dtype)
    if tile_off.shape[0] == 0:  # zero-capacity layer: mirror the XLA path
        z = jnp.zeros((n_dstb * BLK, F), z_dtype)
        if s is not None:
            z = z + s
        return update_epilogue(jnp.dot(z, w), b, act).astype(out_dtype)
    h_k, w_k, b_k, s_k = _fused_operands(h_in, w, b, s, interpret)
    F_pad, N_pad = w_k.shape
    has_bias, has_self = b is not None, s is not None

    in_specs = _stream_specs(max_blk) + [
        pl.BlockSpec((F_pad, N_pad), lambda i, k: (0, 0))]
    operands = _stream_operands(tile_off, val, seg, cols, h_k) + [w_k]
    if has_bias:
        in_specs.append(pl.BlockSpec((N_pad,), lambda i, k: (0,)))
        operands.append(b_k)
    if has_self:
        in_specs.append(pl.BlockSpec((BLK, F_pad), lambda i, k: (i, 0)))
        operands.append(s_k)

    out = pl.pallas_call(
        functools.partial(_fused_kernel, n_blk=max_blk, act=act,
                          has_bias=has_bias, has_self=has_self,
                          z_dtype=z_dtype, interpret=interpret),
        grid=(n_dstb, max_blk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((BLK, N_pad), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_dstb * BLK, N_pad), out_dtype),
        scratch_shapes=[pltpu.VMEM((BLK, F_pad), jnp.float32),  # aggregate
                        *_stream_scratch(h_k)],
        interpret=interpret,
        name="agg_fused_fwd",
    )(*operands)
    return out[:, :N] if N_pad != N else out


def _fused_bwd_call(tile_off, val, seg, cols, h_in, g, w, b, s, *, act,
                    z_dtype, interpret):
    """Backward recompute pass: streams the SAME A segments through the same
    grid, rebuilds each row-block aggregate (and, for activated MLPs, the
    pre-activation) in VMEM, and contracts it against the incoming cotangent.
    Returns (dw (F, N), db (N,) | None, dy (n_dstb*BLK, N) | None)."""
    n_dstb, max_blk = cols.shape
    F = h_in.shape[1]
    N = w.shape[1]
    h_k, w_k, b_k, s_k = _fused_operands(h_in, w, b, s, interpret)
    F_pad, N_pad = w_k.shape
    has_bias, has_self = b is not None, s is not None
    g_k = g if interpret else _pad_lanes(g, 1)

    in_specs = _stream_specs(max_blk) + [
        pl.BlockSpec((BLK, N_pad), lambda i, k: (i, 0))]
    operands = _stream_operands(tile_off, val, seg, cols, h_k) + [g_k]
    if act != "none":
        in_specs.append(pl.BlockSpec((F_pad, N_pad), lambda i, k: (0, 0)))
        operands.append(w_k)
        if has_bias:
            in_specs.append(pl.BlockSpec((N_pad,), lambda i, k: (0,)))
            operands.append(b_k)
    if has_self:
        in_specs.append(pl.BlockSpec((BLK, F_pad), lambda i, k: (i, 0)))
        operands.append(s_k)

    out_specs = [pl.BlockSpec((F_pad, N_pad), lambda i, k: (0, 0))]
    out_shapes = [jax.ShapeDtypeStruct((F_pad, N_pad), jnp.float32)]
    if has_bias:
        out_specs.append(pl.BlockSpec((1, N_pad), lambda i, k: (0, 0)))
        out_shapes.append(jax.ShapeDtypeStruct((1, N_pad), jnp.float32))
    if act != "none":
        out_specs.append(pl.BlockSpec((BLK, N_pad), lambda i, k: (i, 0)))
        out_shapes.append(jax.ShapeDtypeStruct((n_dstb * BLK, N_pad),
                                               g.dtype))

    scratch = [pltpu.VMEM((BLK, F_pad), jnp.float32),
               pltpu.VMEM((F_pad, N_pad), jnp.float32)]
    if has_bias:
        scratch.append(pltpu.VMEM((1, N_pad), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_fused_bwd_kernel, n_blk=max_blk, act=act,
                          has_bias=has_bias, has_self=has_self,
                          z_dtype=z_dtype, interpret=interpret),
        grid=(n_dstb, max_blk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=scratch + _stream_scratch(h_k),
        interpret=interpret,
        name="agg_fused_bwd",
    )(*operands)
    outs = list(outs)
    dw = outs.pop(0)[:F, :N]
    db = outs.pop(0)[0, :N] if has_bias else None
    dy = outs.pop(0)[:, :N] if act != "none" else None
    return dw, db, dy


def _fused_bwd_merged_kernel(*refs, n_blk: int, n_blk_t: int,
                             has_bias: bool, has_self: bool, z_dtype,
                             interpret: bool):
    """Single-dst-block backward: dw recompute AND dh in ONE grid pass.

    With one destination row block (``n_dstb == 1``, the bitwise-pinned
    regime) every source block is touched by at most one tile, so the
    k-step that re-streams tile ``(0, k)`` for the z recompute can ALSO
    emit the dh row block of that tile's source block ``cols[0, k]`` —
    the two backward passes collapse into one grid.  The dh block replays
    the edge-streaming kernel's recurrence verbatim (same TRANSPOSED
    segments, same 0-initialised accumulate over all ``n_blk_t`` slots of
    the block's transposed row), so its bits match ``aggregate_edges`` for
    any edge multiplicity; it is DMA'd to its rows of the HBM output.
    Source blocks no tile touches are never written and are masked to +0.0
    by the caller (exactly the reference's zero-segment output)."""
    st, rest = _Stream.split(refs)
    seg_t_ref, offt_hbm, valt_hbm, g_ref, dz_ref = rest[:5]
    rest = rest[5:]
    s_ref = rest.pop(0) if has_self else None
    dw_ref = rest.pop(0)
    db_ref = rest.pop(0) if has_bias else None
    dh_hbm, acc_ref, dh_buf, dh_sem = rest
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    st.accumulate(k, n_blk, acc_ref, interpret=interpret)

    @pl.when(st.nonempty(k))
    def _emit_dh():
        src_blk = st.cols[0, k]
        dh_acc = jnp.zeros(dh_buf.shape, jnp.float32)
        for k2 in range(n_blk_t):
            t = src_blk * n_blk_t + k2
            at_tile = _densify_segment(seg_t_ref[0, t], seg_t_ref[0, t + 1],
                                       offt_hbm, valt_hbm, st.win,
                                       interpret=interpret)
            dh_acc = dh_acc + jnp.dot(at_tile, dz_ref[...],
                                      preferred_element_type=jnp.float32)
        dh_buf[...] = dh_acc
        row = pl.multiple_of(src_blk * BLK, BLK)
        cp = pltpu.make_async_copy(dh_buf, dh_hbm.at[pl.ds(row, BLK)],
                                   dh_sem)
        cp.start()
        cp.wait()

    @pl.when(k == n_blk - 1)
    def _grads():
        z = acc_ref[...].astype(z_dtype)
        if has_self:
            z = z + s_ref[...]
        dy = g_ref[...]
        dw_ref[...] = jax.lax.dot_general(
            z, dy, (((0,), (0,)), ((), ()))).astype(dw_ref.dtype)
        if has_bias:
            db_ref[...] = jnp.sum(dy.astype(jnp.float32), axis=0,
                                  keepdims=True).astype(db_ref.dtype)


def _fused_bwd_merged_call(tile_off, val, seg, cols, tile_off_t, val_t,
                           seg_t, cols_t, h_in, g, dz32, w, b, s, *,
                           z_dtype, interpret):
    """Single-pass backward for the ``n_dstb == 1`` / ``act == "none"``
    case: one grid computes dw (z recompute off the FORWARD segments) and
    dh (the TRANSPOSED segments' edge-streaming recurrence, inlined per
    source block).  Returns (dw (F, N), db (N,) | None, dh (n_src, F))."""
    n_dstb, max_blk = cols.shape
    n_srcb, max_blk_t = cols_t.shape
    F = h_in.shape[1]
    N = w.shape[1]
    h_k, w_k, b_k, s_k = _fused_operands(h_in, w, b, s, interpret)
    F_pad, N_pad = w_k.shape
    has_bias, has_self = b is not None, s is not None
    g_k = g if interpret else _pad_lanes(g, 1)
    dz_k = dz32 if interpret else _pad_lanes(dz32, 1)
    # one dst row: the whole transposed offsets table is n_srcb + 1 words
    seg_t2 = seg_t.reshape(1, -1)

    in_specs = _stream_specs(max_blk) + [
        pl.BlockSpec(seg_t2.shape, lambda i, k: (0, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pl.ANY),  # transposed tile_off
        pl.BlockSpec(memory_space=pl.ANY),  # transposed val
        pl.BlockSpec((BLK, N_pad), lambda i, k: (i, 0)),
        pl.BlockSpec((BLK, F_pad), lambda i, k: (i, 0)),
    ]
    operands = _stream_operands(tile_off, val, seg, cols, h_k) + [
        seg_t2, *_edge_stream_hbm(tile_off_t, val_t), g_k, dz_k]
    if has_self:
        in_specs.append(pl.BlockSpec((BLK, F_pad), lambda i, k: (i, 0)))
        operands.append(s_k)

    out_specs = [pl.BlockSpec((F_pad, N_pad), lambda i, k: (0, 0))]
    out_shapes = [jax.ShapeDtypeStruct((F_pad, N_pad), jnp.float32)]
    if has_bias:
        out_specs.append(pl.BlockSpec((1, N_pad), lambda i, k: (0, 0)))
        out_shapes.append(jax.ShapeDtypeStruct((1, N_pad), jnp.float32))
    out_specs.append(pl.BlockSpec(memory_space=pl.ANY))  # dh, DMA'd
    out_shapes.append(jax.ShapeDtypeStruct((n_srcb * BLK, F_pad),
                                           jnp.float32))

    outs = pl.pallas_call(
        functools.partial(_fused_bwd_merged_kernel, n_blk=max_blk,
                          n_blk_t=max_blk_t, has_bias=has_bias,
                          has_self=has_self, z_dtype=z_dtype,
                          interpret=interpret),
        grid=(n_dstb, max_blk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((BLK, F_pad), jnp.float32),  # aggregate
                        pltpu.VMEM((BLK, F_pad), jnp.float32),  # dh block
                        pltpu.SemaphoreType.DMA(()),
                        *_stream_scratch(h_k)],
        interpret=interpret,
        name="agg_fused_bwd_merged",
    )(*operands)
    outs = list(outs)
    dw = outs.pop(0)[:F, :N]
    db = outs.pop(0)[0, :N] if has_bias else None
    dh_raw = outs.pop(0)[:, :F]
    # source blocks of no non-empty tile never get a dh write; the
    # reference's zero-segment recurrence leaves them at exactly +0.0
    touched = jnp.where(seg[1:] > seg[:-1], cols[0], n_srcb)
    covered = jnp.zeros((n_srcb,), bool).at[touched].set(True, mode="drop")
    dh = jnp.where(jnp.repeat(covered, BLK)[:, None], dh_raw, 0.0)
    return dw, db, dh


# Differentiable wrapper. ``b`` and ``s`` are ALWAYS passed (dummy arrays
# when ``has_bias``/``has_self`` are off) so the cotangent structure stays
# static; the flags — not array identity — decide what the kernels consume.
# Backward strategy (mirrors the unfused composition op-for-op so the
# bitwise contract holds):
#   dy = g                     (act="none"; else recomputed in-kernel)
#   dz = dot_general(dy, w)    (one XLA dot — row-independent of padding)
#   dh = A^T @ dz              (the SAME edge-streaming grid, transposed
#                               segments — aggregate_edges)
#   ds = dz
#   dw = sum_i z_i^T dy_i      (in-kernel recompute of z, per-row-block)
#   db = sum_rows dy           (in-kernel, only when the bias is fused)

@functools.partial(jax.custom_vjp, nondiff_argnums=(12, 13, 14, 15, 16))
def aggregate_fused_vjp(tile_off: jax.Array, val: jax.Array, seg: jax.Array,
                        cols: jax.Array, tile_off_t: jax.Array,
                        val_t: jax.Array, seg_t: jax.Array,
                        cols_t: jax.Array, h_in: jax.Array, w: jax.Array,
                        b: jax.Array, s: jax.Array, act: str = "none",
                        has_bias: bool = False, has_self: bool = False,
                        z_dtype=None, interpret: bool = True) -> jax.Array:
    """Differentiable ``act((A @ h [+ s]) @ w [+ b])``, A in segment form."""
    return aggregate_fused(tile_off, val, seg, cols, h_in, w,
                           b if has_bias else None,
                           s if has_self else None, act=act,
                           z_dtype=z_dtype, interpret=interpret)


def _fused_fwd(tile_off, val, seg, cols, tile_off_t, val_t, seg_t, cols_t,
               h_in, w, b, s, act, has_bias, has_self, z_dtype, interpret):
    out = aggregate_fused_vjp(tile_off, val, seg, cols, tile_off_t, val_t,
                              seg_t, cols_t, h_in, w, b, s, act, has_bias,
                              has_self, z_dtype, interpret)
    return out, (tile_off, val, seg, cols, tile_off_t, val_t, seg_t, cols_t,
                 h_in, w, b, s)


def _fused_bwd(act, has_bias, has_self, z_dtype, interpret, res, g):
    (tile_off, val, seg, cols, tile_off_t, val_t, seg_t, cols_t,
     h_in, w, b, s) = res
    zd = h_in.dtype if z_dtype is None else z_dtype
    n_dstb = cols.shape[0]
    F = h_in.shape[1]
    if tile_off.shape[0] == 0:
        # zero-capacity layer: A is empty and independent of h, so the
        # cotangents are exactly the XLA composition's on a zero aggregate
        def _f(w_, b_, s_):
            z = jnp.zeros((n_dstb * BLK, F), zd)
            if has_self:
                z = z + s_
            y = jnp.dot(z, w_)
            return update_epilogue(y, b_ if has_bias else None,
                                   act).astype(jnp.result_type(zd, w_.dtype))
        _, pullback = jax.vjp(_f, w, b, s)
        dw, db, ds = pullback(g)
        dh = jnp.zeros_like(h_in)
    elif n_dstb == 1 and act == "none" and F <= 256:
        # single-dst-block fast path: dw recompute and dh share ONE grid
        # (see _fused_bwd_merged_kernel) — bits identical to the two-pass
        # composition below
        dz = jax.lax.dot_general(g, w, (((1,), (1,)), ((), ())))
        dw, db, dh = _fused_bwd_merged_call(
            tile_off, val, seg, cols, tile_off_t, val_t, seg_t, cols_t,
            h_in, g, dz.astype(jnp.float32), w,
            b if has_bias else None, s if has_self else None,
            z_dtype=zd, interpret=interpret)
        dh = dh.astype(h_in.dtype)
        dw = dw.astype(w.dtype)
        db = db.astype(b.dtype) if has_bias else jnp.zeros_like(b)
        ds = dz.astype(s.dtype) if has_self else jnp.zeros_like(s)
    else:
        dw, db, dy = _fused_bwd_call(
            tile_off, val, seg, cols, h_in, g, w,
            b if has_bias else None, s if has_self else None,
            act=act, z_dtype=zd, interpret=interpret)
        if act == "none":
            dy = g
        dz = jax.lax.dot_general(dy, w, (((1,), (1,)), ((), ())))
        dh = aggregate_edges(tile_off_t, val_t, seg_t, cols_t,
                             dz.astype(jnp.float32),
                             interpret=interpret).astype(h_in.dtype)
        dw = dw.astype(w.dtype)
        db = db.astype(b.dtype) if has_bias else jnp.zeros_like(b)
        ds = dz.astype(s.dtype) if has_self else jnp.zeros_like(s)

    def f0(a):
        return np.zeros(a.shape, jax.dtypes.float0)

    return (f0(tile_off), jnp.zeros_like(val), f0(seg), f0(cols),
            f0(tile_off_t), jnp.zeros_like(val_t), f0(seg_t), f0(cols_t),
            dh, dw, db, ds)


aggregate_fused_vjp.defvjp(_fused_fwd, _fused_bwd)
