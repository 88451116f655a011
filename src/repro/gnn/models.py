"""GNN model zoo in the aggregate-update paradigm (paper Alg. 1, §5.3).

Models consume padded MiniBatch arrays (static shapes, jit-friendly):
  feats      (N_0, f0)   input features for the deepest layer's vertices
  edge_src[l](E_l,)      local src index into layer l's vertex set
  edge_dst[l](E_l,)      local dst index into layer l+1's vertex set
  edge_mask[l], node_mask[l], self_idx[l] per sampler.py

``aggregate`` is the scatter-gather kernel's reference semantics (the Pallas
block-CSR kernel in kernels/aggregate.py implements the same contract);
``update`` is the systolic MLP (kernels/update_mlp.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.gnn import GNNModelConfig
from repro.kernels.aggregate import (BLK, aggregate_compact_vjp,
                                     aggregate_edges_vjp,
                                     aggregate_fused_vjp, resolve_interpret)
from repro.nn.param import PSpec


# aggregate_backend values that route through the Pallas SpMM datapath (and
# therefore need the stage-2b layout arrays in the batch)
KERNEL_BACKENDS = ("pallas", "pallas_edges", "pallas_fused")


# Aggregation semantics per model. "mean"/"sum" models can run through the
# block-CSR kernel (the mean's 1/deg weights are baked into the block values
# host-side); GAT's attention weights are device-computed, so it always uses
# the reference edge-list path.
AGG_KIND = {"graphsage": "mean", "gcn": "mean", "gin": "sum", "gat": None}


def _mul_host(a, b):
    """Single-rounding elementwise product, evaluated on the host. Under
    vmap (``vmap_method="broadcast_all"``) a scalar ``a`` arrives as one
    value per batch entry, so its trailing axes are padded to broadcast
    against ``b``."""
    a, b = np.asarray(a), np.asarray(b)
    a = a.reshape(a.shape + (1,) * (b.ndim - a.ndim))
    return np.asarray(np.multiply(a, b), dtype=b.dtype)


def _pinned_mul(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a * b`` with its rounding pinned against XLA FMA contraction.

    XLA CPU freely contracts a multiply into whichever add consumes it as a
    single-rounding FMA, and the contraction decision depends on the whole
    surrounding program.  ``lax.optimization_barrier`` does NOT help: the CPU
    pipeline runs OptimizationBarrierExpander, which deletes the barrier
    before fusion, and trivial Pallas interpret kernels get inlined the same
    way.  A host callback is a genuinely opaque custom call, so the product
    is rounded exactly once no matter what consumes it.
    """
    return jax.pure_callback(
        _mul_host, jax.ShapeDtypeStruct(b.shape, b.dtype), a, b,
        vmap_method="broadcast_all")


@jax.custom_vjp
def _gin_scaled_self(eps: jax.Array, h_self: jax.Array) -> jax.Array:
    """GIN's ``(1+eps) * h_self`` with every rounding pinned, fwd and bwd.

    Left visible to XLA, the scale multiply contracts into whichever add
    consumes it — ``(1+eps)*h + agg`` forward, the dh accumulation and the
    ``sum(g*h)`` eps-cotangent backward.  The fused-aggregation backend
    swaps that surrounding program (the add runs inside the Pallas grid), so
    the same mul+add chain compiles with different roundings and the
    backends drift by an ulp once eps leaves exactly 0.  Pinning the product
    (and the cotangent products) to their own rounding makes the value
    independent of the consumer, keeping all aggregate backends bitwise
    equal.
    """
    return _pinned_mul(1.0 + eps, h_self)


def _gin_scaled_self_fwd(eps, h_self):
    return _gin_scaled_self(eps, h_self), (eps, h_self)


def _gin_scaled_self_bwd(res, g):
    eps, h_self = res
    dh = _pinned_mul(1.0 + eps, g)
    de = _pinned_mul(g, h_self).sum().astype(eps.dtype)
    return de, dh


_gin_scaled_self.defvjp(_gin_scaled_self_fwd, _gin_scaled_self_bwd)


# ---------------------------------------------------------------------------
# Aggregate (scatter-gather) reference ops
# ---------------------------------------------------------------------------

def aggregate(h_src: jax.Array, edge_src: jax.Array, edge_dst: jax.Array,
              edge_mask: jax.Array, n_dst: int, kind: str = "mean"
              ) -> jax.Array:
    """Masked segment aggregation of messages h_src[edge_src] into dst rows."""
    msg = h_src[edge_src] * edge_mask[:, None].astype(h_src.dtype)
    agg = jax.ops.segment_sum(msg, edge_dst, num_segments=n_dst)
    if kind == "sum":
        return agg
    deg = jax.ops.segment_sum(edge_mask.astype(h_src.dtype), edge_dst,
                              num_segments=n_dst)
    if kind == "mean":
        return agg / jnp.maximum(deg, 1.0)[:, None]
    raise ValueError(kind)


def segment_softmax(scores: jax.Array, seg: jax.Array, mask: jax.Array,
                    n_seg: int) -> jax.Array:
    """Numerically-stable per-segment softmax over edges (GAT)."""
    neg = jnp.where(mask, scores, -1e30)
    smax = jax.ops.segment_max(neg, seg, num_segments=n_seg)
    ex = jnp.exp(neg - smax[seg]) * mask.astype(scores.dtype)
    den = jax.ops.segment_sum(ex, seg, num_segments=n_seg)
    return ex / jnp.maximum(den[seg], 1e-9)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def _dims(cfg: GNNModelConfig, f_in: int, n_classes: int) -> list:
    return [f_in] + [cfg.hidden] * (cfg.num_layers - 1) + [n_classes]


def param_spec(cfg: GNNModelConfig, f_in: int, n_classes: int):
    dims = _dims(cfg, f_in, n_classes)
    layers = []
    for l in range(cfg.num_layers):
        fi, fo = dims[l], dims[l + 1]
        if cfg.name == "graphsage":
            layers.append({"w_self": PSpec((fi, fo), ("embed", "ffn")),
                           "w_neigh": PSpec((fi, fo), ("embed", "ffn")),
                           "b": PSpec((fo,), ("ffn",), "zeros")})
        elif cfg.name == "gcn":
            layers.append({"w": PSpec((fi, fo), ("embed", "ffn")),
                           "b": PSpec((fo,), ("ffn",), "zeros")})
        elif cfg.name == "gin":
            layers.append({"eps": PSpec((), (), "zeros"),
                           "w1": PSpec((fi, fo), ("embed", "ffn")),
                           "b1": PSpec((fo,), ("ffn",), "zeros"),
                           "w2": PSpec((fo, fo), ("ffn", "ffn")),
                           "b2": PSpec((fo,), ("ffn",), "zeros")})
        elif cfg.name == "gat":
            layers.append({"w": PSpec((fi, fo), ("embed", "ffn")),
                           "a_src": PSpec((fo,), ("ffn",)),
                           "a_dst": PSpec((fo,), ("ffn",)),
                           "b": PSpec((fo,), ("ffn",), "zeros")})
        else:
            raise ValueError(cfg.name)
    return {"layers": layers}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _blockcsr_aggregate(cfg: GNNModelConfig, batch, l: int, h: jax.Array,
                        n_dst: int) -> jax.Array:
    """Layer-l aggregation through the Pallas block-CSR SpMM.

    The pipeline stage precomputed the COMPACT edge-centric layout for A
    (and A^T for the VJP) with the model's semantics baked into the edge
    values (1/deg for mean, 1 for sum). ``aggregate_backend`` picks how the
    tiles come to exist: ``"pallas"`` densifies the full tile tensor in
    device HBM inside the jit'd step (kernels/aggregate.densify_tiles) and
    feeds the block-CSR kernel; ``"pallas_edges"`` streams the tile-sorted
    edge segments straight into the kernel, which densifies each 128x128
    tile in a VMEM scratch right before its matmul — no dense tile tensor
    in HBM at all. Either way the host ships ~20 B/edge (A + A^T) and a
    single masked SpMM reproduces ``aggregate`` exactly. Execution mode
    follows ``cfg.kernel_interpret`` (None = compiled on real TPU,
    interpreted elsewhere)."""
    cols_t = batch["agg_cols_t"][l]
    n_src_pad = cols_t.shape[0] * BLK
    h32 = h.astype(jnp.float32)
    h_pad = jnp.pad(h32, ((0, n_src_pad - h32.shape[0]), (0, 0)))
    interpret = resolve_interpret(cfg.kernel_interpret)
    if cfg.aggregate_backend == "pallas_edges":
        out = aggregate_edges_vjp(
            batch["agg_tile_off"][l], batch["agg_val"][l],
            batch["agg_tile_seg"][l], batch["agg_cols"][l],
            batch["agg_tile_off_t"][l], batch["agg_val_t"][l],
            batch["agg_tile_seg_t"][l], cols_t, h_pad,
            interpret=interpret)
    else:
        out = aggregate_compact_vjp(
            batch["agg_tile_id"][l], batch["agg_tile_off"][l],
            batch["agg_val"][l], batch["agg_cols"][l],
            batch["agg_tile_id_t"][l], batch["agg_tile_off_t"][l],
            cols_t, h_pad, interpret=interpret)
    return out[:n_dst].astype(h.dtype)


def _fused_aggregate_update(cfg: GNNModelConfig, batch, l: int, h: jax.Array,
                            n_dst: int, w: jax.Array,
                            s: jax.Array | None = None) -> jax.Array:
    """Layer-l ``(A @ h [+ s]) @ w`` through the single-pass fused kernel.

    ``aggregate_backend="pallas_fused"``: one grid streams the tile's edge
    segment into VMEM (double-buffered DMA), densifies in scratch, runs the
    SpMM against the feature block, and applies the update matmul with ``w``
    VMEM-resident on the final k-step — the aggregated intermediate
    ``(Nd*BLK, F)`` never exists in HBM, forward or backward.

    Bitwise contract vs the unfused ``pallas_edges`` composition
    (``agg = kernel(h)[:n_dst].astype(h.dtype)``; ``(agg [+ s]) @ w``):
    the kernel replays the exact edge-stream grid order and fp32
    accumulator, applies the same ``astype`` at the same point
    (``z_dtype=h.dtype``), and the row/lane zero-padding is bitwise-neutral
    for matmuls on this backend (see the design notes in
    kernels/aggregate.py). Bias + activation epilogues deliberately stay in
    XLA out here so their gradient reductions keep the unfused bit pattern.
    ``s`` (the self/residual term added to the aggregate BEFORE the update
    matmul) is padded AFTER any scaling so its cotangent reduces over
    exactly the unfused rows."""
    cols_t = batch["agg_cols_t"][l]
    n_src_pad = cols_t.shape[0] * BLK
    h32 = h.astype(jnp.float32)
    h_pad = jnp.pad(h32, ((0, n_src_pad - h32.shape[0]), (0, 0)))
    n_dst_pad = batch["agg_cols"][l].shape[0] * BLK
    has_self = s is not None
    if has_self:
        s_pad = jnp.pad(s, ((0, n_dst_pad - s.shape[0]), (0, 0)))
    else:  # dummy operand: keeps the custom-vjp arg structure static
        s_pad = jnp.zeros((1, h.shape[1]), h.dtype)
    b_dummy = jnp.zeros((w.shape[1],), w.dtype)
    interpret = resolve_interpret(cfg.kernel_interpret)
    out = aggregate_fused_vjp(
        batch["agg_tile_off"][l], batch["agg_val"][l],
        batch["agg_tile_seg"][l], batch["agg_cols"][l],
        batch["agg_tile_off_t"][l], batch["agg_val_t"][l],
        batch["agg_tile_seg_t"][l], cols_t, h_pad, w, b_dummy, s_pad,
        "none", False, has_self, h.dtype, interpret=interpret)
    return out[:n_dst]


def _layer(cfg: GNNModelConfig, p, h, batch, l: int, n_dst: int):
    src, dst = batch["edge_src"][l], batch["edge_dst"][l]
    emask = batch["edge_mask"][l]
    h_self = h[batch["self_idx"][l]]
    # GAT's attention weights are device-computed: it always runs the
    # reference edge-list path, whatever the backend
    use_kernel = (cfg.aggregate_backend in KERNEL_BACKENDS
                  and AGG_KIND.get(cfg.name) is not None)
    if use_kernel and "agg_tile_off" not in batch:
        raise ValueError(
            f"aggregate_backend={cfg.aggregate_backend!r} needs the batch's "
            f"stage-2b layout arrays (agg_*), but this batch has none; "
            f"build them with kernels.layout.build_layer_layouts")
    use_fused = use_kernel and cfg.aggregate_backend == "pallas_fused"

    def _agg(kind: str) -> jax.Array:
        if use_kernel:
            return _blockcsr_aggregate(cfg, batch, l, h, n_dst)
        return aggregate(h, src, dst, emask, n_dst, kind)

    def _fused(w, s=None):
        return _fused_aggregate_update(cfg, batch, l, h, n_dst, w, s)

    if cfg.name == "graphsage":
        if use_fused:
            out = h_self @ p["w_self"] + _fused(p["w_neigh"]) + p["b"]
        else:
            agg = _agg("mean")
            out = h_self @ p["w_self"] + agg @ p["w_neigh"] + p["b"]
    elif cfg.name == "gcn":
        if use_fused:
            out = _fused(p["w"], s=h_self) * 0.5 + p["b"]
        else:
            agg = _agg("mean")
            out = (agg + h_self) @ p["w"] * 0.5 + p["b"]
    elif cfg.name == "gin":
        hs = _gin_scaled_self(p["eps"], h_self)
        if use_fused:
            y = _fused(p["w1"], s=hs)
        else:
            agg = _agg("sum")
            z = hs + agg
            y = z @ p["w1"]
        out = jax.nn.relu(y + p["b1"]) @ p["w2"] + p["b2"]
    elif cfg.name == "gat":
        hw = h @ p["w"]
        hw_dst = hw[batch["self_idx"][l]]
        e = (jax.nn.leaky_relu(
            (hw[src] * p["a_src"]).sum(-1)
            + (hw_dst[dst] * p["a_dst"]).sum(-1), 0.2))
        alpha = segment_softmax(e, dst, emask, n_dst)
        msg = hw[src] * alpha[:, None]
        out = jax.ops.segment_sum(msg, dst, num_segments=n_dst) + p["b"]
    else:
        raise ValueError(cfg.name)
    return out


def forward(cfg: GNNModelConfig, params, batch) -> jax.Array:
    """Returns logits (T, n_classes) for the target vertices."""
    h = batch["feats"]
    n_layers = cfg.num_layers
    for l in range(n_layers):
        n_dst = batch["self_idx"][l].shape[0]
        h = _layer(cfg, params["layers"][l], h, batch, l, n_dst)
        if l != n_layers - 1:
            h = jax.nn.relu(h)
            h = h * batch["node_mask"][l + 1][:, None].astype(h.dtype)
    return h


# ---------------------------------------------------------------------------
# Mesh dataflow: on-device layer-0 feature assembly
# ---------------------------------------------------------------------------
#
# Under the shard_map trainer the layer-0 feature block is no longer shipped
# pre-assembled from the host: each device holds its residency shard
# (FeatureStore.build_shard_matrix) in HBM and the batch carries only index
# payloads (hit positions + the capped miss-row segment), so the full (N_0, f)
# block is materialized HERE, inside the per-device step body.

def assemble_device_feats(vshard: jax.Array, batch) -> jax.Array:
    """Row-resident strategies (DistDGL/PaGraph): HBM hits + shipped misses.

    ``vshard`` is this device's (cap, f) resident block; the batch carries
    ``shard_pos`` (N_0,) positions into it, ``shard_hit`` (N_0,) float mask,
    and the padded miss segment ``miss_pos`` (M,) / ``miss_rows`` (M, f)
    where pad entries point one past the batch (row N_0) so the scatter
    lands in a discard row. Numerically identical to the host-side
    ``FeatureStore.gather``: hit rows read the shard, miss rows memcpy the
    shipped block, invalid rows stay zero."""
    pos, hit = batch["shard_pos"], batch["shard_hit"]
    mpos, mrows = batch["miss_pos"], batch["miss_rows"]
    n = pos.shape[0]
    base = vshard[pos] * hit[:, None].astype(vshard.dtype)
    out = jnp.zeros((n + 1, vshard.shape[1]), vshard.dtype).at[:n].set(base)
    out = out.at[mpos].set(mrows)
    return out[:n]


def p3_all_to_all_feats(vshard: jax.Array, ids_all: jax.Array,
                        valid_all: jax.Array, feat_dim: int,
                        axis_name: str = "data") -> jax.Array:
    """P3 layer-1 exchange (paper Listing 3) as a REAL ``all_to_all``.

    ``vshard`` is this device's (V, chunk) feature-dimension slice of every
    vertex; ``ids_all`` / ``valid_all`` are the (p, N_0) layer-0 vertex ids
    and masks of ALL devices' batches, replicated so device e can serve its
    slice for everyone. Device e gathers its chunk for each batch d, the
    all-to-all transposes the (device, batch) grid so device d receives all
    p chunks of ITS batch, and the transpose+reshape tiles them back into
    full (N_0, f) rows (the last device's zero padding falls off the
    ``[:, :feat_dim]`` crop). Bitwise equal to the host-side
    ``gather_p3_full`` reconstruction for the same batch."""
    x = vshard[ids_all]                              # (p, N_0, chunk)
    x = x * valid_all[..., None].astype(vshard.dtype)
    x = jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                           tiled=True)               # x[i] = chunk i of mine
    n = ids_all.shape[1]
    return jnp.transpose(x, (1, 0, 2)).reshape(n, -1)[:, :feat_dim]


def loss_fn(cfg: GNNModelConfig, params, batch):
    logits = forward(cfg, params, batch).astype(jnp.float32)
    labels = batch["labels"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None].astype(jnp.int32),
                             axis=-1)[:, 0]
    loss = jnp.mean(lse - ll)
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, {"loss": loss, "acc": acc}
