"""Synchronous GNN trainer on host + p accelerators (the paper's runtime).

Per synchronous iteration (paper Fig. 2 / Alg. 2 + gradient sync):
  1. the two-stage scheduler (scheduler.py) picks p mini-batches;
  2. the host PIPELINE (core/pipeline.py) samples each batch and gathers its
     feature rows through the FeatureStore (cache hit = device HBM, miss =
     host fetch — DC optimization, with beta accounting), running one
     iteration AHEAD of the device so host work overlaps device compute
     (paper Eq. 5-6). With a SamplerPool (``num_sampler_workers > 0``) the
     sample + layout stages run in worker processes, and with
     ``gather_in_workers`` the feature gather moves there too — workers ship
     only the target device's miss rows through the shared-memory ring and
     the training thread keeps just device placement
     (``FeatureStore.place_gathered``). With ``aggregate_backend="pallas"``
     the pipeline stage also precomputes each layer's COMPACT block-CSR
     layout (forward + transpose derived from a single edge-key sort,
     ~20 B/edge total) which the device step densifies into tiles on the fly;
  3. the p batches are stacked on a leading device axis and executed as ONE
     jit'd step: vmap over the device axis + weight-averaged loss =>
     gradients are the mean over the REAL batches (idle-device fill batches
     carry weight 0 and contribute nothing). Under a mesh the device axis is
     sharded over "data", so XLA emits exactly the gradient all-reduce;
  4. one optimizer update applies everywhere (weights stay replicated).

P3 runs layer 1 in feature-dimension-parallel form: each device's store
serves only its feature-dimension slice (zero-widened), and the gather sums
the p slices — the paper's Listing-3 all-to-all reduction.

Fault tolerance: Checkpointer (async, device-count independent) + resumable
scheduler state. Optional int8+error-feedback gradient compression
(distributed/compression.py) models slow cross-pod links.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import compile_counter
from repro.configs.gnn import GNNModelConfig
from repro.data.graphs import Graph
from repro.core.partition import Partition, get_partitioner
from repro.core.feature_cache import FeatureCache
from repro.core.feature_store import FeatureStore
from repro.core.pipeline import PipelineStats, PrefetchExecutor, timed
from repro.core.sampler import (NeighborSampler, MiniBatch,
                                layer_capacities)
from repro.core.sampler_pool import SamplerPool, suggest_ship_rows_cap
from repro.core.scheduling import BatchTask, EpochSource, SchedulingCore
from repro.core import scheduler as sched
from repro.gnn import models as gnn_models
from repro.kernels.aggregate import (BLK, EDGE_STREAM_BACKENDS,
                                     block_capacities,
                                     build_layer_layouts,
                                     compact_layout_bytes,
                                     dense_layout_bytes,
                                     densified_tile_bytes,
                                     edge_stream_layout_bytes)
from repro.nn.param import materialize
from repro.optim.adam import AdamW, SGDM
from repro.optim.schedules import get_schedule
from repro.distributed import compression
from repro.distributed.sharding import make_data_mesh, require_data_axis
from jax.sharding import NamedSharding, PartitionSpec as P


ALGORITHMS = {
    # name: (partitioner, feature-storing strategy)
    "distdgl": ("metis_like", "distdgl"),
    "pagraph": ("pagraph", "pagraph"),
    "p3": ("p3", "p3"),
}


def batch_to_arrays(mb: MiniBatch, feats: Optional[np.ndarray]) -> dict:
    # feats=None is the mesh path: the layer-0 block is assembled ON DEVICE
    # from the residency shard + the batch's index/miss payload, so no
    # pre-gathered (N_0, f) block rides the stacked pytree at all
    out = {} if feats is None else {"feats": feats.astype(np.float32)}
    return {
        **out,
        "edge_src": [np.asarray(a) for a in mb.edge_src],
        "edge_dst": [np.asarray(a) for a in mb.edge_dst],
        "edge_mask": [np.asarray(a) for a in mb.edge_mask],
        "node_mask": [np.asarray(a) for a in mb.node_mask],
        "self_idx": [np.asarray(a) for a in mb.self_idx],
        "labels": np.asarray(mb.labels, np.int32),
        # loss weight of this batch in the synchronous step; idle-device
        # fill batches get 0.0 so they contribute zero loss AND zero gradient
        "weight": np.float32(1.0),
    }


def _arg_spec(x) -> jax.ShapeDtypeStruct:
    """Shape, dtype and — for arrays laid across several devices — the
    sharding of one step argument (single-device arrays may still be
    uncommitted: their placement follows the others')."""
    sharding = getattr(x, "sharding", None)
    if sharding is not None and len(sharding.device_set) < 2:
        sharding = None
    return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding)


def stack_batches(batches: List[dict]) -> dict:
    return jax.tree.map(lambda *xs: np.stack(xs), *batches)


@dataclass
class SyncGNNTrainer:
    graph: Graph
    model_cfg: GNNModelConfig
    num_devices: int
    algorithm: str = "distdgl"
    lr: float = 1e-2
    seed: int = 0
    workload_balancing: bool = True        # paper WB optimization
    host_direct_fetch: bool = True         # paper DC optimization
    grad_compression: bool = False
    # Multi-device execution: a mesh with a "data" axis of extent
    # num_devices switches the step to the shard_map path — per-device
    # feature shards in HBM, genuinely concurrent per-device batches, and a
    # cross-device gradient psum (P3 additionally runs its layer-1 exchange
    # as an on-device all_to_all). data_parallel=True builds the mesh from
    # the process's first num_devices jax devices. mesh=None keeps the
    # single-device vmap step, bit-identical to the pre-mesh trainer.
    mesh: Optional[jax.sharding.Mesh] = None
    data_parallel: bool = False
    optimizer_name: str = "adam"
    pipeline: bool = True                  # overlap host stages w/ device step
    prefetch_depth: int = 2
    aggregate_backend: Optional[str] = None  # overrides model_cfg when set
    # Sampling service knobs — None inherits the model_cfg value; a value
    # here overrides it (mirroring aggregate_backend). Workers > 0 routes
    # stage 1+2b through a SamplerPool of that many processes;
    # gather_in_workers additionally moves stage 2 (the feature gather)
    # into those workers, shipping only the target device's miss rows
    # through the shared-memory ring; worker_affinity pins the workers
    # round-robin over the host's cores.
    num_sampler_workers: Optional[int] = None
    balance_policy: Optional[str] = None
    gather_in_workers: Optional[bool] = None
    worker_affinity: Optional[bool] = None
    # Feature-cache knobs — same None-inherits override pattern.
    # cache_capacity turns the static residency into a frequency-driven
    # fixed-capacity cache (core/feature_cache.py); cache_refresh_every
    # picks the admission cadence (0 = epoch boundaries); ship_rows_cap
    # bounds the ring's variable-length rows segment.
    cache_capacity: Optional[int] = None
    cache_refresh_every: Optional[int] = None
    ship_rows_cap: Optional[int] = None
    # Fault-tolerance knobs (supervised sampling service) — same
    # None-inherits override pattern. max_respawns bounds worker respawns
    # before the pool degrades to in-process sampling;
    # straggler_timeout_s arms speculative re-execution of the head-of-line
    # task; fault_spec injects faults (core/faults.py grammar, tests/bench
    # only).
    max_respawns: Optional[int] = None
    straggler_timeout_s: Optional[float] = None
    speculative_sampling: Optional[bool] = None
    fault_spec: Optional[str] = None
    # Mid-epoch checkpointing: a checkpoint.Checkpointer plus a cadence —
    # every checkpoint_every synchronous iterations the trainer snapshots
    # host state (sampler cursors, balancer loads, cache
    # frequency/residency/generation) at assembly time and saves it with
    # the matching post-update params/opt state (0 = off). A killed run
    # restores with restore_checkpoint() + run_epoch(resume=True) and
    # finishes bit-identical to an uninterrupted one.
    checkpointer: Optional[object] = None
    checkpoint_every: int = 0

    def __post_init__(self):
        overrides = {}
        if self.aggregate_backend is not None:
            overrides["aggregate_backend"] = self.aggregate_backend
        if self.num_sampler_workers is not None:
            overrides["num_sampler_workers"] = self.num_sampler_workers
        if self.balance_policy is not None:
            overrides["balance_policy"] = self.balance_policy
        if self.gather_in_workers is not None:
            overrides["gather_in_workers"] = self.gather_in_workers
        if self.worker_affinity is not None:
            overrides["worker_affinity"] = self.worker_affinity
        if self.cache_capacity is not None:
            overrides["cache_capacity"] = self.cache_capacity
        if self.cache_refresh_every is not None:
            overrides["cache_refresh_every"] = self.cache_refresh_every
        if self.ship_rows_cap is not None:
            overrides["ship_rows_cap"] = self.ship_rows_cap
        if self.max_respawns is not None:
            overrides["max_respawns"] = self.max_respawns
        if self.straggler_timeout_s is not None:
            overrides["straggler_timeout_s"] = self.straggler_timeout_s
        if self.speculative_sampling is not None:
            overrides["speculative_sampling"] = self.speculative_sampling
        if self.fault_spec is not None:
            overrides["fault_spec"] = self.fault_spec
        if overrides:
            # replace_flat: the warning-free internal spelling — these are
            # trainer-level overrides, not user code to be nudged off the
            # deprecated flat kwargs
            self.model_cfg = self.model_cfg.replace_flat(**overrides)
        self.num_sampler_workers = self.model_cfg.num_sampler_workers
        self.balance_policy = self.model_cfg.balance_policy
        self.gather_in_workers = (self.model_cfg.gather_in_workers
                                  and self.model_cfg.num_sampler_workers > 0)
        self.worker_affinity = self.model_cfg.worker_affinity
        backends = ("reference",) + gnn_models.KERNEL_BACKENDS
        if self.model_cfg.aggregate_backend not in backends:
            raise ValueError(
                f"unknown aggregate_backend "
                f"{self.model_cfg.aggregate_backend!r}; "
                f"expected one of {backends}")
        if self.balance_policy not in sched.BALANCE_POLICIES:
            raise ValueError(
                f"unknown balance_policy {self.balance_policy!r}; "
                f"expected one of {sched.BALANCE_POLICIES}")
        if self.num_sampler_workers < 0:
            raise ValueError("num_sampler_workers must be >= 0")
        if self.model_cfg.cache_refresh_every < 0:
            raise ValueError("cache_refresh_every must be >= 0")
        if (self.model_cfg.ship_rows_cap is not None
                and self.model_cfg.ship_rows_cap < 1):
            raise ValueError("ship_rows_cap must be >= 1")
        # set-up seconds by phase: partition, store, pool_spawn and
        # shard_upload (the first upload's host build and enqueue) as they
        # run, and compile (the process's backend compile seconds from here
        # to the end of the first epoch) once that epoch ends
        self.setup_phase_s: Dict[str, float] = {}
        self._compiles = compile_counter()
        self._compile_s0 = self._compiles.seconds
        part_name, store_name = ALGORITHMS[self.algorithm]
        with self._setup_phase("partition"):
            self.partition: Partition = get_partitioner(part_name)(
                self.graph, self.num_devices, self.seed)
        with self._setup_phase("store"):
            self.store = FeatureStore(self.graph, self.partition,
                                      store_name)
        # Frequency-driven HBM feature cache over the store's residency
        # core. P3 bypasses it entirely: every row is already resident as a
        # feature-dimension slice, so there is nothing to admit or ship.
        # None = cache OFF — residency stays the immutable static partition
        # (bit-identical to the pre-cache trainer). Must wrap the core
        # BEFORE the sampler pool shares it (_ensure_pool), because the
        # shared segment is sized from the cache capacity.
        self.cache: Optional[FeatureCache] = None
        if (self.model_cfg.cache_capacity is not None
                and self.algorithm != "p3"):
            self.cache = FeatureCache(
                self.store.core, self.graph.out_degree(),
                self.model_cfg.cache_capacity,
                self.model_cfg.cache_refresh_every)
        # -- multi-device mesh (tentpole): validate BEFORE any jit so a
        # phantom-device misconfiguration fails at construction, loudly
        if self.data_parallel and self.mesh is None:
            self.mesh = make_data_mesh(self.num_devices)
        self._shard = None  # per-device HBM feature shard (mesh path)
        self._miss_cap = 0
        if self.mesh is not None:
            require_data_axis(self.mesh, self.num_devices)
            if self.cache is not None and \
                    self.model_cfg.cache_refresh_every > 0:
                raise ValueError(
                    "mid-epoch cache refresh (cache_refresh_every > 0) is "
                    "not supported under the sharded mesh step: the device "
                    "shards upload once per epoch. Use epoch-boundary "
                    "refresh (cache_refresh_every=0) or drop the mesh.")
            # static miss-segment cap: the sharded batch ships at most this
            # many miss rows per device per iteration (shape-stable for
            # jit). Worst case every layer-0 row misses, so the layer-0
            # node capacity is always safe; ship_rows_cap tightens it.
            n_caps, _ = layer_capacities(self.model_cfg)
            self._miss_cap = (self.model_cfg.ship_rows_cap
                              if self.model_cfg.ship_rows_cap is not None
                              else n_caps[0])
        self._iter_no = 0  # global synchronous-iteration counter
        self._epoch_iter = 0  # iterations assembled within the current epoch
        self._pool_stats0: Dict[str, float] = {}  # epoch-start pool stats
        self.samplers = [
            NeighborSampler(self.graph, self.model_cfg,
                            self._train_ids(i), i, self.seed)
            for i in range(self.num_devices)]
        self.spec = gnn_models.param_spec(
            self.model_cfg, self.graph.features.shape[1],
            self.graph.num_classes)
        self.params = materialize(self.spec, jax.random.PRNGKey(self.seed))
        schedule = get_schedule("cosine", self.lr, 10, 100_000)
        self.optimizer = (AdamW(schedule, weight_decay=0.0)
                          if self.optimizer_name == "adam"
                          else SGDM(schedule))
        self.opt_state = self.optimizer.init(self.params)
        self._err = None  # compression error feedback
        self.step_no = 0
        # the stacked per-device batch (argnum 2 in BOTH step signatures) is
        # rebuilt host-side every iteration and never read after dispatch,
        # so its device buffers are donated — XLA reuses them for outputs
        # instead of holding batch + outputs live simultaneously. Params /
        # opt state / the feature shard are NOT donated (persistent), and
        # donation cannot change values: tests pin the step bitwise at p=1.
        self._jit_step = jax.jit(self._make_step(), donate_argnums=(2,))
        self._step_specs = None  # arg shapes of the first dispatched step
        # static block-CSR capacities per layer (pallas aggregate backend):
        # one shape per config => one compiled executable across the epoch
        # (kernels/layout.block_capacities — SHARED with the sampler-pool
        # workers so both paths emit bit-identical layouts).
        # The HOST only stages the compact ~20 B/edge layout; the dense
        # tiles are densified on DEVICE inside the jit'd step, so the budget
        # below bounds transient device memory, not host staging or H2D.
        self._blk_caps = []
        if self._use_kernel_layout():
            self._blk_caps = block_capacities(self.model_cfg)
            blk_bytes = self.densified_hbm_bytes()
            budget = 4 << 30  # densified-tile device memory per batch
            if blk_bytes > budget:
                raise ValueError(
                    f"aggregate_backend='pallas' would densify "
                    f"{blk_bytes / 2**30:.1f} GiB of block-CSR tiles per "
                    f"batch on device (budget {budget / 2**30:.0f} GiB) at "
                    f"batch_targets={self.model_cfg.batch_targets}, "
                    f"fanouts={self.model_cfg.fanouts}. Reduce the batch "
                    f"size / fanouts, or use "
                    f"aggregate_backend='pallas_edges' (densifies in VMEM, "
                    f"no HBM tile tensor) or 'reference'.")
        # the sampling service + per-epoch balancer are created lazily on
        # the first epoch (close() tears the pool down)
        self._pool: Optional[SamplerPool] = None
        self._balancer = sched.LoadBalancer(self.num_devices,
                                            self.balance_policy)
        self._pstats = PipelineStats()

    def _use_kernel_layout(self) -> bool:
        return (self.model_cfg.aggregate_backend
                in gnn_models.KERNEL_BACKENDS
                and gnn_models.AGG_KIND[self.model_cfg.name] is not None)

    def _edge_stream(self) -> bool:
        return self.model_cfg.aggregate_backend in EDGE_STREAM_BACKENDS

    def densified_hbm_bytes(self) -> int:
        """Transient DEVICE-HBM bytes per batch spent on densified dense
        tile tensors: the full (Nd, max_blk, 128, 128) A + A^T footprint
        under ``aggregate_backend="pallas"``; ZERO under the streaming
        backends ``"pallas_edges"`` / ``"pallas_fused"`` (tiles exist only
        as one VMEM scratch per grid step — and the fused backend keeps the
        aggregated intermediate out of HBM too) and under the
        reference backend (no tiles at all). Tracked by
        ``BENCH_pipeline.json`` schema 5 and gated by check_regression."""
        if not self._blk_caps or self._edge_stream():
            return 0
        return densified_tile_bytes(self._blk_caps)

    def aggregate_intermediate_bytes(self) -> int:
        """Per-batch DEVICE-HBM bytes of the AGGREGATED intermediate — the
        (n_dstb*128, f_in) fp32 layer aggregates the unfused kernel paths
        ("pallas" / "pallas_edges") hand from the SpMM to the update matmul
        through device memory (one write + one read each). ZERO under
        ``"pallas_fused"``: the fused grid applies the update on the final
        k-step while the aggregate is still in VMEM, forward and backward
        (the VJP recomputes it). Feeds the simulator's fused-datapath model
        (SimConfig.agg_intermediate_bytes)."""
        if (not self._blk_caps
                or self.model_cfg.aggregate_backend == "pallas_fused"):
            return 0
        f_in = self.graph.features.shape[1]
        total = 0
        for (_, n_dst, _, _, _) in self._blk_caps:
            n_dstb = (n_dst + BLK - 1) // BLK
            total += n_dstb * BLK * f_in * 4
            f_in = self.model_cfg.hidden
        return total

    def aggregate_h2d_bytes(self, layout: str = "compact") -> int:
        """Per-batch host->device bytes for the aggregate-path layout.

        ``layout="compact"`` is what the trainer ships under
        ``aggregate_backend="pallas"`` (per-edge triples + cols tables);
        ``layout="edges"`` is the edge-streaming variant (tile-sorted
        per-edge arrays + CSR segment offsets, no tile_id);
        ``layout="dense"`` is what the pre-compact path shipped (full 64 KB
        tiles) — kept for the benchmark's trajectory ratio."""
        fn = {"compact": compact_layout_bytes,
              "edges": edge_stream_layout_bytes,
              "dense": dense_layout_bytes}[layout]
        total = 0
        for n_src, n_dst, max_blk, max_blk_t, e_cap in self._blk_caps:
            n_srcb = (n_src + BLK - 1) // BLK
            n_dstb = (n_dst + BLK - 1) // BLK
            total += fn(e_cap, n_dstb, max_blk, n_srcb, max_blk_t)
        return total

    # -- setup helpers ---------------------------------------------------------
    @contextlib.contextmanager
    def _setup_phase(self, phase: str):
        """Add the block's wall seconds to ``setup_phase_s[phase]``."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.setup_phase_s[phase] = (self.setup_phase_s.get(phase, 0.0)
                                         + time.perf_counter() - t0)

    def _train_ids(self, i: int) -> np.ndarray:
        mask = self.partition.assignment[self.graph.train_ids] == i
        ids = self.graph.train_ids[mask]
        return ids if len(ids) else self.graph.train_ids[:1]

    def _upload_shards(self) -> None:
        """Materialize every device's resident feature block and lay it
        across the mesh with a P("data") sharding: device d's slab lands in
        (and stays in) device d's memory — the paper's HBM-resident X_i.
        Re-run at epoch start when a feature cache changed residency;
        only the first upload counts as set-up."""
        with (self._setup_phase("shard_upload") if self._shard is None
              else contextlib.nullcontext()):
            mat = self.store.build_shard_matrix()
            self._shard = jax.device_put(
                mat, NamedSharding(self.mesh, P("data")))

    def _make_step(self):
        cfg = self.model_cfg
        opt = self.optimizer
        use_comp = self.grad_compression
        if self.mesh is not None:
            return self._make_mesh_step(cfg, opt, use_comp)

        def per_device_loss(params, batch):
            return gnn_models.loss_fn(cfg, params, batch)

        def step(params, opt_state, stacked, err):
            # per-batch loss weights: real batches 1.0, idle-device fill
            # batches 0.0 — the weighted mean keeps sync-SGD semantics equal
            # to averaging over only the REAL batches of the iteration.
            # Grads are taken PER DEVICE slot and combined with
            # one explicit weighted contraction (mirroring the mesh step's
            # per-device grads + psum) rather than differentiating the
            # weighted mean directly: the latter lets jax fold the device
            # sum into each dw dot_general (one merged contraction), a
            # reduction regrouping the opaque fused-kernel VJP cannot
            # reproduce — per-device grads are bitwise identical across all
            # aggregate backends, so this form keeps the whole step bitwise
            # at any device count.
            w = stacked["weight"].astype(jnp.float32)
            w_sum = jnp.maximum(w.sum(), 1.0)

            def device_val_grad(b):
                (l, m), g = jax.value_and_grad(
                    per_device_loss, has_aux=True)(params, b)
                return l, m, g

            # one slot after another (lax.map, not vmap): a batched
            # pallas_call cannot block its HBM-resident (memory_space=ANY)
            # operands, so the Mosaic kernels compile only unbatched
            with jax.named_scope("step/loss_grad"):
                losses, metrics, per_dev = jax.lax.map(device_val_grad,
                                                       stacked)
                loss = (losses * w).sum() / w_sum
                grads = jax.tree.map(
                    lambda g: jnp.tensordot(w, g, axes=1) / w_sum, per_dev)
            if use_comp:
                payload, err = compression.compress_tree(grads, err)
                grads = compression.decompress_tree(payload)
            with jax.named_scope("step/optimizer"):
                new_p, new_s, om = opt.update(grads, opt_state, params)
            out_metrics = {"loss": loss,
                           "acc": (metrics["acc"] * w).sum() / w_sum, **om}
            return new_p, new_s, err, out_metrics

        return step

    def _make_mesh_step(self, cfg, opt, use_comp):
        """The shard_map step (tentpole): slot d of the stacked batch axis
        runs on mesh device d against device d's HBM feature shard, as a
        genuinely per-device computation — layer-0 features are assembled
        ON DEVICE (resident reads + the shipped miss segment; P3 runs its
        layer-1 exchange as a real all_to_all) and gradients cross devices
        through one weight-scaled psum. The weighted-psum mean is exactly
        the vmap step's weighted mean, so idle-device fill batches (weight
        0) still contribute nothing; the optimizer update runs outside the
        shard_map on the replicated gradient."""
        p3 = self.algorithm == "p3"
        feat_dim = self.graph.features.shape[1]

        def device_grads(params, stacked, repl, vshard):
            b = dict(jax.tree.map(lambda x: x[0], stacked))
            shard = vshard[0]
            with jax.named_scope("step/assemble_feats"):
                if p3:
                    b["feats"] = gnn_models.p3_all_to_all_feats(
                        shard, repl["ids"], repl["valid"], feat_dim)
                else:
                    b["feats"] = gnn_models.assemble_device_feats(shard, b)
            w = b["weight"].astype(jnp.float32)
            with jax.named_scope("step/loss_grad"):
                (loss, metrics), grads = jax.value_and_grad(
                    lambda q: gnn_models.loss_fn(cfg, q, b),
                    has_aux=True)(params)
            w_sum = jnp.maximum(jax.lax.psum(w, "data"), 1.0)
            grads = jax.tree.map(
                lambda g: jax.lax.psum(g * w, "data") / w_sum, grads)
            loss = jax.lax.psum(loss * w, "data") / w_sum
            acc = jax.lax.psum(metrics["acc"] * w, "data") / w_sum
            return grads, loss, acc

        sharded_grads = jax.shard_map(
            device_grads, mesh=self.mesh,
            in_specs=(P(), P("data"), P(), P("data")),
            out_specs=(P(), P(), P()), check_vma=False)

        def step(params, opt_state, stacked, repl, vshard, err):
            grads, loss, acc = sharded_grads(params, stacked, repl, vshard)
            if use_comp:
                payload, err = compression.compress_tree(grads, err)
                grads = compression.decompress_tree(payload)
            with jax.named_scope("step/optimizer"):
                new_p, new_s, om = opt.update(grads, opt_state, params)
            return new_p, new_s, err, {"loss": loss, "acc": acc, **om}

        return step

    # -- the synchronous loop ---------------------------------------------------
    def epoch_schedule(self) -> List[sched.Assignment]:
        counts = [s.batches_remaining() for s in self.samplers]
        fn = (sched.two_stage_schedule if self.workload_balancing
              else sched.naive_schedule)
        return fn(counts)

    # -- host pipeline stages (run in the prefetch worker) ----------------------
    def _gather_features(self, device: int, mb: MiniBatch) -> np.ndarray:
        if self.algorithm == "p3":
            # Listing-3 all-to-all: every device contributes its feature-
            # dimension slice into one buffer, reconstituting the full rows
            return self.store.gather_p3_full(mb.nodes[0], mb.node_mask[0])
        return self.store.gather(device, mb.nodes[0], mb.node_mask[0])

    def _block_csr_arrays(self, mb: MiniBatch) -> dict:
        """Per-layer COMPACT block-CSR layout (fwd + transpose from one sort)
        for the Pallas aggregate datapath — kernels/layout.
        build_layer_layouts, the SAME routine the sampler-pool workers run,
        so layouts are bit-identical wherever the batch was sampled. The
        host stages only ~20 B/edge; densification happens on device inside
        the jit'd step (HBM scatter under "pallas", per-tile VMEM scratch
        under "pallas_edges"); shapes are pinned by self._blk_caps."""
        return build_layer_layouts(mb.edge_src, mb.edge_dst, mb.edge_mask,
                                   self._blk_caps,
                                   gnn_models.AGG_KIND[self.model_cfg.name],
                                   edge_stream=self._edge_stream())

    def _local_payload(self, task: BatchTask) -> dict:
        """The scheduling core's workers=0 runner: stage 1 through the
        partition's CURSOR-stateful sampler — bit-identical to
        ``batch_at(task.epoch, task.index)`` here, because the schedule
        visits each partition's batches in index order, while keeping the
        checkpointable cursor advancing exactly as before the scheduling-
        core extraction — plus stage 2b (compact layout build)."""
        with timed("feed/sample", self._pstats, "sample_s"):
            mb = self.samplers[task.partition].next_batch()
        layout = None
        if self._blk_caps:
            with timed("feed/layout", self._pstats, "layout_s"):
                layout = self._block_csr_arrays(mb)
        return {"minibatch": mb, "layout": layout,
                "load": mb.work_estimate()}

    def _sample_payload(self, a: sched.Assignment) -> dict:
        """In-process twin of one SamplerPool task: stage 1 (sample) plus
        stage 2b (compact layout build) for one scheduled batch."""
        return self._local_payload(
            BatchTask(a.partition, self.samplers[a.partition].epoch,
                      a.batch_index, a.device))

    def _batch_load(self, a: sched.Assignment, payload: dict) -> float:
        """Eq. 5 load estimate for the dynamic balancer, INCLUDING stage 2:
        vertices + edges traversed (``payload["load"]`` — computed where
        the batch was sampled, never re-derived here) plus the feature
        elements that must cross the bus to the scheduled device (miss rows
        x feature dim). When the worker already gathered for ``a.device``,
        the shipped row count IS that miss count, so the training thread
        does no residency probe at all. A pure function of the batch
        stream + residency either way, so the estimate is identical for
        every sampler-worker count and gather placement.

        Under ``round_robin`` the balancer ignores loads (the assignment is
        static) and the estimate only feeds the ``load_imbalance`` report
        metric, so the miss probe is skipped entirely — the training thread
        pays it only when the ``load`` policy actually consumes it."""
        if self.balance_policy == "round_robin":
            return payload["load"]
        fpay = payload.get("features")
        if self.algorithm == "p3":
            miss = 0  # every row resident (sliced) — nothing crosses
        elif fpay is not None and fpay["device"] == a.device:
            miss = len(fpay["pos"])
        else:
            mb = payload["minibatch"]
            miss = self.store.core.miss_count(a.device, mb.nodes[0],
                                              mb.node_mask[0])
        return sched.LoadBalancer.batch_load(
            payload["load"], miss, self.graph.features.shape[1])

    def _batch_features(self, dev: int, payload: dict) -> np.ndarray:
        """Stage 2 tail for one batch: in-process gather, or — when the
        payload carries worker-gathered rows — just the device placement
        (shipped miss rows memcpy in, resident rows read from HBM). Timing
        lands in ``PipelineStats.gather_s`` either way, so the benchmark
        can show the gather leaving the training process."""
        mb = payload["minibatch"]
        t0 = time.perf_counter()
        fpay = payload.get("features")
        if fpay is not None:
            feats = self.store.place_gathered(
                dev, mb.nodes[0], mb.node_mask[0], fpay["pos"],
                fpay["rows"], p3_full=self.algorithm == "p3",
                shipped_for=fpay["device"])
        else:
            feats = self._gather_features(dev, mb)
        self._pstats.gather_s += time.perf_counter() - t0
        self._pstats.ring_bytes += payload.get("ring_bytes", 0)
        return feats

    def _batch_mesh_payload(self, dev: int, payload: dict) -> dict:
        """Stage 2 under the mesh: instead of assembling the (N_0, f) block
        host-side, emit the index payload device ``dev`` assembles it FROM —
        hit positions into its HBM shard plus the capped miss-row segment
        (the only feature bytes that cross the bus, exactly the paper's
        cached-gather traffic). Worker-gathered rows (``gather_in_workers``)
        slot straight into the miss segment when the worker gathered for
        this device; a balancer-moved batch re-selects for the actual
        placement. Accounting matches the host-side ``gather`` bitwise."""
        mb = payload["minibatch"]
        t0 = time.perf_counter()
        ids = np.asarray(mb.nodes[0])
        valid = np.asarray(mb.node_mask[0], bool)
        n_valid = int(valid.sum())
        pos, hit = self.store.core.resident_positions(dev, ids, valid)
        fpay = payload.get("features")
        if fpay is not None and fpay["device"] == dev:
            mpos, mrows = fpay["pos"], fpay["rows"]
        else:
            mpos, mrows = self.store.core.select_ship_rows(
                dev, self.graph.features, ids, valid)
        self.store.account_rows(dev, n_valid - len(mpos), len(mpos))
        cap = self._miss_cap
        if len(mpos) > cap:
            raise ValueError(
                f"batch ships {len(mpos)} miss rows to device {dev} but "
                f"the mesh step's miss segment holds {cap} "
                f"(ship_rows_cap={self.model_cfg.ship_rows_cap}); raise "
                f"ship_rows_cap or grow the cache")
        # pad positions point one past the batch: the on-device scatter
        # lands them in a discard row (gnn.models.assemble_device_feats)
        mp = np.full(cap, len(ids), np.int32)
        mp[:len(mpos)] = mpos
        mr = np.zeros((cap, self.graph.features.shape[1]), np.float32)
        mr[:len(mrows)] = mrows
        self._pstats.gather_s += time.perf_counter() - t0
        self._pstats.ring_bytes += payload.get("ring_bytes", 0)
        return {"shard_pos": pos, "shard_hit": hit.astype(np.float32),
                "miss_pos": mp, "miss_rows": mr}

    def _assemble_group(self, assignments: List[sched.Assignment],
                        payloads: List[dict]) -> dict:
        """Stage 2 (gather or placement of worker-gathered rows) + stacking
        for one synchronous iteration, from sampled payloads (in-process or
        pool). The balancer maps batches to devices ("round_robin" keeps
        the scheduler's static assignment bit-exactly; "load" re-assigns by
        the gather-aware Eq. 5 estimate), and the stacked device axis
        follows that mapping."""
        mesh_active = self.mesh is not None
        loads = [self._batch_load(a, p)
                 for a, p in zip(assignments, payloads)]
        devices = self._balancer.assign(assignments, loads)
        vertices = 0
        slots: List[Optional[dict]] = [None] * self.num_devices
        slot_mb: List[Optional[MiniBatch]] = [None] * self.num_devices
        order = []  # legacy append order for the round_robin path
        order_mb: List[MiniBatch] = []
        for dev, payload in zip(devices, payloads):
            mb = payload["minibatch"]
            vertices += mb.vertices_traversed()
            if not mesh_active:
                arrs = batch_to_arrays(
                    mb, self._batch_features(dev, payload))
            elif self.algorithm == "p3":
                # no feature bytes ride the batch at all: the layer-1
                # all_to_all reconstructs full rows from the slice shards
                # on device; every contribution is a local HBM read
                arrs = batch_to_arrays(mb, None)
                self.store.account_p3_full(
                    int(np.asarray(mb.node_mask[0]).sum()))
                self._pstats.ring_bytes += payload.get("ring_bytes", 0)
            else:
                arrs = batch_to_arrays(mb, None)
                arrs.update(self._batch_mesh_payload(dev, payload))
            if payload["layout"] is not None:
                arrs.update(payload["layout"])
            slots[dev] = arrs
            slot_mb[dev] = mb
            order.append(arrs)
            order_mb.append(mb)
        if self.balance_policy == "round_robin" and not mesh_active:
            # historical stacking: group order, idle fills appended last
            batches = order
            while len(batches) < self.num_devices:
                fill = dict(batches[-1])
                fill["weight"] = np.float32(0.0)
                batches.append(fill)
        else:
            # device-indexed stacking: slot d holds device d's batch; empty
            # slots run a zero-weight dup of the last real batch. The mesh
            # step REQUIRES this ordering (slot d executes on mesh device
            # d, against device d's shard), so mesh mode uses it for every
            # balance policy.
            batches = list(slots)
            for d in range(self.num_devices):
                if batches[d] is None:
                    fill = dict(order[-1])
                    fill["weight"] = np.float32(0.0)
                    batches[d] = fill
                    slot_mb[d] = order_mb[-1]
        if self.cache is not None:
            # fold this iteration's accesses into the admission counter in
            # CONSUMPTION order (deterministic for any worker count), then
            # run the refresh hook: when (iter+1) % K == 0 it installs the
            # pending admitted set so iteration iter+1 onward — stamped
            # gen(i) = i // K at submission — gathers against it, and one
            # iteration earlier it launches the next ranking on a
            # background thread (overlapped with the device step)
            for payload in payloads:
                mb = payload["minibatch"]
                self.cache.observe(mb.nodes[0], mb.node_mask[0])
            self.cache.end_iteration(self._iter_no)
        out = {"stacked": stack_batches(batches), "vertices": vertices,
               "n_batches": len(assignments), "iteration": self._iter_no}
        self._iter_no += 1
        self._epoch_iter += 1
        if mesh_active and self.algorithm == "p3":
            # replicated all_to_all operands: EVERY device needs every
            # batch's layer-0 ids/masks to serve its feature-dim slice
            out["repl"] = {
                "ids": np.stack([np.asarray(m.nodes[0], np.int32)
                                 for m in slot_mb]),
                "valid": np.stack([np.asarray(m.node_mask[0], np.float32)
                                   for m in slot_mb])}
        if (self.checkpointer is not None and self.checkpoint_every > 0
                and self._epoch_iter % self.checkpoint_every == 0):
            # host state LEADS params: assembly (this prefetch-thread hook)
            # runs ahead of the device step, so the snapshot is taken HERE
            # — describing state after this iteration's assembly — and
            # saved by the MAIN loop right after this same iteration's
            # parameter update, keeping the pair consistent.
            out["host_ckpt"] = self._host_snapshot()
        return out

    def _prepare_group(self, assignments: List[sched.Assignment]) -> dict:
        """Stages 1+2 (sample + gather [+ block-CSR build]) for one
        synchronous iteration — pure host/numpy work, safe to run in the
        prefetch worker thread while the device executes iteration t-1."""
        return self._assemble_group(
            assignments, [self._sample_payload(a) for a in assignments])

    # -- stage 3: the jit'd device step -----------------------------------------
    def _execute(self, prepared: dict, sync: bool = True) -> dict:
        """Dispatch the jit'd step. ``sync=True`` materializes the metrics
        (blocks until the device finishes — strict per-iteration
        semantics). ``sync=False`` returns the raw async metric arrays so
        the epoch loop keeps dispatching while the device computes: the
        host never idles waiting on a result it only reads at epoch end,
        which is the second half of the Eq. 5-6 overlap (the prefetch
        thread being the first). Outstanding steps are bounded by the
        prefetch queue depth. The dispatch, up to the return of the jitted
        step, is timed into ``PipelineStats.dispatch_s`` (span
        ``step/dispatch``)."""
        with timed("step/dispatch", self._pstats, "dispatch_s",
                   iteration=prepared.get("iteration")):
            stacked = prepared["stacked"]
            if self._err is None and self.grad_compression:
                self._err = jax.tree.map(
                    lambda p: jnp.zeros_like(p, jnp.float32), self.params)
            if self.mesh is not None:
                # slot d of every stacked leaf lands on mesh device d; the
                # P3 all_to_all operands replicate. The feature shard was
                # uploaded once (epoch start) and stays in device HBM
                # across iterations.
                data = NamedSharding(self.mesh, P("data"))
                repl = NamedSharding(self.mesh, P())
                stacked = jax.tree.map(
                    lambda x: jax.device_put(x, data), stacked)
                repl_ops = jax.tree.map(lambda x: jax.device_put(x, repl),
                                        prepared.get("repl", {}))
                if self._shard is None:
                    self._upload_shards()
                args = (self.params, self.opt_state, stacked, repl_ops,
                        self._shard, self._err)
            else:
                args = (self.params, self.opt_state, stacked, self._err)
            if self._step_specs is None:  # one shape per config
                self._step_specs = jax.tree.map(_arg_spec, args)
            (self.params, self.opt_state, self._err,
             metrics) = self._jit_step(*args)
        self.step_no += 1
        if not sync:
            return metrics
        out = {k: float(v) for k, v in metrics.items()}
        out["vertices_traversed"] = prepared["vertices"]
        return out

    def compiled_step_text(self) -> str:
        """Optimized HLO text of the jitted step at the shapes it runs
        with: which Pallas kernels (``tpu_custom_call``) and collectives
        (``all-reduce``, ``all-to-all``) the compiler put in. Needs one
        dispatched step; the persistent compile cache makes it cheap."""
        if self._step_specs is None:
            raise RuntimeError("no step has been dispatched yet")
        return self._jit_step.lower(*self._step_specs).compile().as_text()

    def run_iteration(self, assignments: List[sched.Assignment]) -> dict:
        return self._execute(self._prepare_group(assignments))

    # -- the sampling service ---------------------------------------------------
    def _ensure_pool(self) -> SamplerPool:
        """Lazily spawn the sampling service (first epoch); reused across
        epochs, torn down by close()."""
        if self._pool is None:
            kind = (gnn_models.AGG_KIND[self.model_cfg.name]
                    if self._blk_caps else None)
            with self._setup_phase("pool_spawn"):
                self._pool = SamplerPool(
                    self.graph, self.model_cfg,
                    [self._train_ids(i) for i in range(self.num_devices)],
                    seed=self.seed, num_workers=self.num_sampler_workers,
                    agg_kind=kind,
                    blk_caps=self._blk_caps if self._blk_caps else None,
                    residency=(self.store.core if self.gather_in_workers
                               else None),
                    p3_full=self.algorithm == "p3",
                    feat_rows_cap=self._ring_rows_cap(),
                    worker_affinity=self.worker_affinity,
                    max_respawns=self.model_cfg.max_respawns,
                    straggler_timeout_s=self.model_cfg.straggler_timeout_s,
                    speculative=self.model_cfg.speculative_sampling,
                    fault_spec=self.model_cfg.fault_spec)
        return self._pool

    def _ring_rows_cap(self) -> Optional[int]:
        """Ring-slot rows capacity for the sampling service's codec.

        An explicit ``GNNModelConfig.ship_rows_cap`` always wins; with the
        knob unset and ``CacheConfig.auto_ship_rows_cap`` on (the default),
        the cap is MEASURED instead of worst-case: replay the next few
        epochs' schedules through the pure ``batch_at`` streams, count the
        rows each batch would actually ship (misses for the target device;
        every valid layer-0 row under P3 full-row shipping), and size the
        slot from that distribution via ``suggest_ship_rows_cap`` — the
        PR-5 carry-over that shrinks shm well below the worst-case layer-0
        node cap. A later batch that outgrows the measured cap fails
        loudly in ``PayloadCodec.encode`` naming the knob;
        ``auto_ship_rows_cap=False`` restores worst-case sizing."""
        cfg = self.model_cfg
        if cfg.ship_rows_cap is not None:
            return cfg.ship_rows_cap
        if not self.gather_in_workers or not cfg.cache.auto_ship_rows_cap:
            return None
        p3 = self.algorithm == "p3"
        fn = (sched.two_stage_schedule if self.workload_balancing
              else sched.naive_schedule)
        schedule = fn([s.epoch_batches() for s in self.samplers])
        counts = []
        epoch0 = self.samplers[0].epoch
        for epoch in range(epoch0, epoch0 + 3):
            for a in schedule:
                mb = self.samplers[a.partition].batch_at(epoch,
                                                         a.batch_index)
                ids = np.asarray(mb.nodes[0])
                valid = np.asarray(mb.node_mask[0], bool)
                if p3:  # p3_full ships every valid row's reconstruction
                    counts.append(int(valid.sum()))
                else:
                    counts.append(self.store.core.miss_count(
                        a.device, ids, valid))
        # max + headroom: epochs beyond the calibration window permute the
        # same train set, so their per-batch ship counts concentrate around
        # the measured ones — 25% slack absorbs the drift (and a cache's
        # later evictions), and the result never exceeds the worst case
        cap = suggest_ship_rows_cap(counts, percentile=100.0, margin=1.25)
        return min(cap, layer_capacities(cfg)[0][0])

    def _task_gen(self, global_iter: int) -> int:
        """Cache generation the batch of synchronous iteration
        ``global_iter`` must be gathered against. Without a cache the
        residency is immutable and the stamp stays 0. With periodic
        refresh (K > 0): generation ``i // K`` — installed at the END of
        iteration ``i//K * K - 1``'s assembly, i.e. strictly before any of
        iteration i's payloads are consumed, and AFTER every payload of
        the previous generation was consumed (so the single shared buffer
        is never overwritten under a reader). With epoch-boundary refresh
        (K == 0) the generation is constant within an epoch."""
        if self.cache is None:
            return 0
        K = self.model_cfg.cache_refresh_every
        return global_iter // K if K > 0 else self.cache.generation

    def run_epoch(self, resume: bool = False) -> dict:
        """One synchronous epoch. ``resume=True`` continues the epoch a
        restored checkpoint interrupted (see :meth:`restore_checkpoint`):
        sampler cursors, balancer loads and cache state are already the
        mid-epoch values, so resets are skipped, the FULL epoch schedule is
        rebuilt from the cursor-independent batch counts, and the first
        ``_epoch_iter`` iteration groups — already executed before the
        kill — are skipped."""
        if not resume:
            for s in self.samplers:
                s.reset_epoch()
            self._epoch_iter = 0
        # per-epoch beta/miss accounting (hit rates comparable across
        # epochs) + the cache's epoch hook: counter reset, and in
        # epoch-boundary mode the synchronous admission/eviction pass —
        # BEFORE any task submission so workers stamp the new generation
        self.store.reset_stats()
        if self.cache is not None and not resume:
            self.cache.start_epoch()
        if self.mesh is not None and self.cache is not None:
            # epoch-boundary refresh may have changed residency: rebuild
            # the per-device HBM shards against the new resident sets
            self._upload_shards()
        if not resume:
            self._balancer = sched.LoadBalancer(self.num_devices,
                                                self.balance_policy)
        if resume:
            # the interrupted epoch's schedule, reconstructed: the counts
            # must be the FULL epoch's (in-process cursors sit mid-epoch),
            # and the schedule is a pure function of the counts
            counts = [s.epoch_batches() for s in self.samplers]
            fn = (sched.two_stage_schedule if self.workload_balancing
                  else sched.naive_schedule)
            schedule = fn(counts)
        else:
            schedule = self.epoch_schedule()
        groups = list(sched.iterations(schedule))
        run_groups = groups[self._epoch_iter:] if resume else groups
        t0 = time.time()
        self._compiles0 = self._compiles.compiles
        pstats = self._pstats = PipelineStats()
        # the scheduling core streams the epoch's batch source — one unit
        # per iteration group, tasks addressed by pure RNG coordinates
        # (partition, epoch, batch_index). a.device is the scheduler's
        # static target — exact under round_robin; under "load" it is the
        # residency HINT the worker gathers for (placement re-accounts if
        # the balancer moves the batch; values are device-independent so
        # training is unaffected). The generation stamp names the cache
        # contents the worker must gather against — a pure function of the
        # batch's global iteration number, so the hit/miss split is
        # identical for every worker count and completion order.
        base = self._iter_no
        source = EpochSource(run_groups, self.samplers[0].epoch,
                             gen_for_group=lambda gi: self._task_gen(
                                 base + gi))
        if self.num_sampler_workers > 0:
            # stage 1+2b run in the sampler worker processes; the prefetch
            # thread only gathers features, stacks, and keeps the reorder
            # buffer drained while the main thread dispatches device steps.
            # Payloads come back in submission order via the pool's reorder
            # buffer, so the stream is bit-identical to the in-process
            # sampler whatever the worker count or completion order; the
            # bounded submission window caps staged batches exactly like
            # prefetch depth.
            core = SchedulingCore(
                pool=self._ensure_pool(),
                window=max(4 * self.num_sampler_workers,
                           (self.prefetch_depth + 1) * self.num_devices))
            items = core.payload_stream(source)

            def prepare(item):
                return self._assemble_group(*item)
        else:
            items = source.units()

            def prepare(item):
                group, tasks = item
                return self._assemble_group(
                    group, [self._local_payload(t) for t in tasks])
        # per-epoch recovery metrics = the pool's lifetime counters deltaed
        # against this snapshot
        self._pool_stats0 = (dict(self._pool.stats)
                             if self._pool is not None else {})
        try:
            return self._run_epoch_loop(schedule, run_groups, items,
                                        prepare, pstats, t0)
        except BaseException:
            # an abandoned epoch leaves in-flight pool tasks whose sequence
            # numbers would bleed into the next epoch's reorder stream —
            # tear the service down so the next epoch starts clean
            self.close()
            raise

    def _run_epoch_loop(self, schedule, groups, items, prepare, pstats, t0):
        # epoch metrics are the batch-weighted MEAN over the iterations (an
        # epoch-level estimate, not the last 1-group sample); the pipelined
        # path still syncs only once, at epoch end — the per-step metric
        # scalars stay async until then
        step_metrics: List[tuple] = []  # (async metric dict, n_batches)
        vertices = 0
        n_batches = 0
        if self.pipeline:
            prepared_iter = PrefetchExecutor(
                prepare, self.prefetch_depth, pstats,
                first_iteration=self._iter_no).run(items)
            # backpressure: at most prefetch_depth dispatched-but-unfinished
            # steps, else a fast host would pile up live input buffers
            inflight: deque = deque()
            for prepared in prepared_iter:
                m = self._execute(prepared, sync=False)
                inflight.append(m)
                step_metrics.append((m, prepared["n_batches"]))
                if "host_ckpt" in prepared:
                    # params/opt now hold THIS iteration's update (async is
                    # fine — the save thread blocks materializing them),
                    # matching the host state snapshotted at its assembly
                    self.checkpointer.save(self.step_no, self.params,
                                           self.opt_state,
                                           extra=prepared["host_ckpt"])
                if len(inflight) > self.prefetch_depth:
                    jax.block_until_ready(inflight.popleft())
                vertices += prepared["vertices"]
                n_batches += prepared["n_batches"]
            if inflight:  # one final sync per epoch, not per iteration
                jax.block_until_ready(inflight[-1])
        else:
            for prepared in (prepare(it) for it in items):
                m = self._execute(prepared)
                vertices += m.pop("vertices_traversed")
                step_metrics.append((m, prepared["n_batches"]))
                if "host_ckpt" in prepared:
                    self.checkpointer.save(self.step_no, self.params,
                                           self.opt_state,
                                           extra=prepared["host_ckpt"])
                n_batches += prepared["n_batches"]
        metrics: Dict[str, float] = {}
        if step_metrics:
            total = sum(nb for _, nb in step_metrics)
            metrics = {k: sum(float(m[k]) * nb for m, nb in step_metrics)
                       / total
                       for k in step_metrics[0][0]}
        wall = time.time() - t0
        stats = sched.schedule_stats(schedule, self.num_devices)
        n_iter = stats["iterations"]
        # cache-facing traffic split for THIS epoch (stats reset at epoch
        # start): hits are device-HBM reads, misses cross the host bus —
        # miss_bytes_per_iter is the number the regression gate pins
        local_rows = sum(s.local_rows for s in self.store.stats)
        host_rows = sum(s.host_rows for s in self.store.stats)
        host_bytes = sum(s.host_bytes for s in self.store.stats)
        total_rows = local_rows + host_rows
        cache = self.cache
        # this epoch's recovery actions: the supervisor's lifetime counters
        # minus the epoch-start snapshot
        pool = self._pool
        base = self._pool_stats0
        pstat = pool.stats if pool is not None else {}
        recov = {k: pstat.get(k, 0) - base.get(k, 0)
                 for k in ("respawns", "resubmissions", "speculative",
                           "duplicates_dropped", "stale_results",
                           "crc_failures",
                           "degraded_tasks", "recovery_s", "sample_s",
                           "layout_s", "ship_s", "decode_s")}
        if "compile" not in self.setup_phase_s:
            self.setup_phase_s["compile"] = (self._compiles.seconds
                                             - self._compile_s0)
        return {**metrics, "epoch_time_s": wall, "batches": n_batches,
                "pool_respawns": recov["respawns"],
                "pool_resubmissions": recov["resubmissions"],
                # duplicates_dropped now counts ONLY resolved speculative
                # races (post-death resubmission overlaps land in
                # stale_results), so hits can never exceed launches
                "pool_speculative_hits": recov["duplicates_dropped"],
                "pool_speculative_launched": recov["speculative"],
                "pool_stale_results": recov["stale_results"],
                "pool_crc_failures": recov["crc_failures"],
                "pool_degraded_batches": recov["degraded_tasks"],
                "pool_recovery_s": recov["recovery_s"],
                "pool_degraded": pool.degraded if pool is not None
                else False,
                "iterations": n_iter,
                "utilization": stats["utilization"],
                "mesh_devices": (self.num_devices if self.mesh is not None
                                 else 0),
                "fill_slots": stats["fill_slots"],
                "vertices_traversed": vertices,
                "nvtps": vertices / wall if wall > 0 else 0.0,
                "beta": self.store.beta(),
                "pipeline": self.pipeline,
                "sampler_workers": self.num_sampler_workers,
                "balance_policy": self.balance_policy,
                "gather_in_workers": self.gather_in_workers,
                "load_imbalance": self._balancer.imbalance(),
                "host_produce_s": pstats.produce_s,
                "host_wait_s": pstats.wait_s,
                # the feed by stage: the prefetch thread's wait for its
                # next item, less the ring decode it ran (its own line);
                # the main thread's step dispatch; the sampling stages,
                # summed over the workers (or run in this process)
                "host_source_wait_s": pstats.source_wait_s,
                "host_decode_s": recov["decode_s"],
                "host_dispatch_s": pstats.dispatch_s,
                "pool_sample_s": recov["sample_s"] + pstats.sample_s,
                "pool_layout_s": recov["layout_s"] + pstats.layout_s,
                "pool_ship_s": recov["ship_s"],
                "compiles": self._compiles.compiles - self._compiles0,
                # stage-2 split: time the TRAINING PROCESS spent gathering
                # (in-process) or placing (worker-gathered) feature rows,
                # and the ring traffic the offload cost per iteration
                "host_gather_s": pstats.gather_s,
                "ring_bytes": pstats.ring_bytes,
                "ring_bytes_per_iter": (pstats.ring_bytes / n_iter
                                        if n_iter else 0.0),
                "cache_enabled": cache is not None,
                "cache_hit_rate": (local_rows / total_rows
                                   if total_rows else 1.0),
                "miss_bytes": host_bytes,
                "miss_bytes_per_iter": (host_bytes / n_iter
                                        if n_iter else 0.0),
                "cache_admissions": (cache.admissions_epoch if cache
                                     else 0),
                "cache_evictions": (cache.evictions_epoch if cache else 0),
                "cache_refresh_bytes": (cache.refresh_bytes_epoch if cache
                                        else 0)}

    def train(self, epochs: int = 1) -> List[dict]:
        return [self.run_epoch() for _ in range(epochs)]

    # -- mid-epoch checkpoint/resume --------------------------------------------
    def _host_snapshot(self) -> dict:
        """JSON-serializable host-pipeline state as of the just-assembled
        iteration: global/epoch iteration cursors, per-partition sampler
        cursors (the permutation regenerates from the RNG counters),
        balancer running loads, and — with a cache — the frequency counter,
        per-device resident sets, generation and any pending (already
        ranked) admission set. Runs on the prefetch thread inside
        ``_assemble_group``, where this state is exactly one iteration
        ahead of params — the save pairs it with that iteration's update."""
        snap: dict = {"iter_no": self._iter_no,
                      "epoch_iter": self._epoch_iter,
                      "samplers": [s.state() for s in self.samplers],
                      "balancer_load": [float(x)
                                        for x in self._balancer.load]}
        c = self.cache
        if c is not None:
            pending = None
            if c._pending is not None:
                gen, t, holder = c._pending
                # the ranking is determined by the freq snapshot taken at
                # launch — joining here only changes timing, never content
                t.join()
                pending = {"gen": int(gen), "ids": holder[0].tolist()}
            resident = {str(d): c.core.resident_ids(d).tolist()
                        for d in range(c.core.num_devices)
                        if not c.core._all_resident[d]}
            snap["cache"] = {
                "freq": c.freq.tolist(),
                "epochs_run": c._epochs_run,
                "generation": int(c.generation),
                "resident": resident,
                "pending": pending,
                "counters": [c.admissions_total, c.evictions_total,
                             c.refresh_bytes_total, c.refreshes,
                             c.admissions_epoch, c.evictions_epoch,
                             c.refresh_bytes_epoch]}
        return snap

    def restore_checkpoint(self, step: Optional[int] = None) -> int:
        """Restore params + optimizer + host-pipeline state from the newest
        (or the given) verified checkpoint into THIS trainer — construct it
        with the same arguments as the killed run first. Follow with
        ``run_epoch(resume=True)`` to finish the interrupted epoch; the
        completed run's final params are bit-identical to an uninterrupted
        one (counter-based sampler RNG + the restored cursors/cache
        timeline). Returns the restored step."""
        if self.checkpointer is None:
            raise RuntimeError("trainer has no checkpointer")
        if step is None:
            step = self.checkpointer.latest_step()
            if step is None:
                raise FileNotFoundError("no valid checkpoint to restore")
        out = self.checkpointer.restore(step, self.params, self.opt_state)
        self.params = out["params"]
        self.opt_state = out["opt"]
        self.step_no = int(out["step"])
        extra = out["extra"]
        self._iter_no = int(extra["iter_no"])
        self._epoch_iter = int(extra["epoch_iter"])
        for s, st in zip(self.samplers, extra["samplers"]):
            s.restore_state(st)
        self._balancer = sched.LoadBalancer(self.num_devices,
                                            self.balance_policy)
        self._balancer.load = [float(x) for x in extra["balancer_load"]]
        cstate = extra.get("cache")
        if self.cache is not None and cstate is not None:
            c = self.cache
            c.freq[:] = np.asarray(cstate["freq"], np.int64)
            c._epochs_run = int(cstate["epochs_run"])
            (c.admissions_total, c.evictions_total, c.refresh_bytes_total,
             c.refreshes, c.admissions_epoch, c.evictions_epoch,
             c.refresh_bytes_epoch) = cstate["counters"]
            for d_str, ids in cstate["resident"].items():
                c.core.set_resident(int(d_str),
                                    np.asarray(ids, np.int32))
            c.core.publish_generation(int(cstate["generation"]))
            if c._pending is not None:  # drop any stale in-flight ranking
                _, t, _ = c._pending
                c._pending = None
                t.join()
            p = cstate.get("pending")
            if p is not None:
                # reconstruct the pending refresh as already-finished: the
                # checkpoint stored its RESULT, so a dummy joined thread +
                # a filled holder make _join_apply behave identically
                holder = [np.asarray(p["ids"], np.int32)]
                t = threading.Thread(target=lambda: None,
                                     name="hitgnn-cache-refresh")
                t.start()
                c._pending = (int(p["gen"]), t, holder)
        return int(out["step"])

    # -- lifecycle --------------------------------------------------------------
    def close(self) -> None:
        """Tear down the sampling service (worker processes + shared-memory
        segments) and any in-flight cache-refresh thread. Idempotent;
        trainers without workers are no-ops."""
        if getattr(self, "_pool", None) is not None:
            self._pool.close()
            self._pool = None
        if getattr(self, "cache", None) is not None:
            self.cache.close()

    def __enter__(self) -> "SyncGNNTrainer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
