"""Request-driven serving runtime on the training substrate (ROADMAP item 3).

The north-star scenario — "heavy traffic from millions of users" — drives
the SAME host machinery as epoch training, just from a different batch
source: target-node inference requests arrive one at a time, get coalesced
into dynamic micro-batches under a latency SLO, and flow through the
scheduling core into the supervised ``SamplerPool``. Everything the
fault-tolerant pool already provides carries over verbatim — worker
respawn, straggler speculation (the p99 lever), per-fetch absolute
deadlines (the SLO primitive), and fault injection for chaos-testing the
request path.

Three pieces:

* :func:`bucket_ladder` — the fixed menu of micro-batch target counts.
  Every request batch is padded (cyclically, deterministically) up to the
  smallest bucket that fits, and each bucket gets ONE jit-compiled
  forward over its fixed shapes — after one warmup pass per bucket,
  steady-state serving triggers zero recompiles no matter how request
  sizes fluctuate.
* :class:`MicroBatcher` — the pure SLO-deadline coalescing policy (no
  threads, unit-testable): hold arrivals while waiting costs nothing,
  flush when the batch fills the largest bucket or when waiting any
  longer would eat into the oldest request's deadline given the bucket's
  measured (EWMA) service time.
* :class:`ServingRuntime` — the frontend: a synchronous ``predict`` (one
  request = one micro-batch; the deterministic path tests and chaos runs
  pin bitwise) and an asynchronous ``submit`` returning a Future, drained
  by a dispatcher thread through the coalescer.

RNG discipline: each micro-batch is addressed ``(partition=0,
SERVE_EPOCH, request_index, targets)`` — ``SERVE_EPOCH`` is a constant
far above any training epoch, so serving streams never collide with
training streams, and the monotonically increasing request index makes
every submission a pure, re-executable coordinate: a respawned or
speculated worker re-materializes the bit-identical neighborhood.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field, replace
from queue import Empty, Queue
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.configs.gnn import GNNModelConfig
from repro.core.feature_store import FeatureStore
from repro.core.partition import get_partitioner
from repro.core.sampler import (NeighborSampler, layer_capacities_for,
                                slice_minibatch)
from repro.core.sampler_pool import SamplerPool
from repro.core.scheduling import BatchTask, SchedulingCore
from repro.data.graphs import Graph
from repro.kernels.layout import (EDGE_STREAM_BACKENDS, block_capacities,
                                  build_layer_layouts)

# RNG epoch coordinate reserved for serving streams — far above any
# realistic training epoch count, so (seed, partition, epoch, tag) streams
# of the two modes never collide
SERVE_EPOCH = 1 << 30


def bucket_ladder(batch_targets: int,
                  buckets: Optional[Sequence[int]] = None) -> Tuple[int, ...]:
    """The menu of micro-batch target counts, ascending.

    Explicit ``buckets`` are validated (deduplicated, sorted, each within
    ``1..batch_targets``); the default ladder grows geometrically (x4)
    from 8 and always tops out at ``batch_targets``, so a handful of
    compiled forwards covers every request size up to the training batch
    shape."""
    if buckets is not None:
        out = sorted(set(int(b) for b in buckets))
        if not out:
            raise ValueError("bucket ladder must not be empty")
        if out[0] < 1 or out[-1] > batch_targets:
            raise ValueError(
                f"buckets must lie in 1..{batch_targets} (= batch_targets); "
                f"got {out}")
        return tuple(out)
    ladder = []
    b = min(8, batch_targets)
    while b < batch_targets:
        ladder.append(b)
        b *= 4
    ladder.append(batch_targets)
    return tuple(ladder)


@dataclass(frozen=True)
class ServeConfig:
    """Serving-frontend knobs (everything else — fault tolerance,
    speculation, fault injection — rides on ``GNNModelConfig.fault``).

    * ``slo_ms`` — per-request latency objective; the coalescer budgets
      its waiting against it and misses are reported, never errored.
    * ``buckets`` — explicit bucket ladder (None = default, see
      :func:`bucket_ladder`).
    * ``num_workers`` — sampler-pool worker processes (0 = sample
      in-process; bit-identical either way).
    * ``fetch_timeout_s`` — absolute deadline for one micro-batch's
      payloads; a faulted pool recovers within it, so requests complete
      past SLO rather than erroring.
    * ``safety_frac`` — fraction of the SLO held back as slack when the
      coalescer decides how long waiting is still safe.
    """

    slo_ms: float = 50.0
    buckets: Optional[Tuple[int, ...]] = None
    num_workers: int = 0
    fetch_timeout_s: float = 30.0
    safety_frac: float = 0.1


class MicroBatcher:
    """SLO-deadline micro-batch coalescing — pure policy, no threads.

    Requests enter with an absolute deadline (arrival + SLO). The batcher
    flushes when (a) pending targets fill the largest bucket, or (b) the
    clock reaches :meth:`flush_at` — the point where waiting any longer
    would push the OLDEST request past its deadline, given the EWMA
    service-time estimate for the bucket the pending set would flush into
    plus a safety fraction of the SLO."""

    def __init__(self, buckets: Sequence[int], slo_s: float,
                 safety_frac: float = 0.1):
        self.buckets = tuple(sorted(buckets))
        self.slo_s = float(slo_s)
        self.safety_s = safety_frac * self.slo_s
        self._pending: List[Tuple[float, int, Any]] = []  # (deadline, n, it)
        self._est: Dict[int, float] = {b: 0.0 for b in self.buckets}

    def bucket_for(self, n_targets: int) -> int:
        """Smallest bucket admitting ``n_targets`` (the largest bucket
        for anything bigger — the caller chunks oversized requests)."""
        for b in self.buckets:
            if n_targets <= b:
                return b
        return self.buckets[-1]

    def estimate(self, bucket: int) -> float:
        return self._est[bucket]

    def observe(self, bucket: int, service_s: float) -> None:
        """Fold a measured micro-batch service time into the bucket's
        EWMA (the coalescer's notion of how expensive waiting is)."""
        prev = self._est[bucket]
        self._est[bucket] = (service_s if prev == 0.0
                             else 0.7 * prev + 0.3 * service_s)

    # -- pending set ---------------------------------------------------------
    def add(self, item: Any, n_targets: int, deadline: float) -> None:
        self._pending.append((deadline, n_targets, item))

    @property
    def pending(self) -> int:
        return len(self._pending)

    @property
    def pending_targets(self) -> int:
        return sum(n for _, n, _ in self._pending)

    def flush_at(self) -> Optional[float]:
        """Absolute time the pending set must flush to protect the oldest
        request's SLO (None = nothing pending). New arrivals only ever
        move this EARLIER (they cannot relax an existing deadline)."""
        if not self._pending:
            return None
        oldest = min(d for d, _, _ in self._pending)
        b = self.bucket_for(min(self.pending_targets, self.buckets[-1]))
        return oldest - self.estimate(b) - self.safety_s

    def due(self, now: float) -> bool:
        if not self._pending:
            return False
        if self.pending_targets >= self.buckets[-1]:
            return True
        return now >= self.flush_at()

    def take(self) -> List[Any]:
        """Pop the flushing micro-batch: requests in arrival order until
        the next one would overflow the largest bucket (it stays pending
        for the following flush)."""
        out, total = [], 0
        keep: List[Tuple[float, int, Any]] = []
        for deadline, n, item in self._pending:
            if out and total + n > self.buckets[-1]:
                keep.append((deadline, n, item))
                continue
            out.append(item)
            total += n
        self._pending = keep
        return out


@dataclass
class _Request:
    ids: np.ndarray
    arrival: float
    future: Future = field(default_factory=Future)


class ServingRuntime:
    """Target-node inference over a trained (or fresh) parameter set.

    ``predict(ids)`` is the synchronous path: one request becomes one
    micro-batch immediately (deterministic — the bitwise contracts and
    chaos tests pin it). ``submit(ids)`` is the concurrent path: requests
    queue to a dispatcher thread that coalesces them through the
    :class:`MicroBatcher` before sampling. Both share ``_serve_targets``:
    pad the target ids cyclically up to the bucket, submit one
    explicit-target task through the scheduling core (pool or in-process
    twin — payloads bitwise equal either way), gather features
    consumer-side, and run the bucket's compiled forward."""

    def __init__(self, graph: Graph, model_cfg: GNNModelConfig, params,
                 *, algorithm: str = "distdgl",
                 serve_cfg: Optional[ServeConfig] = None,
                 store: Optional[FeatureStore] = None, seed: int = 0):
        from repro.core import trainer as _trainer  # jax-heavy; lazy
        self._trainer_mod = _trainer
        self.graph = graph
        self.cfg = model_cfg
        self.params = params
        self.serve_cfg = serve_cfg if serve_cfg is not None else ServeConfig()
        self.seed = seed
        self.buckets = bucket_ladder(model_cfg.batch_targets,
                                     self.serve_cfg.buckets)
        self.slo_s = self.serve_cfg.slo_ms / 1e3
        if store is None:
            part_name, store_name = _trainer.ALGORITHMS[algorithm]
            partition = get_partitioner(part_name)(graph, 1, seed)
            store = FeatureStore(graph, partition, store_name)
        self.store = store
        # private sampler: the in-process twin of a pool worker. Request
        # batches never draw the tail-pad stream (the runtime pads targets
        # itself), so the train-id set does not influence the payload.
        self._sampler = NeighborSampler(graph, model_cfg, graph.train_ids,
                                        0, seed)
        self._pool: Optional[SamplerPool] = None
        if self.serve_cfg.num_workers >= 1:
            self._pool = SamplerPool(
                graph, model_cfg, [graph.train_ids], seed=seed,
                num_workers=self.serve_cfg.num_workers,
                max_respawns=model_cfg.max_respawns,
                straggler_timeout_s=model_cfg.straggler_timeout_s,
                speculative=model_cfg.speculative_sampling,
                fault_spec=model_cfg.fault_spec)
        self._core = SchedulingCore(
            pool=self._pool, local_fn=self._local_payload,
            fetch_timeout=self.serve_cfg.fetch_timeout_s)
        self.batcher = MicroBatcher(self.buckets, self.slo_s,
                                    self.serve_cfg.safety_frac)
        self._fwd: Dict[int, Any] = {}  # bucket -> jitted forward
        self._next_rid = 0
        self._lock = threading.Lock()
        self._closed = False
        # dispatcher state (submit path)
        self._queue: "Queue[_Request]" = Queue()
        self._dispatcher: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # service metrics
        self.latencies_s: List[float] = []
        self.slo_misses = 0
        self.completed = 0

    # -- compiled forwards ----------------------------------------------------
    def _forward_for(self, bucket: int):
        fn = self._fwd.get(bucket)
        if fn is None:
            import jax
            from repro.gnn import models as gnn_models
            cfg = self.cfg

            def fwd(params, batch):
                return gnn_models.forward(cfg, params, batch)

            fn = self._fwd[bucket] = jax.jit(fwd)
        return fn

    def _bucket_layout(self, bucket: int, mb) -> dict:
        """The stage-2b layout a kernel aggregate backend consumes, at the
        bucket's static capacities (the trainer's planner with
        ``batch_targets=bucket``); empty for the reference path and GAT."""
        from repro.gnn.models import AGG_KIND, KERNEL_BACKENDS
        kind = AGG_KIND[self.cfg.name]
        if self.cfg.aggregate_backend not in KERNEL_BACKENDS or kind is None:
            return {}
        caps = block_capacities(replace(self.cfg, batch_targets=bucket))
        return build_layer_layouts(
            mb.edge_src, mb.edge_dst, mb.edge_mask, caps, kind,
            edge_stream=self.cfg.aggregate_backend in EDGE_STREAM_BACKENDS)

    @property
    def forward_compiles(self) -> int:
        """Compiled-executable count across the bucket forwards — flat
        after warmup is the zero-steady-state-recompile contract."""
        total = 0
        for fn in self._fwd.values():
            cache_size = getattr(fn, "_cache_size", None)
            total += int(cache_size()) if callable(cache_size) else 1
        return total

    def warmup(self) -> int:
        """Compile every bucket's forward up front (one dummy micro-batch
        each, smallest first) so the first real request never pays a
        trace. Returns the compile count."""
        anchor = int(self.graph.train_ids[0])
        for b in self.buckets:
            self._serve_targets(np.full(b, anchor, np.int32))
        return self.forward_compiles

    # -- the request path -----------------------------------------------------
    def _local_payload(self, task: BatchTask) -> dict:
        """Workers=0 twin of a pool request task — the bucket-shaped batch
        straight from the sampler (no codec pad/slice round trip, which is
        exact, so both paths hand identical arrays downstream)."""
        mb = self._sampler.request_batch(task.epoch, task.index,
                                         task.targets)
        return {"minibatch": mb, "layout": None, "features": None,
                "ring_bytes": 0, "load": mb.work_estimate()}

    def _serve_targets(self, ids: np.ndarray) -> np.ndarray:
        """One micro-batch end to end; returns (len(ids), n_classes)
        logits aligned with ``ids``. Thread-confined to the caller — the
        lock serializes device work between predict() callers and the
        dispatcher."""
        import jax
        ids = np.asarray(ids, np.int32)
        m = len(ids)
        bucket = self.batcher.bucket_for(m)
        # cyclic pad: deterministic (no RNG), and np.unique inside the
        # sampler collapses the duplicates so padding costs ~nothing
        padded = ids[np.arange(bucket) % m]
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            task = BatchTask(0, SERVE_EPOCH, rid, 0, 0, padded)
            self._core.submit_unit(rid, [task])
            _, payloads = self._core.collect_unit(
                timeout=self.serve_cfg.fetch_timeout_s)
            mb = payloads[0]["minibatch"]
            if len(mb.targets) != bucket:  # pool path: codec-shaped — slice
                n_caps, e_caps = layer_capacities_for(bucket,
                                                      self.cfg.fanouts)
                mb = slice_minibatch(mb, n_caps, e_caps)
            t0 = time.perf_counter()
            feats = self.store.gather(0, mb.nodes[0], mb.node_mask[0])
            arrs = self._trainer_mod.batch_to_arrays(mb, feats)
            arrs.update(self._bucket_layout(bucket, mb))
            logits = self._forward_for(bucket)(self.params, arrs)
            logits = np.asarray(jax.block_until_ready(logits))
            self.batcher.observe(bucket, time.perf_counter() - t0)
        return logits[:m]

    def predict(self, ids: np.ndarray) -> np.ndarray:
        """Synchronous inference for ``ids`` (chunked through the largest
        bucket when oversized). Records one latency/SLO sample."""
        if self._closed:
            raise RuntimeError("ServingRuntime is closed")
        t0 = time.monotonic()
        ids = np.atleast_1d(np.asarray(ids, np.int32))
        cap = self.buckets[-1]
        out = [self._serve_targets(ids[lo:lo + cap])
               for lo in range(0, len(ids), cap)]
        self._record(time.monotonic() - t0)
        return np.concatenate(out, axis=0)

    def _record(self, latency_s: float) -> None:
        self.latencies_s.append(latency_s)
        self.completed += 1
        if latency_s > self.slo_s:
            self.slo_misses += 1

    # -- concurrent frontend --------------------------------------------------
    def start(self) -> "ServingRuntime":
        """Start the dispatcher thread serving :meth:`submit` requests."""
        if self._dispatcher is None:
            self._stop.clear()
            self._dispatcher = threading.Thread(
                target=self._dispatch_loop, name="hitgnn-serve-dispatch",
                daemon=True)
            self._dispatcher.start()
        return self

    def submit(self, ids: np.ndarray) -> Future:
        """Enqueue one request; the Future resolves to its
        (len(ids), n_classes) logits once a coalesced micro-batch carries
        it through the substrate."""
        if self._closed:
            raise RuntimeError("ServingRuntime is closed")
        if self._dispatcher is None:
            self.start()
        req = _Request(np.atleast_1d(np.asarray(ids, np.int32)),
                       time.monotonic())
        self._queue.put(req)
        return req.future

    def _dispatch_loop(self) -> None:
        batcher = self.batcher
        while not self._stop.is_set():
            now = time.monotonic()
            flush_at = batcher.flush_at()
            wait = (0.05 if flush_at is None
                    else max(0.0, min(flush_at - now, 0.05)))
            try:
                req = self._queue.get(timeout=wait)
                batcher.add(req, len(req.ids),
                            req.arrival + self.slo_s)
            except Empty:
                pass
            while batcher.due(time.monotonic()):
                self._flush(batcher.take())
        # drain: fail any still-queued requests loudly on shutdown
        while True:
            try:
                req = self._queue.get_nowait()
            except Empty:
                break
            req.future.set_exception(RuntimeError("serving runtime closed"))
        for _, _, req in batcher._pending:
            req.future.set_exception(RuntimeError("serving runtime closed"))
        batcher._pending = []

    def _flush(self, requests: List[_Request]) -> None:
        if not requests:
            return
        ids = np.concatenate([r.ids for r in requests])
        try:
            logits = self._serve_targets(ids)
        except BaseException as e:
            for r in requests:
                r.future.set_exception(e)
            return
        now = time.monotonic()
        lo = 0
        for r in requests:
            r.future.set_result(logits[lo:lo + len(r.ids)])
            lo += len(r.ids)
            self._record(now - r.arrival)

    # -- reporting / lifecycle ------------------------------------------------
    def stats(self) -> dict:
        lat = np.asarray(self.latencies_s, np.float64)
        out = {
            "completed": self.completed,
            "slo_ms": self.serve_cfg.slo_ms,
            "slo_misses": self.slo_misses,
            "slo_miss_rate": (self.slo_misses / self.completed
                              if self.completed else 0.0),
            "p50_ms": float(np.percentile(lat, 50) * 1e3) if lat.size
            else 0.0,
            "p99_ms": float(np.percentile(lat, 99) * 1e3) if lat.size
            else 0.0,
            "buckets": list(self.buckets),
            "forward_compiles": self.forward_compiles,
            "pool_workers": self.serve_cfg.num_workers,
        }
        if self._pool is not None:
            out["pool"] = dict(self._pool.stats)
            out["pool_degraded"] = self._pool.degraded
        return out

    def reset_stats(self) -> None:
        """Zero the latency/SLO counters (bench load points call this
        between measurements; compile counts are NOT reset — steady-state
        recompiles must stay visible across points)."""
        self.latencies_s = []
        self.slo_misses = 0
        self.completed = 0

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        if self._dispatcher is not None:
            self._dispatcher.join(timeout=5.0)
            self._dispatcher = None
        if self._pool is not None:
            self._pool.close()

    def __enter__(self) -> "ServingRuntime":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def closed_loop_load(runtime: ServingRuntime, target_pool: np.ndarray,
                     clients: int, requests_per_client: int,
                     ids_per_request: int = 1, seed: int = 0) -> dict:
    """Closed-loop load generator: ``clients`` threads each issue
    ``requests_per_client`` back-to-back requests (submit, wait, repeat) —
    offered load scales with the client count, the classic way to sweep a
    latency/throughput curve without open-loop timer drift. Returns the
    load point's measurements from the runtime's counters (reset first)."""
    runtime.reset_stats()
    target_pool = np.asarray(target_pool, np.int32)
    errors: List[BaseException] = []

    def client(cid: int) -> None:
        rng = np.random.default_rng((seed, cid))
        try:
            for _ in range(requests_per_client):
                ids = rng.choice(target_pool, size=ids_per_request)
                runtime.submit(ids).result(
                    timeout=runtime.serve_cfg.fetch_timeout_s + 30.0)
        except BaseException as e:  # surfaced after the join
            errors.append(e)

    runtime.start()
    t0 = time.monotonic()
    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    if errors:
        raise errors[0]
    stats = runtime.stats()
    done = stats["completed"]
    return {"clients": clients, "requests": done,
            "offered_rps": done / wall if wall > 0 else 0.0,
            "wall_s": wall, "p50_ms": stats["p50_ms"],
            "p99_ms": stats["p99_ms"],
            "slo_miss_rate": stats["slo_miss_rate"]}
