"""Plain reference of the benchmark's GNN models, and the sampled-batch check.

Independent of the system under test: plain ``jax.numpy`` over the
benchmark's own graph arrays (``bench/graphs.py``), the benchmark's own
weights (:func:`make_params`), and the optimizer and model equations the
configuration file states. It imports nothing of the program.

A batch reaches this module as plain numpy arrays (:func:`as_batch`): the
global vertex ids of every layer with their masks, the sampled edges as
local indices, the self index of each upper-layer vertex and the labels.
:func:`check_batch` holds that sample to the graph before any arithmetic:
every sampled edge is an in-edge of the graph, every destination keeps
``min(in-degree, fanout)`` distinct sources, the layer's vertex set is the
destinations plus the sampled sources, and the labels are the graph's.

Each model is a module of its own, ``bench/models/<name>.py``, found by
the configuration's ``model.name`` (``bench/models/__init__.py`` says what
it gives). ``model`` below is the configuration's ``model`` block. What the
models share lives here: the weight rule, softmax cross-entropy averaged
over the targets, AdamW with global-norm clipping and a warm-up-then-cosine learning rate.
``dtype`` float32 runs every matmul at ``highest`` precision; bfloat16
(the control) rounds weights, features and every intermediate to it.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import models, work


# -- weights -----------------------------------------------------------------

def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (all its bits count)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


def make_params(model: dict, f_in: int, n_classes: int, seed: int) -> dict:
    """Weights N(0, 1/fan_in) and biases N(0, 0.01^2), made on the default
    device in one jitted call from ``seed``; float32. Within a layer the
    leaves are drawn in the order of their sorted names."""
    shapes = models.find(model["name"]).param_shapes(
        model, work.model_widths(model, f_in, n_classes))

    def build(key):
        layers = []
        for layer in shapes:
            out = {}
            for name, shape in sorted(layer.items()):
                key, sub = jax.random.split(key)
                std = 0.01 if len(shape) == 1 else shape[0] ** -0.5
                out[name] = std * jax.random.normal(sub, shape, jnp.float32)
            layers.append(out)
        return {"layers": layers}

    return jax.jit(build)(seed_key(seed))


# -- batches -----------------------------------------------------------------

def as_batch(nodes, node_mask, edge_src, edge_dst, edge_mask, self_idx,
             labels) -> dict:
    """The real (unpadded) part of one sampled batch as numpy arrays."""
    n = [int(np.asarray(m).sum()) for m in node_mask]
    e = [int(np.asarray(m).sum()) for m in edge_mask]
    for masks, count in ((node_mask, n), (edge_mask, e)):
        for m, k in zip(masks, count):  # the sampler fills a prefix
            if not np.asarray(m)[:k].all():
                raise ValueError("a batch's valid entries are not a prefix")
    L = len(edge_src)
    return {
        "nodes": [np.asarray(a)[:k].astype(np.int64)
                  for a, k in zip(nodes, n)],
        "edge_src": [np.asarray(a)[:k].astype(np.int64)
                     for a, k in zip(edge_src, e)],
        "edge_dst": [np.asarray(a)[:k].astype(np.int64)
                     for a, k in zip(edge_dst, e)],
        "self_idx": [np.asarray(self_idx[l])[:n[l + 1]].astype(np.int64)
                     for l in range(L)],
        "labels": np.asarray(labels).astype(np.int64),
    }


def _has_edge(indptr, indices, dst, src) -> np.ndarray:
    """Vectorized lower-bound search of ``src`` in CSR row ``dst`` (rows
    hold their sources ascending)."""
    lo = indptr[dst].astype(np.int64)
    end = indptr[dst + 1].astype(np.int64)
    hi = end.copy()
    while True:
        live = lo < hi
        if not live.any():
            break
        mid = (lo + hi) // 2
        val = np.asarray(indices[np.minimum(mid, len(indices) - 1)],
                         np.int64)
        go_right = live & (val < src)
        lo = np.where(go_right, mid + 1, lo)
        hi = np.where(live & ~go_right, mid, hi)
    found = lo < end
    val = np.asarray(indices[np.minimum(lo, len(indices) - 1)], np.int64)
    return found & (val == src)


def check_batch(graph: dict, fanouts: Sequence[int], b: dict) -> List[str]:
    """What is wrong with sampled batch ``b`` against ``graph``; ``fanouts``
    lists each layer's fanout bottom-up (layer 0 reads the input rows)."""
    problems = []
    indptr, indices = graph["indptr"], graph["indices"]
    deg = np.diff(indptr)
    L = len(b["edge_src"])
    for l in range(L):
        src_ids, dst_ids = b["nodes"][l], b["nodes"][l + 1]
        es, ed = b["edge_src"][l], b["edge_dst"][l]
        if len(es) and (es.max() >= len(src_ids) or ed.max() >= len(dst_ids)):
            problems.append(f"layer {l}: an edge indexes past its vertices")
            continue
        if np.any(np.diff(src_ids) <= 0):
            problems.append(f"layer {l}: vertex ids not sorted and distinct")
        s_glob, d_glob = src_ids[es], dst_ids[ed]
        if not _has_edge(indptr, indices, d_glob, s_glob).all():
            problems.append(f"layer {l}: a sampled edge is not in the graph")
        if len(np.unique(ed * len(src_ids) + es)) != len(es):
            problems.append(f"layer {l}: a destination repeats a source")
        kept = np.bincount(ed, minlength=len(dst_ids))
        if not np.array_equal(kept, np.minimum(deg[dst_ids], fanouts[l])):
            problems.append(f"layer {l}: a destination kept other than "
                            f"min(in-degree, {fanouts[l]}) sources")
        si = b["self_idx"][l]
        if len(si) != len(dst_ids) or not np.array_equal(src_ids[si],
                                                         dst_ids):
            problems.append(f"layer {l}: self index does not find the "
                            f"destinations among the sources")
        if not np.array_equal(src_ids, np.union1d(dst_ids, s_glob)):
            problems.append(f"layer {l}: vertices other than the "
                            f"destinations and their sampled sources")
    if not np.array_equal(b["labels"], graph["labels"][b["nodes"][L]]):
        problems.append("labels are not the targets' labels")
    return problems


def stack_batches(batches: Sequence[dict]) -> tuple:
    """Many batches as one block-diagonal batch, for one pass of the
    reference over all of them, and the first target row of each.

    Each layer's rows and edges are padded to the next power of two, so a
    run meets few shapes and its eager operations compile once (a new shape
    compiles every operation again). Padded edges join row 0 to a padded
    destination row, which no real row reads; the rows a model computes
    for a real destination are those it computes for the batch alone."""
    L = len(batches[0]["edge_src"])
    nodes = [np.concatenate([b["nodes"][l] for b in batches])
             for l in range(L + 1)]
    offsets = [np.cumsum([0] + [len(b["nodes"][l]) for b in batches])
               for l in range(L + 1)]
    rows = [1 << len(n).bit_length() for n in nodes]  # at least one spare
    out = {"nodes": [np.pad(n, (0, r - len(n))) for n, r in zip(nodes, rows)],
           "edge_src": [], "edge_dst": [], "self_idx": [],
           "labels": np.pad(np.concatenate([b["labels"] for b in batches]),
                            (0, rows[L] - len(nodes[L])))}
    for l in range(L):
        src = np.concatenate([b["edge_src"][l] + o for b, o in
                              zip(batches, offsets[l])])
        dst = np.concatenate([b["edge_dst"][l] + o for b, o in
                              zip(batches, offsets[l + 1])])
        edges = 1 << max(len(src) - 1, 0).bit_length()
        out["edge_src"].append(np.pad(src, (0, edges - len(src))))
        out["edge_dst"].append(np.pad(dst, (0, edges - len(dst)),
                                      constant_values=rows[l + 1] - 1))
        self_idx = np.concatenate([b["self_idx"][l] + o for b, o in
                                   zip(batches, offsets[l])])
        out["self_idx"].append(np.pad(self_idx,
                                      (0, rows[l + 1] - len(self_idx))))
    return out, offsets[L][:-1]


# -- model ---------------------------------------------------------------------

def _to_device(b: dict, feats: np.ndarray, dtype) -> dict:
    L = len(b["edge_src"])
    return {"h": jnp.asarray(feats, dtype),
            "edge_src": [jnp.asarray(a, jnp.int32) for a in b["edge_src"]],
            "edge_dst": [jnp.asarray(a, jnp.int32) for a in b["edge_dst"]],
            "self_idx": [jnp.asarray(a, jnp.int32) for a in b["self_idx"]],
            "n_dst": [len(b["nodes"][l + 1]) for l in range(L)],
            "labels": jnp.asarray(b["labels"], jnp.int32)}


def forward(model: dict, params: dict, d: dict) -> jax.Array:
    return models.find(model["name"]).forward(model, params, d)


def loss(model: dict, params: dict, d: dict, keep: int = 0) -> jax.Array:
    """Mean cross-entropy over the targets (the first ``keep`` of them
    where ``keep`` is set: the half-batch fault)."""
    logits = forward(model, params, d).astype(jnp.float32)
    labels = d["labels"]
    if keep:
        logits, labels = logits[:keep], labels[:keep]
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - ll)


def _precision(dtype):
    return "highest" if dtype == jnp.float32 else "default"


def logits(model: dict, params: dict, b: dict, feats: np.ndarray,
           dtype=jnp.float32) -> np.ndarray:
    with jax.default_matmul_precision(_precision(dtype)):
        p = jax.tree.map(lambda x: jnp.asarray(x, dtype), params)
        out = forward(model, p, _to_device(b, feats, dtype))
        return np.asarray(out.astype(jnp.float32))


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up to ``lr`` over ``warmup`` steps, then cosine decay to
    ``min_ratio * lr`` at ``total``."""
    base, warm = float(opt["lr"]), int(opt["warmup"])
    if step < warm:
        return base * step / max(warm, 1)
    t = min(max((step - warm) / max(int(opt["total"]) - warm, 1), 0.0), 1.0)
    ratio = float(opt["min_ratio"])
    return base * (ratio + (1 - ratio) * 0.5 * (1 + np.cos(np.pi * t)))


def train_steps(model: dict, params0: dict, steps: List[List[dict]],
                feats: List[List[np.ndarray]], opt: dict,
                dtype=jnp.float32, target_share: float = 1.0) -> dict:
    """Run the data-parallel steps: each step averages the loss and the
    gradient over its batches (one per device), clips the mean gradient
    to global norm ``grad_clip`` and applies AdamW. Returns each step's
    loss, the first step's clipped gradient and the parameters after every
    step, as numpy float64 trees. ``target_share`` below 1 takes each
    batch's loss over that share of its targets alone (a fault)."""
    b1, b2, eps = float(opt["b1"]), float(opt["b2"]), float(opt["eps"])
    wd, clip = float(opt["weight_decay"]), float(opt["grad_clip"])
    cast = lambda t: jax.tree.map(lambda x: jnp.asarray(x, dtype), t)
    p = cast(params0)
    m = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)
    v = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)
    grad_fn = jax.value_and_grad(lambda q, d, keep: loss(model, q, d, keep),
                                 argnums=0)
    out = {"loss": [], "grad1": None, "params": []}
    with jax.default_matmul_precision(_precision(dtype)):
        for k, (batches, fk) in enumerate(zip(steps, feats), start=1):
            vals, grads = [], []
            for b, f in zip(batches, fk):
                keep = (0 if target_share >= 1.0
                        else int(round(len(b["labels"]) * target_share)))
                val, g = grad_fn(p, _to_device(b, f, dtype), keep)
                vals.append(float(val))
                grads.append(jax.tree.map(lambda x: x.astype(jnp.float32),
                                          g))
            g = jax.tree.map(lambda *xs: sum(xs) / len(xs), *grads)
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                 for x in jax.tree.leaves(g)))
            g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip
                                                       / (gnorm + 1e-9)), g)
            if k == 1:
                out["grad1"] = jax.tree.map(np.asarray, g)
            m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
            v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
            lr, bc1, bc2 = lr_at(opt, k), 1 - b1 ** k, 1 - b2 ** k
            p = jax.tree.map(
                lambda q, a, c: (q.astype(jnp.float32) - lr * (
                    (a / bc1) / (jnp.sqrt(c / bc2) + eps)
                    + wd * q.astype(jnp.float32))).astype(dtype), p, m, v)
            out["loss"].append(float(np.mean(vals)))
            out["params"].append(jax.tree.map(
                lambda x: np.asarray(x, np.float64), p))
    return out
