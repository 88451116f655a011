"""The work a batch needs, counted from its real (unpadded) sizes.

Counts come from the sampled batch's vertex and edge counts per layer
(``n_src``, ``n_dst``, ``edges``) and the layer widths, never from padded
capacities, so they read the same whatever kernel or padding a later
change uses. Only matrix products and the neighbour aggregation are
counted (elementwise work, the loss and the optimizer are left out), each
multiply-add as two operations.

Training counts the forward pass and the backward pass without any
recomputation: every layer needs its weight gradients, and every layer but
the first also needs the gradient of its input (the first layer's input is
the feature matrix, which has no gradient). The aggregation's backward is
the transposed aggregation at the layer's input width.

What is shared and what each model gives: the aggregation counts here are
those of the mean over sampled in-edges, which every model so far uses and
``agg_roofline.train`` reads. It needs ``2 * edges * f_in`` operations and,
as the least traffic, one read of each source row, one write of each
destination row, and the edge's two int32 endpoints:
``4 * f_in * (n_src + n_dst) + 8 * edges`` bytes. A model's module
(``bench/models/<name>.py``) gives the rest: its layer widths
(:func:`model_widths`; :func:`widths` unless it says otherwise) and how many
weight matrices per layer multiply the destinations' rows
(``UPDATE_MATMULS``, 1 unless it says otherwise), which
:func:`step_flops` counts beside the aggregation.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

from bench import models


def layer_sizes(node_counts: Sequence[int], edge_counts: Sequence[int]
                ) -> List[Tuple[int, int, int]]:
    """``(n_src, n_dst, edges)`` per layer, bottom-up."""
    return [(int(node_counts[l]), int(node_counts[l + 1]),
             int(edge_counts[l])) for l in range(len(edge_counts))]


def widths(f_in: int, hidden: int, n_classes: int, num_layers: int
           ) -> List[Tuple[int, int]]:
    dims = [f_in] + [hidden] * (num_layers - 1) + [n_classes]
    return list(zip(dims[:-1], dims[1:]))


def model_widths(model: dict, f_in: int, n_classes: int
                 ) -> List[Tuple[int, int]]:
    """The layers' ``(f_in, f_out)`` for a configuration's ``model`` block."""
    mod = models.find(model["name"])
    if hasattr(mod, "widths"):
        return mod.widths(model, f_in, n_classes)
    return widths(f_in, int(model["hidden"]), n_classes,
                  int(model["num_layers"]))


def agg_flops(sizes, dims, train: bool = True) -> float:
    """Aggregation operations: forward at every layer, backward (the
    transposed aggregation) at every layer but the first."""
    total = 0.0
    for l, ((_, _, e), (fi, _)) in enumerate(zip(sizes, dims)):
        total += 2.0 * e * fi
        if train and l > 0:
            total += 2.0 * e * fi
    return total


def agg_bytes(sizes, dims, train: bool = True) -> float:
    """Least HBM traffic of the aggregations counted by :func:`agg_flops`."""
    total = 0.0
    for l, ((ns, nd, e), (fi, _)) in enumerate(zip(sizes, dims)):
        one = 4.0 * fi * (ns + nd) + 8.0 * e
        total += one * (2 if train and l > 0 else 1)
    return total


def step_flops(model: dict, sizes, dims, train: bool = True) -> float:
    """Aggregation plus the update matmuls of a configuration's ``model``
    block; with ``train`` the backward too."""
    k = getattr(models.find(model["name"]), "UPDATE_MATMULS", 1)
    total = agg_flops(sizes, dims, train)
    for l, ((_, nd, _), (fi, fo)) in enumerate(zip(sizes, dims)):
        mm = 2.0 * k * nd * fi * fo
        total += mm                      # forward
        if train:
            total += mm                  # weight gradients
            if l > 0:
                total += mm              # input gradient
    return total
