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

Aggregation (a mean over sampled in-edges) needs ``2 * edges * f_in``
operations and, as the least traffic, one read of each source row, one
write of each destination row, and the edge's two int32 endpoints:
``4 * f_in * (n_src + n_dst) + 8 * edges`` bytes.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

# weight matrices per layer that multiply an (n_dst, f_in) operand
UPDATE_MATMULS = {"graphsage": 2, "gcn": 1}


def layer_sizes(node_counts: Sequence[int], edge_counts: Sequence[int]
                ) -> List[Tuple[int, int, int]]:
    """``(n_src, n_dst, edges)`` per layer, bottom-up."""
    return [(int(node_counts[l]), int(node_counts[l + 1]),
             int(edge_counts[l])) for l in range(len(edge_counts))]


def widths(f_in: int, hidden: int, n_classes: int, num_layers: int
           ) -> List[Tuple[int, int]]:
    dims = [f_in] + [hidden] * (num_layers - 1) + [n_classes]
    return list(zip(dims[:-1], dims[1:]))


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


def step_flops(model: str, sizes, dims, train: bool = True) -> float:
    """Aggregation plus update matmuls; with ``train`` the backward too."""
    k = UPDATE_MATMULS[model]
    total = agg_flops(sizes, dims, train)
    for l, ((_, nd, _), (fi, fo)) in enumerate(zip(sizes, dims)):
        mm = 2.0 * k * nd * fi * fo
        total += mm                      # forward
        if train:
            total += mm                  # weight gradients
            if l > 0:
                total += mm              # input gradient
    return total
