"""GCN as the program defines it for sampled mini-batches:
``(agg + s) @ w * 0.5 + b`` per layer, ``s`` the destination's own row and
``agg`` the mean of its sampled in-neighbours' rows."""
from bench.models import mean_layers

UPDATE_MATMULS = 1


def param_shapes(model, dims):
    return [{"w": (fi, fo), "b": (fo,)} for fi, fo in dims]


def forward(model, params, batch):
    return mean_layers(
        params, batch, lambda p, agg, s: (agg + s) @ p["w"] * 0.5 + p["b"])
