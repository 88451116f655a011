"""GraphSAGE with the mean aggregator (Hamilton et al., arXiv:1706.02216):
``s @ w_self + agg @ w_neigh + b`` per layer, ``s`` the destination's own
row and ``agg`` the mean of its sampled in-neighbours' rows."""
from bench.models import mean_layers

UPDATE_MATMULS = 2


def param_shapes(model, dims):
    return [{"w_self": (fi, fo), "w_neigh": (fi, fo), "b": (fo,)}
            for fi, fo in dims]


def forward(model, params, batch):
    return mean_layers(
        params, batch,
        lambda p, agg, s: s @ p["w_self"] + agg @ p["w_neigh"] + p["b"])
