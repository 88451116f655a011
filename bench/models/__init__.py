"""The benchmark's plain models, one module each, found by name.

A configuration's ``model.name`` names the module ``bench/models/<name>.py``
that holds that model's reference. Like the rest of the reference it is
plain float32 ``jax.numpy`` and imports nothing of the program. A module
gives

* ``param_shapes(model, dims)``: per layer, bottom-up, a dict from leaf
  name to shape, where ``model`` is the configuration's ``model`` block and
  ``dims`` the layers' ``(f_in, f_out)`` widths;
* ``forward(model, params, batch)``: the targets' logits. ``batch`` holds
  the layer-0 rows ``h`` and, per layer bottom-up, the sampled edges
  ``edge_src`` / ``edge_dst`` as local indices, the destinations' own rows
  among the sources ``self_idx`` and the destination count ``n_dst``
  (``bench/reference.py``);

and may give

* ``widths(model, f_in, n_classes)``: the layers' ``(f_in, f_out)``
  (default ``bench/work.widths`` over ``hidden`` and ``num_layers``);
* ``UPDATE_MATMULS``: the weight matrices per layer that multiply the
  destinations' ``(n_dst, f_in)`` rows, for ``bench/work.step_flops``
  (default 1).

A new model is a new file here and nothing else. :func:`mean_layers` is the
layer stack that the models which aggregate by the mean share.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp


def find(name: str):
    """The module of model ``name``."""
    module = f"{__name__}.{name}"
    if name.isidentifier():
        try:
            return importlib.import_module(module)
        except ModuleNotFoundError as e:
            if e.name != module:  # the model's module failed an import
                raise
    raise ValueError(f"no reference for model {name!r}: add "
                     f"bench/models/{name}.py")


def mean_layers(params: dict, d: dict, update) -> jax.Array:
    """The layer stack of a model that aggregates by the mean over the
    sampled in-edges: per layer ``update(p, agg, s)`` of the layer's
    parameters, the mean ``agg`` and the destinations' own rows ``s``, with
    ReLU between layers."""
    h = d["h"]
    L = len(d["edge_src"])
    for l in range(L):
        src, dst, n_dst = d["edge_src"][l], d["edge_dst"][l], d["n_dst"][l]
        ones = jnp.ones(src.shape, h.dtype)
        deg = jax.ops.segment_sum(ones, dst, num_segments=n_dst)
        agg = (jax.ops.segment_sum(h[src], dst, num_segments=n_dst)
               / jnp.maximum(deg, 1.0)[:, None])
        h = update(params["layers"][l], agg, h[d["self_idx"][l]])
        if l != L - 1:
            h = jax.nn.relu(h)
    return h
