"""Compiled-kernel guards: ahead-of-time Mosaic compiles of the streaming
kernels, the HITGNN_COMPILED_KERNELS opt-in, and the compiled-vs-interpret
smoke test.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses — a lane slice off a 128 boundary, an SMEM or VMEM table that does
not fit. The ahead-of-time tests compile the forward and the gradient of
``aggregate_fused_vjp`` and ``aggregate_edges_vjp`` for a DESCRIBED TPU v5e
(no chip needed) at the paper's GraphSAGE shapes — hidden 128, fanouts
(25, 10), 1,024 targets, 100 input features, 47 classes, both layers — and
at the one-dst-block shape of a serving bucket of 8 targets, where the
fused backward takes its merged single-grid kernel. Each compiled program
must hold the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and under xdist
every worker imports this file.

``resolve_interpret`` picks interpret mode everywhere except real TPU;
``HITGNN_COMPILED_KERNELS=1`` is the explicit opt-in that forces the
compiled Mosaic lowering wherever a config override hasn't pinned a mode.
The smoke test runs every streaming kernel through BOTH modes and
compares allclose (not bitwise: the compiled path densifies with the MXU
contraction and lane-pads the operands the interpret path leaves as they
are) — it skips on hosts without a TPU backend, where "compiled" would just
be interpret again.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.gnn import GNNModelConfig
from repro.kernels.aggregate import (BLK, aggregate_edges, aggregate_edges_vjp,
                                     aggregate_fused, aggregate_fused_vjp,
                                     block_capacities, build_block_coo_pair,
                                     resolve_interpret)

# (batch_targets, layer, F_in, F_out) of the paper's GraphSAGE
# (configs/gnn.py) and of its smallest serving bucket
AOT_SHAPES = {"paper_layer0": (1024, 0, 100, 128),
              "paper_layer1": (1024, 1, 128, 47),
              "serve_bucket8_layer1": (8, 1, 128, 47)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_backend():
    if jax.default_backend() != "tpu":
        pytest.skip("no compiled Pallas backend on this host (set "
                    "HITGNN_COMPILED_KERNELS=1 on TPU)")


def _layer_specs(name, sharding):
    """ShapeDtypeStructs of one layer's edge-stream layout (A then A^T),
    the source features and the update weights, from the SAME capacity
    planner the trainer and the sampler workers use."""
    targets, layer, f_in, f_out = AOT_SHAPES[name]
    cfg = GNNModelConfig("graphsage", num_layers=2, hidden=128,
                         fanouts=(25, 10), batch_targets=targets)
    n_src, n_dst, max_blk, max_blk_t, e_cap = block_capacities(cfg)[layer]
    n_srcb, n_dstb = -(-n_src // BLK), -(-n_dst // BLK)

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def stream(n_rows, n_blk):
        return (spec((e_cap,), jnp.int32), spec((e_cap,)),
                spec((n_rows * n_blk + 1,), jnp.int32),
                spec((n_rows, n_blk), jnp.int32))

    return (stream(n_dstb, max_blk) + stream(n_srcb, max_blk_t),
            spec((n_srcb * BLK, f_in)), spec((f_in, f_out)))


def test_aot_shapes_are_the_paper_layers():
    cfg = GNNModelConfig("graphsage", num_layers=2, hidden=128,
                         fanouts=(25, 10), batch_targets=1024)
    assert [(c[0], c[1], c[4]) for c in block_capacities(cfg)] == [
        (292_864, 26_624, 266_240), (26_624, 1_024, 25_600)]


def _assert_mosaic(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", sorted(AOT_SHAPES))
def test_aot_compile_fused(one_chip, name):
    layout, h, w = _layer_specs(name, one_chip)
    b = jax.ShapeDtypeStruct((w.shape[1],), jnp.float32, sharding=one_chip)
    s = jax.ShapeDtypeStruct((1, h.shape[1]), jnp.float32, sharding=one_chip)

    def fwd(layout, h, w, b, s):
        # GraphSAGE's neighbour term: no bias, no self term (models.py)
        return aggregate_fused_vjp(*layout, h, w, b, s, "none", False, False,
                                   jnp.float32, interpret=False)

    def grad(layout, h, w, b, s):
        return jax.grad(lambda hh, ww: fwd(layout, hh, ww, b, s).sum(),
                        argnums=(0, 1))(h, w)

    _assert_mosaic(fwd, layout, h, w, b, s)
    _assert_mosaic(grad, layout, h, w, b, s)


@pytest.mark.parametrize("name", sorted(AOT_SHAPES))
def test_aot_compile_edges(one_chip, name):
    layout, h, _ = _layer_specs(name, one_chip)

    def fwd(layout, h):
        return aggregate_edges_vjp(*layout, h, interpret=False)

    def grad(layout, h):
        return jax.grad(lambda hh: fwd(layout, hh).sum())(h)

    _assert_mosaic(fwd, layout, h)
    _assert_mosaic(grad, layout, h)


def test_resolve_interpret_default_cpu(monkeypatch):
    monkeypatch.delenv("HITGNN_COMPILED_KERNELS", raising=False)
    assert resolve_interpret() is (jax.default_backend() != "tpu")


def test_resolve_interpret_env_opt_in(monkeypatch):
    monkeypatch.setenv("HITGNN_COMPILED_KERNELS", "1")
    assert resolve_interpret() is False


def test_resolve_interpret_env_other_values_ignored(monkeypatch):
    monkeypatch.setenv("HITGNN_COMPILED_KERNELS", "0")
    assert resolve_interpret() is (jax.default_backend() != "tpu")


def test_resolve_interpret_override_beats_env(monkeypatch):
    monkeypatch.setenv("HITGNN_COMPILED_KERNELS", "1")
    assert resolve_interpret(True) is True
    monkeypatch.delenv("HITGNN_COMPILED_KERNELS")
    assert resolve_interpret(False) is False


def _stream_args(n_dst, n_src, n_edges, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_src, n_edges).astype(np.int32)
    dst = rng.integers(0, n_dst, n_edges).astype(np.int32)
    coo = build_block_coo_pair(src, dst, np.ones(n_edges, bool),
                               n_src, n_dst, edge_stream=True)
    return coo


@pytest.mark.parametrize("feat_dim", [64, 100])
def test_compiled_matches_interpret_edges(tpu_backend, feat_dim):
    coo = _stream_args(128, 512, 700, seed=0)
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.standard_normal((coo["n_src_pad"], feat_dim)),
                    jnp.float32)
    args = [jnp.asarray(coo[k])
            for k in ("tile_off", "val", "tile_seg", "cols")]
    interp = aggregate_edges(*args, h, interpret=True)
    comp = aggregate_edges(*args, h, interpret=False)
    np.testing.assert_allclose(np.asarray(comp), np.asarray(interp),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("feat_dim", [64, 100])
def test_compiled_matches_interpret_fused(tpu_backend, feat_dim):
    coo = _stream_args(128, 512, 700, seed=2)
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((coo["n_src_pad"], feat_dim)),
                    jnp.float32)
    w = jnp.asarray(rng.standard_normal((feat_dim, 96)), jnp.float32)
    args = [jnp.asarray(coo[k])
            for k in ("tile_off", "val", "tile_seg", "cols")]
    interp = aggregate_fused(*args, h, w, interpret=True)
    comp = aggregate_fused(*args, h, w, interpret=False)
    np.testing.assert_allclose(np.asarray(comp), np.asarray(interp),
                               rtol=1e-5, atol=1e-5)
