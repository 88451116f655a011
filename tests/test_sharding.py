"""Sharding rules: divisibility fallback, PSpec trees, abstract building."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.nn.param import PSpec, stack_layers, materialize, param_count
from repro.distributed import sharding as shd


def _make_mesh():
    # 1 CPU device: (1,1) mesh exercises the code paths
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


@pytest.fixture(scope="module")
def mesh():
    return _make_mesh()


def test_resolve_divisible(mesh):
    spec = shd.resolve_spec(mesh, (64, 32), ("embed", "heads"))
    assert spec == P("data", "model")


def test_resolve_fallback_nondivisible():
    mesh = _make_mesh()
    # craft a fake 16-wide axis via rules on a real mesh is impossible with
    # 1 device; test the arithmetic path directly instead
    rules = {"heads": ("model",), None: ()}

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    spec = shd.resolve_spec(FakeMesh(), (36, 128), ("heads", None), rules)
    assert spec == P(None, None)  # 36 % 16 != 0 -> replicated
    spec = shd.resolve_spec(FakeMesh(), (32, 128), ("heads", None), rules)
    assert spec == P("model", None)


def test_no_axis_reuse():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    rules = {"vocab": ("model",), "ffn": ("model",), None: ()}
    # two dims both wanting "model": only the first gets it
    spec = shd.resolve_spec(FakeMesh(), (256, 512), ("vocab", "ffn"), rules)
    assert spec == P("model", None)


def test_stack_layers_prepends_dim():
    spec = {"w": PSpec((4, 8), ("embed", "ffn"))}
    stacked = stack_layers(spec, 12)
    assert stacked["w"].shape == (12, 4, 8)
    assert stacked["w"].axes == ("layers", "embed", "ffn")


def test_param_count():
    spec = {"a": PSpec((4, 8), (None, None)), "b": PSpec((3,), (None,))}
    assert param_count(spec) == 35


def test_tree_abstract_no_allocation(mesh):
    spec = {"w": PSpec((128, 64), ("embed", "ffn"))}
    abstract = shd.tree_abstract(mesh, spec, jnp.bfloat16)
    assert isinstance(abstract["w"], jax.ShapeDtypeStruct)
    assert abstract["w"].shape == (128, 64)
    assert abstract["w"].dtype == jnp.bfloat16
    assert abstract["w"].sharding is not None


def test_shard_noop_without_mesh():
    x = jnp.ones((4, 4))
    assert shd.shard(x, "batch", None) is x


def test_materialize_inits():
    spec = {"w": PSpec((16, 16), (None, None)),
            "z": PSpec((4,), (None,), "zeros"),
            "o": PSpec((4,), (None,), "ones")}
    p = materialize(spec, jax.random.PRNGKey(0))
    assert float(jnp.abs(p["w"]).sum()) > 0
    assert (np.asarray(p["z"]) == 0).all()
    assert (np.asarray(p["o"]) == 1).all()


def test_use_mesh_context(mesh):
    assert shd.current_mesh() is None
    with shd.use_mesh(mesh):
        assert shd.current_mesh() is mesh
    assert shd.current_mesh() is None


def test_registry_cells():
    from repro.configs.registry import all_cells
    cells = all_cells()
    assert len(cells) == 40
    runnable = [c for c in cells if c[2]]
    skipped = [c for c in cells if not c[2]]
    assert len(runnable) == 32
    assert len(skipped) == 8
    assert all("long_500k" == c[1] for c in skipped)
