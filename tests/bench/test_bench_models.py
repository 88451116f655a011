"""The benchmark's models are modules found by name (bench/models/).

``reference_frozen.json`` holds, bit for bit, what the reference gave for
``graphsage`` and ``gcn`` on the inputs below before each model moved into
a module of its own (float32 and bfloat16 steps, the half-batch fault,
logits); the work counts below are that version's too. A model added as a
new file runs through the reference and the work counts with no other file
touched.
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1].parent
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import models, reference, work  # noqa: E402

FROZEN = json.loads((Path(__file__).parent
                     / "reference_frozen.json").read_text())

I = lambda *x: np.array(x, np.int64)  # noqa: E731
# two sampled batches as local indices: 6 -> 4 -> 2 and 5 -> 3 -> 3 rows
B1 = {"nodes": [I(0, 1, 2, 3, 4, 5), I(0, 1, 2, 3), I(0, 1)],
      "edge_src": [I(1, 2, 3, 0, 4, 5, 2, 0), I(1, 2, 3, 0, 2)],
      "edge_dst": [I(0, 0, 1, 2, 2, 3, 3, 3), I(0, 0, 0, 1, 1)],
      "self_idx": [I(0, 1, 2, 3), I(0, 2)], "labels": I(1, 2)}
B2 = {"nodes": [I(0, 1, 2, 3, 4), I(0, 1, 2), I(0, 1, 2)],
      "edge_src": [I(3, 4, 0, 1, 2), I(1, 2, 0, 2)],
      "edge_dst": [I(0, 0, 1, 2, 2), I(0, 0, 1, 2)],
      "self_idx": [I(0, 1, 2), I(0, 1, 2)], "labels": I(0, 2, 1)}
_rng = np.random.default_rng(7)
F1 = _rng.standard_normal((6, 3)).astype(np.float32)
F2 = _rng.standard_normal((5, 3)).astype(np.float32)
OPT = {"lr": 0.01, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.01,
       "grad_clip": 1.0, "warmup": 2, "total": 1000, "min_ratio": 0.1}
SEED = 2**33 + 5
STEPS = [[B1, B2], [B2, B1], [B1, B2]]
FEATS = [[F1, F2], [F2, F1], [F1, F2]]


def _model(name, **extra):
    return {"name": name, "num_layers": 2, "hidden": 4, **extra}


def frozen(x):
    a = np.asarray(x)
    return [a.dtype.str, list(a.shape), a.tobytes().hex()]


def tree(t):
    return [frozen(x) for x in jax.tree.leaves(t)]


@pytest.mark.parametrize("name", ["graphsage", "gcn"])
def test_reference_is_bit_for_bit_the_frozen_one(name):
    want = FROZEN[name]
    m = _model(name)
    p = reference.make_params(m, 3, 3, SEED)
    assert tree(p) == want["params"]
    for tag, kw in (("f32", {}), ("bf16", {"dtype": jnp.bfloat16}),
                    ("half", {"target_share": 0.5})):
        r = reference.train_steps(m, p, STEPS, FEATS, OPT, **kw)
        got = want["train_" + tag]
        assert [float(v).hex() for v in r["loss"]] == got["loss"], tag
        assert tree(r["grad1"]) == got["grad1"], tag
        assert [tree(x) for x in r["params"]] == got["params"], tag
    assert frozen(reference.logits(m, p, B1, F1)) == want["logits_f32"]
    assert frozen(reference.logits(m, p, B1, F1, jnp.bfloat16)) \
        == want["logits_bf16"]


def test_work_counts_are_the_frozen_ones():
    # sage-products' layer-0/1 capacities at fanouts (25, 10), 1,024 targets
    sizes = work.layer_sizes([292864, 26624, 1024], [266240, 10240])
    dims = work.model_widths({"name": "graphsage", "hidden": 128,
                              "num_layers": 2}, 100, 47)
    assert dims == [(100, 128), (128, 47)]
    assert [work.agg_flops(sizes, dims), work.agg_flops(sizes, dims, False)
            ] == [58490880.0, 55869440.0]
    assert [work.agg_bytes(sizes, dims), work.agg_bytes(sizes, dims, False)
            ] == [158400512.0, 144162816.0]
    assert [work.step_flops({"name": "graphsage"}, sizes, dims),
            work.step_flops({"name": "graphsage"}, sizes, dims, False)] == [
        2858713088.0, 1443659776.0]
    assert [work.step_flops({"name": "gcn"}, sizes, dims),
            work.step_flops({"name": "gcn"}, sizes, dims, False)] == [
        1458601984.0, 749764608.0]


TOY = '''"""A toy model: sum aggregation, widths of its own, three matmuls."""
import jax
import jax.numpy as jnp

UPDATE_MATMULS = 3


def widths(model, f_in, n_classes):
    return [(f_in, 2 * model["hidden"]), (2 * model["hidden"], n_classes)]


def param_shapes(model, dims):
    return [{"w": (fi, fo)} for fi, fo in dims]


def forward(model, params, batch):
    h = batch["h"]
    for l, p in enumerate(params["layers"]):
        agg = jax.ops.segment_sum(h[batch["edge_src"][l]],
                                  batch["edge_dst"][l],
                                  num_segments=batch["n_dst"][l])
        h = jnp.tanh((agg + h[batch["self_idx"][l]]) @ p["w"])
    return h
'''


def test_a_model_added_as_a_file_runs_through_the_reference(tmp_path,
                                                            monkeypatch):
    (tmp_path / "toy_sum.py").write_text(TOY)
    monkeypatch.setattr(models, "__path__",
                        [*models.__path__, str(tmp_path)])
    try:
        m = _model("toy_sum")
        p = reference.make_params(m, 3, 3, SEED)
        assert [{k: v.shape for k, v in layer.items()}
                for layer in p["layers"]] == [{"w": (3, 8)}, {"w": (8, 3)}]
        r = reference.train_steps(m, p, STEPS, FEATS, OPT)
        assert len(r["loss"]) == 3 and all(np.isfinite(r["loss"]))
        assert all(np.abs(np.asarray(a) - b).max() > 0 for a, b in zip(
            jax.tree.leaves(p), jax.tree.leaves(r["params"][-1])))
        assert reference.logits(m, p, B1, F1).shape == (2, 3)
        sizes = work.layer_sizes([6, 4, 2], [8, 5])
        dims = work.model_widths(m, 3, 3)
        mm = 2.0 * 3 * (4 * 3 * 8 + 2 * 8 * 3)
        assert work.step_flops(m, sizes, dims, train=False) == \
            work.agg_flops(sizes, dims, train=False) + mm
    finally:
        sys.modules.pop("bench.models.toy_sum", None)


@pytest.mark.parametrize("call", [
    lambda: reference.make_params(_model("no_such_model"), 3, 3, 1),
    lambda: work.step_flops(_model("no_such_model"), [(6, 4, 8)], [(3, 4)]),
    lambda: work.model_widths(_model("../gcn"), 3, 3),
], ids=["make_params", "step_flops", "not_a_name"])
def test_an_unknown_model_names_the_file_to_add(call):
    with pytest.raises(ValueError, match=r"add bench/models/.*\.py"):
        call()


def test_the_model_block_reaches_the_program_whole():
    from bench.drivers.train_epochs import model_config_from
    config = {"model": {"name": "gcn", "num_layers": 3, "hidden": 16,
                        "fanouts": [4, 3, 2], "batch_targets": 32,
                        "aggregate_backend": "reference",
                        "kernel_interpret": True, "precision": "float32"},
              "host": {"num_sampler_workers": 2}}
    cfg = model_config_from(config)
    assert (cfg.name, cfg.num_layers, cfg.hidden, cfg.fanouts,
            cfg.batch_targets, cfg.aggregate_backend, cfg.kernel_interpret,
            cfg.host.num_sampler_workers) == (
        "gcn", 3, 16, (4, 3, 2), 32, "reference", True, 2)
    config["model"]["heads"] = 4  # a key the program does not know
    with pytest.raises(TypeError, match="heads"):
        model_config_from(config)
