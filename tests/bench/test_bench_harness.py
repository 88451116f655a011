"""The benchmark harness end to end on the CPU, at a tiny size.

``bench.run.main(..., require_chip=False)`` skips the look for a chip and
drives the rest of a run: graph, trainer or server, window, check. The
cells live in a temporary benchmark directory, so these tests also show
that a configuration, a traffic mix and a per-layer metric are found by
name from added files alone. Planted faults in the timed path must turn
``correct`` false.
"""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import graphs, run  # noqa: E402

TINY = {
    "name": "tiny", "source": "a tiny stand-in for the CPU tests",
    "model": {"name": "graphsage", "num_layers": 2, "hidden": 16,
              "fanouts": [4, 3], "batch_targets": 32,
              "aggregate_backend": "pallas_fused", "precision": "float32"},
    "dataset": {"name": "tiny", "num_vertices": 3000, "mean_in_degree": 12.0,
                "feat_dim": 16, "num_classes": 5, "train_frac": 0.2,
                "graph_seed": 0},
    "algorithm": "distdgl",
    "optimizer": {"name": "adamw", "lr": 0.01, "b1": 0.9, "b2": 0.95,
                  "eps": 1e-8, "weight_decay": 0.0, "grad_clip": 1.0,
                  "warmup": 10, "total": 100000, "min_ratio": 0.1},
    "host": {"num_sampler_workers": 0}, "reduced": [], "assumed": []}
TRAIN = {"driver": "train_epochs", "batches_per_device": 3,
         "warmup_epochs": 1, "check_steps": 3}
SERVE = {"driver": "serve_open_loop", "rate_per_s": 20, "targets_min": 1,
         "targets_max": 8, "zipf_s": 0.99, "slo_ms": 50, "num_workers": 0,
         "traffic_seed": 0, "check_batches": 8, "drain_s": 30}
LIMITS = {"tiny.train": {"loss_gap": 1e-4, "grad_gap": 1e-4,
                         "change_gap": 1e-4},
          "tiny.serve": {"logit_gap": 1e-4}}


def _metric(name, unit, source, layer, moves, cells=None):
    m = {"name": name, "unit": unit, "better": "lower", "source": source,
         "layer": layer, "moves": moves}
    if cells:
        m["workloads"] = cells
    return m


def _benchmark(extra_cells=(), extra_metrics=()):
    return {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "test", "file": "x",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": "tiny.train", "config": "tiny",
                       "traffic": "train", "chips": 1, "why": "test"},
                      {"name": "tiny.serve", "config": "tiny",
                       "traffic": "serve", "chips": 1, "why": "test"},
                      *extra_cells],
        "end_to_end": [
            {"name": "train_targets_per_s", "unit": "targets/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": ["tiny.train"]
             + [c["name"] for c in extra_cells]},
            {"name": "serve_p50_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["tiny.serve"]},
            {"name": "serve_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["tiny.serve"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            _metric("host_wait_share.train", "%", "program_counter",
                    "host feed", "train_targets_per_s"),
            _metric("step_mfu.train", "%", "host_clock", "device step",
                    "train_targets_per_s"),
            _metric("device_idle_share.serve", "%", "device_trace",
                    "device", "serve_p95_ms", ["tiny.serve"]),
            *extra_metrics]}


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


@pytest.fixture
def bench_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(graphs, "CACHE_DIR", tmp_path / "graphs")
    d = tmp_path / "bench"
    shutil.copytree(REPO / "bench" / "metrics", d / "metrics")
    shutil.copy(REPO / "bench" / "peaks.json", d / "peaks.json")
    _write(d / "configs" / "tiny.json", TINY)
    _write(d / "traffic" / "train.json", TRAIN)
    _write(d / "traffic" / "serve.json", SERVE)
    for cell, limits in LIMITS.items():
        _write(d / "limits" / f"{cell}.json", {"limits": limits})
    _write(tmp_path / "BENCHMARK.json", _benchmark())
    return d


def _run(bench_dir, capsys, cell, trace=0, seed=2**33 + 11):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   "1", "--trace", str(trace)], bench_dir=bench_dir,
                  benchmark_json=bench_dir.parent / "BENCHMARK.json",
                  require_chip=False)
    out, err = capsys.readouterr()
    assert rc == 0, err[-3000:]
    return json.loads(out.strip().splitlines()[-1]), err


def test_training_run_is_correct_and_its_line_holds_the_keys(bench_dir,
                                                             capsys):
    line, err = _run(bench_dir, capsys, "tiny.train")
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] % TRAIN["batches_per_device"] == 0
    assert set(line["metrics"]) == {"train_targets_per_s", "setup_s"}
    assert line["metrics"]["train_targets_per_s"]["value"] > 0
    assert set(line["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    # the compared numbers end stderr, each beside its limit
    tail = err.strip().splitlines()[-3:]
    assert all(t.startswith("check ") and "limit" in t for t in tail)


def test_serving_run_is_correct_and_reports_its_tails(bench_dir, capsys):
    line, err = _run(bench_dir, capsys, "tiny.serve")
    assert line["correct"] is True
    assert line["attempted"] == SERVE["rate_per_s"] and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_p50_ms", "serve_p95_ms",
                                    "setup_s"}
    assert 0 < line["metrics"]["serve_p50_ms"]["value"] \
        <= line["metrics"]["serve_p95_ms"]["value"]
    assert '"phase": "generator"' in err


def test_added_config_traffic_and_metric_are_found_by_name(bench_dir,
                                                           capsys):
    # new files only: a configuration, a traffic mix, a metric reader and
    # a limits file, named by a new cell and a new per-layer metric
    _write(bench_dir / "configs" / "tiny-gcn.json",
           dict(TINY, name="tiny-gcn", model=dict(TINY["model"],
                                                  name="gcn")))
    _write(bench_dir / "traffic" / "train-two.json",
           dict(TRAIN, batches_per_device=2, check_steps=2))
    _write(bench_dir / "limits" / "tiny-gcn.train-two.json",
           {"limits": LIMITS["tiny.train"]})
    (bench_dir / "metrics" / "epochs_run.train.py").write_text(
        "def read(ctx):\n    return float(ctx['record']['epochs'])\n")
    cell = {"name": "tiny-gcn.train-two", "config": "tiny-gcn",
            "traffic": "train-two", "chips": 1, "why": "test"}
    _write(bench_dir.parent / "BENCHMARK.json", _benchmark(
        [cell], [_metric("epochs_run.train", "epochs", "host_clock",
                         "device step", "train_targets_per_s",
                         ["tiny-gcn.train-two"])]))
    line, _ = _run(bench_dir, capsys, "tiny-gcn.train-two", trace=1)
    assert line["correct"] is True
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "checks"]
    assert line["metrics"]["epochs_run.train"]["value"] >= 1
    assert "host_wait_share.train" in line["metrics"]
    assert line["attempted"] % 2 == 0
    assert {"busy_s", "window_s", "memory_peak_bytes"} <= set(
        line["device"])


def _frozen_state(monkeypatch):
    from repro.optim.adam import AdamW
    monkeypatch.setattr(AdamW, "update", lambda self, grads, state, params: (
        params, state, {"lr": np.float32(0.0), "grad_norm": np.float32(0)}))


def _half_batch(monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.gnn import models

    def half_loss(cfg, params, batch):
        logits = models.forward(cfg, params, batch).astype(jnp.float32)
        half = logits.shape[0] // 2
        logits, labels = logits[:half], batch["labels"][:half]
        ll = jnp.take_along_axis(logits, labels[:, None].astype(jnp.int32),
                                 axis=-1)[:, 0]
        loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) - ll)
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(
            jnp.float32))
        return loss, {"loss": loss, "acc": acc}

    monkeypatch.setattr(models, "loss_fn", half_loss)


def _altered_answer(monkeypatch):
    from repro.core.serving import ServingRuntime
    serve_targets = ServingRuntime._serve_targets

    def altered(self, ids):
        out = np.array(serve_targets(self, ids))
        out[0] += 0.5
        return out

    monkeypatch.setattr(ServingRuntime, "_serve_targets", altered)


@pytest.mark.parametrize("cell, fault", [
    ("tiny.train", _frozen_state),
    ("tiny.train", _half_batch),
    ("tiny.serve", _altered_answer),
], ids=["state_unchanged", "half_batch", "answer_altered"])
def test_a_fault_in_the_timed_path_is_not_correct(bench_dir, capsys,
                                                  monkeypatch, cell, fault):
    sys.path.insert(0, str(REPO / "src"))
    fault(monkeypatch)
    line, _ = _run(bench_dir, capsys, cell)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_no_chip_no_result(bench_dir, capsys):
    rc = run.main(["--workload", "tiny.train", "--seed", "1", "--seconds",
                   "1"], bench_dir=bench_dir,
                  benchmark_json=bench_dir.parent / "BENCHMARK.json")
    out, err = capsys.readouterr()
    assert rc != 0 and out.strip() == "" and "no TPU" in err


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    cell = json.loads((REPO / "BENCHMARK.json").read_text())[
        "workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "not in this checkout" in proc.stderr


def test_the_bfloat16_control_is_not_correct(bench_dir):
    """The control (bench/control.py): the reference in bfloat16 in the
    program's place fails the cell's limits."""
    sys.path.insert(0, str(REPO / "src"))
    from bench import compare, control
    arrays, _ = graphs.load(TINY["dataset"])
    for seed in (2**32 + 3,):
        numbers, faults = control.training_control(TINY, TRAIN, seed,
                                                   arrays)
        ok, _ = compare.judge(numbers, LIMITS["tiny.train"])
        assert not ok, numbers
        assert not compare.judge(faults["half_batch"],
                                 LIMITS["tiny.train"])[0]


def test_the_bfloat16_serving_control_is_not_correct(bench_dir):
    """The serving control (bench/control.py): the reference's logits in
    bfloat16 in the program's place fail the serving cell's limit. The pack
    is smaller than the largest request, which then makes a micro-batch of
    its own."""
    sys.path.insert(0, str(REPO / "src"))
    from bench import compare, control
    arrays, _ = graphs.load(TINY["dataset"])
    for seed in (2**32 + 3,):
        numbers, _ = control.serving_control(TINY, SERVE, seed, arrays,
                                             pack=3,
                                             batches=8)
        assert not compare.judge(numbers, LIMITS["tiny.serve"])[0], numbers
