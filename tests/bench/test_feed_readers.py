"""The feed's per-layer readers (``bench/metrics/feed_*``, ``pool_*``,
``step_dispatch_ms``, ``idle_on_*``) on a hand-built window of spans, and
on a traced tiny run of the harness."""
import importlib.util
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import feed_trace  # noqa: E402
from bench.feed_trace import FeedTrace, Span  # noqa: E402
import test_bench_harness as harness  # noqa: E402
from test_bench_harness import bench_dir  # noqa: E402,F401  a fixture

READERS = ("feed_pool_wait_share.train", "pool_sample_ms.train",
           "pool_layout_ms.train", "pool_ship_ms.train",
           "feed_assemble_ms.train", "step_dispatch_ms.train",
           "idle_on_pool_share.train", "idle_on_assemble_share.train")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        REPO / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _stages(t, sample, layout, ship):
    return Span("feed/stages", t, t, {"sample_s": sample,
                                      "layout_s": layout, "ship_s": ship})


# a 10 s window on one chip, busy 1-2 s and 5-6 s (idle 8 s): the feed
# waits on the pool 0-3 s (decoding 2.5-3 s) and assembles 3-4 s; later it
# waits 6-8 s and assembles 8-9 s with sampling and layout nested inside
HAND = FeedTrace(
    window=(0.0, 10.0),
    spans=[Span("feed/pool_wait", 0.0, 3.0), Span("feed/decode", 2.5, 3.0),
           _stages(3.0, 0.2, 0.1, 0.05), Span("feed/assemble", 3.0, 4.0),
           Span("step/dispatch", 4.0, 4.5), _stages(4.0, 0.3, 0.1, 0.05),
           Span("feed/pool_wait", 6.0, 8.0), Span("step/dispatch", 7.0, 7.1),
           Span("feed/assemble", 8.0, 9.0), Span("feed/sample", 8.1, 8.3),
           Span("feed/layout", 8.3, 8.4)],
    busy=[[(1.0, 2.0), (5.0, 6.0)]])
RECORD = {"batches": 2, "epoch_time_s": 10.0}
# by hand: pool wait 3 + 2 - 0.5 decode = 4.5 s of 10; sample 0.5 + 0.2,
# layout 0.2 + 0.1, ship 0.1 s over 2 batches; own work 0.5 + 2 - 0.3 =
# 2.2 s over 2 batches; dispatch 0.6 s over 2 steps; idle inside the pool
# wait (0-1, 2-2.5, 6-8) 3.5 s and inside decode or assembly (2.5-4,
# 8-9) 2.5 s, of 8 s idle
WANT = {"feed_pool_wait_share.train": 45.0, "pool_sample_ms.train": 350.0,
        "pool_layout_ms.train": 150.0, "pool_ship_ms.train": 50.0,
        "feed_assemble_ms.train": 1100.0, "step_dispatch_ms.train": 300.0,
        "idle_on_pool_share.train": 43.75,
        "idle_on_assemble_share.train": 31.25}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_hand_built_window(name, monkeypatch):
    monkeypatch.setattr(feed_trace, "load", lambda ctx: HAND)
    ctx = {"trace": object(), "record": RECORD, "chips": 1}
    assert _reader(name).read(ctx) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_without_feed_spans_reads_nothing(name, monkeypatch):
    ctx = {"trace": None, "record": RECORD, "chips": 1}
    assert feed_trace.load(ctx) is None
    assert _reader(name).read(ctx) is None
    # a trace of a program without the spans
    monkeypatch.setattr(feed_trace, "load", lambda ctx: None)
    assert _reader(name).read(dict(ctx, trace=object())) is None


def test_window_stages_from_a_trace_without_a_pool():
    """With no sampling service the stage seconds come from the in-process
    spans alone, and the assembly's own work leaves them out."""
    ft = FeedTrace((0.0, 4.0), [Span("feed/assemble", 0.0, 2.0),
                                Span("feed/sample", 0.5, 1.0),
                                Span("feed/layout", 1.0, 1.25)], [])
    assert ft.stage_seconds("sample") == 0.5
    assert ft.stage_seconds("ship") == 0.0
    assert ft.own_work_seconds() == pytest.approx(1.25)
    # no device planes (a CPU trace): no idle time to attribute
    assert ft.idle_seconds() == 0.0


def test_traced_run_with_a_sampling_service_reads_the_feed(bench_dir,
                                                           capsys):
    harness._write(bench_dir / "configs" / "tiny.json",
                   dict(harness.TINY, host={"num_sampler_workers": 2}))
    metrics = [harness._metric(name, "x", "program_span", "host feed",
                               "train_targets_per_s", ["tiny.train"])
               for name in READERS]
    harness._write(bench_dir.parent / "BENCHMARK.json",
                   harness._benchmark((), metrics))
    line, _ = harness._run(bench_dir, capsys, "tiny.train", trace=1)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # a CPU trace has no device plane, so no idle time to attribute
    for name in READERS[:6]:
        assert got[name] > 0, (name, json.dumps(got))
    assert got["feed_pool_wait_share.train"] < 100.0
    assert "idle_on_pool_share.train" not in got
