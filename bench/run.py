#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload sage-products.train --seed 7 \\
        --seconds 30 --trace 0

Everything is found by name. ``BENCHMARK.json`` (beside ``bench/``) names
the cell's configuration and traffic mix; the configuration is
``bench/configs/<config>.json``, the traffic mix ``bench/traffic/
<traffic>.json``, whose ``driver`` names a module ``bench/drivers/
<driver>.py`` that runs it, and each per-layer metric is read by
``bench/metrics/<metric>.py``. A cell, a mix or a metric is added by
adding files.

The run fails, printing no result, unless JAX reports a TPU whose
``device_kind`` is in ``bench/peaks.json`` and at least as many chips as
the cell asks for. Set-up (graph load, trainer or server, compilation from
the persistent cache in ``bench/.cache/jax``, warm-up) counts as
``setup_s``; then the driver measures for ``--seconds``, checks what the
timed path produced against ``bench/reference.py``, and the last stdout
line is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device``, ``breakdown`` (traced
runs) and last ``checks``, each compared number with its limit; the same
numbers end stderr.
"""
import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"
if str(ROOT) not in sys.path:  # the harness's own modules: bench.<name>
    sys.path.insert(0, str(ROOT))


class Fail(Exception):
    """The run cannot produce a result (no chip, bad cell, missing file)."""


def load_json(path: Path) -> dict:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError:
        raise Fail(f"missing {path}") from None


def load_module(path: Path, name: str):
    if not path.exists():
        raise Fail(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise Fail(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(bench: dict, cell: dict, trace: bool) -> list:
    """The metric entries this cell reports: end-to-end without a trace,
    per-layer with one. A metric without ``workloads`` belongs to every
    cell that reports the end-to-end metric it moves."""
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]])
            and m["moves"] in names]


def tpu_device(chips: int, peaks: dict) -> dict:
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise Fail(f"no TPU: JAX found {len(devices)} {d.platform} "
                   f"device(s); the benchmark runs on the chip only")
    if d.device_kind not in peaks["devices"]:
        raise Fail(f"device kind {d.device_kind!r} is not in "
                   f"bench/peaks.json")
    if len(devices) < chips:
        raise Fail(f"the cell needs {chips} chips; JAX sees "
                   f"{len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": chips}


class Run:
    """What a driver gets: the cell's files, the arguments, the clock."""

    def __init__(self, args, bench_dir: Path, cell: dict, config: dict,
                 traffic: dict, device: dict, peaks: dict):
        self.args = args
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.bench_dir = bench_dir
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.device = device
        self.chips = int(cell["chips"])
        self.peak = peaks["devices"].get(device["kind"], {})
        self.t0 = T0
        self.trace_dir = str(CACHE / "trace" / cell["name"])

    def log(self, **fields) -> None:
        """One JSON line on stderr, stamped with seconds since start."""
        fields["t_s"] = time.perf_counter() - self.t0
        print(json.dumps(fields), file=sys.stderr, flush=True)

    def start_trace(self) -> None:
        """Start the profiler into this cell's trace directory, emptied
        first so one run's trace is all it holds."""
        import shutil

        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # no per-call Python events
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)

    def memory_peak_bytes(self) -> int:
        """Peak bytes in use on the fullest of the cell's chips."""
        import jax
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.devices()[:self.chips])


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, caching every program however fast it compiled."""
    path = str(CACHE / "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def result_line(run: Run, out: dict, metric_entries: list) -> dict:
    """Assemble the result object from a driver's output."""
    metrics = {}
    if run.trace:
        ctx = {"trace": out.get("trace"), "record": out["record"],
               "peak": run.peak, "chips": run.chips,
               "config": run.config}
        for m in metric_entries:
            reader = load_module(run.bench_dir / "metrics"
                                 / f"{m['name']}.py",
                                 "bench_metric_" + m["name"].replace(".",
                                                                     "_"))
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metric_entries:
            value = out["end_to_end"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(run.device,
                  memory_peak_bytes=int(out["memory_peak_bytes"]))
    line = {"correct": bool(out["correct"]),
            "attempted": int(out["attempted"]),
            "failed": int(out["failed"]),
            "metrics": metrics, "device": device}
    trace = out.get("trace")
    if run.trace and trace is not None:
        device["busy_s"] = trace.mean_busy_s
        device["window_s"] = trace.window_s
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in trace.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in trace.gaps[:10]]}
    line["checks"] = out["checks"]
    return line


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, bench_dir: Path = BENCH,
         benchmark_json: Path = ROOT / "BENCHMARK.json",
         require_chip: bool = True) -> int:
    args = parse(argv)
    try:
        bench = load_json(benchmark_json)
        cell = find_cell(bench, args.workload)
        config = load_json(bench_dir / "configs" / f"{cell['config']}.json")
        traffic = load_json(bench_dir / "traffic"
                            / f"{cell['traffic']}.json")
        peaks = load_json(bench_dir / "peaks.json")
        sys.path.insert(0, str(ROOT / "src"))
        try:
            import repro  # noqa: F401  the system under test
        except ImportError as e:
            raise Fail(f"the program (src/repro) is not in this checkout: "
                       f"{e}") from None
        if require_chip:
            enable_compile_cache()
            device = tpu_device(int(cell["chips"]), peaks)
        else:
            import jax
            d = jax.devices()[0]
            device = {"platform": d.platform, "kind": d.device_kind,
                      "count": int(cell["chips"])}
        driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py",
                             "bench_driver_" + traffic["driver"])
        run = Run(args, bench_dir, cell, config, traffic, device, peaks)
        entries = metrics_of(bench, cell, run.trace)
        out = driver.run(run)
        line = result_line(run, out, entries)
    except Fail as e:
        print(f"bench: FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
