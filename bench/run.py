"""Run one cell of the benchmark once and print its result line.

    python bench/run.py --workload resnet56.sync-grpc --seed 7 \
        --seconds 40 --trace 0

Loads the cell named in BENCHMARK.json, builds its inputs and weights
from ``--seed``, warms up (set-up), measures for ``--seconds``, checks
the timed path's output against the plain reference, and prints one
JSON object as the last line of standard output. With ``--trace 1`` the
window is traced and the cell's per-layer metrics are reported in place
of its end-to-end ones; the window is then the traffic's ``trace_seconds``
where that is shorter. Exits non-zero, printing no result, where JAX
finds no TPU or fewer chips than the cell needs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
OUT_DIR = ROOT / ".bench_out"
# the TPU runtime logs under /tmp unless told otherwise; keep them here
os.environ.setdefault("TPU_LOG_DIR", str(OUT_DIR / "tpu_logs"))


def _setup_paths():
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def _enable_cache():
    """JAX's persistent cache, where the program's entry points keep it
    (``JAX_COMPILATION_CACHE_DIR``, else the checkout's ``.jax_cache``;
    none on the CPU); every compile is kept."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    if enable_compile_cache() is None:
        return
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Context:
    """What an entry gets: the cell's data, the run record it fills, and
    the calls that open and close the measured window."""

    def __init__(self, run, config, traffic, seed, seconds, traced, fault,
                 meter, t_start):
        self.run = run
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.seconds = seconds
        self.traced = traced
        self.fault = fault
        self.meter = meter
        self.t_start = t_start
        self.readings = None
        self.detail = {}
        self.on_exit = []  # called once the entry returns or raises
        self.memory_peak_bytes = None
        self._compiles0 = 0
        self._window_ann = None
        self._tracing = False
        self.trace_dir = OUT_DIR / "trace" / run.workload

    def window_open(self) -> float:
        import jax
        if self.traced:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.trace_dir.mkdir(parents=True)
            # device ops and the benchmark's own annotations; no Python
            # function tracing, which would swell the trace tenfold
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self._tracing = True
            self._window_ann = jax.profiler.TraceAnnotation("bench:window")
            self._window_ann.__enter__()
        self._compiles0 = self.meter.compiles
        ws = time.perf_counter()
        self.run.setup_s = ws - self.t_start
        self.run.window = (ws, 0.0)
        return ws

    def window_close(self, t_end: float):
        self.run.window = (self.run.window[0], t_end)
        self.run.compiles_in_window = self.meter.compiles - self._compiles0
        if self._window_ann is not None:
            self._window_ann.__exit__(None, None, None)
            self._window_ann = None

    def window_closed(self):
        """Stop the profiler, if it runs (called once the loop is left)."""
        import jax
        if self._window_ann is not None:
            self._window_ann.__exit__(None, None, None)
            self._window_ann = None
        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False

    def read_memory(self):
        import jax
        peaks = []
        for d in jax.local_devices()[:self.run.chips]:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        self.memory_peak_bytes = max(peaks) if peaks else None


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             allow_cpu: bool = False, fault: str = None, bench=None,
             cell_data=None, t_start: float = None) -> dict:
    """One run of ``workload``; -> the result object. ``allow_cpu`` and
    ``fault`` serve the benchmark's own tests and control checks only."""
    _setup_paths()
    (OUT_DIR / "tpu_logs").mkdir(parents=True, exist_ok=True)
    import jax

    from bench import compare, harness, trace as trace_mod

    bench = bench or harness.load_benchmark()
    cell, config, traffic, limits = cell_data or harness.load_cell(
        bench, workload)
    devices = jax.devices()
    platform = devices[0].platform
    if not allow_cpu:
        if platform != "tpu":
            raise harness.BenchError(f"JAX found no TPU (devices are "
                                     f"{platform!r}); nothing was run")
        if len(devices) < cell["chips"]:
            raise harness.BenchError(f"{workload} needs {cell['chips']} "
                                     f"chips, JAX found {len(devices)}")
        peaks = harness.load_peaks(devices[0].device_kind)
    else:
        peaks = {}
    _enable_cache()
    meter = harness.CompileMeter()
    if traced:
        # the profiler keeps every device operation: a traced run measures
        # the first ``trace_seconds`` of the window, so that writing and
        # reading the trace stays well inside a run's time
        seconds = min(seconds, traffic.get("trace_seconds", seconds))
    run = harness.Run(workload=workload, config=config, traffic=traffic,
                      seed=seed, seconds=seconds, traced=traced,
                      chips=cell["chips"], peaks=peaks)
    ctx = Context(run, config, traffic, seed, seconds, traced, fault, meter,
                  T_START if t_start is None else t_start)
    entry = harness.load_entry(traffic["entry"])
    try:
        entry.run(ctx)
    finally:
        ctx.window_closed()
        for fn in ctx.on_exit:
            fn()
    if traced and not allow_cpu:
        run.trace = trace_mod.reduce_dir(ctx.trace_dir, run)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)

    metrics = {}
    for m in harness.metrics_for(bench, workload, traced):
        value = harness.load_reader(m["name"])(run)
        if value is None:
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct, checks = compare.judge(ctx.readings or {}, limits)
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": min(len(devices), cell["chips"]),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if traced and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = run.trace["breakdown"]
        ctx.detail["idle_by_layer"] = run.trace["idle_by_layer"]
    result["window_compiles"] = run.compiles_in_window
    result["detail"] = dict(ctx.detail,
                            window_s=run.window_s,
                            rounds=len(run.rounds_in_window()),
                            updates=len(run.updates_in_window()))
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _setup_paths()
    from bench.harness import BenchError
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(_finite(result)))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return 0


def _finite(x):
    """JSON has no inf or nan: such a number is written as a string."""
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    return x


if __name__ == "__main__":
    sys.exit(main())
