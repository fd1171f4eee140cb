"""The benchmark's shared machinery, driven by the names in BENCHMARK.json.

Everything that belongs to one configuration, traffic mix, metric or entry
lives in a file of its own and is found here by name:

* ``configs/<config>.json``   the configuration as it is run;
* ``traffic/<mix>.json``      one traffic mix, naming its ``entry``;
* ``entries/<entry>.py``      the code that runs one kind of cell (``run(ctx)``);
* ``metrics/<metric>.py``     one reader per metric (``read(run)``);
* ``limits/<cell>.json``      the limit of each number that decides
                              ``correct`` in that cell.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class BenchError(RuntimeError):
    """The benchmark cannot run as asked (no chip, unknown name, ...)."""


# ---------------------------------------------------------------------------
# discovery
# ---------------------------------------------------------------------------

def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def _by_name(items, name, what):
    for it in items:
        if it["name"] == name:
            return it
    raise BenchError(f"unknown {what} {name!r}")


def load_cell(bench: dict, workload: str, bench_dir: Path = BENCH_DIR):
    """-> (workload entry, config dict, traffic dict, limits dict)."""
    cell = _by_name(bench["workloads"], workload, "workload")
    cfg_entry = _by_name(bench["configs"], cell["config"], "config")
    config = _load_json(bench_dir.parent / cfg_entry["file"])
    traffic = _load_json(bench_dir / "traffic" / f"{cell['traffic']}.json")
    limits = _load_json(bench_dir / "limits" / f"{workload}.json")
    return cell, config, traffic, limits


def _import_file(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_entry(name: str, bench_dir: Path = BENCH_DIR):
    return _import_file(bench_dir / "entries" / f"{name}.py",
                        f"bench_entry_{name}")


def load_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    return _import_file(bench_dir / "metrics" / f"{name}.py",
                        f"bench_metric_{name.replace('.', '_')}").read


def metrics_for(bench: dict, workload: str, traced: bool) -> List[dict]:
    """The metrics a run of ``workload`` reports: its end-to-end metrics
    untraced, its per-layer metrics traced. A metric without a
    ``workloads`` key belongs to every cell that reports what it moves."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
            and m["moves"] in names]


def load_peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    table = _load_json(bench_dir / "peaks.json")
    if device_kind not in table["kinds"]:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"peaks.json; add its published peaks")
    return table["kinds"][device_kind]


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

class Spans:
    """Host spans around the calls into each layer, on ``perf_counter``.

    ``cat`` names the layer (``client_step``, ``wire``, ``hub``), ``side``
    the host (a client id or ``server``). Only the outermost span of a
    category counts, so wrappers may nest. With ``traced`` each span is
    also a ``jax.profiler.TraceAnnotation`` named ``bench:<cat>``, which
    puts it on the device trace's clock."""

    def __init__(self, traced: bool = False):
        self.traced = traced
        self.spans: List[Tuple[str, str, float, float]] = []
        self._depth: Dict[str, int] = {}

    def wrap(self, fn: Callable, cat: str,
             side: Callable[..., str]) -> Callable:
        import functools

        import jax

        @functools.wraps(fn)
        def wrapped(*args, **kw):
            depth = self._depth.get(cat, 0)
            if depth:
                self._depth[cat] = depth + 1
                try:
                    return fn(*args, **kw)
                finally:
                    self._depth[cat] = depth
            self._depth[cat] = 1
            ann = (jax.profiler.TraceAnnotation(f"bench:{cat}")
                   if self.traced else None)
            if ann is not None:
                ann.__enter__()
            t0 = time.perf_counter()
            try:
                return fn(*args, **kw)
            finally:
                t1 = time.perf_counter()
                if ann is not None:
                    ann.__exit__(None, None, None)
                self._depth[cat] = 0
                self.spans.append((cat, side(*args, **kw), t0, t1))

        return wrapped


def union_length(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


# ---------------------------------------------------------------------------
# compile meter (copied from the program's chip smoke script)
# ---------------------------------------------------------------------------

class CompileMeter:
    """Backend compiles, counted from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


# ---------------------------------------------------------------------------
# what a run hands to the metric readers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    workload: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    traced: bool
    chips: int
    peaks: dict
    setup_s: float = 0.0
    window: Tuple[float, float] = (0.0, 0.0)
    rounds: List[Tuple[float, float]] = dataclasses.field(
        default_factory=list)
    # (client id, start, end) of each client update
    updates: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)
    spans: List[Tuple[str, str, float, float]] = dataclasses.field(
        default_factory=list)
    # (time, kernel, bytes from shapes) of each kernel call
    kernel_calls: List[Tuple[float, str, int]] = dataclasses.field(
        default_factory=list)
    # end times of the client steps (one per local step)
    client_steps: List[float] = dataclasses.field(default_factory=list)
    flops_per_step: float = 0.0
    trace: Optional[dict] = None
    compiles_in_window: int = 0
    attempted: int = 0
    failed: int = 0

    # -- window helpers --------------------------------------------------
    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t <= self.window[1]

    def rounds_in_window(self):
        return [r for r in self.rounds if r[0] >= self.window[0] - 1e-9
                and self.in_window(r[1])]

    def updates_in_window(self):
        return [u for u in self.updates if self.in_window(u[2])]

    def span_time(self, cats, sides: Optional[Callable[[str], bool]] = None
                  ) -> float:
        """Union of the spans of ``cats`` (on the sides ``sides`` accepts)
        inside the window."""
        iv = [(s, e) for c, side, s, e in self.spans
              if c in cats and (sides is None or sides(side))]
        return union_length(iv, *self.window)


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    closest ranks."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)
