"""Reduce a profiler trace of the measured window to the numbers the
metric readers use.

The JAX profiler writes ``plugins/profile/<time>/<host>.xplane.pb``. In it
each TPU is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
one event per operation that ran on the device, its ``XLA Modules`` line
one per program execution. The host plane holds the benchmark's own
annotations (``bench:window`` around the window, ``bench:<layer>``
around the calls into each layer) on the same clock.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Tuple

HOST_PREFIX = "bench:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _planes(path: Path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path)).planes


def _short(name: str) -> str:
    """An op event is named by its whole HLO instruction; keep the name
    before ``=`` (``%fedavg_accumulate.1``)."""
    return name.split(" = ", 1)[0]


def read_xplane(path: Path) -> dict:
    """-> {"host": [(name, start_s, end_s)], "devices": [{"ops":
    [(name, start_s, end_s)], "modules": [...]}]} in seconds."""
    host, devices = [], []
    for plane in _planes(path):
        if plane.name.startswith("/device:TPU:") and \
                "NON_CORE" not in plane.name:
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                dev[key] = [(_short(e.name), e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.name[len(HOST_PREFIX):],
                                     e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9))
    devices.sort(key=lambda d: d["name"])
    return {"host": host, "devices": devices}


def _merge(intervals: List[Tuple[float, float]]):
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(raw: dict, chips: int) -> dict:
    """Busy union and idle gaps inside the window, operation and program
    time by name: per chip, averaged over ``chips``."""
    wins = [(s, e) for n, s, e in raw["host"] if n == "window"]
    if not wins:
        raise ValueError("the trace has no bench:window annotation")
    lo, hi = wins[0]
    devs = raw["devices"][:chips]
    if not devs:
        raise ValueError("the trace has no TPU plane")
    busy, ops, modules = 0.0, {}, {}
    gaps = []
    for d in devs:
        clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in d["ops"]]
        clipped = [(n, s, e) for n, s, e in clipped if e > s]
        merged = _merge([(s, e) for _, s, e in clipped])
        busy += sum(e - s for s, e in merged)
        for n, s, e in clipped:
            ops[n] = ops.get(n, 0.0) + (e - s)
        for n, s, e in d["modules"]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                c, t = modules.get(n, (0, 0.0))
                modules[n] = (c + 1, t + e - s)
        if d is devs[0]:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges) - 1, 2)
                    if edges[i + 1] > edges[i]]
    n = len(devs)
    ops = {k: v / n for k, v in ops.items()}
    modules = {k: (c / n, t / n) for k, (c, t) in modules.items()}
    spans = sorted((s, e, name) for name, s, e in raw["host"]
                   if name != "window")
    longest = max((e - s for s, e, _ in spans), default=0.0)
    labelled = [(_label(spans, longest, s, e), e - s) for s, e in gaps]
    idle_by_layer: Dict[str, float] = {}
    for lab, d in labelled:
        idle_by_layer[lab] = idle_by_layer.get(lab, 0.0) + d
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(labelled, key=lambda kv: -kv[1])[:10]
    return {"window_s": hi - lo, "busy_s": busy / n, "ops": ops,
            "modules": modules,
            "idle_by_layer": idle_by_layer,
            "breakdown": {"device_ops": [[k, v] for k, v in top_ops],
                          "idle_gaps": [[k, v] for k, v in top_gaps]}}


def _label(spans, longest: float, lo: float, hi: float) -> str:
    """The host layer span that covers most of ``[lo, hi]``; ``runtime``
    where none does. ``spans``: (start, end, name) sorted by start, none
    longer than ``longest``."""
    import bisect
    first = bisect.bisect_left(spans, (lo - longest,))
    last = bisect.bisect_right(spans, (hi, float("inf")))
    best, best_t = "runtime", 0.0
    cover: Dict[str, float] = {}
    for s, e, name in spans[first:last]:
        o = min(e, hi) - max(s, lo)
        if o > 0:
            cover[name] = cover.get(name, 0.0) + o
    for name, t in cover.items():
        if t > best_t:
            best, best_t = name, t
    return best if best_t > 0.5 * (hi - lo) else "runtime"


def reduce_dir(trace_dir: Path, run) -> dict:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise ValueError(f"no trace under {trace_dir}")
    return reduce(read_xplane(files[-1]), run.chips)
