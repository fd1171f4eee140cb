"""BENCH_6: the perf trajectory record this PR starts.

Measures the two things PR 6 changed — engine throughput and encode
throughput — writes them to ``benchmarks/out/BENCH_6.json`` and gates
against the committed record ``benchmarks/BENCH_6.json`` so a future PR
that regresses either by >10% fails the bench run.

Cross-machine comparisons use *ratios*, not absolute seconds:

* ``encode.speedup``        — fused ``quantize_flat_batch`` MB/s over the
  legacy pure-NumPy per-message codec MB/s, small-message regime (this
  is where per-message dispatch overhead dominated).
* ``engine.replay_per_unit``— cached-replay cells/s normalised by a
  fixed NumPy reference workload timed in the same process: pure engine
  dispatch overhead, no spawn noise, machine-independent.
* ``engine.parallel_speedup`` (informational, recorded when workers>1)
  — serial wall over parallel wall on the synthetic grid. On a quick
  grid the spawn+import cost dominates, so this is < 1 by design; it is
  recorded to track the trajectory, not gated.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import time

import numpy as np

BENCH_NAME = "trajectory"
BENCH_ORDER = 990  # after every fig study
BENCH_IN_QUICK = True

_RECORD = os.path.join(os.path.dirname(__file__), "BENCH_6.json")
_OUT = os.path.join(os.path.dirname(__file__), "out", "BENCH_6.json")
# BENCH_7 (PR 7, fleet-scale engine): written by benchmarks/fig11_scale.py
# on every bench run; gated here against the committed record
_RECORD7 = os.path.join(os.path.dirname(__file__), "BENCH_7.json")
_OUT7 = os.path.join(os.path.dirname(__file__), "out", "BENCH_7.json")

# encode bench: many small messages — the regime the batched API targets
_N_MSGS, _N_ELEMS = 64, 10_000
# engine bench: enough cells that per-cell dispatch overhead integrates
_N_CELLS = 24
_GATE = 0.9  # measured must stay within 10% of the committed record


def _ref_unit_s() -> float:
    """A fixed NumPy workload timed on this machine: the normaliser that
    makes engine throughput comparable across hosts."""
    a = np.random.default_rng(0).normal(size=(256, 256)).astype(np.float32)
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(8):
            a = np.tanh(a @ a.T) * np.float32(0.1)
        best = min(best, time.perf_counter() - t0)
    return best


def _bench_cell(cell):
    """Synthetic engine cell: a deterministic ~ms NumPy workload (module
    level so --workers can pickle it)."""
    n = cell.params["n"]
    a = np.random.default_rng(n).normal(size=(128, 128)).astype(np.float32)
    for _ in range(16):
        a = np.tanh(a @ a.T) * np.float32(0.1)
    return {"sim_time_s": float(abs(a).sum()), "n": n}


def _encode_bench():
    from repro.kernels import ops, ref
    from repro.kernels.quantize import ROW_TILE
    block = 256
    rng = np.random.default_rng(42)
    msgs = [rng.normal(size=_N_ELEMS).astype(np.float32)
            for _ in range(_N_MSGS)]
    nbytes = _N_MSGS * _N_ELEMS * 4
    mult = block * ROW_TILE

    def numpy_legacy():
        out = []
        for x in msgs:
            xp = np.zeros(-(-x.size // mult) * mult, np.float32)
            xp[: x.size] = x
            q, s = ref.quantize_blocks_np(xp.reshape(-1, block))
            out.append({"q": q.reshape(-1), "scales": s.reshape(-1),
                        "block": block, "orig_len": x.size})
        return out

    def fused():
        out = ops.quantize_flat_batch(msgs, block=block)
        return [{k: np.asarray(v) if k in ("q", "scales") else v
                 for k, v in p.items()} for p in out]

    fused()  # warm the jit cache before timing either path
    legacy_pk = numpy_legacy()
    # interleaved best-of-9: the ratio (not the absolute MB/s) is the
    # recorded number, so both paths must see the same machine noise
    t = [float("inf"), float("inf")]
    for _ in range(9):
        t0 = time.perf_counter()
        numpy_legacy()
        t[0] = min(t[0], time.perf_counter() - t0)
        t0 = time.perf_counter()
        fused_pk = fused()
        t[1] = min(t[1], time.perf_counter() - t0)
    # the wire-critical int8 payload must be bit-identical across paths
    q_bitexact = all(np.array_equal(a["q"], b["q"])
                     for a, b in zip(legacy_pk, fused_pk))
    # and vs the per-message batched-API entry point: fully identical
    per_msg = [ops.quantize_flat(x, block=block) for x in msgs]
    wire_identical = all(
        np.array_equal(np.asarray(a["q"]), b["q"])
        and np.array_equal(np.asarray(a["scales"]), b["scales"])
        for a, b in zip(per_msg, fused_pk))
    mb = nbytes / 2**20
    return {"n_msgs": _N_MSGS, "elems_per_msg": _N_ELEMS,
            "numpy_mb_s": mb / t[0], "batched_mb_s": mb / t[1],
            "speedup": t[0] / t[1], "q_bitexact": q_bitexact,
            "wire_bytes_identical": wire_identical}


def _engine_bench(workers: int):
    from repro.sweep import Axis, Engine, Study, Sweep
    sw = Sweep(name="bench6",
               axes=(Axis("params.n", values=tuple(range(_N_CELLS))),))
    study = Study(name="bench6", sweeps=lambda quick: (sw,),
                  cell=_bench_cell)
    cells = sw.expand()
    tmp = tempfile.mkdtemp(prefix="bench6_")
    try:
        eng = Engine(os.path.join(tmp, "serial"))
        t0 = time.perf_counter()
        serial = eng.run_cells(study, cells, verbose=False)
        serial_wall = time.perf_counter() - t0
        replay_wall = float("inf")
        for _ in range(5):  # ~ms-scale: best-of-5 beats the scheduler
            t0 = time.perf_counter()
            replay = eng.run_cells(study, cells, verbose=False)
            replay_wall = min(replay_wall, time.perf_counter() - t0)
        assert replay == serial and eng.last_stats.n_cached == _N_CELLS
        unit = _ref_unit_s()
        out = {"n_cells": _N_CELLS,
               "serial_cells_s": _N_CELLS / serial_wall,
               "replay_cells_s": _N_CELLS / replay_wall,
               "replay_per_unit": _N_CELLS / replay_wall * unit,
               "ref_unit_s": unit}
        if workers > 1:
            eng_p = Engine(os.path.join(tmp, "par"))
            t0 = time.perf_counter()
            par = eng_p.run_cells(study, cells, verbose=False,
                                  workers=workers)
            par_wall = time.perf_counter() - t0
            with open(eng.store_path("bench6"), "rb") as f:
                blob_s = f.read()
            with open(eng_p.store_path("bench6"), "rb") as f:
                blob_p = f.read()
            assert par == serial, "--workers changed the results"
            assert blob_s == blob_p, "--workers changed the store bytes"
            out.update({"workers": workers,
                        "parallel_wall_s": par_wall,
                        "parallel_speedup": serial_wall / par_wall,
                        "store_bytes_identical": True})
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _gate(measured: dict, verbose: bool) -> None:
    if not os.path.exists(_RECORD):
        if verbose:
            print(f"[trajectory] no committed record at {_RECORD}; "
                  f"nothing to gate against")
        return
    with open(_RECORD) as f:
        rec = json.load(f)
    checks = [
        ("encode.speedup", measured["encode"]["speedup"],
         rec["encode"]["speedup"]),
        ("engine.replay_per_unit", measured["engine"]["replay_per_unit"],
         rec["engine"]["replay_per_unit"]),
    ]
    for name, got, want in checks:
        assert got >= _GATE * want, (
            f"perf regression: {name} measured {got:.3f} < "
            f"{_GATE:.0%} of the recorded {want:.3f} (BENCH_6)")
        if verbose:
            print(f"[trajectory] gate ok: {name} {got:.3f} "
                  f"(recorded {want:.3f})")


def _gate_bench7(verbose: bool) -> None:
    """BENCH_7 (fleet-scale engine): gate fig11's measured ratios.

    The fig11 study writes ``out/BENCH_7.json`` when it runs; in a bench
    sweep it runs before this module (BENCH_ORDER). Skips quietly when
    the measurement is absent (e.g. ``--only trajectory``). The gates
    are the PR's absolute invariants — a >= 5x engine speedup at 1k
    clients and a flat streaming-hub memory peak — not machine-relative
    ratios, so they hold on any host."""
    if not os.path.exists(_RECORD7) or not os.path.exists(_OUT7):
        if verbose:
            print("[trajectory] BENCH_7: no fig11 measurement/record to "
                  "gate against")
        return
    with open(_OUT7) as f:
        got = json.load(f)
    assert got["speedup_1k"] >= 5.0, (
        f"perf regression: fig11 engine speedup at 1k clients "
        f"{got['speedup_1k']:.2f}x < the required 5x (BENCH_7)")
    assert got["mem_ratio_max_fleet"] <= 1.5, (
        f"perf regression: streaming-hub peak memory grew "
        f"{got['mem_ratio_max_fleet']:.2f}x with fleet size (BENCH_7)")
    if verbose:
        print(f"[trajectory] gate ok: fig11 speedup_1k "
              f"{got['speedup_1k']:.1f}x, mem ratio "
              f"{got['mem_ratio_max_fleet']:.2f}x")


def run(verbose: bool = True, quick: bool = False, fresh: bool = False,
        workers: int = 0):
    from repro.sweep.engine import refuse_pool_on_tpu
    refuse_pool_on_tpu(workers)  # before any cell, not after the serial run
    encode = _encode_bench()
    assert encode["q_bitexact"], "batched codec broke int8 wire parity"
    assert encode["wire_bytes_identical"], \
        "batched codec broke per-message wire parity"
    engine = _engine_bench(workers)
    measured = {"bench": "BENCH_6", "recorded_at_pr": 6,
                "encode": encode, "engine": engine}
    os.makedirs(os.path.dirname(_OUT), exist_ok=True)
    with open(_OUT, "w") as f:
        json.dump(measured, f, indent=2)
    if verbose:
        print(f"[trajectory] encode: numpy {encode['numpy_mb_s']:.0f} "
              f"MB/s -> batched {encode['batched_mb_s']:.0f} MB/s "
              f"(x{encode['speedup']:.2f}, wire bytes identical)")
        par = (f", x{engine['parallel_speedup']:.2f} with "
               f"{engine['workers']} workers" if "workers" in engine else "")
        print(f"[trajectory] engine: {engine['serial_cells_s']:.0f} "
              f"cells/s serial, {engine['replay_cells_s']:.0f} cells/s "
              f"replay{par}")
        print(f"[trajectory] record -> {_OUT}")
    _gate(measured, verbose)
    _gate_bench7(verbose)
    msg_bytes = encode["elems_per_msg"] * 4
    return [{"name": "trajectory/encode",
             "us_per_call": 1e6 * msg_bytes / (encode["batched_mb_s"]
                                               * 2**20),
             "derived": f"speedup={encode['speedup']:.3g};"
                        f"batched_mb_s={encode['batched_mb_s']:.4g}"},
            {"name": "trajectory/engine",
             "us_per_call": 1e6 / engine["replay_cells_s"],
             "derived": f"replay_per_unit="
                        f"{engine['replay_per_unit']:.4g}"}]


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--workers", type=int, default=0)
    args = ap.parse_args()
    run(quick=args.quick, workers=args.workers)
