"""Readings of the numbers that decide ``correct``, for setting limits.

    python bench/control.py --workload resnet56.sync-grpc \
        --seeds 11,12,13 --faults none,control,half_batch --seconds 2

Runs the cell once per (seed, fault) in this one process, on the chip,
at the cell's own size, and prints one JSON line per run with the
readings. ``none`` is the program as it is; ``highest`` the program with
every matmul and convolution at full float32 precision; ``control`` puts
the reference, computed in bfloat16, in the program's place; the others
plant a fault under the timed path (see ``entries/live_fl.py``). The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="none")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    for fault in args.faults.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            if fault == "highest":
                # a look, not a fault: the program with every matmul and
                # convolution at full float32 precision
                import jax
                with jax.default_matmul_precision("highest"):
                    res = bench_run.run_cell(args.workload, seed,
                                             args.seconds, False, t_start=t0)
            else:
                res = bench_run.run_cell(
                    args.workload, seed, args.seconds, False,
                    fault=None if fault == "none" else fault, t_start=t0)
            print(json.dumps(bench_run._finite({
                "workload": args.workload, "seed": seed, "fault": fault,
                "correct": res["correct"], "checks": res["checks"],
                "detail": res["detail"],
                "wall_s": time.perf_counter() - t0})), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
