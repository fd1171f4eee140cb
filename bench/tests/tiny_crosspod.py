"""The cross-pod cell at a tiny size for CPU tests: the program's small
HuBERT (2 layers, d_model 64, 32 CNN channels, 128 clusters; the
published kernels, strides and positional conv) on 4 host devices, 2
utterances of 10,320 samples (32 frames) per pod and local step, held to
the real cell's limits.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python bench/tests/tiny_crosspod.py none sum:4 ...

runs the cell once per fault named (``none`` for none), on seed 3 or the
seed after the colon, and prints one JSON line each.
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src"), str(ROOT / "bench")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "hubert-xlarge.crosspod-int8"


def tiny_cell():
    from bench import harness
    config = json.loads((harness.BENCH_DIR / "configs" / "hubert-xlarge.json")
                        .read_text())
    config.update(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                  conv_channels=32, final_dim=32, num_clusters=128,
                  program_smoke=True)
    traffic = json.loads((harness.BENCH_DIR / "traffic"
                          / "crosspod-int8.json").read_text())
    traffic.update(pod_batch=2, samples=10_320)
    limits = json.loads((harness.BENCH_DIR / "limits" / f"{CELL}.json")
                        .read_text())
    return {"name": CELL, "chips": 4}, config, traffic, limits


def run_tiny(seed: int = 3, seconds: float = 1.0, fault=None):
    import run as bench_run
    return bench_run.run_cell(CELL, seed, seconds, False, allow_cpu=True,
                              fault=fault, cell_data=tiny_cell(),
                              t_start=time.perf_counter())


if __name__ == "__main__":
    for arg in sys.argv[1:]:
        name, _, seed = arg.partition(":")
        res = run_tiny(seed=int(seed or 3),
                       fault=None if name == "none" else name)
        print(json.dumps({"fault": name, "seed": int(seed or 3),
                          "correct": res["correct"],
                          "checks": res["checks"],
                          "window_compiles": res["window_compiles"],
                          "metrics": res["metrics"],
                          "detail": res["detail"]}), flush=True)
