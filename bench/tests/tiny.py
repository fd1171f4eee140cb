"""Tiny versions of the cells for CPU tests: the program's reduced ResNet
(2 blocks per stage, 16 px, 8 classes) on a few silos of 64 examples, 4
local steps a round, held to the real cells' limits."""
import json
import time

from bench import harness

CELLS = {"sync-grpc": "resnet56.sync-grpc",
         "fedbuff-qsgd-s3": "resnet56.fedbuff-qsgd-s3"}


def tiny_cell(mix: str, clients: int = None):
    config = {"name": "resnet-tiny", "model": "resnet", "tier": "small",
              "widths": [16, 32, 64], "blocks_per_stage": 2,
              "image_size": 16, "num_classes": 8, "dtype": "float32",
              "program_reduced": True}
    traffic = json.loads((harness.BENCH_DIR / "traffic" / f"{mix}.json")
                         .read_text())
    traffic["num_clients"] = clients or (3 if mix == "sync-grpc" else 5)
    traffic["examples_per_silo"] = 64
    traffic["local_steps"] = 4
    limits = json.loads((harness.BENCH_DIR / "limits"
                         / f"{CELLS[mix]}.json").read_text())
    return {"name": CELLS[mix], "chips": 1}, config, traffic, limits


def run_tiny(mix: str, seed: int = 3, seconds: float = 1.0, fault=None):
    import run as bench_run
    return bench_run.run_cell(CELLS[mix], seed, seconds, False,
                              allow_cpu=True, fault=fault,
                              cell_data=tiny_cell(mix),
                              t_start=time.perf_counter())
