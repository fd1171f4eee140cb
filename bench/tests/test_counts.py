"""Analytic parameters, FLOPs and kernel bytes against hand counts."""
import json

import pytest

from bench import bytes as kbytes
from bench import flops, harness
from bench.reference import vision


def _config(name):
    return json.loads((harness.BENCH_DIR / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name, params", [("resnet56", 868_123)])
def test_param_counts(name, params):
    cfg = _config(name)
    assert vision.param_count(cfg) == params == cfg["params"]


def test_resnet_flops_by_hand():
    cfg = {"model": "resnet", "widths": [4, 8], "blocks_per_stage": 1,
           "image_size": 4, "num_classes": 2}
    stem = 2 * 4 * 4 * 9 * 3 * 4
    stage0 = 2 * (2 * 4 * 4 * 9 * 4 * 4)
    stage1 = 2 * 2 * 2 * 9 * 4 * 8 + 2 * 2 * 2 * 9 * 8 * 8 + 2 * 2 * 2 * 4 * 8
    head = 2 * 8 * 2
    assert flops.resnet_forward(cfg) == stem + stage0 + stage1 + head
    assert flops.train_step(cfg, 16) == 3 * 16 * (stem + stage0 + stage1
                                                  + head)


def test_resnet56_flops_match_the_published_count():
    # ResNet-56 on 32 px: about 125.7M multiply-adds (10 classes)
    cfg = dict(_config("resnet56"), num_classes=10)
    assert flops.resnet_forward(cfg) / 2 == pytest.approx(125.7e6, rel=0.01)


def test_kernel_bytes_by_hand():
    t = 848 * 1024  # 868,123 padded to whole 1024-lane tiles
    assert kbytes.fedavg_reduce(7, 868_123) == (7 * t + 7 + t) * 4
    assert kbytes.fedavg_accumulate(868_123) == 3 * t * 4
    rows = 424 * 8  # padded to whole (8, 256) tiles
    assert kbytes.quantize_rows([868_123], 256) == rows * 256 * 5 + rows * 4
    assert kbytes.dequantize_rows(rows, 256) == rows * 256 * 5 + rows * 4
