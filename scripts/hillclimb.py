"""§Perf hillclimbing: the three selected cells, hypothesis -> change ->
re-lower -> validate. Every variant is persisted under artifacts/hillclimb/.

Cells (selection rationale in EXPERIMENTS.md §Perf):
  A. granite-moe-1b-a400m x train_4k  — worst roofline fraction (1.5%)
  B. deepseek-67b        x train_4k  — most collective-bound (72s ICI term)
  C. qwen3-8b x train_4k FL round @2x16x16 — the paper's technique
     (cross-silo sync at pod scale): f32 vs int8 delta exchange, local-K.

Usage: PYTHONPATH=src python scripts/hillclimb.py [A|B|C|all]
"""
import dataclasses
import json
import os
import sys

import jax

from repro.configs.base import (MULTI_POD_MESH, SINGLE_POD_MESH, MeshConfig)
from repro.launch.dryrun import run_cell

OUT = "artifacts/hillclimb"


def report(rec, label):
    if rec["status"] != "ok":
        print(f"  {label}: {rec['status']} {rec.get('error','')[:200]}")
        return
    rl = rec["roofline"]
    print(f"  {label:34s} compute={rl['t_compute']*1e3:9.1f}ms "
          f"memory={rl['t_memory']*1e3:7.1f}ms "
          f"ici={rl['t_collective']*1e3:9.1f}ms "
          f"dcn={rl['t_dcn']*1e3:8.1f}ms -> {rl['dominant']}-bound "
          f"frac={rl['roofline_fraction']*100:5.2f}% "
          f"useful={rl['useful_flops_ratio']*100:5.1f}%")


def mesh_cfg(shape, axes=("data", "model"), **kw):
    return MeshConfig(shape=shape, axis_names=axes, **kw)


def cell_A():
    print("== Cell A: granite-moe-1b-a400m x train_4k (worst fraction) ==")
    arch, shape = "granite-moe-1b-a400m", "train_4k"
    rec = run_cell(arch, shape, multi_pod=False, out_dir=OUT,
                   tag_suffix="__base", verbose=False)
    report(rec, "baseline 16x16 TP16")
    # H1: TP=16 on d_ff=512 experts is pure overhead for a 1.3B model;
    # 256-way FSDP (model axis width 1) removes activation all-reduces
    # and EP resharding entirely. Predict collective 3.5s -> ~0.2s.
    rec = run_cell(arch, shape, multi_pod=False, out_dir=OUT,
                   mesh_cfg=mesh_cfg((256, 1)), mesh_label="pod256x1",
                   tag_suffix="__fsdp256", train_kw=dict(microbatches=1),
                   verbose=False)
    report(rec, "H1 remap 256x1 pure FSDP")
    # H2: intermediate 64x4 (keeps some TP for activation memory headroom)
    rec = run_cell(arch, shape, multi_pod=False, out_dir=OUT,
                   mesh_cfg=mesh_cfg((64, 4)), mesh_label="pod64x4",
                   tag_suffix="__fsdp64tp4", train_kw=dict(microbatches=1),
                   verbose=False)
    report(rec, "H2 remap 64x4")
    # H3: on the best mesh, bigger dispatch groups cut router/dispatch
    # matmul flops per token (group 2048 -> 512: dispatch cost ~ g*k*cf*d)
    rec = run_cell(arch, shape, multi_pod=False, out_dir=OUT,
                   mesh_cfg=mesh_cfg((256, 1)), mesh_label="pod256x1",
                   tag_suffix="__fsdp256_group512",
                   overrides=dict(moe_group_size=512),
                   train_kw=dict(microbatches=1), verbose=False)
    report(rec, "H3 256x1 + dispatch group 512")
    # H4: capacity factor 1.25 -> 1.0 (drop tokens instead of padding)
    rec = run_cell(arch, shape, multi_pod=False, out_dir=OUT,
                   mesh_cfg=mesh_cfg((256, 1)), mesh_label="pod256x1",
                   tag_suffix="__fsdp256_group512_cap1",
                   overrides=dict(moe_group_size=512, capacity_factor=1.0),
                   train_kw=dict(microbatches=1), verbose=False)
    report(rec, "H4 + capacity 1.0")


def cell_B():
    print("== Cell B: deepseek-67b x train_4k (most collective-bound) ==")
    arch, shape = "deepseek-67b", "train_4k"
    rec = run_cell(arch, shape, multi_pod=False, out_dir=OUT,
                   tag_suffix="__base", verbose=False)
    report(rec, "baseline 16x16 TP16 mb8")
    # H1: TP16 activation all-reduces dominate (95L x ~4 AR x act bytes).
    # Remap to FSDP64 x TP4: AR group 16->4 shrinks ring factor and the
    # per-device activation slab 4x. Predict ici 72s -> ~15-20s.
    rec = run_cell(arch, shape, multi_pod=False, out_dir=OUT,
                   mesh_cfg=mesh_cfg((64, 4)), mesh_label="pod64x4",
                   tag_suffix="__fsdp64tp4", train_kw=dict(microbatches=4),
                   verbose=False)
    report(rec, "H1 remap 64x4 mb4")
    # H2: push further: FSDP128 x TP2
    rec = run_cell(arch, shape, multi_pod=False, out_dir=OUT,
                   mesh_cfg=mesh_cfg((128, 2)), mesh_label="pod128x2",
                   tag_suffix="__fsdp128tp2", train_kw=dict(microbatches=2),
                   verbose=False)
    report(rec, "H2 remap 128x2 mb2")
    # H3: pure FSDP 256 (param all-gathers replace activation ARs; for 67B
    # params the AG traffic ~3x param bytes may exceed H2's activation cost)
    rec = run_cell(arch, shape, multi_pod=False, out_dir=OUT,
                   mesh_cfg=mesh_cfg((256, 1)), mesh_label="pod256x1",
                   tag_suffix="__fsdp256", train_kw=dict(microbatches=2),
                   verbose=False)
    report(rec, "H3 remap 256x1 pure FSDP mb2")
    # H3 REFUTED as run: mb2 makes the per-microbatch batch (128) indivisible
    # by 256 -> the batch spec falls back to replication and every chip
    # recomputes the full batch. H3' fixes the microbatching.
    rec = run_cell(arch, shape, multi_pod=False, out_dir=OUT,
                   mesh_cfg=mesh_cfg((256, 1)), mesh_label="pod256x1",
                   tag_suffix="__fsdp256_mb1", train_kw=dict(microbatches=1),
                   verbose=False)
    report(rec, "H3' remap 256x1 pure FSDP mb1")
    # H4: 128x2 with mb1 (fewer passes -> fewer param re-gathers)
    rec = run_cell(arch, shape, multi_pod=False, out_dir=OUT,
                   mesh_cfg=mesh_cfg((128, 2)), mesh_label="pod128x2",
                   tag_suffix="__fsdp128tp2_mb1", train_kw=dict(microbatches=1),
                   verbose=False)
    report(rec, "H4 remap 128x2 mb1")


def cell_C():
    print("== Cell C: qwen3-8b FL round @2x16x16 (paper technique) ==")
    arch, shape = "qwen3-8b", "train_4k"
    # baseline: fully synchronous two-pod training (per-step DCN all-reduce)
    rec = run_cell(arch, shape, multi_pod=True, out_dir=OUT,
                   tag_suffix="__sync_base", verbose=False)
    report(rec, "baseline sync 2x16x16")
    # H1: the paper's round structure at pod scale — K=2 local steps then
    # f32 delta exchange (DCN bytes /K, paid as one fused sync)
    rec = run_cell(arch, shape, multi_pod=True, fl=True, out_dir=OUT,
                   fl_compress="none", tag_suffix="__fl_f32", verbose=False)
    report(rec, "H1 FL round K=2, f32 deltas")
    # H2: + int8 quantised deltas (QSGD kernel semantics, int8 all-gather
    # + local reduce): DCN bytes /4 vs f32
    rec = run_cell(arch, shape, multi_pod=True, fl=True, out_dir=OUT,
                   fl_compress="int8", tag_suffix="__fl_int8", verbose=False)
    report(rec, "H2 FL round K=2, int8 deltas")
    # H3: amortise further: K=8 local steps per exchange
    rec = run_cell(arch, shape, multi_pod=True, fl=True, out_dir=OUT,
                   fl_compress="int8", fl_local_steps=8,
                   tag_suffix="__fl_int8_k8", verbose=False)
    report(rec, "H3 FL round K=8, int8 deltas")


if __name__ == "__main__":
    # 512 placeholder host devices for the production meshes; read by JAX
    # when its backend starts, so set before the first cell and never at
    # import
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("A", "all"):
        cell_A()
        jax.clear_caches()
    if which in ("B", "all"):
        cell_B()
        jax.clear_caches()
    if which in ("C", "all"):
        cell_C()
