"""Multi-pod dry-run: ``.lower().compile()`` every (arch x shape x mesh)
cell against the production meshes and record memory / cost / roofline.

Usage:
    python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    python -m repro.launch.dryrun --all                   # single-pod 16x16
    python -m repro.launch.dryrun --all --multi-pod       # 2x16x16
    python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --fl \
        --multi-pod                                       # cross-pod FL round

Artifacts: artifacts/dryrun/<mesh>/<arch>__<shape>[__fl].json
"""
import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

import jax

from repro.configs import ARCH_ORDER, get_config
from repro.configs.base import (MULTI_POD_MESH, SINGLE_POD_MESH, TrainConfig)
from repro.configs.shapes import SHAPES, SHAPE_ORDER, applicability
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.step_builders import bundle_for
from repro.roofline.analysis import analyze

# the chip the production meshes are made of (roofline peaks row)
TARGET_DEVICE_KIND = "TPU v5 lite"

# per-arch training knobs that make the big models fit 16 GB v5e HBM
TRAIN_OVERRIDES = {
    "deepseek-67b": dict(microbatches=16),
    "llama4-maverick-400b-a17b": dict(microbatches=16,
                                      moment_dtype="bfloat16"),
    "stablelm-12b": dict(microbatches=8),
    "qwen3-8b": dict(microbatches=8),
    "granite-3-8b": dict(microbatches=8),
    "llama-3.2-vision-11b": dict(microbatches=8),
    "hubert-xlarge": dict(microbatches=4),
    "granite-moe-1b-a400m": dict(microbatches=4),
    "xlstm-1.3b": dict(microbatches=4),
    "zamba2-1.2b": dict(microbatches=4),
}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, fl: bool = False,
             out_dir: str = "artifacts/dryrun", mesh=None, overrides=None,
             fl_compress: str = "", tag_suffix: str = "",
             mesh_cfg=None, mesh_label: str = "", train_kw=None,
             fl_local_steps: int = 2, verbose: bool = True):
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    ok, reason = applicability(cfg, shape)
    mesh_name = mesh_label or ("pod2x16x16" if multi_pod else "pod16x16")
    tag = f"{arch}__{shape_name}" + ("__fl" if fl else "") + tag_suffix
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "fl": fl,
              "fl_compress": fl_compress}
    if not ok:
        record.update(status="skipped", reason=reason)
        _persist(out_dir, mesh_name, tag, record, verbose)
        return record

    if mesh_cfg is None:
        mesh_cfg = MULTI_POD_MESH if multi_pod else SINGLE_POD_MESH
    if mesh is None:
        if tuple(mesh_cfg.shape) in ((16, 16), (2, 16, 16)):
            mesh = make_production_mesh(multi_pod=multi_pod)
        else:
            mesh = make_mesh(mesh_cfg)
    tkw = dict(TRAIN_OVERRIDES.get(arch, {}))
    if train_kw:
        tkw.update(train_kw)
    if fl and fl_compress:
        tkw["crosspod_compression"] = fl_compress
    train_cfg = TrainConfig(**tkw)
    kind = "fl_round" if fl else (
        "train" if shape.kind == "train" else shape.kind)
    t0 = time.time()
    try:
        kw = {"local_steps": fl_local_steps} if fl else {}
        bundle = bundle_for(kind, cfg, shape, mesh, mesh_cfg, train_cfg, **kw)
        with mesh:
            lowered = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                              out_shardings=bundle.out_shardings
                              ).lower(*bundle.in_specs)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        pod_size = 256 if multi_pod else 0
        rl = analyze(compiled, arch=arch, shape=shape, kind=kind,
                     mesh_name=mesh_name, chips=mesh.devices.size,
                     pod_size=pod_size, cfg=cfg,
                     device_kind=TARGET_DEVICE_KIND)
        if fl:
            # an FL round performs local_steps optimizer steps per call
            rl.model_flops *= fl_local_steps
        record.update(
            status="ok", kind=kind,
            compile_s=round(time.time() - t0, 1),
            memory_analysis={
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "generated_code_bytes": int(mem.generated_code_size_in_bytes),
            },
            xla_cost_analysis={k: float(v) for k, v in ca.items()
                               if k in ("flops", "bytes accessed",
                                        "transcendentals")},
            roofline=rl.to_dict(),
            train_overrides=tkw,
        )
        if verbose:
            print(f"[dryrun] {tag} @{mesh_name}: OK ({record['compile_s']}s)")
            print(f"  memory/device: args={mem.argument_size_in_bytes/2**30:.2f}GiB "
                  f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB")
            print(f"  roofline: compute={rl.t_compute*1e3:.2f}ms "
                  f"memory={rl.t_memory*1e3:.2f}ms "
                  f"collective={rl.t_collective*1e3:.2f}ms "
                  f"dcn={rl.t_dcn*1e3:.2f}ms -> {rl.dominant}-bound; "
                  f"useful-flops={rl.useful_flops_ratio:.2%} "
                  f"roofline-frac={rl.roofline_fraction:.2%}")
    except Exception as e:  # noqa: BLE001 — a failed cell is a bug report
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:],
                      compile_s=round(time.time() - t0, 1))
        if verbose:
            print(f"[dryrun] {tag} @{mesh_name}: FAILED {record['error']}")
    _persist(out_dir, mesh_name, tag, record, verbose)
    return record


def _persist(out_dir, mesh_name, tag, record, verbose):
    d = os.path.join(out_dir, mesh_name)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, f"{tag}.json"), "w") as f:
        json.dump(record, f, indent=1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_ORDER)
    ap.add_argument("--shape", choices=SHAPE_ORDER)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--fl", action="store_true",
                    help="lower the cross-pod FL round instead of train_step")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args(argv)

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
    cells = []
    if args.all:
        for arch in ARCH_ORDER:
            for shape in SHAPE_ORDER:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    results = []
    for arch, shape in cells:
        tag = f"{arch}__{shape}" + ("__fl" if args.fl else "")
        path = os.path.join(args.out, mesh_name, f"{tag}.json")
        if args.skip_existing and os.path.exists(path):
            with open(path) as f:
                rec = json.load(f)
            if rec.get("status") in ("ok", "skipped"):
                print(f"[dryrun] {tag}: cached ({rec['status']})")
                results.append(rec)
                continue
        results.append(run_cell(arch, shape, multi_pod=args.multi_pod,
                                fl=args.fl, out_dir=args.out, mesh=mesh))
        jax.clear_caches()
        import gc
        gc.collect()
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped (documented), "
          f"{n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    # the 2x16x16 production mesh needs 512 placeholder host devices; JAX
    # reads the flag when its backend first starts, so it is set here and
    # never by importing this module
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    sys.exit(main())
