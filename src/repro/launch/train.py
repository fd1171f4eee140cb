"""Real training driver (CPU-runnable with reduced configs; the same code
lowers onto the production meshes).

Example:
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
        --steps 30 --ckpt-dir /tmp/ckpt
Restart behaviour: if --ckpt-dir has a checkpoint, training resumes from it
(fault-tolerance path: kill the process mid-run and rerun the command).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import ARCH_ORDER, get_config, smoke_config
from repro.configs.base import SMOKE_MESH, ShapeConfig, TrainConfig
from repro.data import audio_stream, lm_batch_iterator
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_smoke_mesh
from repro.launch.step_builders import make_train_step
from repro.models.layers import abstract_init
from repro.optim.optimizers import adamw_init


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b", choices=ARCH_ORDER)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=None,
                    help="tokens per sequence (default 64); for an audio "
                         "model, waveform samples (default 10,320: 32 "
                         "frames)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    audio = cfg.family == "audio"
    if args.seq is None:
        args.seq = 10_320 if audio else 64
    shape = ShapeConfig(name="cli", seq_len=args.seq,
                        global_batch=args.batch, kind="train")
    train_cfg = TrainConfig(learning_rate=args.lr, warmup_steps=5,
                            total_steps=args.steps)
    mesh = make_smoke_mesh()
    bundle = make_train_step(cfg, shape, mesh, SMOKE_MESH, train_cfg)
    model = bundle.model

    rng = jax.random.key(0)
    params, _ = model.init(rng)
    opt_state = adamw_init(params, train_cfg)
    start_step = 0
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if ckpt is not None and ckpt.latest_step() is not None:
        (params, opt_state), start_step, meta = ckpt.restore(
            (params, opt_state))
        print(f"[train] resumed from step {start_step}")

    step_fn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                      out_shardings=bundle.out_shardings)
    data = (audio_stream(cfg, 0, args.batch, args.seq) if audio else
            lm_batch_iterator(0, args.batch, args.seq, cfg.vocab_size))
    losses = []
    t0 = time.time()
    with mesh:
        for step in range(start_step, args.steps):
            np_batch = next(data)
            batch = {k: jnp.asarray(v) for k, v in np_batch.items()}
            if cfg.family == "vlm":
                batch["image_embeds"] = jnp.zeros(
                    (args.batch, cfg.num_image_tokens, cfg.d_model),
                    jnp.bfloat16)
            params, opt_state, metrics = step_fn(params, opt_state, batch,
                                                 jnp.int32(step))
            losses.append(float(metrics["loss"]))
            if step % 5 == 0 or step == args.steps - 1:
                print(f"[train] step {step} loss={losses[-1]:.4f} "
                      f"gnorm={float(metrics['gnorm']):.3f}")
            if ckpt is not None and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, (params, opt_state))
    dt = time.time() - t0
    print(f"[train] {args.steps - start_step} steps in {dt:.1f}s; "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    assert np.isfinite(losses[-1])
    return 0 if losses[-1] < losses[0] else 1


if __name__ == "__main__":
    raise SystemExit(main())
