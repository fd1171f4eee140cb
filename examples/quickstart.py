"""Quickstart: build an assigned arch (reduced), train it on synthetic LM
data until loss drops, then decode a few tokens.

    PYTHONPATH=src python examples/quickstart.py [arch]
"""
import sys

import jax
import jax.numpy as jnp

from repro.configs import ARCH_ORDER, smoke_config
from repro.configs.base import SMOKE_MESH, ShapeConfig, TrainConfig
from repro.data import audio_stream, lm_batch_iterator
from repro.launch.mesh import make_smoke_mesh
from repro.launch.step_builders import make_train_step
from repro.optim.optimizers import adamw_init


def main():
    arch = sys.argv[1] if len(sys.argv) > 1 else "qwen3-8b"
    assert arch in ARCH_ORDER, f"pick one of {ARCH_ORDER}"
    cfg = smoke_config(arch)
    print(f"[quickstart] arch={arch} (reduced: {cfg.num_layers} layers, "
          f"d={cfg.d_model})")

    seq = 10_320 if cfg.family == "audio" else 64  # samples or tokens
    shape = ShapeConfig(name="qs", seq_len=seq, global_batch=8, kind="train")
    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=5, total_steps=40)
    mesh = make_smoke_mesh()
    bundle = make_train_step(cfg, shape, mesh, SMOKE_MESH, tcfg)
    model = bundle.model

    params, _ = model.init(jax.random.key(0))
    opt = adamw_init(params, tcfg)
    step_fn = jax.jit(bundle.fn)
    data = (audio_stream(cfg, 0, 8, seq) if cfg.family == "audio" else
            lm_batch_iterator(0, 8, seq, cfg.vocab_size))

    losses = []
    with mesh:
        for step in range(40):
            raw = next(data)
            batch = {k: jnp.asarray(v) for k, v in raw.items()}
            if cfg.family == "vlm":
                batch["image_embeds"] = jnp.zeros(
                    (8, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
            params, opt, m = step_fn(params, opt, batch, jnp.int32(step))
            losses.append(float(m["loss"]))
            if step % 10 == 0:
                print(f"  step {step:3d}  loss {losses[-1]:.3f}")
    print(f"[quickstart] loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"({'learned' if losses[-1] < losses[0] else 'no progress?!'})")

    if cfg.causal:
        cache = model.init_cache(2, 16)
        tok = jnp.zeros((2, 1), jnp.int32)
        out = []
        decode = jax.jit(model.decode_step)
        for pos in range(8):
            logits, cache = decode(params, cache,
                                   {"tokens": tok, "pos": jnp.int32(pos)})
            lg = logits[:, -1] if logits.ndim == 3 else logits
            tok = jnp.argmax(lg, -1, keepdims=True).astype(jnp.int32)
            out.append(int(tok[0, 0]))
        print(f"[quickstart] greedy decode: {out}")


if __name__ == "__main__":
    main()
