"""Dev-only quick check of every family's fwd/bwd/decode on tiny configs."""
import sys

import jax
import jax.numpy as jnp

from repro.configs import ARCH_ORDER, smoke_config
from repro.data import audio_stream
from repro.models import build_model


def check(name):
    cfg = smoke_config(name)
    model = build_model(cfg)
    rng = jax.random.key(0)
    params, axes = model.init(rng)
    n = sum(int(jnp.size(l)) for l in jax.tree.leaves(params))
    b, s = 2, 16
    if cfg.family == "audio":  # 20 frames of waveform
        batch = next(audio_stream(cfg, 0, b, 6_480))
    else:
        batch = {"tokens": jax.random.randint(rng, (b, s), 0,
                                              cfg.vocab_size),
                 "targets": jax.random.randint(rng, (b, s), 0,
                                               cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["image_embeds"] = jax.random.normal(
            rng, (b, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)

    loss, metrics = jax.jit(model.loss)(params, batch)
    assert jnp.isfinite(loss), (name, loss)
    grads = jax.jit(jax.grad(lambda p: model.loss(p, batch)[0]))(params)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))
    assert jnp.isfinite(gnorm), (name, "grad nan")

    out = [f"{name}: params={n:,} loss={float(loss):.3f} gnorm={float(gnorm):.3f}"]
    if cfg.causal:
        cache = model.init_cache(b, 32)
        db = {"tokens": batch.get("tokens", jnp.zeros((b, s), jnp.int32))[:, :1],
              "pos": jnp.int32(0)}
        logits, cache = jax.jit(model.decode_step)(params, cache, db)
        assert jnp.all(jnp.isfinite(logits.astype(jnp.float32))), (name, "decode nan")
        out.append("decode ok")
    print(" | ".join(out))


if __name__ == "__main__":
    names = sys.argv[1:] or ARCH_ORDER
    for nm in names:
        check(nm)
