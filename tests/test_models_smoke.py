"""Per-arch reduced-config smoke tests: one forward/train step on CPU,
asserting output shapes + finite values; decode parity with full forward
for recurrent models."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_ORDER, get_config, smoke_config
from repro.configs.shapes import SHAPES, applicability
from repro.data import audio_stream
from repro.models import build_model, param_count


pytestmark = pytest.mark.slow  # minutes-long; PR CI runs -m 'not slow'


def _batch(cfg, rng, b=2, s=16):
    """Token batches; waveforms of ``s`` frames for an audio model."""
    if cfg.family == "audio":
        samples = 320 * (s - 1) + 400  # the CNN's hop and receptive field
        return next(audio_stream(cfg, 0, b, samples))
    batch = {"tokens": jax.random.randint(rng, (b, s), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["image_embeds"] = jax.random.normal(
            rng, (b, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
    batch["targets"] = jax.random.randint(jax.random.fold_in(rng, 7),
                                          (b, s), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", ARCH_ORDER)
def test_smoke_forward_and_grad(arch):
    cfg = smoke_config(arch)
    model = build_model(cfg)
    rng = jax.random.key(0)
    params, axes = model.init(rng)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: x is None or (isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x)))
    b, s = 2, 16
    batch = _batch(cfg, rng, b, s)
    logits, aux = jax.jit(model.forward)(params, batch)
    assert logits.shape == (b, s, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, dtype=np.float32)))
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert np.isfinite(float(loss))
    grads = jax.jit(jax.grad(lambda p: model.loss(p, batch)[0]))(params)
    gnorm = np.sqrt(sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
                        for g in jax.tree.leaves(grads)))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", [a for a in ARCH_ORDER
                                  if get_config(a).causal])
def test_smoke_decode_step(arch):
    cfg = smoke_config(arch)
    model = build_model(cfg)
    rng = jax.random.key(1)
    params, _ = model.init(rng)
    b = 2
    cache = model.init_cache(b, 32)
    batch = {"tokens": jnp.zeros((b, 1), jnp.int32), "pos": jnp.int32(0)}
    logits, new_cache = jax.jit(model.decode_step)(params, cache, batch)
    assert logits.shape[0] == b and logits.shape[-1] == cfg.vocab_size
    assert np.all(np.isfinite(np.asarray(logits, dtype=np.float32)))
    assert jax.tree.structure(cache) == jax.tree.structure(new_cache)


@pytest.mark.parametrize("arch", ["xlstm-1.3b", "zamba2-1.2b"])
def test_recurrent_decode_matches_parallel_forward(arch):
    """Chunkwise-parallel training form == recurrent decode form."""
    cfg = smoke_config(arch)
    model = build_model(cfg)
    rng = jax.random.key(2)
    params, _ = model.init(rng)
    b, s = 1, 8
    tokens = jax.random.randint(rng, (b, s), 0, cfg.vocab_size)
    full_logits, _ = jax.jit(model.forward)(params, {"tokens": tokens})
    cache = model.init_cache(b, s)
    step = jax.jit(model.decode_step)
    outs = []
    for pos in range(s):
        logits, cache = step(params, cache,
                             {"tokens": tokens[:, pos:pos + 1],
                              "pos": jnp.int32(pos)})
        outs.append(logits.reshape(b, -1))
    dec = np.stack([np.asarray(o, dtype=np.float32) for o in outs], axis=1)
    ref = np.asarray(full_logits, dtype=np.float32)
    np.testing.assert_allclose(dec, ref, rtol=0.15, atol=0.15)  # bf16 noise


def test_causal_attention_is_causal():
    cfg = smoke_config("qwen3-8b")
    model = build_model(cfg)
    rng = jax.random.key(3)
    params, _ = model.init(rng)
    t1 = jax.random.randint(rng, (1, 16), 0, cfg.vocab_size)
    t2 = t1.at[:, -1].set((t1[:, -1] + 5) % cfg.vocab_size)
    l1, _ = jax.jit(model.forward)(params, {"tokens": t1})
    l2, _ = jax.jit(model.forward)(params, {"tokens": t2})
    # changing the last token must not change logits at earlier positions
    np.testing.assert_allclose(np.asarray(l1[:, :-1], dtype=np.float32),
                               np.asarray(l2[:, :-1], dtype=np.float32),
                               rtol=1e-2, atol=1e-2)


def test_block_causal_matches_full_mask():
    import dataclasses
    cfg = smoke_config("qwen3-8b")
    m1 = build_model(dataclasses.replace(cfg, block_causal=True))
    m2 = build_model(dataclasses.replace(cfg, block_causal=False))
    rng = jax.random.key(4)
    params, _ = m1.init(rng)
    tokens = jax.random.randint(rng, (2, 64), 0, cfg.vocab_size)
    l1, _ = jax.jit(m1.forward)(params, {"tokens": tokens})
    l2, _ = jax.jit(m2.forward)(params, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(l1, np.float32),
                               np.asarray(l2, np.float32), rtol=2e-2,
                               atol=2e-2)


def test_full_config_param_counts():
    """Analytic sanity for the full (assigned) configs via eval_shape."""
    expect = {  # billions, loose bands around the advertised sizes
        "qwen3-8b": (7, 10), "deepseek-67b": (60, 72),
        "granite-3-8b": (7, 10), "stablelm-12b": (11, 13.5),
        "llama4-maverick-400b-a17b": (380, 420),
        "granite-moe-1b-a400m": (0.8, 1.6), "hubert-xlarge": (0.95, 0.98),
        "llama-3.2-vision-11b": (9, 12),
        "xlstm-1.3b": (1.0, 2.1), "zamba2-1.2b": (0.9, 1.8),
    }
    for arch, (lo, hi) in expect.items():
        n = param_count(get_config(arch)) / 1e9
        assert lo <= n <= hi, f"{arch}: {n:.2f}B not in [{lo},{hi}]"


def test_applicability_matrix_counts():
    runnable = skipped = 0
    for arch in ARCH_ORDER:
        for s in SHAPES.values():
            ok, reason = applicability(get_config(arch), s)
            runnable += ok
            skipped += not ok
            if not ok:
                assert reason
    assert runnable + skipped == 40
    assert skipped == 9  # documented in DESIGN.md
