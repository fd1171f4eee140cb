"""HuBERT X-Large (arXiv:2106.07447) as the program builds it: its size,
its frames, its masks and loss, and a small HuBERT against the plain
reference in ``bench/reference/hubert.py``."""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import audio as bench_audio  # noqa: E402
from bench.reference import hubert as ref  # noqa: E402
from repro.configs import get_config, smoke_config  # noqa: E402
from repro.data.synthetic import audio_stream, span_mask  # noqa: E402
from repro.models import build_model, param_count  # noqa: E402
from repro.models.hubert import frames_for, weight_norm  # noqa: E402

from _crosspod_child import reference_config  # noqa: E402

BENCH_CONFIG = json.loads(
    (ROOT / "bench" / "configs" / "hubert-xlarge.json").read_text())


def test_published_parameter_count():
    """964M (the paper's Table 1) from the program's abstract shapes, at
    48 layers; the reference's layout counts the same."""
    n = param_count(get_config("hubert-xlarge"))
    assert abs(n / 964e6 - 1) <= 0.005
    assert n == ref.param_count(BENCH_CONFIG) == BENCH_CONFIG["params"]


@pytest.mark.parametrize("samples, frames", [(160_000, 499), (250_000, 781)])
def test_frames(samples, frames):
    """20 ms frames: 400 samples of receptive field, a hop of 320."""
    assert frames_for(get_config("hubert-xlarge"), samples) == frames
    assert ref.frames(BENCH_CONFIG, samples) == frames


def test_span_masks_come_from_the_seed():
    def draw(seed):
        return span_mask(np.random.default_rng(seed), 8, 499, 0.08, 10)

    a = draw(5)
    np.testing.assert_array_equal(a, draw(5))
    assert not np.array_equal(a, draw(6))
    for row in a:  # 39 or 40 spans of 10 frames, possibly overlapping
        edges = np.flatnonzero(np.diff(np.r_[0, row.astype(int), 0]))
        runs = edges[1::2] - edges[::2]
        assert runs.min() >= 10 and 10 <= row.sum() <= 400


def test_span_masks_follow_the_benchmarks_rule():
    """The program's masks and the benchmark's, drawn independently by
    the configured rule, mask the same share of frames."""
    prog = span_mask(np.random.default_rng(1), 400, 499, 0.08, 10)
    rng = np.random.default_rng(2)
    bench = np.stack([bench_audio.span_mask(rng, 499, 0.08, 10)
                      for _ in range(400)])
    assert abs(prog.mean() - bench.mean()) <= 0.01
    assert abs(prog.sum(1).std() - bench.sum(1).std()) <= 3


def _smoke(dtype="bfloat16"):
    cfg = dataclasses.replace(smoke_config("hubert-xlarge"), dtype=dtype,
                              param_dtype=dtype)
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    batch = next(audio_stream(cfg, 3, 2, 10_320))  # 32 frames
    return cfg, model, params, {k: jnp.asarray(v) for k, v in batch.items()}


def test_loss_reads_only_the_masked_frames():
    cfg, model, params, batch = _smoke()
    loss = jax.jit(lambda b: model.loss(params, b)[0])
    base = float(loss(batch))
    mask = np.asarray(batch["mask"])
    i, j = np.argwhere(~mask)[0]
    changed = batch["targets"].at[i, j].add(1) % cfg.vocab_size
    assert float(loss(dict(batch, targets=changed))) == base
    i, j = np.argwhere(mask)[0]
    changed = batch["targets"].at[i, j].add(1) % cfg.vocab_size
    assert float(loss(dict(batch, targets=changed))) != base


def test_init_follows_the_configured_rule():
    """The program's initial weights and the reference's own, drawn from
    different keys by the configuration's ``assumed.init``: the same
    leaves are zero, and each drawn leaf has the same mean and spread to
    within five standard errors of its size."""
    cfg, model, params, _ = _smoke("float32")
    theirs = ref.init(reference_config(cfg), jax.random.key(7))
    for (path, a), b in zip(jax.tree.flatten_with_path(params)[0],
                            jax.tree.leaves(theirs)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape, path
        if b.std() == 0:
            assert a.std() == 0 and a.mean() == b.mean(), path
            continue
        err = 5 / np.sqrt(a.size)
        assert abs(a.mean() - b.mean()) <= err * np.sqrt(2) * b.std(), path
        assert abs(a.std() / b.std() - 1) <= err, path


def test_weight_norm_identity():
    """Each kernel position's norm is its gain, whatever the direction's
    scale; the positional conv sees only the direction."""
    cfg, model, params, batch = _smoke("float32")
    pc = params["pos_conv"]
    w = weight_norm(pc["v"], pc["g"])
    np.testing.assert_allclose(jnp.sqrt(jnp.sum(w * w, axis=(1, 2))),
                               pc["g"], rtol=1e-5)
    x = jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model))
    scaled = dict(pc, v=pc["v"] * 3.5)
    np.testing.assert_allclose(model.pos_conv(scaled, x),
                               model.pos_conv(pc, x), rtol=1e-5, atol=1e-6)


def test_small_hubert_matches_the_reference():
    """Loss within 1e-5 relative; each gradient leaf within 1e-4 of the
    larger of its own norm and the median leaf's (a key bias has a
    gradient of nought, which both sides compute as round-off)."""
    cfg, model, params, batch = _smoke("float32")
    rc = reference_config(cfg)
    assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(
        tuple, ref.param_shapes(rc), is_leaf=lambda x: isinstance(x, tuple))
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.jit(jax.value_and_grad(
            model.loss, has_aux=True))(params, batch)
        ref_loss, ref_g = jax.jit(lambda p, b: ref.loss_and_grad(rc, p, b))(
            params, batch)
    assert abs(float(loss) / float(ref_loss) - 1) <= 1e-5
    gaps = [(np.linalg.norm(a - b), np.linalg.norm(b))
            for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref_g))]
    med = np.median([n for _, n in gaps])
    assert max(d / max(n, med) for d, n in gaps) <= 1e-4


def test_decoder_blocks_keep_their_defaults():
    """RMSNorm without bias, RoPE, bias-free projections: the options
    HuBERT turns on are off for the decoder zoo."""
    cfg = smoke_config("qwen3-8b")
    assert (cfg.norm, cfg.proj_bias, cfg.rope) == ("rms", False, True)
    params, _ = build_model(cfg).init(jax.random.key(0))
    block = params["seg0"]["b0_self"]
    assert set(block) == {"ln1", "ln2", "attn", "mlp"}
    assert not {"bq", "bk", "bv", "bo"} & set(block["attn"])
    assert not {"b_up", "b_down"} & set(block["mlp"])
