"""HuBERT (Hsu et al., arXiv:2106.07447): masked prediction of cluster ids
from the raw waveform.

    features = CNN(waveform)           conv -> LayerNorm over channels -> GELU,
                                       once per (kernel, stride) pair
    x = Linear(LayerNorm(features))    to the encoder's width
    x[masked frames] = mask_emb
    x = x + GELU(pos_conv(x))          wav2vec 2.0's grouped conv, weight-
                                       normed over its kernel positions
    x = final LayerNorm(blocks(x))     pre-LN blocks on ``TransformerLM``'s
                                       scanned stack (LayerNorm with bias,
                                       biased projections, GELU MLP, no RoPE)
    logits = cos(W x + b, codes) / logit_temp          one code per cluster
    loss = cross-entropy over the masked frames
           + feature_pen_weight * mean(features ** 2)

Dropout and layer drop are off. ``ShapeConfig.seq_len`` counts waveform
samples; ``frames_for`` gives the frames they make.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import layers as L
from repro.models.transformer import TransformerLM, _remat
from repro.sharding.rules import Sharder

_CONV_DIMS = ("NWC", "WIO", "NWC")


def frames_for(cfg: ModelConfig, samples: int) -> int:
    """Frames the waveform CNN makes of ``samples`` (valid convolutions)."""
    n = samples
    for k, s in zip(cfg.conv_kernels, cfg.conv_strides):
        n = (n - k) // s + 1
    return n


def masked_mean(values, mask):
    """Mean of ``values`` over the frames where ``mask`` is set."""
    m = mask.astype(jnp.float32)
    return jnp.sum(values * m) / jnp.maximum(jnp.sum(m), 1.0)


def _conv(x, w, stride: int):
    """Valid conv of x (b, n, c_in) with w (k, c_in, c_out), as one matmul
    over the k strided windows laid side by side. Vmapped over stacked
    pods, a ``conv_general_dilated`` whose kernel differs per pod folds the
    pods into its channels, and the partitioner may then all-gather every
    pod's activations; a batched matmul stays on its pod."""
    k, c_in, c_out = w.shape
    t = (x.shape[1] - k) // stride + 1
    win = jnp.concatenate([x[:, j:j + stride * (t - 1) + 1:stride]
                           for j in range(k)], axis=-1)
    return jnp.einsum("btc,co->bto", win, w.reshape(k * c_in, c_out))


def _conv_block(p, x, *, stride: int, eps: float):
    y = _conv(x, p["w"].astype(x.dtype), stride)
    y = L.layer_norm(y + p["b"].astype(y.dtype), p["ln"], p["ln_bias"], eps)
    return jax.nn.gelu(y)


def weight_norm(v, g):
    """The kernel ``g * v / |v|``, with ``|v|`` taken over the input and
    output channels at each of the kernel's positions; float32."""
    v = v.astype(jnp.float32)
    return (g.astype(jnp.float32)[:, None, None] * v
            / jnp.sqrt(jnp.sum(v * v, axis=(1, 2), keepdims=True)))


class HuBERT:
    """Waveform in, cosine logits over the clusters out."""

    def __init__(self, cfg: ModelConfig, sharder: Optional[Sharder] = None):
        self.cfg = cfg
        self.sharder = sharder or Sharder()
        self.encoder = TransformerLM(cfg, self.sharder)

    # ------------------------------------------------------------------
    def init(self, rng):
        """Returns (params, axes)."""
        cfg = self.cfg
        dt = jnp.dtype(cfg.param_dtype)
        f32 = jnp.float32
        d, c, k_pos = cfg.d_model, cfg.conv_channels, cfg.pos_conv_kernel
        ks = jax.random.split(rng, 7)
        b = L.Builder()

        feature, c_in = L.Builder(), 1
        conv_keys = jax.random.split(ks[0], len(cfg.conv_kernels))
        for i, (k, key) in enumerate(zip(cfg.conv_kernels, conv_keys)):
            conv = L.Builder()
            std = math.sqrt(2.0 / (k * c_in))  # fan-in normal
            conv.add("w", ((jax.random.normal(key, (k, c_in, c), f32)
                            * std).astype(dt), (None, None, None)))
            conv.add("b", L.zeros_init((c,), ("norm",), dt))
            conv.add("ln", L.zeros_init((c,), ("norm",), dt))
            conv.add("ln_bias", L.zeros_init((c,), ("norm",), dt))
            feature.sub(f"conv{i}", conv)
            c_in = c
        b.sub("feature", feature)

        proj = L.Builder()
        proj.add("ln", L.zeros_init((c,), ("norm",), dt))
        proj.add("ln_bias", L.zeros_init((c,), ("norm",), dt))
        proj.add("w", L.dense_init(ks[1], (c, d), (None, "embed"), dt))
        proj.add("b", L.zeros_init((d,), ("norm",), dt))
        b.sub("proj", proj)
        b.add("mask_emb", (jax.random.uniform(ks[2], (d,), f32).astype(dt),
                           ("norm",)))

        pos = L.Builder()
        v = jax.random.normal(ks[3], (k_pos, d // cfg.pos_conv_groups, d),
                              f32) * math.sqrt(4.0 / (k_pos * d))
        pos.add("v", (v.astype(dt), (None, None, "embed")))
        pos.add("g", (jnp.sqrt(jnp.sum(v * v, axis=(1, 2))).astype(dt),
                      ("norm",)))
        pos.add("b", L.zeros_init((d,), ("norm",), dt))
        b.sub("pos_conv", pos)

        seg_keys = jax.random.split(ks[4], len(self.encoder.segments))
        seg_p, seg_a = self.encoder.init_segments(seg_keys)
        for name in seg_p:
            b.sub(name, (seg_p[name], seg_a[name]))

        final = L.Builder()
        final.add("scale", L.zeros_init((d,), ("norm",), dt))
        final.add("bias", L.zeros_init((d,), ("norm",), dt))
        b.sub("final_norm", final)

        head = L.Builder()
        head.add("w", L.dense_init(ks[5], (d, cfg.final_dim),
                                   ("embed", None), dt))
        head.add("b", L.zeros_init((cfg.final_dim,), ("norm",), dt))
        head.add("codes", (jax.random.uniform(
            ks[6], (cfg.vocab_size, cfg.final_dim), f32).astype(dt),
            ("vocab", None)))
        b.sub("head", head)
        return b.build()

    # ------------------------------------------------------------------
    def features(self, params, waveform):
        """(b, samples) -> (b, frames, conv_channels), compute dtype."""
        cfg = self.cfg
        x = waveform.astype(jnp.dtype(cfg.dtype))[..., None]
        for i, s in enumerate(cfg.conv_strides):
            block = _remat(lambda p, y, _s=s: _conv_block(
                p, y, stride=_s, eps=cfg.norm_eps), cfg.remat)
            x = block(params["feature"][f"conv{i}"], x)
            x = self.sharder(x, ("batch", "seq", None))
        return x

    def pos_conv(self, p, x):
        """GELU of the grouped conv whose kernel is ``weight_norm(v, g)``;
        the output keeps the input's length (padding K//2 on the left)."""
        cfg = self.cfg
        k = cfg.pos_conv_kernel
        w = weight_norm(p["v"], p["g"])
        y = jax.lax.conv_general_dilated(
            x, w.astype(x.dtype), (1,), [(k // 2, (k - 1) // 2)],
            dimension_numbers=_CONV_DIMS,
            feature_group_count=cfg.pos_conv_groups)
        return jax.nn.gelu(y + p["b"].astype(y.dtype))

    def encode(self, params, batch):
        """-> (encoder output after the final LayerNorm, feature penalty)."""
        cfg = self.cfg
        feats = self.features(params, batch["waveform"])
        pen = jnp.mean(jnp.square(feats.astype(jnp.float32)))
        pp = params["proj"]
        x = L.layer_norm(feats, pp["ln"], pp["ln_bias"], cfg.norm_eps)
        x = (jnp.einsum("btc,cd->btd", x, pp["w"].astype(x.dtype))
             + pp["b"].astype(x.dtype))
        mask = batch.get("mask")
        if mask is not None:
            x = jnp.where(mask[..., None],
                          params["mask_emb"].astype(x.dtype), x)
        x = x + self.pos_conv(params["pos_conv"], x)
        x = self.sharder(x, ("batch", "seq", None))
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        x, _ = self.encoder._stack_apply(params, x, positions=positions)
        fn = params["final_norm"]
        return L.layer_norm(x, fn["scale"], fn["bias"], cfg.norm_eps), pen

    def forward(self, params, batch):
        """-> (logits (b, frames, clusters) in float32, feature penalty)."""
        cfg = self.cfg
        x, pen = self.encode(params, batch)
        h = params["head"]
        y = (jnp.einsum("btd,df->btf", x, h["w"].astype(x.dtype))
             + h["b"].astype(x.dtype)).astype(jnp.float32)
        codes = h["codes"].astype(jnp.float32)
        dots = jnp.einsum("btf,vf->btv", y, codes)
        norms = (jnp.linalg.norm(y, axis=-1)[..., None]
                 * jnp.linalg.norm(codes, axis=-1))
        logits = dots / jnp.maximum(norms, 1e-8) / cfg.logit_temp
        return self.sharder(logits, ("batch", "seq", "vocab")), pen

    def loss(self, params, batch):
        cfg = self.cfg
        logits, pen = self.forward(params, batch)
        lse = jax.nn.logsumexp(logits, axis=-1)
        hit = jnp.take_along_axis(logits, batch["targets"][..., None],
                                  axis=-1)[..., 0]
        ce = masked_mean(lse - hit, batch["mask"])
        return ce + cfg.feature_pen_weight * pen, {"ce": ce, "pen": pen}

    # ------------------------------------------------------------------
    def input_specs(self, shape: ShapeConfig):
        """Waveforms of ``shape.seq_len`` samples; for training, the span
        mask and the cluster id of each frame."""
        b, n = shape.global_batch, shape.seq_len
        t = frames_for(self.cfg, n)
        specs = {"waveform": jax.ShapeDtypeStruct((b, n), jnp.float32)}
        axes = {"waveform": ("batch", None)}
        if shape.kind == "train":
            specs["mask"] = jax.ShapeDtypeStruct((b, t), jnp.bool_)
            specs["targets"] = jax.ShapeDtypeStruct((b, t), jnp.int32)
            axes["mask"] = axes["targets"] = ("batch", "seq")
        return specs, axes
