"""Plain reference of HuBERT X-Large (Hsu et al., arXiv:2106.07447): the
forward pass, the masked loss and its gradients.

Straightforward JAX in float32: convolutions are XLA's, attention one
softmax over every frame, the layers a scan over their stacked weights. It
imports nothing of the program. It follows the paper's X-Large column
and wav2vec 2.0's positional convolution, with the departures that the
configuration file lists under ``assumed``:

* CNN layer: conv (with bias) -> LayerNorm over channels -> GELU;
* blocks are pre-LN: ``x + attn(LN(x))``, ``x + fc2(GELU(fc1(LN(x))))``,
  biased projections, no position encoding inside the blocks, and a
  LayerNorm after the last block;
* every LayerNorm's scale is stored as its offset from 1;
* GELU is the tanh approximation;
* logits are ``cos(W x + b, code_c) / logit_temp`` for every cluster c; the
  loss is their cross-entropy over the masked frames plus
  ``feature_pen_weight`` times the mean square of the CNN's output.

Parameters are a nested dict that names each tensor, laid out as
``param_shapes`` says; the benchmark checks the program's layout against
it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

BLOCK = "b0_self"  # the one kind of layer, stacked on a leading axis


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

def param_shapes(cfg) -> dict:
    d, c, f = cfg["d_model"], cfg["conv_channels"], cfg["d_ff"]
    n, k_pos = cfg["num_layers"], cfg["pos_conv_kernel"]
    feature, c_in = {}, 1
    for i, k in enumerate(cfg["conv_kernels"]):
        feature[f"conv{i}"] = {"w": (k, c_in, c), "b": (c,), "ln": (c,),
                               "ln_bias": (c,)}
        c_in = c
    block = {"ln1": (d,), "ln1_bias": (d,), "ln2": (d,), "ln2_bias": (d,),
             "attn": {"wq": (d, d), "wk": (d, d), "wv": (d, d),
                      "wo": (d, d), "bq": (d,), "bk": (d,), "bv": (d,),
                      "bo": (d,)},
             "mlp": {"w_up": (d, f), "b_up": (f,), "w_down": (f, d),
                     "b_down": (d,)}}
    return {
        "feature": feature,
        "proj": {"ln": (c,), "ln_bias": (c,), "w": (c, d), "b": (d,)},
        "mask_emb": (d,),
        "pos_conv": {"v": (k_pos, d // cfg["pos_conv_groups"], d),
                     "g": (k_pos,), "b": (d,)},
        "seg0": {BLOCK: jax.tree.map(lambda s: (n,) + s, block,
                                     is_leaf=lambda x: isinstance(x, tuple))},
        "final_norm": {"scale": (d,), "bias": (d,)},
        "head": {"w": (d, cfg["final_dim"]), "b": (cfg["final_dim"],),
                 "codes": (cfg["num_clusters"], cfg["final_dim"])},
    }


def param_count(cfg) -> int:
    return sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def frames(cfg, samples: int) -> int:
    for k, s in zip(cfg["conv_kernels"], cfg["conv_strides"]):
        samples = (samples - k) // s + 1
    return samples


def init(cfg, key) -> dict:
    """Random weights from ``key``, float32, by the configuration's
    ``assumed.init``: the CNN's kernels normal with std sqrt(2 / fan-in);
    the mask embedding and the cluster codes uniform in [0, 1); the
    positional conv's direction normal with std sqrt(4 / (kernel x width))
    and its gains the direction's norms; every other matrix normal with
    std 1 / sqrt(fan-in) cut at two std; biases and LayerNorm offsets
    zero."""
    shapes = param_shapes(cfg)
    paths, treedef = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(paths))
    out = {}
    for k, (path, shape) in zip(keys, paths):
        names = [p.key for p in path]
        if names[0] == "feature" and names[-1] == "w":
            w = jax.random.normal(k, shape) * math.sqrt(
                2.0 / (shape[0] * shape[1]))
        elif names[-1] in ("mask_emb", "codes"):
            w = jax.random.uniform(k, shape)
        elif names[-2:] == ["pos_conv", "v"]:
            w = jax.random.normal(k, shape) * math.sqrt(
                4.0 / (cfg["pos_conv_kernel"] * cfg["d_model"]))
        elif names[-2:] == ["pos_conv", "g"]:
            continue  # from the direction, below
        elif len(shape) - (names[0] == "seg0") >= 2:  # a matrix
            w = jax.random.truncated_normal(k, -2.0, 2.0, shape) / math.sqrt(
                shape[-2])
        else:
            w = jnp.zeros(shape)
        out[tuple(names)] = w
    v = out[("pos_conv", "v")]
    out[("pos_conv", "g")] = jnp.sqrt(jnp.sum(jnp.square(v), axis=(1, 2)))
    return jax.tree.unflatten(treedef, [
        out[tuple(p.key for p in path)] for path, _ in paths])


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------

def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * (1.0 + scale) + bias


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def conv1d(x, w, stride: int, pad=(0, 0), groups: int = 1):
    """x (b, n, c_in), w (k, c_in / groups, c_out) -> (b, t, c_out): output
    t sums ``x[t * stride + j] @ w[j]`` over the window j < k, each output
    channel reading only its group's input channels."""
    return jax.lax.conv_general_dilated(
        x, w, (stride,), [pad], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=groups)


def block(cfg, p, x):
    """One pre-LN encoder layer; x (b, t, d)."""
    eps = cfg["norm_eps"]
    b, t, d = x.shape
    h_n = cfg["num_heads"]
    hd = d // h_n
    a = p["attn"]
    h = layer_norm(x, p["ln1"], p["ln1_bias"], eps)
    q = (h @ a["wq"] + a["bq"]).reshape(b, t, h_n, hd)
    k = (h @ a["wk"] + a["bk"]).reshape(b, t, h_n, hd)
    v = (h @ a["wv"] + a["bv"]).reshape(b, t, h_n, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + o.reshape(b, t, d) @ a["wo"] + a["bo"]
    m = p["mlp"]
    h = layer_norm(x, p["ln2"], p["ln2_bias"], eps)
    return x + gelu(h @ m["w_up"] + m["b_up"]) @ m["w_down"] + m["b_down"]


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def forward(cfg, params, waveform, mask):
    """-> (logits (b, frames, clusters), feature penalty)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    eps = cfg["norm_eps"]
    x = waveform.astype(jnp.float32)[..., None]
    for i, s in enumerate(cfg["conv_strides"]):
        c = p["feature"][f"conv{i}"]
        x = gelu(layer_norm(conv1d(x, c["w"], s) + c["b"], c["ln"],
                            c["ln_bias"], eps))
    pen = jnp.mean(jnp.square(x))
    pp = p["proj"]
    x = layer_norm(x, pp["ln"], pp["ln_bias"], eps) @ pp["w"] + pp["b"]
    x = jnp.where(mask[..., None], p["mask_emb"], x)
    pc = p["pos_conv"]
    k = cfg["pos_conv_kernel"]
    w = pc["g"][:, None, None] * pc["v"] / jnp.sqrt(
        jnp.sum(jnp.square(pc["v"]), axis=(1, 2), keepdims=True))
    x = x + gelu(conv1d(x, w, 1, (k // 2, (k - 1) // 2),
                        cfg["pos_conv_groups"]) + pc["b"])
    layer = jax.checkpoint(lambda y, lp: (block(cfg, lp, y), None))
    x, _ = jax.lax.scan(layer, x, p["seg0"][BLOCK])
    fn = p["final_norm"]
    x = layer_norm(x, fn["scale"], fn["bias"], eps)
    h = p["head"]
    y = x @ h["w"] + h["b"]
    codes = h["codes"]
    cos = (jnp.einsum("btf,vf->btv", y, codes)
           / jnp.maximum(jnp.linalg.norm(y, axis=-1)[..., None]
                         * jnp.linalg.norm(codes, axis=-1), 1e-8))
    return cos / cfg["logit_temp"], pen


def loss(cfg, params, batch):
    """Cross-entropy over the masked frames + the feature penalty."""
    logits, pen = forward(cfg, params, batch["waveform"], batch["mask"])
    ce = (jax.nn.logsumexp(logits, axis=-1)
          - jnp.take_along_axis(logits, batch["targets"][..., None],
                                axis=-1)[..., 0])
    m = batch["mask"].astype(jnp.float32)
    ce = jnp.sum(ce * m) / jnp.maximum(jnp.sum(m), 1.0)
    return ce + cfg["feature_pen_weight"] * pen


def loss_and_grad(cfg, params, batch):
    return jax.value_and_grad(lambda p: loss(cfg, p, batch))(params)
