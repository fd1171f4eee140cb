"""Plain reference of the image tiers: init, forward, loss, local SGD.

Straightforward ``jax.numpy`` with no kernels, batching or caching. It
follows the published description (ResNet-56: He et al., arXiv:1512.03385
section 4.2), with the departures that the configuration file lists under
``assumed``:

* normalisation uses the batch's own statistics (mean and biased variance
  over batch and space, eps 1e-5) with a learned scale and bias, and keeps
  no running averages;
* the loss is the mean softmax cross-entropy over the batch.

Parameters are laid out as a nested dict/list tree that names each
tensor; the benchmark checks that layout against the program's before a
run. ``dtype`` is the precision the whole computation runs in: float32
at the highest matmul precision for the reference, bfloat16 for the
control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# shapes of the parameter tree
# ---------------------------------------------------------------------------

def _conv_shape(k, c_in, c_out, groups=1):
    return (k, k, c_in // groups, c_out)


def resnet_layout(cfg):
    """-> nested tree of ("conv"|"norm_scale"|"norm_bias"|"dense"|"bias",
    shape, fan_in or std) leaves."""
    w0 = cfg["widths"][0]
    t = {"stem": {"w": ("conv", _conv_shape(3, 3, w0), 3 * 3 * 3),
                  "bn": _norm(w0)}}
    c_in = w0
    for si, width in enumerate(cfg["widths"]):
        stage = []
        for bi in range(cfg["blocks_per_stage"]):
            stride = 2 if (si > 0 and bi == 0) else 1
            blk = {"c1": ("conv", _conv_shape(3, c_in, width), 9 * c_in),
                   "bn1": _norm(width),
                   "c2": ("conv", _conv_shape(3, width, width), 9 * width),
                   "bn2": _norm(width)}
            if stride != 1 or c_in != width:
                blk["proj"] = ("conv", _conv_shape(1, c_in, width), c_in)
            stage.append(blk)
            c_in = width
        t[f"stage{si}"] = stage
    t["head"] = {"w": ("dense", (c_in, cfg["num_classes"]), 0.01),
                 "b": ("bias", (cfg["num_classes"],), None)}
    return t


def _norm(c):
    return {"scale": ("norm_scale", (c,), None),
            "bias": ("norm_bias", (c,), None)}


LAYOUTS = {"resnet": resnet_layout}


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], str)


def layout(cfg):
    return LAYOUTS[cfg["model"]](cfg)


def param_shapes(cfg):
    return jax.tree.map(lambda s: s[1], layout(cfg), is_leaf=_is_spec)


def param_count(cfg) -> int:
    return sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def init(cfg, key):
    """Random weights from ``key``: He-normal convolutions, N(0, 0.01^2)
    dense layers, unit norm scales, zero biases (float32)."""
    specs = layout(cfg)
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (kind, shape, arg) in zip(keys, leaves):
        if kind == "conv":
            out.append(jax.random.normal(k, shape, jnp.float32)
                       * math.sqrt(2.0 / arg))
        elif kind == "dense":
            out.append(jax.random.normal(k, shape, jnp.float32) * arg)
        elif kind == "norm_scale":
            out.append(jnp.ones(shape, jnp.float32))
        else:
            out.append(jnp.zeros(shape, jnp.float32))
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _conv(x, w, stride=1, groups=1):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)


def _norm_apply(p, x, eps=1e-5):
    mean = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2), keepdims=True)
    x = (x - mean) / jnp.sqrt(var + eps)
    return x * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def resnet_forward(cfg, p, images):
    relu = jax.nn.relu
    x = relu(_norm_apply(p["stem"]["bn"], _conv(images, p["stem"]["w"])))
    for si in range(len(cfg["widths"])):
        for bi, blk in enumerate(p[f"stage{si}"]):
            stride = 2 if (si > 0 and bi == 0) else 1
            h = relu(_norm_apply(blk["bn1"], _conv(x, blk["c1"], stride)))
            h = _norm_apply(blk["bn2"], _conv(h, blk["c2"]))
            sc = _conv(x, blk["proj"], stride) if "proj" in blk else x
            x = relu(h + sc)
    x = jnp.mean(x, axis=(1, 2))
    return x @ p["head"]["w"].astype(x.dtype) + p["head"]["b"].astype(x.dtype)


FORWARDS = {"resnet": resnet_forward}


def loss(cfg, p, images, labels):
    logits = FORWARDS[cfg["model"]](cfg, p, images).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def make_local_sgd(cfg, lr: float, dtype):
    """-> jitted ``(params, images (S,B,...), labels (S,B)) -> (params,
    mean loss)``: S plain SGD steps in ``dtype``."""
    def step(p, batch):
        images, labels = batch
        l, g = jax.value_and_grad(
            lambda q: loss(cfg, q, images.astype(dtype), labels))(p)
        p = jax.tree.map(lambda a, b: (a - jnp.asarray(lr, dtype)
                                       * b.astype(dtype)).astype(dtype),
                         p, g)
        return p, l

    def run(params, images, labels):
        p = jax.tree.map(lambda a: a.astype(dtype), params)
        p, losses = jax.lax.scan(step, p, (images, labels))
        return p, jnp.mean(losses.astype(jnp.float32))

    return jax.jit(run)
