"""TransformerLM: one substrate for the dense / moe / audio / vlm families.

Layer stacks are organised into *segments*: a segment is a fixed sequence of
block kinds repeated N times, scanned with ``jax.lax.scan`` over stacked
parameters.  This keeps the HLO small for 95-layer models while supporting
interleave patterns (MoE every k-th layer, cross-attention every 5th layer).

Block kinds: ``self`` (attn+mlp), ``moe`` (attn+moe-ffn), ``cross``
(gated cross-attn + mlp, llama-3.2-vision style).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import layers as L
from repro.sharding.rules import Sharder


def _remat(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(fn)


class TransformerLM:
    """Decoder-only (or encoder-only) transformer with GQA."""

    def __init__(self, cfg: ModelConfig, sharder: Optional[Sharder] = None):
        self.cfg = cfg
        self.sharder = sharder or Sharder()
        self.segments = self._plan_segments()

    # ------------------------------------------------------------------
    def _plan_segments(self):
        cfg = self.cfg
        Ln = cfg.num_layers
        if cfg.family == "vlm" and cfg.cross_attn_every:
            k = cfg.cross_attn_every
            assert Ln % k == 0
            kinds = tuple(["cross"] + ["self"] * (k - 1))
            return [(kinds, Ln // k)]
        if cfg.num_experts and cfg.moe_interleave > 1:
            k = cfg.moe_interleave
            assert Ln % k == 0
            kinds = tuple(["self"] * (k - 1) + ["moe"])
            return [(kinds, Ln // k)]
        if cfg.num_experts:
            return [(("moe",), Ln)]
        return [(("self",), Ln)]

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------
    def _block_init(self, rng, kind: str):
        cfg = self.cfg
        b = L.Builder()
        ks = jax.random.split(rng, 4)
        dt = jnp.dtype(cfg.param_dtype)
        L.norm_init(b, "ln1", cfg)
        L.norm_init(b, "ln2", cfg)
        if kind == "cross":
            b.sub("xattn", L.attn_init(ks[0], cfg))
            b.add("xgate", L.zeros_init((), (), dt))
            b.sub("mlp", L.mlp_init(ks[1], cfg,
                                    d_ff=cfg.d_ff_dense or cfg.d_ff))
        else:
            b.sub("attn", L.attn_init(ks[0], cfg))
            if kind == "moe":
                b.sub("moe", L.moe_init(ks[1], cfg))
            else:
                b.sub("mlp", L.mlp_init(ks[1], cfg,
                                        d_ff=cfg.d_ff_dense or cfg.d_ff))
        return b.build()

    def init(self, rng):
        """Returns (params, axes)."""
        cfg = self.cfg
        ks = jax.random.split(rng, len(self.segments) + 1)
        params, axes = self.init_segments(ks[1:])
        emb_p, emb_a = L.embed_init(ks[0], cfg)
        params["embed"], axes["embed"] = emb_p, emb_a
        return params, axes

    def init_segments(self, keys):
        """The layer stacks alone, one key per segment: (params, axes)
        keyed ``seg<i>``, as ``_stack_apply`` takes them."""
        params, axes = {}, {}
        for si, (kinds, repeat) in enumerate(self.segments):
            seg_p, seg_a = {}, {}
            for bi, kind in enumerate(kinds):
                def one(r, _kind=kind):
                    return self._block_init(r, _kind)
                p, a = L.stack_init(one, jax.random.fold_in(keys[si], bi),
                                    repeat)
                seg_p[f"b{bi}_{kind}"] = p
                seg_a[f"b{bi}_{kind}"] = a
            params[f"seg{si}"] = seg_p
            axes[f"seg{si}"] = seg_a
        return params, axes

    def param_axes(self):
        return L.abstract_init(self.init)[1]

    def param_shapes(self):
        return L.abstract_init(self.init)[0]

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------
    def _block_apply(self, kind, p, x, *, positions, image_embeds=None,
                     causal=None):
        cfg = self.cfg
        shard = self.sharder
        h = L.norm_apply(p, "ln1", x, cfg)
        aux = jnp.zeros((), jnp.float32)
        if kind == "cross":
            a = L.cross_attn_apply(p["xattn"], h, image_embeds, cfg)
            x = x + jnp.tanh(p["xgate"].astype(a.dtype)) * a
        else:
            a = L.attn_apply(p["attn"], h, cfg, positions=positions,
                             causal=causal, block_causal=cfg.block_causal)
            x = x + a
        x = shard(x, ("batch", "seq", None))
        h = L.norm_apply(p, "ln2", x, cfg)
        if kind == "moe":
            y, aux = L.moe_apply(p["moe"], h, cfg,
                                 group_size=cfg.moe_group_size,
                                 capacity_factor=cfg.capacity_factor)
        else:
            y = L.mlp_apply(p["mlp"], h)
        x = x + y
        return shard(x, ("batch", "seq", None)), aux

    def _stack_apply(self, params, x, *, positions, image_embeds=None):
        cfg = self.cfg

        for si, (kinds, repeat) in enumerate(self.segments):
            seg = params[f"seg{si}"]

            def body(carry, layer_p):
                x, aux = carry
                for bi, kind in enumerate(kinds):
                    x, a = self._block_apply(
                        kind, layer_p[f"b{bi}_{kind}"], x,
                        positions=positions, image_embeds=image_embeds)
                    aux = aux + a
                return (x, aux), None

            body = _remat(body, cfg.remat)
            (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), seg)
        return x, aux

    def forward(self, params, batch):
        """-> logits (b, s, vocab)."""
        cfg = self.cfg
        if cfg.external_embeddings:
            x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
        else:
            x = L.embed_lookup(params["embed"], batch["tokens"], cfg,
                               jnp.dtype(cfg.dtype))
        x = self.sharder(x, ("batch", "seq", None))
        s = x.shape[1]
        positions = batch.get("positions", jnp.arange(s, dtype=jnp.int32))
        img = batch.get("image_embeds")
        if img is not None:
            img = img.astype(x.dtype)
        x, aux = self._stack_apply(params, x, positions=positions,
                                   image_embeds=img)
        logits = L.lm_logits(params["embed"], x, cfg)
        logits = self.sharder(logits, ("batch", "seq", "vocab"))
        return logits, aux

    def loss(self, params, batch):
        logits, aux = self.forward(params, batch)
        ce = L.cross_entropy(logits, batch["targets"])
        loss = ce + 0.01 * aux
        return loss, {"ce": ce, "aux": aux}

    # ------------------------------------------------------------------
    # decode path
    # ------------------------------------------------------------------
    def cache_spec(self, batch_size: int, max_seq: int):
        """ShapeDtypeStructs (+ axes) for a decode cache."""
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        kv_shape = (batch_size, max_seq, cfg.num_kv_heads, cfg.head_dim)
        kv_axes = ("batch", "seq_kv", None, None)
        cache, axes = {}, {}
        for si, (kinds, repeat) in enumerate(self.segments):
            seg_c, seg_a = {}, {}
            for bi, kind in enumerate(kinds):
                if kind == "cross":
                    n_img = cfg.num_image_tokens
                    xshape = (repeat, batch_size, n_img, cfg.num_kv_heads,
                              cfg.head_dim)
                    seg_c[f"b{bi}_{kind}"] = {
                        "xk": jax.ShapeDtypeStruct(xshape, dt),
                        "xv": jax.ShapeDtypeStruct(xshape, dt)}
                    seg_a[f"b{bi}_{kind}"] = {
                        "xk": ("layers", "batch", None, None, None),
                        "xv": ("layers", "batch", None, None, None)}
                else:
                    shape = (repeat,) + kv_shape
                    seg_c[f"b{bi}_{kind}"] = {
                        "k": jax.ShapeDtypeStruct(shape, dt),
                        "v": jax.ShapeDtypeStruct(shape, dt)}
                    seg_a[f"b{bi}_{kind}"] = {
                        "k": ("layers",) + kv_axes, "v": ("layers",) + kv_axes}
            cache[f"seg{si}"] = seg_c
            axes[f"seg{si}"] = seg_a
        return cache, axes

    def init_cache(self, batch_size: int, max_seq: int):
        spec, _ = self.cache_spec(batch_size, max_seq)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)

    def decode_step(self, params, cache, batch):
        """One token: batch = {tokens: (b,1), pos: scalar int32,
        image_embeds?}. Returns (logits, new_cache)."""
        cfg = self.cfg
        pos = batch["pos"]
        if cfg.external_embeddings:
            x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
        else:
            x = L.embed_lookup(params["embed"], batch["tokens"], cfg,
                               jnp.dtype(cfg.dtype))
        new_cache = {}
        for si, (kinds, repeat) in enumerate(self.segments):
            seg = params[f"seg{si}"]
            seg_cache = cache[f"seg{si}"]

            def body(x, xs):
                layer_p, layer_c = xs
                new_c = {}
                for bi, kind in enumerate(kinds):
                    key = f"b{bi}_{kind}"
                    p = layer_p[key]
                    h = L.norm_apply(p, "ln1", x, cfg)
                    if kind == "cross":
                        # static image kv — attend, no cache update
                        o = _cross_decode(p["xattn"], h, layer_c[key], cfg)
                        x = x + jnp.tanh(p["xgate"].astype(o.dtype)) * o
                        new_c[key] = layer_c[key]
                    else:
                        o, kv = L.attn_decode(p["attn"], h, layer_c[key], cfg,
                                              pos=pos)
                        x = x + o
                        new_c[key] = kv
                    h = L.norm_apply(p, "ln2", x, cfg)
                    if kind == "moe":
                        y, _ = L.moe_apply(p["moe"], h, cfg,
                                           group_size=cfg.moe_group_size,
                                           capacity_factor=cfg.capacity_factor)
                    else:
                        y = L.mlp_apply(p["mlp"], h)
                    x = x + y
                return x, new_c

            x, new_seg = jax.lax.scan(body, x, (seg, seg_cache))
            new_cache[f"seg{si}"] = new_seg
        logits = L.lm_logits(params["embed"], x, cfg)
        return logits, new_cache

    # ------------------------------------------------------------------
    def input_specs(self, shape: ShapeConfig):
        """ShapeDtypeStruct stand-ins + logical axes for every model input."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        dt = jnp.dtype(cfg.dtype)
        specs, axes = {}, {}
        if shape.kind in ("train", "prefill"):
            if cfg.external_embeddings:
                specs["embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), dt)
                axes["embeds"] = ("batch", "seq", None)
            else:
                specs["tokens"] = jax.ShapeDtypeStruct((b, s), i32)
                axes["tokens"] = ("batch", "seq")
            if cfg.family == "vlm":
                specs["image_embeds"] = jax.ShapeDtypeStruct(
                    (b, cfg.num_image_tokens, cfg.d_model), dt)
                axes["image_embeds"] = ("batch", None, None)
            if shape.kind == "train":
                specs["targets"] = jax.ShapeDtypeStruct((b, s), i32)
                axes["targets"] = ("batch", "seq")
        else:  # decode
            specs["tokens"] = jax.ShapeDtypeStruct((b, 1), i32)
            axes["tokens"] = ("batch", None)
            specs["pos"] = jax.ShapeDtypeStruct((), i32)
            axes["pos"] = None
        return specs, axes


def _cross_decode(p, x, xcache, cfg: ModelConfig):
    """Cross-attention for a single token against static image kv."""
    b, _, _ = x.shape
    hd = cfg.head_dim
    q = jnp.einsum("bsd,df->bsf", x, p["wq"].astype(x.dtype)).reshape(
        b, 1, cfg.num_heads, hd)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.norm_eps)
    k, v = xcache["xk"], xcache["xv"]
    o = L.decode_attention(q, k, v, jnp.int32(k.shape[1]))
    o = o.reshape(b, 1, cfg.num_heads * hd)
    return jnp.einsum("bsf,fd->bsd", o, p["wo"].astype(x.dtype))
