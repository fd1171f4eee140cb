"""Shared building blocks for the model zoo.

Conventions
-----------
* Parameters are nested dicts of jnp arrays; every init function returns the
  pair ``(params, axes)`` where ``axes`` is an isomorphic pytree of tuples of
  *logical* axis names (see ``repro.sharding.rules``).
* All forward functions are pure; compute dtype comes from the config, params
  keep their own dtype.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def _dtype(name: str):
    return jnp.dtype(name)


def dense_init(rng, shape, axes, dtype, scale: Optional[float] = None):
    """Truncated-normal (fan-in) initialised matrix + its logical axes."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    w = (jax.random.truncated_normal(rng, -2.0, 2.0, shape, jnp.float32) * std)
    return w.astype(dtype), tuple(axes)


def zeros_init(shape, axes, dtype):
    return jnp.zeros(shape, dtype), tuple(axes)


def ones_init(shape, axes, dtype):
    return jnp.ones(shape, dtype), tuple(axes)


class Builder:
    """Collects (param, axes) pairs into parallel pytrees."""

    def __init__(self):
        self.params = {}
        self.axes = {}

    def add(self, name, pair):
        p, a = pair
        self.params[name] = p
        self.axes[name] = a
        return p

    def sub(self, name, builder_or_pair):
        if isinstance(builder_or_pair, Builder):
            self.params[name] = builder_or_pair.params
            self.axes[name] = builder_or_pair.axes
        else:
            p, a = builder_or_pair
            self.params[name] = p
            self.axes[name] = a

    def build(self):
        return self.params, self.axes


def _is_axes_tuple(x) -> bool:
    return isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)


def stack_init(init_fn, rng, n: int):
    """vmap an ``init_fn(rng) -> (params, axes)`` over ``n`` layer seeds and
    prepend the 'layers' logical axis. Axes (static strings) are captured by
    side effect since traced functions may only return arrays."""
    rngs = jax.random.split(rng, n)
    side = {}

    def params_only(r):
        p, a = init_fn(r)
        side["axes"] = a
        return p

    params = jax.vmap(params_only)(rngs)
    axes = jax.tree.map(lambda a: ("layers",) + tuple(a), side["axes"],
                        is_leaf=_is_axes_tuple)
    return params, axes


def abstract_init(init_fn, rng=None):
    """eval_shape an ``init_fn(rng) -> (params, axes)``: returns
    (ShapeDtypeStruct pytree, axes) without allocating."""
    import jax as _jax
    rng = rng if rng is not None else _jax.random.key(0)
    side = {}

    def params_only(r):
        p, a = init_fn(r)
        side["axes"] = a
        return p

    shapes = _jax.eval_shape(params_only, rng)
    return shapes, side["axes"]


# ---------------------------------------------------------------------------
# normalisation / rope
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    """LayerNorm with a bias, statistics in float32. ``scale`` is stored
    as its offset from 1, as ``rms_norm``'s is."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    x = x - mean
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    x = x * (1.0 + scale.astype(jnp.float32)) + bias.astype(jnp.float32)
    return x.astype(dt)


def norm_init(b: "Builder", name: str, cfg: ModelConfig):
    """The block norm ``name`` (and ``<name>_bias`` for LayerNorm)."""
    dt = _dtype(cfg.param_dtype)
    b.add(name, zeros_init((cfg.d_model,), ("norm",), dt))
    if cfg.norm == "layer":
        b.add(f"{name}_bias", zeros_init((cfg.d_model,), ("norm",), dt))


def norm_apply(p, name: str, x, cfg: ModelConfig):
    if cfg.norm == "layer":
        return layer_norm(x, p[name], p[f"{name}_bias"], cfg.norm_eps)
    return rms_norm(x, p[name], cfg.norm_eps)


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (flash-style chunked, pure jnp — memory O(seq * chunk))
# ---------------------------------------------------------------------------

def _attn_block(q, k, v, mask, scale):
    """q: (b,cq,hkv,g,d)  k/v: (b,ck,hkv,d) -> (scores-stats, out-partial)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, axis=-1)  # (b,h,g,q)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return m, l, o


def _merge(m1, l1, o1, m2, l2, o2):
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    # o partials are (b,q,h,g,d); stats (b,h,g,q) -> move q axis
    s1 = jnp.moveaxis(a1, -1, 1)[..., None]
    s2 = jnp.moveaxis(a2, -1, 1)[..., None]
    return m, l, o1 * s1 + o2 * s2


def flash_attention(q, k, v, *, causal: bool, q_chunk: int = 1024,
                    kv_chunk: int = 1024, kv_valid_len=None,
                    block_causal: bool = True):
    """Chunked (flash-style) attention with GQA, O(seq*chunk) live memory.

    q: (b, sq, hq, d); k,v: (b, skv, hkv, d). hq = g * hkv.
    ``block_causal=True`` skips fully-masked KV blocks for causal attention
    (true lower-triangular schedule — ~2x fewer attention FLOPs).
    ``kv_valid_len``: optional scalar — mask kv positions >= this (decode
    with a preallocated cache).
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    q = q.reshape(b, sq, hkv, g, d)

    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    nq = max(sq // q_chunk, 1)
    nk = max(skv // kv_chunk, 1)
    # fall back to single-block if not divisible
    if sq % q_chunk:
        nq, q_chunk = 1, sq
    if skv % kv_chunk:
        nk, kv_chunk = 1, skv

    kb = k.reshape(b, nk, kv_chunk, hkv, d)
    vb = v.reshape(b, nk, kv_chunk, hkv, d)
    kv_pos = jnp.arange(skv).reshape(nk, kv_chunk)

    outs = []
    for qi in range(nq):
        qc = jax.lax.dynamic_slice_in_dim(q, qi * q_chunk, q_chunk, axis=1)
        q_pos = qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, xs):
            m, l, o = carry
            kc, vc, kpos = xs
            mask = None
            if causal:
                mask = q_pos[:, None] >= kpos[None, :]
            if kv_valid_len is not None:
                vm = kpos < kv_valid_len
                mask = vm[None, :] if mask is None else (mask & vm[None, :])
            if mask is not None:
                mask = mask[None, None, None]  # (1,1,1,q,k) vs (b,h,g,q,k)
            m2, l2, o2 = _attn_block(qc, kc, vc, mask, scale)
            return _merge(m, l, o, m2, l2, o2), None

        m0 = jnp.full((b, hkv, g, q_chunk), -1e30, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, q_chunk), jnp.float32)
        o0 = jnp.zeros((b, q_chunk, hkv, g, d), jnp.float32)

        if causal and block_causal and nq == nk and sq == skv:
            hi = qi + 1  # blocks [0, qi] can contribute
            xs = (kb[:, :hi].swapaxes(0, 1), vb[:, :hi].swapaxes(0, 1),
                  kv_pos[:hi])
            (m, l, o), _ = jax.lax.scan(kv_step, (m0, l0, o0), xs)
        else:
            xs = (kb.swapaxes(0, 1), vb.swapaxes(0, 1), kv_pos)
            (m, l, o), _ = jax.lax.scan(kv_step, (m0, l0, o0), xs)

        l = jnp.moveaxis(l, -1, 1)[..., None]  # (b,q,h,g,1)
        outs.append((o / jnp.maximum(l, 1e-30)).astype(v.dtype))
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return out.reshape(b, sq, hq, d)


def decode_attention(q, k_cache, v_cache, cache_len):
    """Single-token attention against a preallocated cache.

    q: (b, 1, hq, d); caches: (b, smax, hkv, d); cache_len: scalar int
    (number of valid positions, including the token just written).
    """
    b, _, hq, d = q.shape
    _, smax, hkv, _ = k_cache.shape
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    qh = q.reshape(b, hkv, g, d)
    s = jnp.einsum("bhgd,bkhd->bhgk", qh, k_cache,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(smax)[None, None, None, :] < cache_len
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, 1, hq, d).astype(v_cache.dtype)


# ---------------------------------------------------------------------------
# attention module (params + apply)
# ---------------------------------------------------------------------------

def attn_init(rng, cfg: ModelConfig, d_in: Optional[int] = None,
              lora_rank: int = 0):
    d = d_in or cfg.d_model
    hd, hq, hkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(rng, 8)
    b = Builder()
    b.add("wq", dense_init(ks[0], (d, hq * hd), ("embed", "heads"), dt))
    b.add("wk", dense_init(ks[1], (d, hkv * hd), ("embed", "kv_heads"), dt))
    b.add("wv", dense_init(ks[2], (d, hkv * hd), ("embed", "kv_heads"), dt))
    b.add("wo", dense_init(ks[3], (hq * hd, d), ("heads", "embed"), dt))
    if cfg.proj_bias:
        b.add("bq", zeros_init((hq * hd,), ("norm",), dt))
        b.add("bk", zeros_init((hkv * hd,), ("norm",), dt))
        b.add("bv", zeros_init((hkv * hd,), ("norm",), dt))
        b.add("bo", zeros_init((d,), ("norm",), dt))
    if cfg.qk_norm:
        b.add("q_norm", zeros_init((hd,), ("norm",), dt))
        b.add("k_norm", zeros_init((hd,), ("norm",), dt))
    if lora_rank:
        for i, nm in enumerate(("wq", "wk", "wv")):
            out = hq * hd if nm == "wq" else hkv * hd
            b.add(f"{nm}_lora_a", dense_init(ks[4 + i], (d, lora_rank),
                                             ("embed", "norm"), dt))
            b.add(f"{nm}_lora_b", zeros_init((lora_rank, out), ("norm", "heads"), dt))
    return b.build()


def _proj_qkv(p, x, cfg: ModelConfig, lora_scope=None):
    def mm(name, w):
        y = jnp.einsum("bsd,df->bsf", x, w.astype(x.dtype))
        bias = p.get("b" + name[1:])  # bq for wq, ...
        if bias is not None:
            y = y + bias.astype(x.dtype)
        if lora_scope is not None and f"{name}_lora_a" in p:
            a = lora_scope(p[f"{name}_lora_a"]).astype(x.dtype)
            bb = lora_scope(p[f"{name}_lora_b"]).astype(x.dtype)
            y = y + jnp.einsum("bsd,dr,rf->bsf", x, a, bb)
        return y

    b, s, _ = x.shape
    hd = cfg.head_dim
    q = mm("wq", p["wq"]).reshape(b, s, cfg.num_heads, hd)
    k = mm("wk", p["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
    v = mm("wv", p["wv"]).reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def attn_apply(p, x, cfg: ModelConfig, *, positions, causal=None,
               block_causal=True, lora_scope=None):
    causal = cfg.causal if causal is None else causal
    q, k, v = _proj_qkv(p, x, cfg, lora_scope)
    if cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    o = flash_attention(q, k, v, causal=causal, q_chunk=cfg.attn_chunk,
                        kv_chunk=cfg.attn_chunk, block_causal=block_causal)
    b, s, _, _ = o.shape
    o = o.reshape(b, s, cfg.num_heads * cfg.head_dim)
    return _out_proj(p, o, x.dtype)


def _out_proj(p, o, dtype):
    y = jnp.einsum("bsf,fd->bsd", o, p["wo"].astype(dtype))
    return y + p["bo"].astype(dtype) if "bo" in p else y


def attn_prefill(p, x, cfg: ModelConfig, *, positions, smax,
                 lora_scope=None):
    """Forward + return kv to seed a decode cache padded to smax."""
    q, k, v = _proj_qkv(p, x, cfg, lora_scope)
    if cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    o = flash_attention(q, k, v, causal=cfg.causal, q_chunk=cfg.attn_chunk,
                        kv_chunk=cfg.attn_chunk)
    b, s, _, _ = o.shape
    pad = [(0, 0), (0, smax - s), (0, 0), (0, 0)]
    k_cache = jnp.pad(k, pad)
    v_cache = jnp.pad(v, pad)
    o = o.reshape(b, s, cfg.num_heads * cfg.head_dim)
    return _out_proj(p, o, x.dtype), (k_cache, v_cache)


def attn_decode(p, x, cache, cfg: ModelConfig, *, pos, lora_scope=None):
    """x: (b,1,d); cache: dict(k,v) of (b,smax,hkv,hd); pos: scalar index."""
    q, k, v = _proj_qkv(p, x, cfg, lora_scope)
    positions = pos[None, None] if jnp.ndim(pos) == 0 else pos
    q = apply_rope(q, positions.astype(jnp.int32), cfg.rope_theta)
    k = apply_rope(k, positions.astype(jnp.int32), cfg.rope_theta)
    k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), pos, axis=1)
    o = decode_attention(q, k_cache, v_cache, pos + 1)
    b = x.shape[0]
    o = o.reshape(b, 1, cfg.num_heads * cfg.head_dim)
    out = jnp.einsum("bsf,fd->bsd", o, p["wo"].astype(x.dtype))
    return out, {"k": k_cache, "v": v_cache}


def cross_attn_apply(p, x, kv_embeds, cfg: ModelConfig):
    """Cross attention onto (b, n_img, d) context (no rope)."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = jnp.einsum("bsd,df->bsf", x, p["wq"].astype(x.dtype)).reshape(
        b, s, cfg.num_heads, hd)
    k = jnp.einsum("bnd,df->bnf", kv_embeds, p["wk"].astype(x.dtype)).reshape(
        b, -1, cfg.num_kv_heads, hd)
    v = jnp.einsum("bnd,df->bnf", kv_embeds, p["wv"].astype(x.dtype)).reshape(
        b, -1, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    o = flash_attention(q, k, v, causal=False, q_chunk=cfg.attn_chunk,
                        kv_chunk=cfg.attn_chunk)
    o = o.reshape(b, s, cfg.num_heads * hd)
    return jnp.einsum("bsf,fd->bsd", o, p["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------

def mlp_init(rng, cfg: ModelConfig, d_ff: Optional[int] = None,
             d_in: Optional[int] = None):
    d = d_in or cfg.d_model
    ff = d_ff or cfg.d_ff
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(rng, 3)
    b = Builder()
    if not cfg.mlp_gelu:
        b.add("w_gate", dense_init(ks[0], (d, ff), ("embed", "mlp"), dt))
    b.add("w_up", dense_init(ks[1], (d, ff), ("embed", "mlp"), dt))
    b.add("w_down", dense_init(ks[2], (ff, d), ("mlp", "embed"), dt))
    if cfg.proj_bias:
        b.add("b_up", zeros_init((ff,), ("norm",), dt))
        b.add("b_down", zeros_init((d,), ("norm",), dt))
    return b.build()


def mlp_apply(p, x):
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
    if "b_up" in p:
        u = u + p["b_up"].astype(x.dtype)
    if "w_gate" in p:
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(u)
    y = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))
    return y + p["b_down"].astype(x.dtype) if "b_down" in p else y


def moe_init(rng, cfg: ModelConfig):
    E, ff, d = cfg.num_experts, cfg.d_ff, cfg.d_model
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(rng, 5)
    b = Builder()
    b.add("router", dense_init(ks[0], (d, E), ("embed", "expert"), dt,
                               scale=0.02))
    b.add("w_gate", dense_init(ks[1], (E, d, ff), ("expert", "expert_in", "mlp"), dt))
    b.add("w_up", dense_init(ks[2], (E, d, ff), ("expert", "expert_in", "mlp"), dt))
    b.add("w_down", dense_init(ks[3], (E, ff, d), ("expert", "mlp", "expert_in"), dt))
    if cfg.num_shared_experts:
        b.sub("shared", mlp_init(ks[4], cfg, d_ff=ff * cfg.num_shared_experts))
    return b.build()


def moe_apply(p, x, cfg: ModelConfig, *, group_size: int = 2048,
              capacity_factor: float = 1.25):
    """GShard-style grouped top-k dispatch (einsum-only, MXU-friendly).

    Tokens are split into groups; each group dispatches into per-expert
    capacity slots via one-hot matmuls. Over-capacity tokens are dropped
    (residual passes through), standard for capacity-based TPU MoE.
    """
    b, s, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    tokens = x.reshape(-1, d)
    T = tokens.shape[0]
    g = min(group_size, T)
    if T % g:
        g = T  # single group fallback
    n_groups = T // g
    cap = max(int(g * k * capacity_factor / E), 1)

    xt = tokens.reshape(n_groups, g, d)
    logits = jnp.einsum("ngd,de->nge", xt, p["router"].astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    # top-k gating
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # (n, g, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert queue
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # (n,g,k,E)
    flat = onehot.reshape(n_groups, g * k, E)
    pos_in_e = (jnp.cumsum(flat, axis=1) - flat).reshape(n_groups, g, k, E)
    pos = jnp.sum(pos_in_e * onehot, axis=-1)  # (n,g,k)
    keep = pos < cap
    gate_vals = gate_vals * keep

    # dispatch/combine one-hots: (n, g, k, E, cap) reduced over k
    cap_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32) * keep[..., None]
    dispatch = jnp.einsum("ngke,ngkc->ngec", onehot, cap_oh)
    combine = jnp.einsum("ngke,ngkc,ngk->ngec", onehot, cap_oh, gate_vals)

    xe = jnp.einsum("ngec,ngd->necd", dispatch.astype(x.dtype), xt)
    xe = xe.transpose(1, 0, 2, 3).reshape(E, n_groups * cap, d)  # (E, n*cap, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(x.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, p["w_up"].astype(x.dtype))
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(x.dtype))
    ye = ye.reshape(E, n_groups, cap, d).transpose(1, 0, 2, 3)  # (n,E,cap,d)
    y = jnp.einsum("ngec,necd->ngd", combine.astype(x.dtype), ye)
    y = y.reshape(b, s, d)

    # load-balancing auxiliary loss (Switch-style)
    me = jnp.mean(flat, axis=1) * E  # fraction routed per expert * E
    pe = jnp.mean(probs, axis=1) * E
    aux = jnp.mean(jnp.sum(me * pe, axis=-1)) / E

    if cfg.num_shared_experts and "shared" in p:
        y = y + mlp_apply(p["shared"], x)
    return y, aux


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------

def embed_init(rng, cfg: ModelConfig):
    dt = _dtype(cfg.param_dtype)
    b = Builder()
    ks = jax.random.split(rng, 2)
    if not cfg.external_embeddings:
        b.add("embedding", dense_init(ks[0], (cfg.vocab_size, cfg.d_model),
                                      ("vocab", "embed"), dt, scale=1.0))
    if not cfg.tie_embeddings:
        b.add("lm_head", dense_init(ks[1], (cfg.d_model, cfg.vocab_size),
                                    ("embed", "vocab"), dt))
    b.add("final_norm", zeros_init((cfg.d_model,), ("norm",), dt))
    return b.build()


def embed_lookup(p, tokens, cfg: ModelConfig, compute_dtype):
    emb = jnp.take(p["embedding"], tokens, axis=0).astype(compute_dtype)
    return emb * math.sqrt(cfg.d_model) if cfg.tie_embeddings else emb


def lm_logits(p, x, cfg: ModelConfig):
    x = rms_norm(x, p["final_norm"], cfg.norm_eps)
    w = p["embedding"].T if cfg.tie_embeddings else p["lm_head"]
    return jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))


def cross_entropy(logits, targets, *, z_loss: float = 1e-4):
    """Mean token cross-entropy in fp32 with z-loss regulariser."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    loss = jnp.mean(lse - ll)
    if z_loss:
        loss = loss + z_loss * jnp.mean(jnp.square(lse))
    return loss
