"""Serving driver: batched prefill + decode with a KV/SSM cache.

CPU-runnable on reduced configs; the decode step is the same function the
dry-run lowers for the decode_32k / long_500k cells.

    PYTHONPATH=src python -m repro.launch.serve --arch zamba2-1.2b \
        --requests 8 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_ORDER, smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_smoke_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b", choices=ARCH_ORDER)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = smoke_config(args.arch)
    if not cfg.causal:
        print(f"[serve] {args.arch} is encoder-only; no decode loop")
        return 0
    from repro.models import build_model
    model = build_model(cfg)
    rng = jax.random.key(0)
    params, _ = model.init(rng)

    b = args.requests
    max_seq = args.prompt_len + args.gen
    prompts = jax.random.randint(rng, (b, args.prompt_len), 0,
                                 cfg.vocab_size)

    decode = jax.jit(model.decode_step)
    cache = model.init_cache(b, max_seq)

    # prefill via decode steps for recurrent caches (uniform across families)
    t0 = time.time()
    tok = prompts[:, :1]
    logits = None
    for pos in range(args.prompt_len):
        batch = {"tokens": prompts[:, pos:pos + 1], "pos": jnp.int32(pos)}
        logits, cache = decode(params, cache, batch)
    prefill_s = time.time() - t0

    t0 = time.time()
    out_tokens = []
    tok = jnp.argmax(logits[:, -1] if logits.ndim == 3 else logits, axis=-1,
                     keepdims=True).astype(jnp.int32)
    for i in range(args.gen):
        batch = {"tokens": tok, "pos": jnp.int32(args.prompt_len + i)}
        logits, cache = decode(params, cache, batch)
        lg = logits[:, -1] if logits.ndim == 3 else logits
        tok = jnp.argmax(lg, axis=-1, keepdims=True).astype(jnp.int32)
        out_tokens.append(np.asarray(tok))
    decode_s = time.time() - t0
    gen = np.concatenate(out_tokens, axis=1)
    assert gen.shape == (b, args.gen) and np.all(gen >= 0)
    print(f"[serve] {b} reqs: prefill({args.prompt_len} tok) {prefill_s:.2f}s, "
          f"decode {args.gen} tok in {decode_s:.2f}s "
          f"({b * args.gen / max(decode_s, 1e-9):.1f} tok/s)")
    print(f"[serve] sample generation: {gen[0][:8].tolist()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
