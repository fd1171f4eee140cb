"""Cross-pod federated training, executed on a (pod, data, model) mesh of
the devices present: each 'pod' runs K local AdamW steps on its own data
shard, then pods exchange int8-quantised deltas (the paper's cross-silo
round at pod granularity). Loss must drop and pods must stay in sync.

    JAX_PLATFORMS=cpu python examples/multipod_fl_train.py  # 8 host devices
    python examples/multipod_fl_train.py                    # the chips present
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.configs.base import MeshConfig, ShapeConfig, TrainConfig
from repro.data import synthetic_lm_batch
from repro.launch.mesh import make_mesh
from repro.launch.step_builders import make_fl_round_step
from repro.optim.optimizers import adamw_init


def _mesh_shape(n: int):
    """(pod, data, model) over n devices: two pods where there are two
    devices or more, the model axis 2-way where the rest is even."""
    pods = 2 if n >= 2 else 1
    rest = n // pods
    model = 2 if rest % 2 == 0 else 1
    return pods, rest // model, model


def main():
    shape3 = _mesh_shape(len(jax.devices()))
    mcfg = MeshConfig(shape=shape3, axis_names=("pod", "data", "model"))
    mesh = make_mesh(mcfg)
    n_pods = shape3[0]
    cfg = smoke_config("qwen3-8b")
    K = 4
    shape = ShapeConfig(name="fl", seq_len=32, global_batch=8, kind="train")
    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=2, total_steps=64,
                       crosspod_compression="int8")
    bundle = make_fl_round_step(cfg, shape, mesh, mcfg, tcfg, local_steps=K)
    model = bundle.model

    anchor, _ = model.init(jax.random.key(0))
    stack = lambda t: jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (n_pods,) + a.shape), t)
    params = stack(anchor)
    opt = jax.vmap(lambda p: adamw_init(p, tcfg))(params)

    fl_round = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                       out_shardings=bundle.out_shardings)
    rng = np.random.default_rng(0)
    losses = []
    with mesh:
        for rnd in range(8):
            raw = synthetic_lm_batch(rng, n_pods * K * 4, 32, cfg.vocab_size)
            batches = {k: jnp.asarray(v).reshape(n_pods, K, 4, 32)
                       for k, v in raw.items()}
            params, opt, anchor, loss = fl_round(params, opt, anchor,
                                                 batches,
                                                 jnp.int32(rnd * K))
            losses.append(float(loss))
            print(f"[multipod-fl] round {rnd} (K={K} local steps/pod, int8 "
                  f"delta sync): loss={losses[-1]:.3f}")
    # pods hold identical params after sync
    leaf = jax.tree.leaves(params)[0]
    drift = float(jnp.max(jnp.abs(leaf.astype(jnp.float32)
                                  - leaf[:1].astype(jnp.float32))))
    print(f"[multipod-fl] loss {losses[0]:.3f} -> {losses[-1]:.3f}; "
          f"cross-pod param drift after sync = {drift:.2e}")
    assert losses[-1] < losses[0], "no learning?"
    assert drift < 1e-3, "pods out of sync"
    print("[multipod-fl] OK")


if __name__ == "__main__":
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # eight host devices for the (2, 2, 2) mesh; read when JAX's
        # backend starts, so it must be set before main() touches JAX
        os.environ.setdefault("XLA_FLAGS",
                              "--xla_force_host_platform_device_count=8")
    main()
