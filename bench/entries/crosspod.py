"""Entry: the cross-pod FL round of ``repro.launch.fl_round``.

Set-up builds the program's round (``fl_round.CrossPodRound``) for the
configuration's model on the cell's chips; while the round compiles, the
benchmark makes the initial anchor from the seed with the reference's own
init (``bench/reference/hubert.py``) and draws the first ``check_steps``
rounds' batches (``bench/audio.py``). It hands both to the program, which
runs those rounds through the timed path, then rounds back to back
(closed loop) until ``seconds`` have passed. Once the program's state is
freed, the plain reference (``bench/reference/crosspod.py``) replays the
checked rounds from the same anchor and batches; its seconds are not
set-up.

The program's recorder (``repro.obs``) is on: the window's pod steps
(``crosspod.local_steps``) and exchanged bytes
(``crosspod.exchange_bytes``) are read from its counters.
"""
from __future__ import annotations

import concurrent.futures
import gc
import json
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from bench import audio, compare, hubert_flops
from bench.entries.live_fl import Patches, _seed_key
from bench.harness import BenchError
from bench.reference import hubert as ref_hubert
from bench.reference.crosspod import distances, replay

# configuration keys and the program's names for them
WIDTHS = {"num_layers": "num_layers", "d_model": "d_model",
          "num_heads": "num_heads", "d_ff": "d_ff",
          "conv_channels": "conv_channels", "conv_kernels": "conv_kernels",
          "conv_strides": "conv_strides",
          "pos_conv_kernel": "pos_conv_kernel",
          "pos_conv_groups": "pos_conv_groups", "final_dim": "final_dim",
          "num_clusters": "vocab_size", "logit_temp": "logit_temp",
          "mask_prob": "mask_prob", "mask_length": "mask_length",
          "feature_pen_weight": "feature_pen_weight", "norm_eps": "norm_eps",
          "dtype": "dtype", "param_dtype": "param_dtype"}


def _host_leaves(tree):
    return [np.asarray(x) for x in jax.device_get(jax.tree.leaves(tree))]


def make_weights(config: dict, seed: int) -> List[np.ndarray]:
    """The initial anchor by the reference's init, made on the device in
    one jitted call: host float32 leaves in ``param_shapes`` order."""
    return _host_leaves(jax.jit(lambda k: ref_hubert.init(config, k))(
        _seed_key(seed)))


def _moved(rounds, start) -> List[float]:
    """Per leaf, the norm of the program's anchor's change from ``start``
    (host leaves, as the program keeps them), on the devices."""
    on = jax.device_put(start, [a.sharding for a in
                                jax.tree.leaves(rounds.anchor)])
    return distances(jax.tree.leaves(rounds.anchor), on)


def _as_leaves(moved: List[float]):
    """A change reduced to its per-leaf norms, as ``compare`` reads one
    (the norm of a one-element array is its value)."""
    return [np.array([m]) for m in moved]


def _program(config: dict):
    """-> (the program's fl_round module, its model config), checked
    against the configuration's widths."""
    try:
        from repro.launch import fl_round
    except ImportError as e:
        raise BenchError(f"the program has no cross-pod round "
                         f"(repro.launch.fl_round): {e}") from e
    from repro.configs import get_config, smoke_config
    prog = (smoke_config if config.get("program_smoke") else get_config)(
        config["program_arch"])
    for key, field in WIDTHS.items():
        want, got = config[key], getattr(prog, field)
        if (list(want) if isinstance(want, list) else want) != (
                list(got) if isinstance(got, tuple) else got):
            raise BenchError(f"the program's {prog.name} has {field}={got!r},"
                             f" the configuration {key}={want!r}")
    return fl_round, prog


def run(ctx):
    fl_round, prog_cfg = _program(ctx.config)
    from repro import obs
    from repro.configs.base import TrainConfig

    cfg, t, run_ = ctx.config, ctx.traffic, ctx.run
    devices = jax.devices()[:t["pods"]]
    tcfg = TrainConfig(
        learning_rate=t["learning_rate"], warmup_steps=t["warmup_steps"],
        total_steps=t["total_steps"], beta1=t["beta1"], beta2=t["beta2"],
        eps=t["eps"], weight_decay=t["weight_decay"],
        grad_clip=t["grad_clip"], moment_dtype=cfg["moment_dtype"],
        crosspod_compression=t["compression"])
    patches = Patches()
    ctx.on_exit.append(patches.restore)
    _plant(ctx.fault, patches)
    rec = obs.enable()
    ctx.on_exit.append(obs.disable)
    rounds = fl_round.CrossPodRound(
        prog_cfg, pods=t["pods"], local_steps=t["local_steps"],
        pod_batch=t["pod_batch"], samples=t["samples"], train_cfg=tcfg,
        seed=ctx.seed, devices=devices)
    shapes = ref_hubert.param_shapes(cfg)
    want = jax.tree.map(tuple, shapes, is_leaf=lambda x: isinstance(x, tuple))
    if jax.tree.map(lambda a: a.shape, rounds.bundle.in_specs[2]) != want:
        raise BenchError(f"the program's {cfg['name']} parameter layout "
                         f"differs from the reference's")
    treedef = jax.tree.structure(shapes,
                                 is_leaf=lambda x: isinstance(x, tuple))
    n_check = t["check_steps"]
    # the round compiles while the weights and batches are made
    pool = concurrent.futures.ThreadPoolExecutor(1)
    compiling = pool.submit(rounds.compile)
    pool.shutdown(wait=False)
    p0 = make_weights(cfg, ctx.seed)
    frames = ref_hubert.frames(cfg, t["samples"])
    batches = [audio.round_batches(cfg, t, ctx.seed, r, frames)
               for r in range(n_check)]
    run_.flops_per_step = hubert_flops.train_step(cfg, t["pod_batch"],
                                                  t["samples"])
    # the anchor as the program keeps it, the start of its changes
    kept = [x.astype(jnp.dtype(cfg["param_dtype"])) for x in p0]

    # -- the checked rounds, then the window, through the timed path -------
    compiling.result()
    rounds.start(anchor=jax.tree.unflatten(treedef, kept), batches=batches)
    check_losses = []
    for r in range(n_check):
        check_losses.append(rounds.round())
        if r == 0:
            d1 = _moved(rounds, kept)
    d3 = _moved(rounds, kept)
    del kept
    losses = []
    ws = ctx.window_open()
    while True:
        t0 = time.perf_counter()
        losses.append(rounds.round())
        t1 = time.perf_counter()
        run_.rounds.append((t0, t1))
        if t1 - ws >= ctx.seconds:
            break
    ctx.window_close(t1)
    ctx.window_closed()
    ctx.read_memory()
    patches.restore()
    for c in rec.counts:
        if c.name == "crosspod.local_steps":
            run_.client_steps.extend([c.t] * int(c.n))
        elif c.name == "crosspod.exchange_bytes":
            # each chip receives every other pod's payload
            run_.kernel_calls.append((c.t, "exchange",
                                      (t["pods"] - 1) * int(c.n)))
    run_.attempted = len(losses)
    run_.failed = sum(1 for l in losses if not np.isfinite(l))
    rounds.release()
    del rounds
    gc.collect()

    # -- the reference, once the program's state is freed ------------------
    def reference(param_dtype=jnp.float32):
        """-> (the rounds' losses, per-leaf norms of the anchor's change
        after the first round and after the last)."""
        key = json.dumps([ctx.seed, cfg, t, str(param_dtype)], sort_keys=True)
        losses, moved = replay(cfg, t, devices, p0, batches, key=key,
                               param_dtype=param_dtype)
        return losses, moved[0], moved[-1]

    t0 = time.perf_counter()
    ref_losses, ref_d1, ref_d3 = reference()
    if ctx.fault == "control":
        # the reference one precision below the configuration's in the
        # program's place: parameters kept in float8 between updates
        check_losses, d1, d3 = reference(jnp.float8_e4m3fn)
    reference_s = time.perf_counter() - t0
    # compare's numbers are per-leaf norms of the changes p1 - p0 and
    # p3 - p0; it is handed each change as those norms
    zero = _as_leaves([0.0] * len(p0))
    check = {"losses": check_losses, "p0": zero, "p1": _as_leaves(d1),
             "p3": _as_leaves(d3), "step_losses": [], "step_grads": []}
    ref_read = {"losses": ref_losses, "p0": zero, "p1": _as_leaves(ref_d1),
                "p3": _as_leaves(ref_d3), "step_losses": [],
                "step_grads": [], "hub_weight": []}
    ctx.readings = compare.readings(check, ref_read)
    ctx.detail = {"losses": check_losses, "ref_losses": ref_losses,
                  "window_losses": [losses[0], losses[-1]],
                  "reference_s": reference_s,
                  **compare.explain(check, ref_read,
                                    [p.shape for p in p0])}


# ---------------------------------------------------------------------------
# faults, planted only by the control and fault checks (never in a run)
# ---------------------------------------------------------------------------

def _plant(fault, patches):
    from repro.launch import step_builders
    from repro.models import hubert
    exchange = step_builders.exchange_delta
    if fault == "sum":  # the pods' deltas summed, not averaged
        patches.set(step_builders, "exchange_delta",
                    lambda delta, **k: exchange(delta, **k) * k["n_pods"])
    elif fault == "skipped":  # no exchange: the anchor stays as it was
        patches.set(step_builders, "exchange_delta",
                    lambda delta, **k: jnp.zeros(delta.shape[1:],
                                                 jnp.float32))
    elif fault == "all_frames":  # the loss over every frame
        patches.set(hubert, "masked_mean", lambda values, mask: jnp.mean(
            values))
    elif fault == "half_batch":  # each local step sees half of its batch
        loss = hubert.HuBERT.loss
        patches.set(hubert.HuBERT, "loss", lambda self, params, batch: loss(
            self, params, {k: v[: v.shape[0] // 2] for k, v in batch.items()}))
    elif fault == "local_only":  # no exchange between the chips: the
        # anchor read back takes the first pod's own delta, as that pod
        # would if each kept only its own
        patches.set(step_builders, "exchange_delta",
                    lambda delta, **k: delta[0])
