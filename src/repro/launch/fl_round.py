"""The cross-pod FL round, run round after round.

    PYTHONPATH=src python -m repro.launch.fl_round --arch hubert-xlarge \
        --pods 4 --rounds 10 --local-steps 4 --pod-batch 4 --samples 160000

Each pod stands for one silo's accelerator and holds a whole replica: the
``pod`` mesh axis runs over the first ``--pods`` devices. A round is
``launch.step_builders.make_fl_round_step``'s program: every pod takes
``--local-steps`` AdamW steps on its own batches, the pods exchange their
deltas (int8 with one shared scale per leaf, all-gathered over ``pod``, or
float32), and every pod is reset to the new anchor (DiLoCo-style).

Weights and every round's batches come from ``--seed``; each round's
batches are drawn and uploaded while the device runs the round before.
``--smoke`` runs the family's small configuration (CPU-sized). On the
CPU, ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` gives four
devices. ``--spans PATH`` records the spans and counters below (names in
README.md) and writes them as JSONL when the run ends.

Spans: ``crosspod.round`` (``round``) around ``crosspod.dispatch`` (the
call), ``crosspod.input`` (drawing and uploading the next round's
batches) and ``crosspod.sync`` (waiting for the round). Counters, per
round: ``crosspod.rounds``, ``crosspod.local_steps`` (pods x local
steps), ``crosspod.exchange_bytes`` (what one pod sends),
``hubert.frames`` and ``hubert.masked_frames``; per upload
``copy.h2d_bytes`` (``site="crosspod.input"``).
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import ARCH_ORDER, get_config, smoke_config
from repro.configs.base import (MeshConfig, ModelConfig, ShapeConfig,
                                TrainConfig)
from repro.data.synthetic import audio_batch, cluster_templates
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.step_builders import make_fl_round_step
from repro.models.hubert import frames_for
from repro.optim.optimizers import adamw_init


def seed_key(seed: int):
    """A PRNG key from any whole seed (more bits than int32 holds)."""
    k = jax.random.fold_in(jax.random.key(0), (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, seed & 0xFFFFFFFF)


class CrossPodRound:
    """One deployment of the round: mesh, compiled step, data and state.

    ``start()`` makes the anchor, the pods' parameters and optimizer
    states from the seed (one jitted init each, laid out as the step takes
    them) and uploads round 0's batches; each ``round()`` then runs one
    round and returns its loss (the mean over pods and local steps)."""

    def __init__(self, cfg: ModelConfig, *, pods: int, local_steps: int,
                 pod_batch: int, samples: int, train_cfg: TrainConfig,
                 seed: int, devices=None):
        if cfg.family != "audio":
            raise ValueError(f"{cfg.name}: the round's data generator makes "
                             f"audio; {cfg.family} is not supported")
        self.cfg, self.train_cfg, self.seed = cfg, train_cfg, seed
        self.pods, self.local_steps = pods, local_steps
        self.pod_batch, self.samples = pod_batch, samples
        self.frames = frames_for(cfg, samples)
        self.devices = list(devices or jax.devices()[:pods])
        if len(self.devices) != pods:
            raise ValueError(f"{pods} pods need {pods} devices, found "
                             f"{len(self.devices)}")
        mesh_cfg = MeshConfig(shape=(pods, 1, 1),
                              axis_names=("pod", "data", "model"))
        self.mesh = make_mesh(mesh_cfg, devices=self.devices)
        shape = ShapeConfig(name="crosspod", seq_len=samples,
                            global_batch=pods * pod_batch, kind="train")
        self.bundle = b = make_fl_round_step(cfg, shape, self.mesh, mesh_cfg,
                                             train_cfg,
                                             local_steps=local_steps)
        ps_sh, os_sh, a_sh, self.batch_sharding, _ = b.in_shardings
        self._anchor_sharding = a_sh
        self._step = jax.jit(b.fn, in_shardings=b.in_shardings,
                             out_shardings=b.out_shardings,
                             donate_argnums=(0, 1, 2))
        model = b.model
        self._init_anchor = jax.jit(lambda k: model.init(k)[0],
                                    out_shardings=a_sh)
        self._init_params = jax.jit(lambda a: jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (pods,) + x.shape), a),
            out_shardings=ps_sh)
        self._init_opt = jax.jit(jax.vmap(lambda p: adamw_init(p, train_cfg)),
                                 out_shardings=os_sh)
        hop = math.prod(cfg.conv_strides)
        self.templates = cluster_templates(np.random.default_rng([seed, 0]),
                                           cfg.vocab_size, hop)
        self.params = self.opt = self.anchor = None
        self.r = 0
        self._given = []
        self._next = None
        self._compiled = None

    # -- data ------------------------------------------------------------
    def host_batch(self, r: int) -> dict:
        """Round ``r``'s batches, drawn from the seed: each leaf shaped
        (pods, local steps, pod batch, ...)."""
        cfg = self.cfg
        lead = (self.pods, self.local_steps, self.pod_batch)
        flat = audio_batch(np.random.default_rng([self.seed, 1, r]),
                           self.templates, math.prod(lead), self.samples,
                           self.frames, mask_prob=cfg.mask_prob,
                           mask_length=cfg.mask_length)
        return {k: v.reshape(lead + v.shape[1:]) for k, v in flat.items()}

    def _prepare(self, r: int):
        host = self._given[r] if r < len(self._given) else self.host_batch(r)
        obs.count("copy.h2d_bytes", sum(v.nbytes for v in host.values()),
                  site="crosspod.input")
        return (jax.device_put(host, self.batch_sharding),
                host["mask"].size, int(host["mask"].sum()))

    def compile(self):
        """The round compiled for its arguments' shapes and layouts; the
        first ``round()`` compiles it otherwise. May run in a thread of
        its own while the devices do other work."""
        if self._compiled is None:
            with self.mesh:
                self._compiled = self._step.lower(
                    *self.bundle.in_specs).compile()
        return self._compiled

    # -- state -----------------------------------------------------------
    def init_anchor(self):
        """The initial anchor, on the devices, from the seed."""
        with self.mesh:
            return self._init_anchor(seed_key(self.seed))

    def start(self, anchor=None, batches=()):
        """Make the state and upload round 0's batches. ``anchor``: the
        initial anchor, a tree laid out as the model's parameters (any
        float dtype, on the host), in place of the seed's; ``batches``:
        host batches as ``host_batch`` makes them, for the first rounds,
        in place of the seed's draws."""
        if anchor is None:
            self.anchor = self.init_anchor()
        else:
            dt = jnp.dtype(self.cfg.param_dtype)
            self.anchor = jax.device_put(
                jax.tree.map(lambda x: np.asarray(x, dt), anchor),
                self._anchor_sharding)
        self._given = list(batches)
        with self.mesh:
            self.params = self._init_params(self.anchor)
            self.opt = self._init_opt(self.params)
        self.r = 0
        self._next = self._prepare(0)

    def release(self):
        """Drop the round's device state."""
        self.params = self.opt = self.anchor = self._next = None
        self._given = []

    # -- one round -------------------------------------------------------
    def round(self) -> float:
        r = self.r
        with obs.span("crosspod.round", round=r):
            batch, frames, masked = self._next
            with obs.span("crosspod.dispatch"), self.mesh:
                self.params, self.opt, self.anchor, loss = self.compile()(
                    self.params, self.opt, self.anchor, batch,
                    jnp.int32(r * self.local_steps))
            with obs.span("crosspod.input"):
                self._next = self._prepare(r + 1)
            with obs.span("crosspod.sync"):
                jax.block_until_ready((self.params, self.opt, self.anchor))
                loss = float(loss)
            obs.count("crosspod.rounds", 1)
            obs.count("crosspod.local_steps", self.pods * self.local_steps)
            obs.count("crosspod.exchange_bytes", self.bundle.exchange_bytes)
            obs.count("hubert.frames", frames)
            obs.count("hubert.masked_frames", masked)
        self.r = r + 1
        return loss


def _parser():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="hubert-xlarge", choices=ARCH_ORDER)
    ap.add_argument("--smoke", action="store_true",
                    help="the family's small configuration")
    ap.add_argument("--pods", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--pod-batch", type=int, default=4,
                    help="utterances per pod per local step")
    ap.add_argument("--samples", type=int, default=160_000,
                    help="waveform samples per utterance (16 kHz)")
    ap.add_argument("--compression", default="int8", choices=("int8", "none"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--spans", default=None, metavar="PATH",
                    help="record the run's spans and counters (repro.obs) "
                         "and write them to PATH as JSONL when it ends")
    return ap


def parse_args(argv=None) -> argparse.Namespace:
    return _parser().parse_args(argv)


def build(args: argparse.Namespace) -> CrossPodRound:
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    # lr 3e-4 after one warm-up step; the cosine decay is flat over a run
    tcfg = TrainConfig(learning_rate=3e-4, warmup_steps=1,
                       total_steps=10 ** 9,
                       crosspod_compression=args.compression)
    return CrossPodRound(cfg, pods=args.pods, local_steps=args.local_steps,
                         pod_batch=args.pod_batch, samples=args.samples,
                         train_cfg=tcfg, seed=args.seed)


def main(argv=None) -> int:
    args = parse_args(argv)
    rec = obs.enable() if args.spans else None
    try:
        return _main(args)
    finally:
        if rec is not None:
            obs.disable()
            rec.write_jsonl(args.spans)


def _main(args) -> int:
    enable_compile_cache()
    run = build(args)
    print(f"[crosspod] {run.cfg.name}: {run.cfg.num_layers} layers, "
          f"{args.pods} pods, {args.local_steps} local steps of "
          f"{args.pod_batch} x {args.samples} samples ({run.frames} "
          f"frames), {args.compression} exchange of "
          f"{run.bundle.exchange_bytes} B per pod")
    run.start()
    losses = []
    for _ in range(args.rounds):
        t0 = time.perf_counter()
        losses.append(run.round())
        print(f"[crosspod] round {run.r - 1} loss={losses[-1]!r} "
              f"wall_s={time.perf_counter() - t0!r}", flush=True)
    print(json.dumps({"losses": losses}))
    return 0 if all(math.isfinite(l) for l in losses) else 1


if __name__ == "__main__":
    sys.exit(main())
