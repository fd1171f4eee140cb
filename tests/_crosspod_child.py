"""Run by ``test_crosspod_round.py`` in a process of its own, with four
host devices: two rounds of ``repro.launch.fl_round`` at a small size, the
same rounds through ``bench/reference/crosspod.py``, and the compiled
round's collectives. Prints one JSON object."""
import dataclasses
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench.reference import crosspod as ref_crosspod  # noqa: E402
from repro import obs  # noqa: E402
from repro.configs import smoke_config  # noqa: E402
from repro.configs.base import TrainConfig  # noqa: E402
from repro.launch.fl_round import CrossPodRound  # noqa: E402

PODS, STEPS, BATCH, SAMPLES, ROUNDS = 4, 2, 2, 10_320, 2
TRAFFIC = {"learning_rate": 3e-4, "warmup_steps": 1, "total_steps": 10 ** 9,
           "min_lr_ratio": 0.1, "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
           "weight_decay": 0.01, "grad_clip": 1.0}


def reference_config(cfg):
    return {"num_layers": cfg.num_layers, "d_model": cfg.d_model,
            "num_heads": cfg.num_heads, "d_ff": cfg.d_ff,
            "conv_channels": cfg.conv_channels,
            "conv_kernels": list(cfg.conv_kernels),
            "conv_strides": list(cfg.conv_strides),
            "pos_conv_kernel": cfg.pos_conv_kernel,
            "pos_conv_groups": cfg.pos_conv_groups,
            "final_dim": cfg.final_dim, "num_clusters": cfg.vocab_size,
            "logit_temp": cfg.logit_temp,
            "feature_pen_weight": cfg.feature_pen_weight,
            "norm_eps": cfg.norm_eps}


def main():
    # float32 throughout, so that the program and the reference agree to
    # round-off (the configuration's bfloat16 is the cell's business)
    cfg = dataclasses.replace(smoke_config("hubert-xlarge"), dtype="float32",
                              param_dtype="float32")
    t = dict(TRAFFIC)
    tcfg = TrainConfig(learning_rate=t["learning_rate"],
                       warmup_steps=t["warmup_steps"],
                       total_steps=t["total_steps"],
                       crosspod_compression="int8")
    run = CrossPodRound(cfg, pods=PODS, local_steps=STEPS, pod_batch=BATCH,
                        samples=SAMPLES, train_cfg=tcfg, seed=11)
    # the anchor and batches as a caller hands them to ``start``
    anchor = jax.device_get(run.init_anchor())
    p0 = [np.asarray(x) for x in jax.tree.leaves(anchor)]
    batches = [run.host_batch(r) for r in range(ROUNDS)]
    with jax.default_matmul_precision("highest"):
        ref = ref_crosspod.Reference(reference_config(cfg), t,
                                     run.devices).run(p0, batches)
    rec = obs.enable()
    run.start(anchor=anchor, batches=batches)
    losses, anchors = [], []
    for _ in range(ROUNDS):
        losses.append(run.round())
        anchors.append([np.asarray(x) for x in jax.device_get(
            jax.tree.leaves(run.anchor))])
    obs.disable()

    # per round and leaf: the largest gap to the reference, and how far
    # the reference moved the leaf
    leaves = [[[float(np.max(np.abs(a - b))),
                float(np.max(np.abs(b - base)))]
               for a, b, base in zip(ours, jax.device_get(
                   jax.tree.leaves(theirs)), p0)]
              for ours, theirs in zip(anchors, ref["anchors"])]
    b = run.bundle
    hlo = jax.jit(b.fn, in_shardings=b.in_shardings,
                  out_shardings=b.out_shardings).lower(
                      *b.in_specs).compile().as_text()
    gathers = [[dt, [int(n) for n in dims.split(",") if n]] for dt, dims in
               re.findall(r"= (\w+)\[([\d,]*)\]\S* all-gather\(", hlo)]
    names = {s.id: s.name for s in rec.spans}
    print(json.dumps({
        "losses": losses, "ref_losses": ref["losses"], "leaves": leaves,
        "gathers": gathers, "leaf_shapes": [list(x.shape) for x in p0],
        "exchange_bytes": b.exchange_bytes,
        "spans": {s.name: names.get(s.parent) for s in rec.spans},
        "counts": {n: rec.total(n) for n in (
            "crosspod.rounds", "crosspod.local_steps",
            "crosspod.exchange_bytes", "hubert.frames",
            "hubert.masked_frames")},
        "h2d": [[c.attrs.get("site"), c.n] for c in rec.counts
                if c.name == "copy.h2d_bytes"],
        "batch_bytes": sum(v.nbytes for v in batches[0].values()),
        "frames": int(batches[0]["mask"].size),
        "masked": [int(x["mask"].sum()) for x in batches]}))


if __name__ == "__main__":
    main()
