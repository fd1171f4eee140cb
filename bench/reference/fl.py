"""Plain reference of the live FL steps: local SGD, the int8 update codec,
FedAvg, the staleness-weighted streaming fold and the damped merge.

Inputs are the benchmark's own: the initial weights it made, its silos,
the data draws each client made, and, for the event-driven modes, the
schedule of the run (which client's update, trained on which global
version, reached the hub for which merge). Each update's weight and
staleness discount are worked out here. Nothing the program computed is
read. ``dtype`` is the precision of all of it: float32 at the highest
matmul precision for the reference, bfloat16 for the control.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from . import vision


class Reference:
    def __init__(self, config: dict, traffic: dict, silos, dtype):
        self.config = config
        self.traffic = traffic
        self.silos = silos
        self.dtype = dtype
        self.sgd = vision.make_local_sgd(config, traffic["learning_rate"],
                                         dtype)

    def _precision(self):
        return jax.default_matmul_precision(
            "highest" if self.dtype == jnp.float32 else "default")

    def client_update(self, params, silo: int, draw_seed: int):
        """Local SGD of one client from ``params``: -> (params, mean loss)."""
        t = self.traffic
        ds = self.silos[silo]
        idx = ds.batch_indices(t["batch_size"], draw_seed, t["local_steps"])
        images = np.stack([ds.features[i] for i in idx])
        labels = np.stack([ds.labels[i] for i in idx])
        with self._precision():
            p, loss = self.sgd(params, jnp.asarray(images),
                               jnp.asarray(labels))
        return p, float(loss)

    def first_steps(self, params0, draw_seeds: List[int]):
        """Each client's first local step from ``params0``, on the first
        batch of its first draw: -> ([loss], [gradient as leaves]), the
        gradient worked out from the state after the step, ``(p0 - p) /
        lr``, as the optimizer got it."""
        t = self.traffic
        lr = t["learning_rate"]
        step = vision.make_local_sgd(self.config, lr, self.dtype)
        losses, grads = [], []
        for i, seed in enumerate(draw_seeds):
            ds = self.silos[i]
            idx = ds.batch_indices(t["batch_size"], seed, 1)[0]
            with self._precision():
                p, loss = step(params0, jnp.asarray(ds.features[idx][None]),
                               jnp.asarray(ds.labels[idx][None]))
            losses.append(float(loss))
            grads.append([(np.asarray(a, np.float32)
                           - np.asarray(b, np.float32)) / lr
                          for a, b in zip(jax.tree.leaves(params0),
                                          jax.tree.leaves(p))])
        return losses, grads

    # -- the int8 block codec of the update path ---------------------------
    def qsgd_roundtrip(self, params, block: int):
        """Blockwise symmetric int8 over the leaves laid end to end:
        scale = max|x| / 127 per block of ``block``, code = round(x /
        scale) in [-127, 127], decoded as code * scale."""
        leaves, treedef = jax.tree.flatten(params)
        flat = jnp.concatenate([l.reshape(-1).astype(self.dtype)
                                for l in leaves])
        n = flat.shape[0]
        pad = (-n) % block
        x = jnp.pad(flat, (0, pad)).reshape(-1, block)
        scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0
        inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
        recon = (jnp.clip(jnp.round(x * inv), -127, 127) * scale
                 ).reshape(-1)[:n]
        out, off = [], 0
        for l in leaves:
            out.append(recon[off:off + l.size].reshape(l.shape))
            off += l.size
        return jax.tree.unflatten(treedef, out)

    # -- sync FedAvg -------------------------------------------------------
    def sync_rounds(self, params0, draws: List[List[int]], n_rounds: int):
        """``draws[r][i]``: client i's draw seed in round r. Every client
        trains from the global model and the server takes the example-
        weighted mean of the client models. -> [(mean loss, params,
        sum of the weights)]."""
        out, g = [], jax.tree.map(lambda a: jnp.asarray(a, self.dtype),
                                  params0)
        for r in range(n_rounds):
            models, losses, weights = [], [], []
            for i, seed in enumerate(draws[r]):
                p, l = self.client_update(g, i, seed)
                models.append(p)
                losses.append(l)
                weights.append(self.silos[i].num_examples())
            w = jnp.asarray(weights, self.dtype) / sum(weights)
            g = jax.tree.map(
                lambda *ls: sum(wi * li for wi, li in zip(w, ls)), *models)
            out.append((float(np.mean(losses)), g, float(sum(weights))))
        return out

    # -- fedbuff with the streaming hub -----------------------------------
    def fedbuff_merges(self, params0, schedule: List[List[Dict]]):
        """``schedule[k]``: the updates that reached the hub for merge k,
        each ``{"client", "version", "draw_seed"}``. Each update is the
        client's model trained from global version ``version``, sent
        through the int8 codec (when the mix has one), and folded as
        ``acc += weight * (1 + staleness)^-a * update``, with the client's
        example count as its weight and ``k - version`` as its staleness;
        a merge takes ``m = acc / sum(eff)``, ``lam = server_lr * sum(eff)
        / sum(weight)`` clipped to [0, 1], and the global becomes ``(1 -
        lam) * g + lam * m``. -> [(mean loss, params, sum(eff))]."""
        t = self.traffic
        a = t["staleness_exponent"]
        codec = t.get("update_codec_block")
        versions = [jax.tree.map(lambda x: jnp.asarray(x, self.dtype),
                                 params0)]
        out = []
        for k, merge in enumerate(schedule):
            acc, sum_eff, sum_w, losses = None, 0.0, 0.0, []
            for u in merge:
                p, l = self.client_update(versions[u["version"]],
                                          u["client"], u["draw_seed"])
                if codec:
                    p = self.qsgd_roundtrip(p, codec)
                weight = float(self.silos[u["client"]].num_examples())
                staleness = k - u["version"]
                eff = weight * (1.0 + max(staleness, 0)) ** (-a)
                sum_eff += eff
                sum_w += weight
                e = jnp.asarray(eff, self.dtype)
                acc = (jax.tree.map(lambda x: e * x, p) if acc is None
                       else jax.tree.map(lambda s, x: s + e * x, acc, p))
                losses.append(l)
            merged = jax.tree.map(
                lambda s: s / jnp.asarray(sum_eff, self.dtype), acc)
            lam = min(max(t["server_lr"] * sum_eff / sum_w, 0.0), 1.0)
            g = versions[-1]
            if lam < 1.0 - 1e-12:
                la = jnp.asarray(lam, self.dtype)
                merged = jax.tree.map(lambda x, m: (1 - la) * x + la * m,
                                      g, merged)
            versions.append(merged)
            out.append((float(np.mean(losses)), merged, sum_eff))
        return out
