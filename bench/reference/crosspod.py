"""Plain reference of the cross-pod FL round: each pod's local AdamW steps
from the round's anchor on its own batches, the int8 exchange of the
pods' deltas with one scale per leaf shared by the pods, and the reset of
every pod to the new anchor. It imports nothing of the program.

It replays the rounds the program ran first, from the same initial anchor
and batches. Per round r and local step i the learning rate is the
traffic's warm-up-then-cosine schedule at step ``r * local_steps + i``;
AdamW clips the gradient to a global norm, corrects both moments' bias
and decays the weights decoupled from the gradient. Each pod keeps its
own moments across rounds.

It fits "in blocks": one pod at a time, that pod's float32 replica laid
across the devices it is given (each leaf split along its last axis that
they divide), the pods' moments kept on the devices in that layout, and
each pod's parameters after its local steps brought to the host until the
exchange, which then runs leaf by leaf.

Everything is computed in float32 at the highest matmul precision.
``param_dtype`` is the precision the parameters and the anchor are kept
in between updates: float32 for the reference, lower for a control.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import hubert

F32 = jnp.float32


def learning_rate(t: dict, step: int) -> float:
    """Linear warm-up over ``warmup_steps``, then cosine from the base rate
    down to ``min_lr_ratio`` of it at ``total_steps``."""
    base, warm = t["learning_rate"], t["warmup_steps"]
    if step < warm:
        return base * min(1.0, (step + 1) / max(warm, 1))
    frac = min(max((step - warm) / max(t["total_steps"] - warm, 1), 0.0),
               1.0)
    lo = t["min_lr_ratio"]
    return base * (lo + (1 - lo) * 0.5 * (1 + math.cos(math.pi * frac)))


class Reference:
    def __init__(self, config: dict, traffic: dict, devices,
                 param_dtype=F32):
        self.cfg, self.t = config, traffic
        self.param_dtype = param_dtype
        self.mesh = Mesh(np.asarray(devices), ("x",))
        self.n_dev = len(devices)
        self.replicated = NamedSharding(self.mesh, P())
        self.by_pod = NamedSharding(self.mesh, P("x"))
        shapes = hubert.param_shapes(config)
        self.treedef = jax.tree.structure(
            shapes, is_leaf=lambda x: isinstance(x, tuple))
        self.layout = jax.tree.map(self._sharding, shapes,
                                   is_leaf=lambda x: isinstance(x, tuple))
        self._step = jax.jit(self._train_step, donate_argnums=(0, 1, 2),
                             out_shardings=(self.layout, self.layout,
                                            self.layout, None, None))
        self._exchange = jax.jit(self._exchange_leaf)
        self._copy = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree),
                             out_shardings=self.layout)
        self._zeros = jax.jit(lambda tree: jax.tree.map(jnp.zeros_like,
                                                        tree),
                              out_shardings=self.layout)

    def _sharding(self, shape):
        axis = next((a for a in reversed(range(len(shape)))
                     if shape[a] % self.n_dev == 0), None)
        spec = [None] * len(shape)
        if axis is not None:
            spec[axis] = "x"
        return NamedSharding(self.mesh, P(*spec))

    def _keep(self, tree):
        """Parameters as kept between updates."""
        if self.param_dtype == F32:
            return tree
        return jax.tree.map(
            lambda x: x.astype(self.param_dtype).astype(F32), tree)

    # -- one local step --------------------------------------------------
    def _train_step(self, params, m, v, count, batch, lr):
        t = self.t
        loss, g = hubert.loss_and_grad(self.cfg, params, batch)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(
            1.0, t["grad_clip"] / jnp.maximum(gnorm, 1e-9)), g)
        count = count + 1
        b1, b2 = t["beta1"], t["beta2"]
        c1 = 1.0 - b1 ** count.astype(F32)
        c2 = 1.0 - b2 ** count.astype(F32)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        params = jax.tree.map(
            lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + t["eps"])
                                      + t["weight_decay"] * p), params, m, v)
        return params, m, v, count, loss

    # -- the exchange of one leaf ----------------------------------------
    def _exchange_leaf(self, ends, anchor):
        """ends (pods, ...): each pod's leaf after its local steps."""
        delta = ends - anchor[None]
        scale = jnp.max(jnp.abs(delta)) / 127.0 + 1e-12
        q = jnp.clip(jnp.round(delta / scale), -127, 127)
        return anchor + jnp.sum(q, axis=0) * scale / ends.shape[0]

    # -- rounds ----------------------------------------------------------
    def run(self, p0: List[np.ndarray], batches: List[Dict]) -> Dict:
        """``p0``: the initial anchor's leaves (host, in ``param_shapes``
        order); ``batches``: per round, leaves shaped (pods, local steps,
        pod batch, ...). -> {"losses": per round, the mean over pods and
        local steps; "anchors": per round, the new anchor; "start": the
        initial anchor as kept}, anchors on the devices."""
        t = self.t
        pods, steps = batches[0]["waveform"].shape[:2]
        put = lambda leaves: jax.tree.unflatten(self.treedef, [  # noqa: E731
            jax.device_put(np.asarray(x, np.float32), s) for x, s in zip(
                leaves, jax.tree.leaves(self.layout))])
        anchor = start = self._keep(put(p0))
        moments = [(self._zeros(anchor), self._zeros(anchor),
                    jnp.zeros((), jnp.int32)) for _ in range(pods)]
        out = {"losses": [], "anchors": [], "start": start}
        for r, host in enumerate(batches):
            ends, losses = [], []
            for pod in range(pods):
                params = self._copy(anchor)
                m, v, count = moments[pod]
                for i in range(steps):
                    batch = jax.device_put({k: x[pod, i] for k, x in
                                            host.items()}, self.replicated)
                    lr = learning_rate(t, r * steps + i)
                    with jax.default_matmul_precision("highest"):
                        params, m, v, count, loss = self._step(
                            params, m, v, count, batch, F32(lr))
                    params = self._keep(params)
                    losses.append(loss)
                moments[pod] = (m, v, count)
                ends.append([np.asarray(x) for x in
                             jax.device_get(jax.tree.leaves(params))])
                del params
            new = []
            for j, (a, lay) in enumerate(zip(jax.tree.leaves(anchor),
                                             jax.tree.leaves(self.layout))):
                stacked = jax.device_put(np.stack([e[j] for e in ends]),
                                         self.by_pod)
                new.append(jax.device_put(self._exchange(stacked, a), lay))
            del ends
            anchor = self._keep(jax.tree.unflatten(self.treedef, new))
            out["losses"].append(float(np.mean(jax.device_get(losses))))
            out["anchors"].append(anchor)
        return out


_replayed: Dict = {}


def replay(config: dict, traffic: dict, devices, p0, batches, *, key: str,
           param_dtype=F32):
    """``Reference.run`` reduced to (its losses, per round the per-leaf
    distances of the anchor from the start). Computed once per ``key`` in
    a process: the control checks plant several faults on one seed."""
    if key not in _replayed:
        out = Reference(config, traffic, devices, param_dtype).run(p0,
                                                                   batches)
        _replayed[key] = (out["losses"], [distances(a, out["start"])
                                          for a in out["anchors"]])
    return _replayed[key]


@jax.jit
def _distances(tree, start):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(F32) - b.astype(F32))))
            for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(start))]


def distances(tree, start) -> List[float]:
    """Per leaf, the Euclidean norm of ``tree - start``, on the devices."""
    return [float(d) for d in jax.device_get(_distances(tree, start))]
