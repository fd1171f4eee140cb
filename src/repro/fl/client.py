"""FL client: local training + timing breakdown.

Two compute modes:
* live       — real jit'd local SGD on the client's silo shard (tests,
               examples, small tiers);
* simulated  — training time charged from the tier's calibrated
               per-round seconds (paper-scale Fig 5 runs with virtual
               payloads).

Migration = host<->accelerator staging of the payload (the paper's
'CPU-GPU migration' state); charged at PCIe-class bandwidth, or measured
when live.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.message import FLMessage, TensorPayload, VirtualPayload
from repro.fl.flat import FlatParams

PCIE_BW = 12e9  # bytes/s host<->device staging


@dataclasses.dataclass
class ClientTiming:
    communication: float = 0.0
    migration: float = 0.0
    serialization: float = 0.0
    waiting: float = 0.0
    training: float = 0.0


class FLClient:
    def __init__(self, client_id: str, backend, *, dataset=None,
                 train_fn: Optional[Callable] = None,
                 sim_train_s: float = 0.0, batch_size: int = 16,
                 straggle_factor: float = 1.0, seed: int = 0):
        """train_fn(params, batch) -> (new_params, loss) — jit'd by caller.

        ``sim_train_s`` > 0 with a live ``train_fn`` trains for real but
        charges the calibrated time instead of measured wall seconds —
        "live compute, simulated clock", which keeps event-driven runs
        deterministic (jit compile jitter never leaks into the sim)."""
        self.client_id = client_id
        self.backend = backend
        self.dataset = dataset
        self.train_fn = train_fn
        self.sim_train_s = sim_train_s
        self.batch_size = batch_size
        self.straggle_factor = straggle_factor
        self.seed = seed
        self._round = 0
        self._sends = 0  # distinct virtual updates must not alias in the
        # object store's content-addressed cache (each round re-uploads)

    # ------------------------------------------------------------------
    def local_train(self, params, local_steps: int):
        """Live local training. Returns (new_params, mean_loss, seconds).

        Each step is a ``client.step`` span split into ``step.input``
        (the batch draw and its upload), ``step.dispatch`` (the jitted
        step's call) and ``step.sync`` (the loss read back, which waits
        for the step); the upload and read-back bytes are counted.

        A ``train_fn`` may return the parameters as a ``FlatParams``
        carrier and take it back on the next step (``client.flat_steps``
        counts those calls); the epoch's result is then turned back into
        the model's tree (``step.unpack``)."""
        with obs.span("client.local_train", steps=local_steps) as sp:
            if obs.recording():
                obs.count("copy.h2d_bytes", sum(
                    np.asarray(l).nbytes for l in jax.tree.leaves(params)
                    if not isinstance(l, jax.Array)), site="model")
            it = self.dataset.batches(self.batch_size,
                                      seed=self.seed + self._round)
            losses = []
            for _ in range(local_steps):
                with obs.span("client.step"):
                    with obs.span("step.input"):
                        host = next(it)
                        batch = {k: jnp.asarray(v) for k, v in host.items()}
                    flat = isinstance(params, FlatParams)
                    with obs.span("step.dispatch"):
                        params, loss = self.train_fn(params, batch)
                    with obs.span("step.sync"):
                        losses.append(float(loss))
                if obs.recording():
                    obs.count("client.steps", 1)
                    if flat:
                        obs.count("client.flat_steps", 1)
                    obs.count("copy.h2d_bytes", sum(
                        np.asarray(v).nbytes for v in host.values()),
                        site="batch")
                    obs.count("copy.d2h_bytes", np.dtype(loss.dtype).itemsize,
                              site="loss")
            if isinstance(params, FlatParams):
                with obs.span("step.unpack"):
                    params = params.tree()
            jax.block_until_ready(jax.tree.leaves(params)[0])
        return params, float(np.mean(losses)), sp.seconds

    # ------------------------------------------------------------------
    def run_round(self, msg: FLMessage, ready_t: float, local_steps: int,
                  server_id: str = "server"):
        """Handle one received global model; returns (update_msg, timing,
        send_start_t). Works in live or simulated mode depending on the
        payload type."""
        version = msg.metadata.get("version", msg.round)
        with obs.span("client.update",
                      update=f"{self.client_id}/v{version}"):
            return self._run_round(msg, ready_t, local_steps, server_id,
                                   version)

    def _run_round(self, msg, ready_t, local_steps, server_id, version):
        self._round = msg.round
        timing = ClientTiming()
        payload = msg.payload
        nbytes = payload.nbytes
        # host -> device staging
        mig_in = nbytes / PCIE_BW
        timing.migration += mig_in
        t = ready_t + mig_in

        if isinstance(payload, VirtualPayload) or self.train_fn is None:
            train_s = self.sim_train_s * self.straggle_factor
            self._sends += 1
            update_payload = VirtualPayload(
                nbytes, tag=f"upd:{self.client_id}:{self._sends}")
            num_examples = 128
        else:
            new_params, loss, train_s = self.local_train(payload.tree,
                                                         local_steps)
            if self.sim_train_s > 0:
                train_s = self.sim_train_s  # live compute, simulated clock
            train_s *= self.straggle_factor
            update_payload = TensorPayload(new_params)
            num_examples = self.dataset.num_examples()
            self.last_loss = loss
        timing.training += train_s
        t += train_s
        # device -> host staging of the update
        mig_out = update_payload.nbytes / PCIE_BW
        timing.migration += mig_out
        t += mig_out
        update = FLMessage("client_update", self.client_id, server_id,
                           round=msg.round, payload=update_payload,
                           metadata={"num_examples": num_examples,
                                     # global version this update was
                                     # trained against (async staleness)
                                     "version": version})
        return update, timing, t
