"""Entry: the live FL deployment of ``repro.launch.fl_train``.

Set-up builds the deployment (``fl_train.build_deployment``) on the
benchmark's silos and weights, then drives its round entry through the
first ``check_steps`` aggregation steps: sync rounds
(``FLServer.run_round``) or fedbuff merges (one ``FLServer.run_async``
call). Those steps compile every shape and are what the reference checks.
The same deployment then runs on into the measured window: sync rounds
until ``seconds`` have passed, or the same ``run_async`` call until the
first merge that ends after them. Both loops are closed: a round starts
when the previous one ended; a client gets its next model only once its
update has reached the hub.

Host spans wrap the calls into each layer (see ``Spans``); nothing
inside the program is changed.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import bytes as kbytes
from bench import compare, flops, silos as bench_silos
from bench.harness import BenchError, Spans
from bench.reference import vision
from bench.reference.fl import Reference


class Patches:
    """Attribute substitutions, undone on ``restore``."""

    def __init__(self):
        self._saved = []

    def set(self, obj, name, value):
        self._saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def restore(self):
        for obj, name, old in reversed(self._saved):
            setattr(obj, name, old)
        self._saved.clear()


def _seed_key(seed: int):
    """A PRNG key from any whole seed (more bits than int32 holds)."""
    k = jax.random.key(0)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, seed & 0xFFFFFFFF)


def make_weights(config: dict, seed: int):
    """The initial global model, made on the device in one jitted call."""
    return jax.jit(lambda k: vision.init(config, k))(_seed_key(seed))


def _host_leaves(tree) -> List[np.ndarray]:
    return [np.array(x, np.float32) for x in jax.device_get(
        jax.tree.leaves(tree))]


def run(ctx):
    from repro.configs.base import FLConfig
    from repro.core import channel as channel_mod
    from repro.core.backends import grpc_s3
    from repro.core.backends.base import CommBackend
    from repro.fl import aggregator, scheduler, server as server_mod
    from repro.fl.client import FLClient
    from repro.kernels import ops
    from repro.launch import fl_train

    cfg, t, run_ = ctx.config, ctx.traffic, ctx.run
    silos = bench_silos.make_silos(
        t["num_clients"], examples_per_silo=t["examples_per_silo"],
        num_classes=cfg["num_classes"], image_size=cfg["image_size"],
        alpha=t["label_alpha"], seed=ctx.seed)
    patches = Patches()
    ctx.on_exit.append(patches.restore)
    patches.set(fl_train, "make_silo_datasets", lambda *a, **k: silos)
    fl_cfg = FLConfig(num_clients=t["num_clients"], backend=t["backend"],
                      environment=t["environment"], mode=t["mode"],
                      buffer_k=t["buffer_k"],
                      staleness_exponent=t["staleness_exponent"],
                      server_lr=t["server_lr"], compression=t["compression"],
                      streaming_hub=t["streaming_hub"], seed=0)
    sc = fl_cfg.to_scenario(tier=cfg["tier"], local_steps=t["local_steps"],
                            reduced=cfg.get("program_reduced", False))
    try:
        server, prog_params, _env, _store = fl_train.build_deployment(
            fl_cfg, scenario=sc)
    finally:
        patches.restore()
    params0 = make_weights(cfg, ctx.seed)
    if (jax.tree.structure(prog_params) != jax.tree.structure(params0)
            or [x.shape for x in jax.tree.leaves(prog_params)]
            != [x.shape for x in jax.tree.leaves(params0)]):
        raise BenchError(f"the program's {cfg['name']} parameter layout "
                         f"differs from the configuration's")
    del prog_params
    clients = server.clients
    for c in clients:
        if c.batch_size != t["batch_size"]:
            raise BenchError(f"the program trains on batches of "
                             f"{c.batch_size}, the traffic says "
                             f"{t['batch_size']}")
    run_.flops_per_step = flops.train_step(cfg, t["batch_size"])

    # -- host spans around each layer ------------------------------------
    spans = Spans(traced=ctx.traced)
    run_.spans = spans.spans
    owner = {id(server.backend.channel): "server"}
    owner.update({id(c.backend.channel): c.client_id for c in clients})
    ch_side = lambda ch, *a, **k: owner.get(id(ch), "?")  # noqa: E731
    many_side = lambda items, *a, **k: (  # noqa: E731
        owner.get(id(items[0][0]), "?") if items else "?")
    Channel = channel_mod.Channel
    for name in ("encode", "decode", "decode_batch"):
        patches.set(Channel, name,
                    spans.wrap(getattr(Channel, name), "wire", ch_side))
    patches.set(channel_mod, "encode_many",
                spans.wrap(channel_mod.encode_many, "wire", many_side))
    hub = lambda *a, **k: "server"  # noqa: E731
    patches.set(server_mod, "fedavg",
                spans.wrap(server_mod.fedavg, "hub", hub))
    patches.set(scheduler, "fedavg",
                spans.wrap(scheduler.fedavg, "hub", hub))
    patches.set(aggregator.StreamingAccumulator, "fold",
                spans.wrap(aggregator.StreamingAccumulator.fold, "hub", hub))
    patches.set(FLClient, "local_train",
                spans.wrap(FLClient.local_train, "client_step",
                           lambda c, *a, **k: c.client_id))

    # -- per-update timing: from the client's receive of the model to its
    # update handed to the backend --------------------------------------
    open_updates: Dict[str, list] = {}

    def wrap_recv(cls):
        orig = cls.recv

        def recv(self, now):
            t0 = time.perf_counter()
            out = orig(self, now)
            if self.host_id != "server" and any(
                    m.msg_type == "model_sync" for m, _ in out):
                rec = [self.host_id, t0, None]
                open_updates[self.host_id] = rec
                run_.updates.append(rec)
            return out
        patches.set(cls, "recv", recv)

    def close_update(host):
        rec = open_updates.get(host)
        if rec is not None:
            rec[2] = time.perf_counter()

    wrap_recv(CommBackend)
    wrap_recv(grpc_s3.GrpcS3Backend)

    draws_by_client: Dict[str, list] = {c.client_id: [] for c in clients}
    orig_run_round = FLClient.run_round

    def client_run_round(self, msg, ready_t, local_steps, *a, **k):
        out = orig_run_round(self, msg, ready_t, local_steps, *a, **k)
        draws_by_client[self.client_id].append(
            (int(msg.metadata.get("version", msg.round)),
             self.dataset.draws[-1][1], getattr(self, "last_loss", None)))
        close_update(self.client_id)
        return out
    patches.set(FLClient, "run_round", client_run_round)

    def wrap_isend(cls):
        orig = cls.isend

        def isend(self, msg, now):
            out = orig(self, msg, now)
            if msg.msg_type == "client_update":
                close_update(self.host_id)
            return out
        patches.set(cls, "isend", isend)
    wrap_isend(CommBackend)
    wrap_isend(grpc_s3.GrpcS3Backend)

    # -- client steps and kernel calls -----------------------------------
    first_step: Dict[str, tuple] = {}  # client -> (loss, params after)
    for c in clients:
        step = c.train_fn
        if ctx.fault == "half_batch":
            step = _half_batch(step)

        def counted(params, batch, _step=step, _cid=c.client_id):
            out = _step(params, batch)
            run_.client_steps.append(time.perf_counter())
            if _cid not in first_step:
                first_step[_cid] = (float(out[1]), _host_leaves(out[0]))
            return out
        c.train_fn = counted

    def kernel(fn, name, nbytes):
        def wrapped(*a, **k):
            run_.kernel_calls.append((time.perf_counter(), name,
                                      nbytes(*a, **k)))
            return fn(*a, **k)
        return wrapped

    def _len(x):
        return int(np.prod(np.shape(x)))

    # the weight the sync hub's FedAvg combined in its latest call
    hub_weights: List[float] = []

    def averaged(fn):
        def wrapped(updates, weights, *a, **k):
            hub_weights.append(float(np.sum(np.asarray(weights,
                                                       np.float64))))
            return fn(updates, weights, *a, **k)
        return wrapped

    patches.set(ops, "fedavg_aggregate", averaged(kernel(
        ops.fedavg_aggregate, "fedavg",
        lambda ups, w, **k: kbytes.fedavg_reduce(
            len(ups), sum(_len(l) for l in jax.tree.leaves(ups[0]))))))
    patches.set(ops, "fedavg_accumulate_flat", kernel(
        ops.fedavg_accumulate_flat, "fedavg",
        lambda acc, x, w, **k: kbytes.fedavg_accumulate(_len(x))))
    patches.set(ops, "quantize_flat_batch", kernel(
        ops.quantize_flat_batch, "quantize",
        lambda flats, block=256, **k: kbytes.quantize_rows(
            [_len(f) for f in flats], block)))
    patches.set(ops, "dequantize_flat_batch", kernel(
        ops.dequantize_flat_batch, "quantize",
        lambda pl, **k: kbytes.dequantize_rows(
            sum(_len(p["q"]) // int(p["block"]) for p in pl),
            int(pl[0]["block"]) if pl else 1)))
    if ctx.fault == "half_updates":
        patches.set(server_mod, "fedavg", _half_updates(server_mod.fedavg))
        patches.set(aggregator.StreamingAccumulator, "fold",
                    _half_folds(aggregator.StreamingAccumulator.fold))
    if ctx.fault == "altered_update":
        patches.set(FLClient, "local_train",
                    _altered(FLClient.local_train, clients[0].client_id))

    n_check = t["check_steps"]
    check = {"losses": [], "p0": _host_leaves(params0)}
    if t["mode"] == "sync":
        _run_sync(ctx, server, params0, check, n_check, hub_weights)
    else:
        _run_async(ctx, server, fl_cfg, params0, check, n_check, patches,
                   spans, hub_weights)
    patches.restore()
    ctx.window_closed()
    run_.updates = [tuple(u) for u in run_.updates if u[2] is not None]
    run_.attempted = len(run_.updates_in_window())
    run_.failed = sum(1 for c in clients
                      if not np.isfinite(getattr(c, "last_loss", 0.0)))
    ctx.read_memory()

    # -- the reference, once the program's state is freed ----------------
    schedule = check.pop("schedule", None)
    first_draws = [draws_by_client[c.client_id][0][1] for c in clients]
    draws = [[draws_by_client[c.client_id][r][1] for c in clients]
             for r in range(n_check)] if t["mode"] == "sync" else None
    if schedule is not None:
        # each merge's loss: the mean of its updates' own client losses
        check["losses"] = []
        for merge in schedule:
            losses = []
            for u in merge:
                queue = draws_by_client[u.pop("client_id")]
                j = next(i for i, (v, _, _) in enumerate(queue)
                         if v == u["version"])
                _, u["draw_seed"], loss = queue.pop(j)
                losses.append(loss)
            check["losses"].append(float(np.mean(losses)))
    lr = t["learning_rate"]
    check["step_losses"] = [first_step[c.client_id][0] for c in clients]
    check["step_grads"] = [[(a - b) / lr for a, b in zip(
        check["p0"], first_step[c.client_id][1])] for c in clients]
    del server, clients, first_step
    gc.collect()

    def replay(dtype):
        ref = Reference(cfg, t, silos, dtype)
        step_losses, step_grads = ref.first_steps(params0, first_draws)
        steps = (ref.sync_rounds(params0, draws, n_check)
                 if schedule is None
                 else ref.fedbuff_merges(params0, schedule))
        return {"step_losses": step_losses, "step_grads": step_grads,
                "losses": [l for l, _, _ in steps], "p0": check["p0"],
                "p1": _host_leaves(steps[0][1]),
                "p3": _host_leaves(steps[-1][1]),
                "hub_weight": [w for _, _, w in steps]}

    ref_read = replay(jnp.float32)
    if ctx.fault == "control":
        check = replay(jnp.bfloat16)
    if ctx.fault == "unchanged":
        check["p1"] = check["p3"] = check["p0"]
    ctx.readings = compare.readings(check, ref_read)
    ctx.detail = {"losses": check["losses"], "ref_losses": ref_read["losses"],
                  **compare.explain(check, ref_read,
                                    [p.shape for p in check["p0"]])}


# ---------------------------------------------------------------------------
# the two round loops
# ---------------------------------------------------------------------------

def _run_sync(ctx, server, params0, check, n_check, hub_weights):
    from repro.core.message import TensorPayload
    run_ = ctx.run
    params = params0
    check["hub_weight"] = []

    def one_round():
        nonlocal params
        t0 = time.perf_counter()
        rep = server.run_round(TensorPayload(params))
        params = server.global_params
        jax.block_until_ready(params)
        t1 = time.perf_counter()
        run_.rounds.append((t0, t1))
        return rep, t1

    for r in range(n_check):
        n = len(hub_weights)
        rep, _ = one_round()
        check["losses"].append(rep.losses)
        check["hub_weight"].append(hub_weights[-1] if len(hub_weights) > n
                                   else None)
        if r == 0:
            check["p1"] = _host_leaves(params)
    check["p3"] = _host_leaves(params)
    ws = ctx.window_open()
    while True:
        _, t1 = one_round()
        if t1 - ws >= ctx.seconds:
            break
    ctx.window_close(t1)


def _run_async(ctx, server, fl_cfg, params0, check, n_check, patches, spans,
               hub_weights):
    from repro.core.message import TensorPayload
    from repro.fl import make_strategy, scheduler
    run_ = ctx.run
    schedule: List[list] = [[]]
    check["schedule"] = schedule
    check["hub_weight"] = []
    Sched = scheduler.FLScheduler
    fold = Sched.hub_fold

    state = {"k": 0, "prev": None, "ws": None}

    def hub_fold(self, rec, now):
        if state["k"] < n_check and rec.client is not None:
            schedule[-1].append({"client": _index(self, rec.client),
                                 "client_id": rec.client.client_id,
                                 "version": int(rec.version)})
        return fold(self, rec, now)
    patches.set(Sched, "hub_fold", hub_fold)

    agg = spans.wrap(Sched.aggregate, "hub", lambda *a, **k: "server")

    def aggregate(self, records, now):
        before, n = self.n_aggregations, len(hub_weights)
        # the effective weight the streaming hub has folded into this
        # merge, else the one the dense hub's FedAvg averages
        folded = self._acc.sum_eff if self._acc is not None else None
        done = agg(self, records, now)
        if self.n_aggregations == before:
            return done
        if folded is None and len(hub_weights) > n:
            folded = hub_weights[-1]
        t1 = time.perf_counter()
        if state["k"] < n_check:
            check["hub_weight"].append(folded)
        state["k"] += 1
        k = state["k"]
        if k < n_check:
            schedule.append([])
            if k == 1:
                check["p1"] = _host_leaves(self.global_params)
        elif k == n_check:
            check["p3"] = _host_leaves(self.global_params)
            state["ws"] = state["prev"] = ctx.window_open()
        else:
            run_.rounds.append((state["prev"], t1))
            state["prev"] = t1
            if t1 - state["ws"] >= ctx.seconds:
                ctx.window_close(t1)
                self.loop.stop()
        return done
    patches.set(Sched, "aggregate", aggregate)

    strategy = make_strategy(fl_cfg, fl_cfg.num_clients)
    server.run_async(TensorPayload(params0), strategy,
                     streaming_hub=fl_cfg.streaming_hub,
                     max_aggregations=10 ** 9)
    if state["k"] <= n_check or ctx.run.window[1] == 0.0:
        raise BenchError("the fedbuff run stopped before its window closed")


def _index(sched, client):
    return sched._index[client.client_id]


# ---------------------------------------------------------------------------
# faults, planted only by the control and fault checks (never in a run)
# ---------------------------------------------------------------------------

def _half_batch(step):
    """Each local step sees the first half of its batch."""
    def fn(params, batch):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return step(params, half)
    return fn


def _half_updates(fedavg):
    """The hub averages the first half of a round's updates only."""
    def fn(updates, weights, *a, **k):
        n = max(1, len(updates) // 2)
        return fedavg(updates[:n], weights[:n], *a, **k)
    return fn


def _half_folds(fold):
    """The streaming hub folds every other update only."""
    seen = {"n": 0}

    def fn(self, rec, alpha, *a, **k):
        seen["n"] += 1
        if seen["n"] % 2 == 0:
            return None
        return fold(self, rec, alpha, *a, **k)
    return fn


def _altered(local_train, client_id):
    """One client's update comes back with every value's sign flipped."""
    def fn(self, params, local_steps):
        new, loss, secs = local_train(self, params, local_steps)
        if self.client_id == client_id:
            new = jax.tree.map(lambda x: -x, new)
        return new, loss, secs
    return fn
