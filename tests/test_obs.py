"""repro.obs: spans and counters of the live FL path.

Off, a span only times its block; on, it records a nested record, opens
a ``fl:`` profiler annotation, and the seconds the program consumes are
the records' own. The live deployments are the reduced CPU ResNet.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import FLConfig
from repro.core.message import TensorPayload
from repro.fl import aggregator, make_strategy
from repro.launch import fl_train
from repro.scenario import Scenario, with_overrides
from repro.sweep.runners import run_scenario

LOCAL_STEPS = 2
BATCH = 16
# one reduced-ResNet batch: 16 images of 16 x 16 x 3 float32, 16 int32 labels
BATCH_BYTES = BATCH * 16 * 16 * 3 * 4 + BATCH * 4


@pytest.fixture
def recorder():
    rec = obs.enable()
    try:
        yield rec
    finally:
        obs.disable()


class _Spy:
    """Stands in for ``jax.profiler.TraceAnnotation``; logs each name."""
    names = []

    def __init__(self, name):
        _Spy.names.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def spy(monkeypatch):
    _Spy.names = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Spy)
    return _Spy


# ---------------------------------------------------------------------------
# the recorder itself
# ---------------------------------------------------------------------------

def test_off_path_times_but_records_and_annotates_nothing(spy):
    assert not obs.recording()
    with obs.span("client.step", update="c0/v1") as sp:
        obs.count("copy.h2d_bytes", 10, site="batch")
    assert sp.end >= sp.start > 0
    assert sp.seconds == sp.end - sp.start
    assert spy.names == []
    rec = obs.enable()
    obs.disable()
    with obs.span("client.step"):
        obs.count("copy.h2d_bytes", 10)
    assert rec.spans == [] and rec.counts == []


def test_spans_nest_with_parent_ids_and_inherit_the_update(spy, recorder):
    with obs.span("client.update", update="c0/v3") as root:
        with obs.span("client.local_train", steps=2):
            with obs.span("client.step") as step:
                obs.count("copy.h2d_bytes", 7, site="batch")
        with obs.span("wire.encode", side="c0"):
            pass
    with obs.span("hub.fold", update="c1/v2"):
        pass
    with obs.span("round"):
        pass
    by = {s.name: s for s in recorder.spans}
    assert [s.name for s in recorder.spans] == [
        "client.step", "client.local_train", "wire.encode", "client.update",
        "hub.fold", "round"]
    assert by["client.update"].parent is None
    assert by["client.local_train"].parent == by["client.update"].id
    assert by["client.step"].parent == by["client.local_train"].id
    assert by["wire.encode"].parent == by["client.update"].id
    assert by["hub.fold"].parent is None and by["round"].parent is None
    assert len({s.id for s in recorder.spans}) == 6
    assert {s.update for s in recorder.spans if s.name != "hub.fold"
            and s.name != "round"} == {"c0/v3"}
    assert by["hub.fold"].update == "c1/v2" and by["round"].update is None
    assert by["client.local_train"].attrs == {"steps": 2}
    assert by["wire.encode"].attrs == {"side": "c0"}
    assert by["client.update"].seconds == root.seconds
    assert by["client.step"].seconds == step.seconds
    (c,) = recorder.counts
    assert (c.name, c.n, c.parent, c.attrs) == (
        "copy.h2d_bytes", 7, by["client.step"].id, {"site": "batch"})
    assert by["client.step"].start <= c.t <= by["client.step"].end
    assert spy.names == ["fl:client.update", "fl:client.local_train",
                         "fl:client.step", "fl:wire.encode", "fl:hub.fold",
                         "fl:round"]
    assert recorder.total("copy.h2d_bytes") == 7
    assert [s.name for s in recorder.named("round")] == ["round"]


def test_a_raising_block_still_closes_its_span(recorder):
    with pytest.raises(ValueError):
        with obs.span("outer"):
            with obs.span("inner"):
                raise ValueError("boom")
    with obs.span("after"):
        pass
    by = {s.name: s for s in recorder.spans}
    assert by["inner"].parent == by["outer"].id
    assert by["after"].parent is None


def test_spans_reach_the_profiler_trace(recorder, tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("hub.fedavg", updates=2):
            with obs.span("hub.fold"):
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names = {e.name for p in ProfileData.from_file(str(path)).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events}
    assert {"fl:hub.fedavg", "fl:hub.fold"} <= names


def test_compile_events_are_counted(recorder):
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()
    assert recorder.total("jax.compiles") >= 1
    assert recorder.total("jax.compile_s") > 0


def test_write_jsonl(recorder, tmp_path):
    with obs.span("round", round=0):
        obs.count("copy.d2h_bytes", 4, site="loss")
    path = tmp_path / "spans.jsonl"
    recorder.write_jsonl(path)
    span, count = [json.loads(l) for l in path.read_text().splitlines()]
    assert span["span"] == "round" and span["attrs"] == {"round": 0}
    assert span["parent"] is None and span["end"] >= span["start"]
    assert count == {"count": "copy.d2h_bytes", "n": 4, "t": count["t"],
                     "parent": span["id"], "attrs": {"site": "loss"}}


# ---------------------------------------------------------------------------
# one clock: the program's seconds are the spans'
# ---------------------------------------------------------------------------

def test_aggregator_seconds_are_the_span_durations(recorder):
    rng = np.random.default_rng(0)
    trees = [{"w": jnp.asarray(rng.normal(size=(64,)), jnp.float32)}
             for _ in range(3)]
    _, agg_s = aggregator.fedavg(trees, [1.0, 2.0, 3.0])
    (fa,) = recorder.named("hub.fedavg")
    assert agg_s == fa.seconds and fa.attrs == {"updates": 3}

    class Rec:  # the UpdateRecord fields the fold reads
        def __init__(self, i, tree):
            self.client = type("C", (), {"client_id": f"client{i}"})()
            self.version, self.weight, self.count = 4, 1.0 + i, 1
            self.payload = TensorPayload(tree)

    acc = aggregator.StreamingAccumulator()
    for i, t in enumerate(trees):
        acc.fold(Rec(i, t), 0.5)
    _, total = acc.merged(version=5)
    folds = recorder.named("hub.fold")
    (merge,) = recorder.named("hub.merge")
    assert [s.update for s in folds] == ["client0/v4", "client1/v4",
                                         "client2/v4"]
    assert acc.agg_s == sum(s.seconds for s in folds)
    assert total == acc.agg_s + merge.seconds
    assert merge.attrs == {"updates": 3, "weight": acc.sum_eff,
                           "version": 5}


# ---------------------------------------------------------------------------
# the live path on the reduced CPU deployment
# ---------------------------------------------------------------------------

def _deploy(mode: str, **kw):
    cfg = FLConfig(num_clients=3, environment="geo_distributed", mode=mode,
                   seed=0, **kw)
    sc = cfg.to_scenario(tier="small", local_steps=LOCAL_STEPS, reduced=True)
    server, params, _, _ = fl_train.build_deployment(cfg, scenario=sc)
    return cfg, server, params


@pytest.fixture(scope="module")
def sync_run():
    cfg, server, params = _deploy("sync", backend="grpc")
    rec = obs.enable()
    try:
        report = server.run_round(TensorPayload(params))
    finally:
        obs.disable()
    return server, params, report, rec


@pytest.fixture(scope="module")
def fedbuff_run():
    cfg, server, params = _deploy(
        "fedbuff", backend="grpc+s3", buffer_k=2, compression="qsgd",
        streaming_hub=True)
    rec = obs.enable()
    try:
        server.run_async(TensorPayload(params), make_strategy(cfg, 3),
                         streaming_hub=True, max_aggregations=2)
    finally:
        obs.disable()
    return server, params, rec


def test_round_and_local_train_seconds_are_span_durations(sync_run):
    server, _, _, rec = sync_run
    (rnd,) = rec.named("round")
    assert server.wall_s == rnd.seconds and rnd.attrs == {"round": 0}
    client = server.clients[0]
    rec2 = obs.enable()
    try:
        _, _, secs = client.local_train(server.global_params, LOCAL_STEPS)
    finally:
        obs.disable()
    (lt,) = rec2.named("client.local_train")
    assert secs == lt.seconds


def test_client_step_splits_into_input_dispatch_and_sync(sync_run):
    _, _, _, rec = sync_run
    steps = rec.named("client.step")
    assert len(steps) == 3 * LOCAL_STEPS == rec.total("client.steps")
    ids = {s.id for s in steps}
    for name in ("step.input", "step.dispatch", "step.sync"):
        kids = rec.named(name)
        assert len(kids) == len(steps) and {k.parent for k in kids} == ids
    # the three phases are the step's work: its self time is the gaps
    # between them
    for s in steps:
        kids = [k for k in rec.spans if k.parent == s.id]
        assert sum(k.seconds for k in kids) <= s.seconds
    updates = rec.named("client.update")
    assert sorted(u.update for u in updates) == [
        "client0/v0", "client1/v0", "client2/v0"]
    assert {s.update for s in steps} == {u.update for u in updates}


def test_copy_counters_equal_a_hand_count(sync_run):
    _, params, _, rec = sync_run
    model_bytes = 4 * sum(int(np.prod(x.shape))
                          for x in jax.tree.leaves(params))

    def site(name, where):
        return sum(c.n for c in rec.counts
                   if c.name == name and c.attrs.get("site") == where)

    n_steps = 3 * LOCAL_STEPS
    assert site("copy.h2d_bytes", "batch") == n_steps * BATCH_BYTES
    assert site("copy.d2h_bytes", "loss") == n_steps * 4
    # each client's model arrives unpickled on the host and is uploaded
    # by its first step
    assert site("copy.h2d_bytes", "model") == 3 * model_bytes


def test_wire_spans_name_their_side(sync_run):
    _, _, _, rec = sync_run
    decodes = rec.named("wire.decode")
    assert {s.attrs["side"] for s in decodes} == {"client0", "client1",
                                                  "client2"}
    (bcast,) = rec.named("hub.broadcast")
    assert bcast.attrs == {"clients": 3}
    encodes = [s for s in rec.named("wire.encode")
               if s.attrs["side"] == "server"]
    assert encodes and all(s.parent == bcast.id for s in encodes)
    ids = {s.id for s in encodes}
    assert any(s.parent in ids for s in rec.named("wire.serialize"))


def test_fedbuff_update_id_is_shared_by_client_and_hub(fedbuff_run):
    _, _, rec = fedbuff_run
    client_ids = {s.update for s in rec.named("client.update")}
    folds = rec.named("hub.fold")
    assert folds and {s.update for s in folds} <= client_ids
    assert all(s.update.split("/v")[0].startswith("client") for s in folds)
    merges = rec.named("hub.merge")
    aggs = rec.named("sched.aggregate")
    assert [m.attrs["version"] for m in merges] == [1, 2]
    assert [a.attrs["version"] for a in aggs] == [1, 2]
    assert all(m.parent in {a.id for a in aggs} for m in merges)
    assert sum(m.attrs["updates"] for m in merges) == 4
    (run,) = rec.named("sched.run")
    assert run.attrs == {"mode": "fedbuff"}


def test_fedbuff_codec_spans_and_quantize_copies(fedbuff_run):
    _, params, rec = fedbuff_run
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    padded = -(-n // 2048) * 2048  # whole (8, 256) row tiles
    by_id = {s.id: s for s in rec.spans}
    compress = [s for s in rec.named("wire.compress")
                if s.attrs["side"] != "server"]
    assert compress and all(by_id[s.parent].name == "wire.encode"
                            for s in compress)
    quant = [c for c in rec.counts if c.attrs.get("site") == "quantize"]
    calls = [c for c in quant if c.name == "copy.h2d_bytes"]
    assert calls and all(c.n == padded * 4 for c in calls)
    # the flat update comes off the device, int8 codes and block scales
    # come back
    assert all(c.n == 4 * n + padded + padded // 256 * 4
               for c in quant if c.name == "copy.d2h_bytes")
    assert all(by_id[c.parent].name == "wire.compress" for c in quant)


def test_virtual_fedbuff_trace_is_bit_identical_with_the_recorder_on():
    sc = with_overrides(Scenario(name="obs-onoff"), {
        "strategy.mode": "fedbuff", "strategy.rounds": 4,
        "strategy.buffer_k": 3, "topology.kind": "geo_distributed",
        "topology.num_clients": 7, "channel.backend": "grpc+s3",
        "channel.compression": "qsgd", "channel.chunk_mb": 4.0})
    off = run_scenario(sc)
    rec = obs.enable()
    try:
        on = run_scenario(sc)
    finally:
        obs.disable()
    assert rec.named("sched.aggregate") and rec.named("wire.compress")
    assert json.dumps(on, sort_keys=True, default=str) == json.dumps(
        off, sort_keys=True, default=str)


def test_fl_train_writes_spans_as_jsonl(tmp_path):
    path = tmp_path / "spans.jsonl"
    rc = fl_train.main(["--environment", "geo_distributed", "--clients",
                        "2", "--rounds", "1", "--local-steps", "1",
                        "--backend", "grpc", "--spans", str(path)])
    assert rc == 0 and not obs.recording()
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    names = {l.get("span") or l.get("count") for l in lines}
    assert {"round", "client.update", "client.step", "step.sync",
            "wire.decode", "hub.fedavg", "copy.h2d_bytes"} <= names
    times = [l.get("start", l.get("t")) for l in lines]
    assert times == sorted(times)


def test_batched_kernel_calls_count_their_copies(recorder):
    from repro.kernels import ops
    x = jnp.arange(3000, dtype=jnp.float32)  # on the device
    (packed,) = ops.quantize_flat_batch([x], block=256)
    ops.dequantize_flat_batch([packed])
    ops.topk_flat_batch([np.arange(100, dtype=np.float32)], k_frac=0.1)
    padded = 4096  # whole (8, 256) row tiles

    def at(name, site):
        (c,) = [c for c in recorder.counts
                if c.name == name and c.attrs == {"site": site}]
        return c.n

    assert at("copy.h2d_bytes", "quantize") == padded * 4
    assert at("copy.d2h_bytes", "quantize") == 3000 * 4 + padded + 16 * 4
    assert at("copy.h2d_bytes", "dequantize") == padded + 16 * 4
    assert at("copy.d2h_bytes", "dequantize") == padded * 4
    assert at("copy.h2d_bytes", "topk") == 100 * 4
    assert at("copy.d2h_bytes", "topk") == 10 * 4 + 10 * 4
