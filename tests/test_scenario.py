"""Scenario layer: spec round-trips, validation messages, preset
bit-for-bit equivalence with the legacy ``*_env`` constructors, graph
presets, and fl_train's --scenario/flag precedence."""
import dataclasses
import json
import random

import pytest

from repro.core import Fabric, FLMessage, ObjectStore, VirtualPayload, \
    make_backend
from repro.core.netsim import (NCAL, Environment, geo_distributed_env,
                               geo_proximal_env, lan_env)
from repro.fl.client import FLClient
from repro.fl.scheduler import FLScheduler
from repro.fl.server import FLServer
from repro.scenario import (TOPOLOGY_PRESETS, ChannelSpec, EdgeSpec,
                            FaultSpec, FleetSpec, Scenario, ScenarioError,
                            StrategySpec, TopologySpec, build_runtime,
                            with_overrides)

LEGACY = {"lan": lan_env, "geo_proximal": geo_proximal_env,
          "geo_distributed": geo_distributed_env}


# ---------------------------------------------------------------------------
# round-trip
# ---------------------------------------------------------------------------

def _preset_scenarios():
    for kind in TOPOLOGY_PRESETS:
        yield Scenario(name=f"rt:{kind}",
                       topology=TopologySpec.preset(kind, num_clients=9))


def test_roundtrip_every_preset():
    for s in _preset_scenarios():
        assert Scenario.from_dict(s.to_dict()) == s
        assert Scenario.from_json(s.to_json()) == s


def _random_scenario(rng: random.Random) -> Scenario:
    kind = rng.choice(TOPOLOGY_PRESETS)
    n = rng.randint(1, 20)
    edges = tuple(
        EdgeSpec(src=f"client{rng.randrange(n)}", dst="server",
                 bw_single_mb=rng.uniform(1, 500),
                 bw_multi_mb=rng.uniform(500, 3000),
                 latency_ms=rng.uniform(0.1, 200),
                 max_conns=rng.choice([0, 4, 16]),
                 symmetric=rng.random() < 0.5)
        for _ in range(rng.randrange(3)))
    return Scenario(
        name=f"rand{rng.randrange(1000)}", seed=rng.randrange(100),
        topology=TopologySpec(kind=kind, num_clients=n, edges=edges),
        fleet=FleetSpec(tier=rng.choice(["small", "big"]),
                        local_steps=rng.randint(1, 8)),
        channel=ChannelSpec(backend=rng.choice(["grpc", "grpc+s3", "auto"]),
                            compression=rng.choice(["none", "qsgd",
                                                    "topk:0.1"]),
                            wire_codec=rng.choice(["none", "zlib",
                                                   "zlib:9"]),
                            chunk_mb=rng.choice([0.0, 4.0])),
        faults=FaultSpec(link_loss=rng.choice([0.0, 0.1]),
                         nack_rtts=rng.choice([1.0, 2.0])),
        strategy=StrategySpec(mode=rng.choice(["sync", "fedbuff", "hier"]),
                              rounds=rng.randint(1, 9),
                              buffer_k=rng.randrange(5)))


def test_roundtrip_randomized_specs():
    rng = random.Random(7)
    for _ in range(25):
        s = _random_scenario(rng)
        assert Scenario.from_dict(s.to_dict()) == s
        # and through an actual JSON wire (tuples -> lists -> tuples)
        assert Scenario.from_dict(json.loads(json.dumps(s.to_dict()))) == s


# ---------------------------------------------------------------------------
# validation: readable failures
# ---------------------------------------------------------------------------

def test_unknown_key_raises_with_path():
    d = Scenario().to_dict()
    d["topology"]["bandwith"] = 3
    with pytest.raises(ScenarioError, match=r"scenario\.topology.*bandwith"):
        Scenario.from_dict(d)


def test_unknown_toplevel_key_lists_valid_keys():
    with pytest.raises(ScenarioError, match="unknown key.*topologyy"):
        Scenario.from_dict({"topologyy": {}})


def test_unknown_edge_key_names_the_edge_index():
    d = Scenario().to_dict()
    d["topology"]["edges"] = [{"src": "client0", "dst": "server",
                               "bw_single_mb": 1, "bw_multi_mb": 2,
                               "latency_ms": 1, "colour": "red"}]
    with pytest.raises(ScenarioError, match=r"edges\[0\].*colour"):
        Scenario.from_dict(d)


def test_invalid_edge_endpoint_raises():
    spec = TopologySpec(kind="star", num_clients=2, edges=(
        EdgeSpec("client9", "server", 10, 100, 5),))
    with pytest.raises(ScenarioError, match="client9.*names no host"):
        spec.build()


def test_nonpositive_edge_bandwidth_raises():
    spec = TopologySpec(num_clients=2, edges=(
        EdgeSpec("client0", "server", 0, 100, 5),))
    with pytest.raises(ScenarioError, match="positive"):
        spec.build()


def test_bad_preset_and_mode_and_loss():
    with pytest.raises(ScenarioError, match="unknown preset"):
        TopologySpec(kind="mesh").build()
    with pytest.raises(ScenarioError, match="strategy.mode"):
        Scenario(strategy=StrategySpec(mode="chaotic")).validate()
    with pytest.raises(ScenarioError, match="link_loss"):
        Scenario(faults=FaultSpec(link_loss=1.0)).validate()
    with pytest.raises(ScenarioError, match="channel.compression"):
        Scenario(channel=ChannelSpec(compression="gzip")).validate()


# ---------------------------------------------------------------------------
# preset envs == legacy constructors, bit for bit
# ---------------------------------------------------------------------------

def test_preset_hosts_match_legacy_envs():
    for name, legacy in LEGACY.items():
        for n in (4, 7, 14):
            built = TopologySpec.preset(name, num_clients=n).build()
            ref = legacy(n)
            assert built.name == ref.name
            assert built.server == ref.server
            assert built.clients == ref.clients
            assert built.trusted == ref.trusted
            assert built.has_object_store == ref.has_object_store


def _legacy_graphless(env: Environment) -> Environment:
    """The same hosts with no link graph: link() falls back to the
    historical implicit rule — the pre-scenario timing reference."""
    return dataclasses.replace(env, links=None)


def _fig2_trace(env, backend):
    """Fig-2-style concurrent broadcast timing over one WAN link."""
    fabric = Fabric(env)
    store = ObjectStore(NCAL)
    for h in [env.server] + list(env.clients):
        fabric.register(h.host_id)
    be = make_backend(backend, env, fabric, "server", store=store)
    msgs = [FLMessage("m", "server", env.clients[-1].host_id,
                      payload=VirtualPayload(64 << 20, tag=f"c{i}"))
            for i in range(8)]
    done, arrives = be.broadcast(msgs, 0.0)
    return (done, tuple(arrives))


def _fig5_trace(env, backend):
    """Fig-5-style full synchronous round timing."""
    fabric = Fabric(env)
    store = ObjectStore(NCAL)
    for h in [env.server] + list(env.clients):
        fabric.register(h.host_id)
    clients = [FLClient(h.host_id,
                        make_backend(backend, env, fabric, h.host_id,
                                     store=store), sim_train_s=20.0)
               for h in env.clients]
    server = FLServer(make_backend(backend, env, fabric, "server",
                                   store=store), clients, local_steps=1,
                      live=False)
    rep = server.run_round(VirtualPayload(128 << 20, tag="r0"))
    return (rep.round_time, tuple(sorted(rep.server.items())))


def _fig6_trace(env, backend):
    """Fig-6-style event-driven run: the full loop trace."""
    from repro.fl.async_strategies import FedBuffStrategy
    fabric = Fabric(env)
    store = ObjectStore(NCAL)
    for h in [env.server] + list(env.clients):
        fabric.register(h.host_id)
    clients = [FLClient(h.host_id,
                        make_backend(backend, env, fabric, h.host_id,
                                     store=store), sim_train_s=30.0)
               for h in env.clients]
    sched = FLScheduler(make_backend(backend, env, fabric, "server",
                                     store=store), clients,
                        FedBuffStrategy(buffer_k=3,
                                        staleness_exponent=0.5),
                        local_steps=1)
    sched.run(VirtualPayload(32 << 20, tag="t"), max_aggregations=4)
    return tuple(sched.loop.trace)


@pytest.mark.parametrize("env_name,backend", [
    ("geo_distributed", "grpc"), ("geo_distributed", "grpc+s3"),
    ("geo_proximal", "grpc"), ("lan", "mpi_generic"),
    ("lan", "mpi_mem_buff"),
])
def test_preset_graph_traces_bit_for_bit(env_name, backend):
    """The explicit graph must reproduce the implicit region-pair rule
    exactly on fig2/5/6-style workloads (same floats, same event order)."""
    built = TopologySpec.preset(env_name, num_clients=7).build()
    legacy = _legacy_graphless(built)
    assert built.links and legacy.links is None
    for tracer in (_fig2_trace, _fig5_trace, _fig6_trace):
        assert tracer(built, backend) == tracer(legacy, backend), \
            f"{tracer.__name__} diverged for {env_name}/{backend}"


def test_make_env_is_the_preset_shim():
    from repro.core.netsim import make_env
    with pytest.warns(DeprecationWarning, match="TopologySpec.preset"):
        env = make_env("geo_distributed", 5)
    assert env.links  # graph-built
    assert env == TopologySpec.preset("geo_distributed", 5).build()


# ---------------------------------------------------------------------------
# graph presets + explicit edges
# ---------------------------------------------------------------------------

def test_star_graph_is_hub_and_spoke():
    env = TopologySpec.preset("star", 6).build()
    assert len(env.links) == 2 * 6  # hub<->client only
    assert all("server" in (a, b) for a, b in env.links)


def test_ring_graph_has_bottleneck_client_edges():
    env = TopologySpec.preset("ring", 14).build()
    e = env.link("client0", "client1")  # ncal ~ oregon
    r0, r1 = env.clients[0].region, env.clients[1].region
    assert e.region.bw_single == min(r0.bw_single, r1.bw_single)
    assert e.region.latency == r0.latency + r1.latency


def test_multi_hub_graph_has_intra_region_dc_edges():
    env = TopologySpec.preset("multi_hub", 14).build()
    # clients 0 and 7 share ncal (round-robin over 7 regions)
    assert env.link("client0", "client7").region.name == "lan_tcp"
    # cross-region pairs fall back to the WAN rule
    assert env.link("client0", "client1").region.name == "oregon"


def test_edge_spec_overrides_preset_link_and_caps_conns():
    spec = TopologySpec(kind="geo_distributed", num_clients=3, edges=(
        EdgeSpec("client2", "server", bw_single_mb=10, bw_multi_mb=1000,
                 latency_ms=50, max_conns=4),))
    env = spec.build()
    e = env.link("client2", "server")
    assert e.region.latency == pytest.approx(50e-3)
    # max_conns folds into the saturation bandwidth
    assert e.region.bw_multi == pytest.approx(4 * 10 * 1024 ** 2)
    # symmetric by default
    assert env.link("server", "client2").region is e.region
    # untouched edges keep the preset rule
    assert env.link("client1", "server").region.name == "oregon"


def test_asymmetric_edge_shorthand_roundtrip():
    s = Scenario(name="asym", topology=TopologySpec(
        num_clients=2, edges=(
            EdgeSpec("client0", "server", 100, 1000, 10,
                     rev_bw_single_mb=5, rev_bw_multi_mb=50,
                     rev_latency_ms=80),)))
    assert Scenario.from_dict(json.loads(json.dumps(s.to_dict()))) == s


def test_asymmetric_edge_builds_directed_pair():
    env = TopologySpec(num_clients=2, edges=(
        EdgeSpec("client0", "server", 100, 1000, 10,
                 rev_bw_single_mb=5, rev_latency_ms=80),)).build()
    fwd = env.link("client0", "server")
    rev = env.link("server", "client0")
    assert fwd.region.bw_single == 100 * 1024 ** 2
    assert fwd.region.latency == pytest.approx(10e-3)
    assert rev.region.bw_single == 5 * 1024 ** 2
    assert rev.region.latency == pytest.approx(80e-3)
    # unset rev components inherit the forward values
    assert rev.region.bw_multi == fwd.region.bw_multi


def test_asymmetric_edge_rejects_symmetric_false():
    spec = TopologySpec(num_clients=2, edges=(
        EdgeSpec("client0", "server", 100, 1000, 10, symmetric=False,
                 rev_bw_single_mb=5),))
    with pytest.raises(ScenarioError, match="directed-pair"):
        spec.check()


def test_asymmetric_edge_rejects_lone_negative_rev_bandwidth():
    """A typo'd negative rev_* must error, not silently fall back to a
    symmetric edge (asymmetric-intent detection uses != 0, not > 0)."""
    spec = TopologySpec(num_clients=2, edges=(
        EdgeSpec("client0", "server", 100, 1000, 10,
                 rev_bw_single_mb=-5),))
    with pytest.raises(ScenarioError, match="rev_.*positive"):
        spec.check()


def test_backend_consumes_asymmetric_edge():
    """The declared thin uplink must actually slow sends one way only."""
    rt = build_runtime(Scenario(
        name="asym", channel=ChannelSpec(backend="grpc"),
        topology=TopologySpec(num_clients=2, edges=(
            EdgeSpec("client0", "server", bw_single_mb=200,
                     bw_multi_mb=2000, latency_ms=5,
                     rev_bw_single_mb=2, rev_bw_multi_mb=20),))))
    msg_up = FLMessage("m", "client0", "server",
                       payload=VirtualPayload(16 << 20, tag="u"))
    msg_dn = FLMessage("m", "server", "client0",
                       payload=VirtualPayload(16 << 20, tag="d"))
    t_up = rt.make_backend("client0").isend(msg_up, 0.0).arrive
    t_dn = rt.make_backend("server").isend(msg_dn, 0.0).arrive
    assert t_dn > 10 * t_up  # the reverse leg is ~100x thinner


def test_backend_consumes_custom_edge():
    """A declared slow edge must actually slow that backend's sends."""
    fast = build_runtime(Scenario(name="fast"))
    slow = build_runtime(Scenario(name="slow", topology=TopologySpec(
        edges=(EdgeSpec("client0", "server", bw_single_mb=1,
                        bw_multi_mb=2, latency_ms=500),))))
    msg = FLMessage("m", "server", "client0",
                    payload=VirtualPayload(8 << 20, tag="x"))
    t_fast = fast.make_backend("server").isend(msg, 0.0).arrive
    t_slow = slow.make_backend("server").isend(
        dataclasses.replace(msg), 0.0).arrive
    assert t_slow > 10 * t_fast


# ---------------------------------------------------------------------------
# fl_train: --scenario + override precedence
# ---------------------------------------------------------------------------

def _resolve(tmp_path, spec_dict, argv):
    from repro.launch.fl_train import _parser, resolve_scenario
    path = tmp_path / "sc.json"
    path.write_text(json.dumps(spec_dict))
    ap = _parser()
    return resolve_scenario(ap.parse_args(["--scenario", str(path)] + argv),
                            ap)


def test_fl_train_flag_overrides_scenario(tmp_path):
    spec = {"name": "t", "topology": {"kind": "multi_hub",
                                      "num_clients": 6},
            "channel": {"backend": "grpc", "chunk_mb": 4.0},
            "strategy": {"mode": "hier", "rounds": 9}}
    # unset flags: the spec wins
    sc = _resolve(tmp_path, spec, [])
    assert sc.topology.kind == "multi_hub" and sc.strategy.rounds == 9
    assert sc.channel.chunk_mb == 4.0
    # set flags: the flag wins, everything else stays from the spec
    sc = _resolve(tmp_path, spec, ["--rounds", "2", "--backend", "grpc+s3"])
    assert sc.strategy.rounds == 2
    assert sc.channel.backend == "grpc+s3"
    assert sc.topology.kind == "multi_hub"
    assert sc.channel.chunk_mb == 4.0


def test_fl_train_wire_domain_compression_routes_to_wire_codec(tmp_path):
    sc = _resolve(tmp_path, {"name": "t"}, ["--compression", "zlib:9"])
    assert sc.channel.wire_codec == "zlib:9"
    assert sc.channel.compression == "none"


def test_fl_train_rejects_bad_scenario(tmp_path):
    from repro.launch.fl_train import _parser, resolve_scenario
    path = tmp_path / "bad.json"
    path.write_text(json.dumps({"strategy": {"mode": "chaotic"}}))
    ap = _parser()
    with pytest.raises(SystemExit):
        resolve_scenario(ap.parse_args(["--scenario", str(path)]), ap)


def test_with_overrides_skips_none_and_rejects_unknown():
    sc = Scenario()
    assert with_overrides(sc, {"channel.backend": None}) == sc
    out = with_overrides(sc, {"faults.link_loss": 0.2})
    assert out.faults.link_loss == 0.2 and sc.faults.link_loss == 0.0
    with pytest.raises(ScenarioError, match="not a field"):
        with_overrides(sc, {"channel.nope": 1})


def test_relay_conns_reaches_the_strategy_through_fl_config():
    from repro.fl import make_strategy
    sc = Scenario(strategy=StrategySpec(mode="hier", relay_conns=32))
    assert make_strategy(sc.fl_config()).relay_conns == 32


def test_two_different_wire_codecs_rejected_at_validate():
    sc = Scenario(channel=ChannelSpec(compression="zlib:1",
                                      wire_codec="zlib:9"))
    with pytest.raises(ScenarioError, match="two wire codecs"):
        sc.validate()


def test_runtime_builds_fault_model_from_spec():
    rt = build_runtime(Scenario(name="f", seed=3,
                                faults=FaultSpec(link_loss=0.1,
                                                 max_retries=7,
                                                 nack_rtts=2.0)))
    fm = rt.fabric.fault_model
    assert fm is not None and fm.chunk_loss_rate == 0.1
    assert fm.max_retries == 7 and fm.nack_rtts == 2.0 and fm.seed == 3
    assert build_runtime(Scenario(name="c")).fabric.fault_model is None


def test_fl_train_reduced_flag_overrides_fleet(tmp_path):
    sc = _resolve(tmp_path, {"name": "t"}, [])
    assert sc.fleet.reduced is True  # CPU default: the reduced ResNet
    sc = _resolve(tmp_path, {"name": "t"}, ["--no-reduced"])
    assert sc.fleet.reduced is False
    spec = {"name": "t", "fleet": {"reduced": False}}
    assert _resolve(tmp_path, spec, []).fleet.reduced is False
    assert _resolve(tmp_path, spec, ["--reduced"]).fleet.reduced is True
    back = Scenario.from_dict(json.loads(json.dumps(
        _resolve(tmp_path, spec, []).to_dict())))
    assert back.fleet.reduced is False


@pytest.mark.parametrize("losses,rc", [
    ([2.0, 1.5], 0),
    ([2.0, 2.5], 0),  # not improving: noisy, still a finished run
    ([2.0, None], 0),  # a round without live clients
    ([2.0, float("nan")], 1),
    ([float("inf")], 1),
])
def test_fl_train_fails_on_non_finite_loss(losses, rc):
    from repro.launch.fl_train import _finite_or_fail
    assert _finite_or_fail(losses) == rc
