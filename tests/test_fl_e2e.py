"""End-to-end FL: live training rounds over real backends, quorum /
straggler / fault handling, aggregation correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core import TensorPayload
from repro.fl.fault import FaultPlan, apply_stragglers
from repro.launch.fl_train import build_deployment


pytestmark = pytest.mark.slow  # minutes-long; PR CI runs -m 'not slow'


def run_rounds(backend, environment, rounds=2, **kw):
    fl_cfg = FLConfig(backend=backend, environment=environment,
                      rounds=rounds, **{k: v for k, v in kw.items()
                                        if k in FLConfig.__dataclass_fields__})
    server, params, env, store = build_deployment(
        fl_cfg, local_steps=kw.get("local_steps", 2))
    reports = []
    for r in range(rounds):
        rep = server.run_round(TensorPayload(params),
                               dropped=kw.get("dropped", set()) if r == 0 else set())
        if server.global_params is not None:
            params = server.global_params
        reports.append(rep)
    return reports, server, store


@pytest.mark.parametrize("backend", ["grpc", "grpc+s3", "torch_rpc",
                                     "mpi_mem_buff", "auto"])
def test_round_completes_and_loss_improves(backend):
    reports, server, _ = run_rounds(backend, "geo_distributed", rounds=3)
    losses = [r.losses for r in reports]
    assert all(l is not None for l in losses)
    assert losses[-1] < losses[0]  # learning across rounds
    assert all(r.n_participants == 7 for r in reports)
    assert all(r.round_time > 0 for r in reports)


def test_lan_uses_no_object_store():
    reports, server, store = run_rounds("auto", "lan", rounds=1)
    assert store.stats["puts"] == 0  # auto never routes to S3 on LAN
    assert reports[0].n_participants == 7


def test_quorum_proceeds_with_dropped_clients():
    reports, server, _ = run_rounds("grpc+s3", "geo_distributed", rounds=1,
                                    quorum_fraction=0.5,
                                    dropped={"client0", "client1"})
    rep = reports[0]
    assert not rep.aborted
    assert rep.n_participants >= 4  # 5 alive, quorum of 4 counted
    assert rep.n_dropped >= 2


def test_mpi_aborts_on_dropout_but_grpc_does_not():
    rep_mpi, _, _ = run_rounds("mpi_generic", "geo_distributed", rounds=1,
                               quorum_fraction=0.5, dropped={"client0"})
    rep_grpc, _, _ = run_rounds("grpc+s3", "geo_distributed", rounds=1,
                                quorum_fraction=0.5, dropped={"client0"})
    assert rep_mpi[0].aborted  # static world, no fault isolation (§II-C)
    assert not rep_grpc[0].aborted


def test_straggler_deadline_drops_slow_client():
    fl_cfg = FLConfig(backend="grpc+s3", environment="geo_distributed",
                      quorum_fraction=0.7)
    server, params, env, store = build_deployment(fl_cfg, local_steps=2)
    plan = FaultPlan(straggler_rate=0.99, straggler_factor=50.0, seed=2)
    _, stragglers = plan.for_round(0, [c.client_id for c in server.clients])
    apply_stragglers(server.clients, stragglers, 50.0)
    rep = server.run_round(TensorPayload(params))
    assert rep.n_participants >= 4  # quorum met without the stragglers
    assert rep.n_participants < 7 or not stragglers


def test_aggregation_is_weighted_average():
    from repro.fl.aggregator import fedavg
    t1 = {"w": jnp.full((8, 8), 2.0)}
    t2 = {"w": jnp.full((8, 8), 6.0)}
    agg, secs = fedavg([t1, t2], [1, 3])
    np.testing.assert_allclose(np.asarray(agg["w"]), 5.0, rtol=1e-6)
    assert secs >= 0


def test_report_states_cover_paper_fig5():
    reports, _, _ = run_rounds("grpc", "geo_distributed", rounds=1)
    srv, cl = reports[0].server, reports[0].clients
    for k in ("communication", "migration", "serialization", "waiting",
              "aggregation"):
        assert k in srv and srv[k] >= 0
    for k in ("communication", "migration", "serialization", "waiting",
              "training"):
        assert k in cl and cl[k] >= 0
    assert cl["training"] > 0


def test_full_model_deployment_shapes_silos_from_its_config():
    """--no-reduced deploys the tier's published model; the silos follow
    its config and every client shares one compiled step."""
    server, params, _, _ = build_deployment(FLConfig(num_clients=3),
                                            reduced=False)
    cfg = server.model.cfg
    assert (cfg.name, cfg.blocks_per_stage) == ("resnet56", 9)
    for c in server.clients:
        assert c.dataset.features.shape[1:] == (cfg.image_size,
                                                cfg.image_size, 3)
        assert c.dataset.num_classes == cfg.num_classes == 203
    assert len({id(c.train_fn) for c in server.clients}) == 1
    assert params["head"]["w"].shape == (64, 203)
