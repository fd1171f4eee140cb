"""repro.fl.flat: the client step carries the parameters as one flat
buffer per dtype, and everything outside the step sees the model's tree.

The live deployments are the reduced CPU ResNet; the layout tests also
take the ResNet56 tree at its published widths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import FLConfig
from repro.configs.paper_tiers import build_tier_model
from repro.core.message import TensorPayload
from repro.fl import FLClient
from repro.fl.flat import FlatParams, Layout, flat_pack, flat_unpack
from repro.launch import fl_train
from repro.models.vision import ResNet, ResNetConfig

LOCAL_STEPS = 3
LR = 0.05


def _reduced():
    return ResNet(ResNetConfig(blocks_per_stage=2, num_classes=8,
                               image_size=16))


@pytest.fixture(scope="module", params=["reduced", "resnet56"])
def model_tree(request):
    model = (_reduced() if request.param == "reduced"
             else build_tier_model("small")[0])
    return model, jax.jit(model.init)(jax.random.key(0))


def _batch(model, seed=0):
    rng = np.random.default_rng(seed)
    n = model.cfg.image_size
    return {"images": jnp.asarray(rng.standard_normal((16, n, n, 3)),
                                  jnp.float32),
            "labels": jnp.asarray(rng.integers(0, model.cfg.num_classes, 16),
                                  jnp.int32)}


def _tree_step(model):
    """The step over the model's tree: the same update, leaf by leaf."""
    @jax.jit
    def step(params, batch):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, batch)[0])(params)
        return jax.tree.map(lambda p, g: p - LR * g, params, grads), loss
    return step


def _assert_same_tree(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# the carrier
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("source", ["device", "host"])
def test_pack_then_unpack_returns_the_tree_bit_for_bit(model_tree, source):
    _, tree = model_tree
    if source == "host":
        tree = jax.tree.map(np.asarray, tree)
    carrier = FlatParams.pack(tree)
    (buf,) = carrier.buffers  # every leaf is float32: one buffer
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert buf.shape == (n,) and buf.dtype == jnp.float32
    assert isinstance(buf, jax.Array)
    _assert_same_tree(carrier.tree(), tree)


def test_carrier_leaves_are_the_tree_leaves(model_tree):
    _, tree = model_tree
    carrier = FlatParams.pack(tree)
    leaves = jax.tree.leaves(carrier)
    want = jax.tree.leaves(tree)
    assert len(leaves) == len(want)
    for x, y in zip(leaves, want):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # unpacked once, then kept
    assert all(a is b for a, b in zip(leaves, jax.tree.leaves(carrier)))


def test_resnet56_layout_is_one_float32_buffer_of_the_whole_model():
    model, _ = build_tier_model("small")
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    layout, leaves = Layout.of(shapes)
    assert len(leaves) == 169
    assert layout.sizes == (868_123,)
    assert layout.dtypes == (np.dtype(np.float32),) * 169


def test_mixed_dtypes_get_one_buffer_each_in_leaf_order():
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": jnp.ones((4,), jnp.bfloat16),
            "c": jnp.full((), 7.0, jnp.float32),
            "d": jnp.arange(3, dtype=jnp.int32)}
    carrier = FlatParams.pack(tree)
    assert [b.dtype for b in carrier.buffers] == [jnp.float32, jnp.bfloat16,
                                                  jnp.int32]
    assert [b.shape for b in carrier.buffers] == [(7,), (4,), (3,)]
    np.testing.assert_array_equal(np.asarray(carrier.buffers[0]),
                                  [0, 1, 2, 3, 4, 5, 7])
    _assert_same_tree(carrier.tree(), tree)


def test_layouts_of_equal_trees_are_equal_and_hash_alike(model_tree):
    _, tree = model_tree
    a, _ = Layout.of(tree)
    b, _ = Layout.of(jax.tree.map(np.asarray, tree))
    assert a == b and hash(a) == hash(b)
    c, _ = Layout.of({"x": jnp.zeros(3)})
    assert a != c


def test_tree_map_over_a_carrier_keeps_a_carrier():
    tree = {"w": jnp.arange(4.0), "b": jnp.ones((2, 2))}
    doubled = jax.tree.map(lambda x: 2 * x, FlatParams.pack(tree))
    assert isinstance(doubled, FlatParams)
    np.testing.assert_array_equal(np.asarray(doubled.buffers[0]),
                                  [2, 2, 2, 2, 0, 2, 4, 6])


def test_a_carrier_needs_its_buffers_or_its_leaves():
    layout, _ = Layout.of({"x": jnp.zeros(3)})
    with pytest.raises(ValueError):
        FlatParams(layout)


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------

def test_carrier_steps_equal_tree_steps():
    model = _reduced()
    tree = jax.jit(model.init)(jax.random.key(1))
    step = fl_train.make_train_step(model)
    tree_step = _tree_step(model)
    flat, ref = tree, tree
    for i in range(LOCAL_STEPS):
        batch = _batch(model, seed=i)
        flat, loss = step(flat, batch)
        ref, ref_loss = tree_step(ref, batch)
        assert isinstance(flat, FlatParams)
        assert float(loss) == float(ref_loss)
    _assert_same_tree(flat.tree(), ref)


def test_step_takes_host_trees_and_carriers_alike():
    model = _reduced()
    tree = jax.jit(model.init)(jax.random.key(2))
    step = fl_train.make_train_step(model)
    batch = _batch(model)
    a, la = step(jax.tree.map(np.asarray, tree), batch)
    b, lb = step(FlatParams.pack(tree), batch)
    assert float(la) == float(lb)
    _assert_same_tree(a.tree(), b.tree())


def test_jit_names_guard_the_client_step_reader():
    """The device-trace reader of the step's time keys on modules named
    ``jit_train_fn``; the carrier's own jits must not match it."""
    model = _reduced()
    tree = jax.eval_shape(model.init, jax.random.key(0))
    layout, leaves = Layout.of(tree)
    buffers = (jax.ShapeDtypeStruct(layout.sizes, jnp.float32),)
    n = model.cfg.image_size
    batch = {"images": jax.ShapeDtypeStruct((16, n, n, 3), jnp.float32),
             "labels": jax.ShapeDtypeStruct((16,), jnp.int32)}

    def module(lowered):
        head = lowered.as_text().split("{", 1)[0]
        return head.split("@", 1)[1].split()[0]

    step = fl_train.make_train_step(model)
    assert module(step.train_fn.lower(layout, buffers, batch)) == "jit_train_fn"
    for jitted, arg in ((flat_pack, leaves), (flat_unpack, buffers)):
        name = module(jitted.lower(layout, arg))
        assert name.startswith("jit_") and not name.startswith("jit_train_fn")


# ---------------------------------------------------------------------------
# the client's local epoch
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def deployment():
    cfg = FLConfig(num_clients=2, environment="geo_distributed", seed=0)
    sc = cfg.to_scenario(tier="small", local_steps=LOCAL_STEPS, reduced=True)
    server, params, _, _ = fl_train.build_deployment(cfg, scenario=sc)
    return server, params


def test_local_train_returns_the_model_tree_and_counts_flat_steps(deployment):
    server, params = deployment
    client = server.clients[0]
    host = jax.tree.map(np.asarray, params)
    rec = obs.enable()
    try:
        new, loss, _ = client.local_train(host, LOCAL_STEPS)
    finally:
        obs.disable()
    assert not isinstance(new, FlatParams)
    assert jax.tree.structure(new) == jax.tree.structure(params)
    assert [x.shape for x in jax.tree.leaves(new)] == [
        x.shape for x in jax.tree.leaves(params)]
    assert np.isfinite(loss)
    # every step after the first took the carrier
    assert rec.total("client.steps") == LOCAL_STEPS
    assert rec.total("client.flat_steps") == LOCAL_STEPS - 1
    (lt,) = rec.named("client.local_train")
    (pack,) = rec.named("step.pack")
    (unpack,) = rec.named("step.unpack")
    assert unpack.parent == lt.id
    by_id = {s.id: s for s in rec.spans}
    assert by_id[pack.parent].name == "step.dispatch"
    # the model still crosses to the device once, as one upload
    model_bytes = 4 * sum(int(np.prod(x.shape))
                          for x in jax.tree.leaves(params))
    assert sum(c.n for c in rec.counts if c.name == "copy.h2d_bytes"
               and c.attrs.get("site") == "model") == model_bytes


def test_local_train_matches_the_tree_step(deployment):
    server, params = deployment
    client = server.clients[1]
    flat_new, flat_loss, _ = client.local_train(params, LOCAL_STEPS)
    plain = FLClient("plain", None, dataset=client.dataset,
                     train_fn=_tree_step(server.model), batch_size=16,
                     seed=client.seed)
    plain._round = client._round
    tree_new, tree_loss, _ = plain.local_train(params, LOCAL_STEPS)
    assert flat_loss == tree_loss
    _assert_same_tree(flat_new, tree_new)


def test_a_plain_pytree_train_fn_takes_the_tree_path(deployment):
    server, params = deployment
    seen = []
    tree_step = _tree_step(server.model)

    def step(p, batch):
        seen.append(type(p))
        return tree_step(p, batch)

    client = FLClient("plain", None, dataset=server.clients[0].dataset,
                      train_fn=step, batch_size=16, seed=0)
    rec = obs.enable()
    try:
        new, _, _ = client.local_train(params, LOCAL_STEPS)
    finally:
        obs.disable()
    assert seen == [dict] * LOCAL_STEPS
    assert jax.tree.structure(new) == jax.tree.structure(params)
    assert rec.total("client.steps") == LOCAL_STEPS
    assert rec.total("client.flat_steps") == 0
    assert rec.named("step.pack") == [] and rec.named("step.unpack") == []


def test_a_sync_round_moves_model_trees_on_the_wire(deployment):
    server, params = deployment
    rec = obs.enable()
    try:
        report = server.run_round(TensorPayload(params))
    finally:
        obs.disable()
    assert np.isfinite(report.losses)
    assert (jax.tree.structure(server.global_params)
            == jax.tree.structure(params))
    n = len(server.clients)
    assert rec.total("client.flat_steps") == n * (LOCAL_STEPS - 1)
    assert rec.total("client.steps") == n * LOCAL_STEPS
