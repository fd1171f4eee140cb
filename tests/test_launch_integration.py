"""Launch-layer integration: step bundles lower+compile on a local mesh,
trainer checkpoints and resumes, serve decodes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.configs.base import SMOKE_MESH, ShapeConfig, TrainConfig
from repro.launch.mesh import make_smoke_mesh
from repro.launch.step_builders import bundle_for


pytestmark = pytest.mark.slow  # minutes-long; PR CI runs -m 'not slow'


@pytest.mark.parametrize("arch,kind", [
    ("qwen3-8b", "train"), ("qwen3-8b", "decode"),
    ("granite-moe-1b-a400m", "train"), ("zamba2-1.2b", "decode"),
    ("hubert-xlarge", "prefill"),
])
def test_bundle_lowers_and_compiles(arch, kind):
    cfg = smoke_config(arch)
    mesh = make_smoke_mesh()
    # an audio model takes waveforms: seq_len counts samples (20 frames)
    shape = ShapeConfig(name="t",
                        seq_len=6_480 if cfg.family == "audio" else 32,
                        global_batch=4, kind=kind)
    b = bundle_for(kind, cfg, shape, mesh, SMOKE_MESH,
                   TrainConfig(microbatches=2 if kind == "train" else 1))
    with mesh:
        compiled = jax.jit(b.fn, in_shardings=b.in_shardings,
                           out_shardings=b.out_shardings
                           ).lower(*b.in_specs).compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes >= 0
    ca = compiled.cost_analysis()
    assert ca is not None


def test_train_step_executes_and_learns():
    from repro.data import lm_batch_iterator
    from repro.optim.optimizers import adamw_init

    cfg = smoke_config("granite-3-8b")
    mesh = make_smoke_mesh()
    shape = ShapeConfig(name="t", seq_len=32, global_batch=4, kind="train")
    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=2, total_steps=30)
    b = bundle_for("train", cfg, shape, mesh, SMOKE_MESH, tcfg)
    params, _ = b.model.init(jax.random.key(0))
    opt = adamw_init(params, tcfg)
    fn = jax.jit(b.fn)
    it = lm_batch_iterator(0, 4, 32, cfg.vocab_size)
    losses = []
    with mesh:
        for step in range(30):
            batch = {k: jnp.asarray(v) for k, v in next(it).items()}
            params, opt, m = fn(params, opt, batch, jnp.int32(step))
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses[-1])


def test_trainer_checkpoint_restart(tmp_path):
    """Kill/restart semantics: second invocation resumes from step 10."""
    from repro.launch.train import main as train_main

    d = str(tmp_path / "ck")
    rc = train_main(["--arch", "granite-moe-1b-a400m", "--steps", "10",
                     "--batch", "2", "--seq", "16", "--ckpt-dir", d,
                     "--ckpt-every", "5"])
    assert rc in (0, 1)  # 10 steps may not strictly reduce a MoE loss
    from repro.checkpoint.ckpt import list_steps
    assert list_steps(d), "no checkpoint written"
    # resume and continue to 14
    rc = train_main(["--arch", "granite-moe-1b-a400m", "--steps", "14",
                     "--batch", "2", "--seq", "16", "--ckpt-dir", d,
                     "--ckpt-every", "5"])
    assert rc in (0, 1)  # short continuation may not strictly reduce loss
    assert max(list_steps(d)) >= 10


def test_fl_round_bundle_on_pod_mesh():
    """The paper-technique step lowers when a pod axis exists (uses the
    2-device CPU mesh via axis sizes (2,1,1))."""
    from repro.configs.base import MeshConfig
    from repro.launch.mesh import make_mesh
    n_pods = 1 if jax.device_count() < 2 else 2
    mcfg = MeshConfig(shape=(n_pods, 1, 1),
                      axis_names=("pod", "data", "model"))
    mesh = make_mesh(mcfg)
    cfg = smoke_config("qwen3-8b")
    shape = ShapeConfig(name="t", seq_len=16, global_batch=2 * n_pods,
                        kind="train")
    tcfg = TrainConfig(crosspod_compression="int8")
    b = bundle_for("fl_round", cfg, shape, mesh, mcfg, tcfg, local_steps=2)
    with mesh:
        compiled = jax.jit(b.fn, in_shardings=b.in_shardings,
                           out_shardings=b.out_shardings
                           ).lower(*b.in_specs).compile()
    assert compiled is not None
