"""Smoke run of the live cross-silo FL path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the cross-pod FL round, four chips

One chip, in this order: the main-path Pallas kernels at ResNet56 width,
compiled, against ``kernels/ref.py``; then ``fl_train`` in this process with
ResNet56 at its published configuration over 7 geo-distributed silos, for
3 sync rounds and for 3 fedbuff aggregations with qsgd and the streaming
hub.

Four chips: only the cross-pod FL round, through ``repro.launch.fl_round``
with HuBERT X-Large as published, the pod axis over the chips (chips
stand in for silos): the int8 delta exchange against the f32 exchange it
is compared with.

Everything runs in this one process, because a TPU chip belongs to one
process at a time. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or when any phase fails, the script exits non-zero and
prints no such line.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import io
import json
import math
import re
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

RESNET56_PARAMS = 868_123  # the small tier's flat update length
N_CLIENTS = 7  # silos of the geo_distributed deployment
QSGD_BLOCK = 256  # qsgd's default quantisation block
TOPK_FRAC = 0.05  # topk's default kept fraction

FL_BASE = ["--environment", "geo_distributed", "--clients", str(N_CLIENTS),
           "--rounds", "3", "--no-reduced"]
FL_RUNS = {
    "sync": FL_BASE,
    "fedbuff+qsgd+streaming-hub": FL_BASE + [
        "--mode", "fedbuff", "--compression", "qsgd", "--streaming-hub"],
}

# The four-chip phase: the cross-pod FL round through repro.launch.fl_round,
# HuBERT X-Large as published (48 layers), one pod per chip
CROSSPOD_ARGV = ["--arch", "hubert-xlarge", "--pods", "4", "--local-steps",
                 "2", "--pod-batch", "4", "--samples", "160000"]
CROSSPOD_ROUNDS = 3
# int8 losses must track f32 within this relative gap: the shared-scale
# int8 delta errs by at most max|delta|/254 per element (0.4% of the
# largest step), which moves a 3-round loss far less than this, while a
# wrong scale or a sum in place of the mean moves it by much more
CROSSPOD_LOSS_RTOL = 0.05
CROSSPOD_MAX_DRIFT = 1e-3


def require_tpu(n_chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU "
                 f"(devices are {devs[0].platform!r}); nothing was run")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} TPU chips, found {len(devs)}")
    return devs


# ---------------------------------------------------------------------------
# one chip: kernels
# ---------------------------------------------------------------------------

def _check_int8_at_ties(q, q_ref, x, s_ref) -> int:
    """int8 codes must equal the reference's, except one level apart where
    ``x / scale`` lies within 4 ULP of a rounding tie: the chip's scale and
    reciprocal may each differ from the IEEE reference by 1 ULP, which
    moves such a value across the tie. Returns how many differ."""
    inv = np.divide(np.float32(1.0), s_ref, where=s_ref > 0,
                    out=np.zeros_like(s_ref))
    v = np.abs(x * inv)
    at_tie = np.abs(v - np.floor(v) - 0.5) <= 4 * np.spacing(v)
    diff = np.abs(q.astype(np.int32) - q_ref.astype(np.int32))
    bad = (diff > 1) | ((diff == 1) & ~at_tie)
    if bad.any():
        i = np.argwhere(bad)[0]
        raise AssertionError(
            f"{int(bad.sum())} int8 code(s) differ from the reference away "
            f"from a rounding tie, first at {tuple(i)}: {q[tuple(i)]} vs "
            f"{q_ref[tuple(i)]} (x/scale = {x[tuple(i)] * inv[i[0], 0]!r})")
    return int((diff == 1).sum())


def check_kernels(t: int = RESNET56_PARAMS, n_clients: int = N_CLIENTS,
                  seed: int = 0):
    """Each main-path kernel through its ``kernels/ops`` entry point on one
    flat update of length ``t``, against the host reference, at the parity
    tests' tolerances; then the lowering of each holds a Mosaic kernel."""
    from repro.kernels import fedavg_reduce as fr
    from repro.kernels import ops
    from repro.kernels import quantize as qz
    from repro.kernels import ref as kref

    rng = np.random.default_rng(seed)
    x = rng.normal(size=t).astype(np.float32)

    (packed,) = ops.quantize_flat_batch([x], block=QSGD_BLOCK)
    rows = packed["q"].size // QSGD_BLOCK
    xp = np.zeros(rows * QSGD_BLOCK, np.float32)
    xp[:t] = x
    xp = xp.reshape(rows, QSGD_BLOCK)
    q = packed["q"].reshape(rows, QSGD_BLOCK)
    s = packed["scales"].reshape(rows, 1)
    q_ref, s_ref = kref.quantize_blocks_np(xp)
    np.testing.assert_array_max_ulp(s, s_ref, maxulp=1)
    n_ties = _check_int8_at_ties(q, q_ref, xp, s_ref)
    print(f"[kernels] quantize_blocks ({rows}x{QSGD_BLOCK}): scales <= 1 "
          f"ULP; int8 bit-exact except {n_ties} value(s) one level apart "
          f"at a rounding tie")

    (xd,) = ops.dequantize_flat_batch([packed])
    np.testing.assert_allclose(
        xd, kref.dequantize_blocks_np(q, s).reshape(-1)[:t], rtol=1e-6)
    print(f"[kernels] dequantize_blocks ({rows}x{QSGD_BLOCK}): "
          f"within rtol 1e-6")

    ups = [rng.normal(size=t).astype(np.float32) for _ in range(n_clients)]
    w = rng.uniform(16, 64, size=n_clients).astype(np.float32)
    agg = ops.fedavg_aggregate([{"x": jnp.asarray(u)} for u in ups], w)
    expect = np.sum(np.stack(ups).astype(np.float64)
                    * (w / w.sum())[:, None], axis=0)
    np.testing.assert_allclose(np.asarray(agg["x"]), expect,
                               rtol=1e-4, atol=1e-5)
    print(f"[kernels] fedavg_reduce ({n_clients} clients x {t}): "
          f"within rtol 1e-4, atol 1e-5")

    acc = rng.normal(size=t).astype(np.float32)
    got = ops.fedavg_accumulate_flat(acc, ups[0], 0.37)
    np.testing.assert_allclose(np.asarray(got),
                               acc + np.float32(0.37) * ups[0], atol=1e-6)
    print(f"[kernels] fedavg_accumulate ({t}): within atol 1e-6")

    (sparse,) = ops.topk_flat_batch([x], k_frac=TOPK_FRAC)
    k = max(1, int(t * TOPK_FRAC))
    order = np.argsort(-np.abs(x), kind="stable")[:k]
    np.testing.assert_array_equal(sparse["idx"], order)
    np.testing.assert_array_equal(sparse["vals"], x[order])
    print(f"[kernels] top-k (lax.top_k, k={k} of {t}): indices and values "
          f"exact")

    t_pad = -(-t // fr.COL_TILE) * fr.COL_TILE
    f32 = jnp.float32
    spec = jax.ShapeDtypeStruct
    lowered = {
        "quantize_blocks": qz.quantize_blocks.lower(
            spec((rows, QSGD_BLOCK), f32)),
        "dequantize_blocks": qz.dequantize_blocks.lower(
            spec((rows, QSGD_BLOCK), jnp.int8), spec((rows, 1), f32)),
        "fedavg_reduce": fr.fedavg_reduce.lower(
            spec((n_clients, t_pad), f32), spec((n_clients,), f32)),
        "fedavg_accumulate": fr.fedavg_accumulate.lower(
            spec((t_pad,), f32), spec((t_pad,), f32), spec((), f32)),
    }
    for name, lo in lowered.items():
        if "tpu_custom_call" not in lo.as_text():
            raise AssertionError(f"{name} did not lower to tpu_custom_call")
    print(f"[kernels] lowered to tpu_custom_call: {', '.join(lowered)}")


# ---------------------------------------------------------------------------
# one chip: the live FL path through fl_train
# ---------------------------------------------------------------------------

class _Tee(io.TextIOBase):
    def __init__(self, *streams):
        self.streams = streams

    def write(self, s):
        for st in self.streams:
            st.write(s)
        return len(s)

    def flush(self):
        for st in self.streams:
            st.flush()


_LOSSES = re.compile(r"^\[fl(?::\w+)?\] losses: (\[.*?\])", re.M)


def round_walls(rec, *, sync: bool):
    """Host seconds of each sync round (its ``round`` span), or of each
    merge: from the run's start (``sched.run``) or the previous merge to
    the end of its ``sched.aggregate`` span."""
    if sync:
        return [s.seconds for s in rec.named("round")]
    (run,) = rec.named("sched.run")
    ends = [s.end for s in rec.named("sched.aggregate")]
    return [b - a for a, b in zip([run.start] + ends, ends)]


def run_fl(name: str, argv, *, sync: bool):
    """``fl_train.main(argv)`` in this process; asserts exit 0, finite
    losses and, for sync, a last-round loss no higher than the first."""
    from repro import obs
    from repro.launch import fl_train
    buf = io.StringIO()
    rec = obs.enable()
    t0 = time.perf_counter()
    try:
        with contextlib.redirect_stdout(_Tee(sys.stdout, buf)):
            rc = fl_train.main(list(argv))
    finally:
        obs.disable()
    wall = time.perf_counter() - t0
    compile_s, hits = rec.total("jax.compile_s"), rec.total("jax.cache_hits")
    out = buf.getvalue()
    if rc != 0:
        raise RuntimeError(f"fl_train ({name}) exited {rc}")
    m = _LOSSES.search(out)
    if m is None:
        raise AssertionError(f"fl_train ({name}) printed no losses line")
    losses = json.loads(m.group(1))
    if len(losses) != 3 or not all(
            l is not None and math.isfinite(l) for l in losses):
        raise AssertionError(f"{name}: want 3 finite losses, got {losses}")
    if sync and not losses[-1] <= losses[0]:
        raise AssertionError(f"{name}: last-round loss {losses[-1]} > "
                             f"first {losses[0]}")
    print(f"[smoke] {name}: compile_s={compile_s!r} "
          f"persistent_cache_hits={hits:g} run_wall_s={wall!r} "
          f"wall_s_per_round={round_walls(rec, sync=sync)} losses={losses}")


def one_chip():
    """-> names of the phases that failed."""
    phases = [("kernels", check_kernels)] + [
        (f"fl_train {name}", functools.partial(run_fl, name, argv,
                                               sync=name == "sync"))
        for name, argv in FL_RUNS.items()]
    return run_phases(phases)


def run_phases(phases):
    """Runs every phase, reporting each failure with its traceback, so one
    call shows all that is wrong; -> names of the phases that failed."""
    failed = []
    for name, fn in phases:
        try:
            fn()
        except Exception:  # noqa: BLE001 — reported here, failed in main
            traceback.print_exc()
            print(f"[smoke] FAILED: {name}")
            failed.append(name)
    return failed


# ---------------------------------------------------------------------------
# four chips: the cross-pod FL round
# ---------------------------------------------------------------------------

def crosspod(rounds: int = CROSSPOD_ROUNDS):
    """``repro.launch.fl_round``'s round with the f32 exchange, then int8,
    from the same seed and batches. Asserts the arrays span every chip,
    the pods agree after each sync, and int8 losses track f32."""
    from repro import obs
    from repro.launch import fl_round

    @jax.jit
    def drift(stacked):
        return jnp.max(jnp.stack([
            jnp.max(jnp.abs(l.astype(jnp.float32)
                            - l[:1].astype(jnp.float32)))
            for l in jax.tree.leaves(stacked)]))

    losses = {}
    for comp in ("none", "int8"):
        run = fl_round.build(fl_round.parse_args(
            CROSSPOD_ARGV + ["--compression", comp]))
        if comp == "none":
            print(f"[crosspod] {run.cfg.name}: {run.cfg.num_layers} layers, "
                  f"{run.cfg.param_count()} params per pod, "
                  f"{run.pod_batch} x {run.samples} samples ({run.frames} "
                  f"frames) per pod per step, {run.local_steps} local steps "
                  f"per round")
        rec = obs.enable()
        try:
            run.start()
            held = {s.device for s in
                    jax.tree.leaves(run.params)[0].addressable_shards}
            if held != set(run.devices):
                raise AssertionError(f"stacked params on {len(held)} "
                                     f"devices, want all {run.pods}")
            ls, walls, drifts = [], [], []
            for _ in range(rounds):
                t0 = time.perf_counter()
                ls.append(run.round())
                walls.append(time.perf_counter() - t0)
                drifts.append(float(drift(run.params)))
        finally:
            obs.disable()
            run.release()
        peak = [d.memory_stats().get("peak_bytes_in_use")
                if d.memory_stats() else None for d in run.devices]
        print(f"[crosspod] {comp}: compile_s={rec.total('jax.compile_s')!r} "
              f"round_wall_s={walls} losses={ls} pod_drift={drifts} "
              f"peak_bytes_in_use={peak}")
        if not all(math.isfinite(l) for l in ls):
            raise AssertionError(f"{comp}: non-finite loss {ls}")
        if max(drifts) >= CROSSPOD_MAX_DRIFT:
            raise AssertionError(f"{comp}: pods differ after sync {drifts}")
        losses[comp] = ls
    gap = [abs(a - b) / abs(b) for a, b in zip(losses["int8"],
                                               losses["none"])]
    if max(gap) > CROSSPOD_LOSS_RTOL:
        raise AssertionError(f"int8 losses stray from f32 by {gap}")
    print(f"[crosspod] int8 vs f32 relative loss gap per round: {gap}")


def four_chips():
    return run_phases([("crosspod", crosspod)])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-pod FL round over four chips")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    devs = require_tpu(n_chips)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"[smoke] {len(devs)} x {devs[0].device_kind}; compile cache: "
          f"{enable_compile_cache()}")
    failed = four_chips() if args.four_chips else one_chip()
    if failed:
        sys.exit(f"chip_smoke: failed phases: {', '.join(failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
