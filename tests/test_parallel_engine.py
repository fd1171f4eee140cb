"""Parallel engine: conditional (per-value) sub-axes, multiprocess-safe
RunStore appends, and --workers N == serial bit-for-bit."""
import json
import multiprocessing as mp
import os

import pytest

from repro.sweep import (Axis, CellResult, Engine, RunStore, Study, Sweep,
                         SweepError)


# ---------------------------------------------------------------------------
# conditional axes: per-value sub-grids
# ---------------------------------------------------------------------------

def _chunked_backend_axis():
    """The fig8 shape: chunking only exists on the grpc branch."""
    return Axis("channel.backend", values=("grpc", "grpc+s3"),
                sub={"grpc": (Axis("params.chunk_mb", values=(4.0, 8.0)),),
                     "grpc+s3": (Axis("params.chunk_mb", values=(0.0,)),)})


def test_conditional_axis_nests_under_parent_value():
    sw = Sweep(name="c", axes=(
        _chunked_backend_axis(),
        Axis("faults.link_loss", values=(0.0, 0.01))))
    cells = sw.expand()
    triples = [(c.overrides["channel.backend"], c.params["chunk_mb"],
                c.overrides["faults.link_loss"]) for c in cells]
    # branch cells stay contiguous; later axes cross inside each branch
    assert triples == [("grpc", 4.0, 0.0), ("grpc", 4.0, 0.01),
                       ("grpc", 8.0, 0.0), ("grpc", 8.0, 0.01),
                       ("grpc+s3", 0.0, 0.0), ("grpc+s3", 0.0, 0.01)]


def test_conditional_axis_roundtrip_through_json():
    sw = Sweep(name="c", axes=(
        _chunked_backend_axis(),
        Axis("faults.link_loss", lo=0.0, hi=0.1, steps=3)))
    assert Sweep.from_dict(json.loads(json.dumps(sw.to_dict()))) == sw


def test_conditional_axis_rejected_in_random_search():
    sw = Sweep(name="c", samples=4, seed=1,
               axes=(_chunked_backend_axis(),))
    with pytest.raises(SweepError, match="grid"):
        sw.expand()


def test_conditional_axis_branch_scoped_duplicate_rule():
    # the same field on two *different* branches is fine (that's the
    # whole point) ...
    Sweep(name="ok", axes=(_chunked_backend_axis(),)).check()
    # ... but a duplicate within one branch is still a conflict
    with pytest.raises(SweepError, match="duplicate"):
        Sweep(name="dup", axes=(
            Axis("channel.backend", values=("grpc",),
                 sub={"grpc": (Axis("params.x", values=(1,)),
                               Axis("params.x", values=(2,)))}),)).check()
    # and a sub-axis contradicting an enclosing axis is too
    with pytest.raises(SweepError, match="duplicate"):
        Sweep(name="shadow", axes=(
            Axis("faults.link_loss", values=(0.0,)),
            Axis("channel.backend", values=("grpc",),
                 sub={"grpc": (Axis("faults.link_loss",
                                    values=(0.1,)),)}),)).check()


def test_conditional_axis_sub_key_must_name_a_value():
    with pytest.raises(SweepError, match="no axis value"):
        Sweep(name="k", axes=(
            Axis("channel.backend", values=("grpc",),
                 sub={"tcp": (Axis("params.x", values=(1,)),)}),)).check()


def test_conditional_axis_from_dict_rejects_non_list_sub():
    with pytest.raises(SweepError, match=r"sub\['grpc'\]"):
        Sweep.from_dict({"name": "x", "axes": [
            {"field": "channel.backend", "values": ["grpc"],
             "sub": {"grpc": {"field": "params.x", "values": [1]}}}]})


def test_fig8_fedbuff_chunking_is_spec_not_code():
    """The backend-coupled chunk_mb lives in the fig8 *sweep spec* (a
    conditional axis), not in an if-branch inside its cell runner."""
    from benchmarks.fig8_faults_wan import STUDY
    axes = [ax for sw in STUDY.sweeps(True) for ax in sw.axes]
    cond = [ax for ax in axes if ax.sub]
    assert cond, "fig8 lost its conditional chunking axis"
    ax = cond[0]
    assert ax.field == "channel.backend"
    chunks = {k: sub[0].values[0] for k, sub in ax.sub.items()}
    assert chunks["grpc"] > 0.0 and chunks["grpc+s3"] == 0.0


# ---------------------------------------------------------------------------
# RunStore: concurrent appends from real processes
# ---------------------------------------------------------------------------

def _append_burst(path, wid, n):
    store = RunStore(path)
    for i in range(n):
        store.put(CellResult.from_metrics(
            "stress", f"stress/w{wid}/{i}", f"{wid:02d}{i:04d}".ljust(24, "f"),
            {}, {"w": wid, "i": i},
            {"sim_time_s": float(i), "blob": "x" * 256}))


def test_runstore_concurrent_appends_never_interleave(tmp_path):
    """4 writer processes x 25 records into ONE store file: every line
    must parse, every record must survive."""
    path = str(tmp_path / "stress.jsonl")
    ctx = mp.get_context("spawn")
    procs = [ctx.Process(target=_append_burst, args=(path, w, 25))
             for w in range(4)]
    for p in procs:
        p.start()
    for p in procs:
        p.join()
        assert p.exitcode == 0
    with open(path) as f:
        lines = f.read().splitlines()
    assert len(lines) == 100
    recs = [CellResult.from_dict(json.loads(line)) for line in lines]
    assert len({r.fingerprint for r in recs}) == 100
    assert len(RunStore(path)) == 100


# ---------------------------------------------------------------------------
# --workers N == serial, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_workers_store_bit_identical_to_serial(tmp_path):
    """The acceptance bar: the fig4a quick grid run with workers=4
    produces a byte-identical run store to the serial run."""
    from benchmarks.fig4a_p2p_latency import STUDY
    cells = [c for sw in STUDY.sweeps(True) for c in sw.expand()]
    eng_a, eng_b = Engine(str(tmp_path / "a")), Engine(str(tmp_path / "b"))
    res_a = eng_a.run_cells(STUDY, cells, verbose=False)
    res_b = eng_b.run_cells(STUDY, cells, verbose=False, workers=4)
    assert res_a == res_b  # same records, same order
    with open(eng_a.store_path(STUDY.name), "rb") as f:
        blob_a = f.read()
    with open(eng_b.store_path(STUDY.name), "rb") as f:
        blob_b = f.read()
    assert blob_a == blob_b and len(blob_a) > 0


def test_workers_flag_plumbed_through_registry():
    from benchmarks.registry import discover
    entries = {e.name: e for e in discover()}
    assert entries["fig4a"].accepts_workers
    assert entries["fig8"].accepts_workers
    # legacy non-sweep modules must not be handed a workers kwarg
    assert not entries["kernels"].accepts_workers


# ---------------------------------------------------------------------------
# one process per chip: no worker pool on a TPU
# ---------------------------------------------------------------------------

def test_workers_refused_when_backend_is_tpu(tmp_path, monkeypatch):
    import jax
    from benchmarks.fig4a_p2p_latency import STUDY
    from repro.sweep.engine import refuse_pool_on_tpu
    cells = [c for sw in STUDY.sweeps(True) for c in sw.expand()][:2]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="one process per chip"):
        Engine(str(tmp_path)).run_cells(STUDY, cells, verbose=False,
                                        workers=2)
    refuse_pool_on_tpu(1)  # the parent alone may run cells
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    refuse_pool_on_tpu(4)
