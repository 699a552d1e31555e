"""Training supervisor (DESIGN.md §10): health guard, hardened checkpoints,
layered fault injection.

The fault-injection matrix tests assert the acceptance contract: every fault
class (preemption, pipeline-worker crash, mid-save checkpoint failure,
NaN batch) recovers without operator intervention, and the post-recovery
loss stream matches an uninterrupted run (exactly for preemption / pipeline
/ checkpoint faults, rtol=1e-6 for NaN rollback-and-retry).
"""
import json
import zlib
from pathlib import Path

import numpy as np
import pytest

from repro.checkpoint import CheckpointError, CheckpointManager
from repro.core import LMC
from repro.graph import ClusterSampler
from repro.models import make_gnn
from repro.optim import sgd
from repro.train import (FaultPlan, GNNTrainer, HealthConfig, HealthGuard,
                         StalenessBudgetError, TrainingDivergedError)


def _trainer(g, parts, tmp, **kw):
    # no straggler deadline: the matrix compares loss streams bit for bit,
    # and a step that the host's load slows past the deadline would drop
    # its store update in one of the two runs only (straggler handling is
    # tested in test_fault_tolerance.py)
    gnn = make_gnn("gcn", g.feature_dim, 32, g.num_classes, 2)
    s = ClusterSampler(g, 16, 2, parts=parts, seed=1)
    return GNNTrainer(gnn, LMC, g, s, sgd(lr=0.3), ckpt_dir=tmp,
                      ckpt_every=10, straggler_deadline=float("inf"), **kw)


def _losses(tr):
    """step -> loss, keeping the LAST record per step (replays overwrite)."""
    return {h["step"]: h["loss"] for h in tr.history if "loss" in h}


def _events(tr, kind=None):
    evs = [h for h in tr.history if h.get("event")]
    return [e for e in evs if kind is None or e["event"] == kind] \
        if kind else evs


@pytest.fixture(scope="module")
def clean_runs(small_graph, small_parts, tmp_path_factory):
    """Shared uninterrupted baselines: synchronous and pipelined streams."""
    base = tmp_path_factory.mktemp("clean")
    t_sync = _trainer(small_graph, small_parts, str(base / "sync"))
    t_sync.run(40)
    t_pipe = _trainer(small_graph, small_parts, str(base / "pipe"),
                      prefetch=2)
    t_pipe.run(30)
    t_pipe.close()
    return {"sync": _losses(t_sync), "pipe": _losses(t_pipe)}


# ------------------------------------------------------- fault matrix
def test_matrix_preemption(small_graph, small_parts, tmp_path, clean_runs):
    plan = FaultPlan(preempt_at=(25,))
    tr = _trainer(small_graph, small_parts, str(tmp_path),
                  failure_injector=plan)
    tr.run(40)
    evs = _events(tr, "preemption")
    assert len(evs) == 1 and evs[0]["restored"]
    got = _losses(tr)
    ref = clean_runs["sync"]
    np.testing.assert_array_equal([ref[s] for s in sorted(ref)],
                                  [got[s] for s in sorted(got)])


def test_matrix_pipeline_worker_crash(small_graph, small_parts, tmp_path,
                                      clean_runs):
    plan = FaultPlan(pipeline_at=(13,))
    tr = _trainer(small_graph, small_parts, str(tmp_path),
                  failure_injector=plan, prefetch=2)
    tr.run(30)
    tr.close()
    assert len(_events(tr, "pipeline-fault")) == 1
    got = _losses(tr)
    ref = clean_runs["pipe"]
    np.testing.assert_array_equal([ref[s] for s in sorted(ref)],
                                  [got[s] for s in sorted(got)])


def test_matrix_ckpt_write_failure(small_graph, small_parts, tmp_path,
                                   clean_runs):
    plan = FaultPlan(ckpt_write_at=(30,))
    tr = _trainer(small_graph, small_parts, str(tmp_path),
                  failure_injector=plan)
    tr.run(40)
    assert len(_events(tr, "ckpt-write-failed")) == 1
    # the aborted save left no partial/tmp state and older steps survive
    assert 30 not in tr.ckpt.all_steps()
    assert not list(Path(tmp_path).glob("*.tmp.*"))
    assert tr.ckpt.latest_step() == 40
    got = _losses(tr)
    ref = clean_runs["sync"]
    np.testing.assert_array_equal([ref[s] for s in sorted(ref)],
                                  [got[s] for s in sorted(got)])


def test_matrix_nan_batch_rollback(small_graph, small_parts, tmp_path,
                                   clean_runs):
    """Injected NaN gradients -> health rollback -> stream-deterministic
    replay (rtol=1e-6, as in test_resume_is_deterministic)."""
    plan = FaultPlan(nan_batch_at=(25,))
    tr = _trainer(small_graph, small_parts, str(tmp_path),
                  failure_injector=plan, health=HealthConfig())
    tr.run(40)
    evs = _events(tr, "health-rollback")
    assert len(evs) == 1 and "non-finite" in evs[0]["reason"]
    got = _losses(tr)
    ref = clean_runs["sync"]
    np.testing.assert_allclose([ref[s] for s in sorted(ref)],
                               [got[s] for s in sorted(got)], rtol=1e-6)
    # and the run still converges: one batch's loss varies up to 2x with
    # its clusters, so compare the means of the first and last ten steps
    losses = [h["loss"] for h in tr.history if "loss" in h]
    assert np.mean(losses[-10:]) < np.mean(losses[:10])


# ------------------------------------------------------- health policies
def test_nan_skip_batch_policy(small_graph, small_parts, tmp_path):
    plan = FaultPlan(nan_batch_at=(15,))
    tr = _trainer(small_graph, small_parts, str(tmp_path),
                  failure_injector=plan,
                  health=HealthConfig(policy="skip-batch"))
    tr.run(30)
    evs = _events(tr, "health-skip-batch")
    assert len(evs) == 1
    losses = _losses(tr)
    assert 16 not in losses          # the poisoned step was skipped, not applied
    assert all(np.isfinite(v) for v in losses.values())
    assert losses[max(losses)] < losses[min(losses)]


def test_rollback_without_checkpoint_degrades_to_skip(small_graph,
                                                      small_parts):
    plan = FaultPlan(nan_batch_at=(5,))
    gnn = make_gnn("gcn", small_graph.feature_dim, 32,
                   small_graph.num_classes, 2)
    s = ClusterSampler(small_graph, 16, 2, parts=small_parts, seed=1)
    tr = GNNTrainer(gnn, LMC, small_graph, s, sgd(lr=0.3),
                    failure_injector=plan, health=HealthConfig())  # no ckpt
    tr.run(12)
    evs = _events(tr, "health-skip-batch")
    assert len(evs) == 1 and evs[0]["policy"] == "rollback"
    assert all(np.isfinite(v) for v in _losses(tr).values())


def test_retry_budget_exhausts(small_graph, small_parts):
    """Persistent divergence without recovery aborts instead of live-locking."""
    plan = FaultPlan(nan_batch_at=(3, 4, 5, 6, 7))
    gnn = make_gnn("gcn", small_graph.feature_dim, 32,
                   small_graph.num_classes, 2)
    s = ClusterSampler(small_graph, 16, 2, parts=small_parts, seed=1)
    tr = GNNTrainer(gnn, LMC, small_graph, s, sgd(lr=0.3),
                    failure_injector=plan, health=HealthConfig(),
                    max_retries=2)
    with pytest.raises(TrainingDivergedError):
        tr.run(20)


def test_lr_backoff_on_rollback(small_graph, small_parts, tmp_path):
    plan = FaultPlan(nan_batch_at=(15,))
    tr = _trainer(small_graph, small_parts, str(tmp_path),
                  failure_injector=plan,
                  health=HealthConfig(lr_backoff=0.5))
    tr.run(25)
    assert len(_events(tr, "health-rollback")) == 1
    assert tr.lr == pytest.approx(0.15)   # 0.3 * 0.5
    assert all(np.isfinite(v) for v in _losses(tr).values())


# ------------------------------------------------------- health guard unit
def test_guard_spike_detection():
    g = HealthGuard(HealthConfig(spike_factor=10.0, warmup=4), 2, 8)
    for _ in range(6):
        assert g.check_step(1.0, 0.5) is None
        g.observe(1.0)
    assert g.check_step(1.5, 0.5) is None         # normal fluctuation
    reason = g.check_step(50.0, 0.5)              # 50x the median baseline
    assert reason is not None and "spike" in reason
    assert g.check_step(float("nan"), 0.5) is not None
    assert g.check_step(1.0, float("inf")) is not None


def test_guard_grad_norm_limit():
    g = HealthGuard(HealthConfig(grad_norm_limit=10.0), 2, 8)
    assert g.check_step(1.0, 9.0) is None
    assert "exceeds limit" in g.check_step(1.0, 11.0)


def test_guard_staleness_counters():
    g = HealthGuard(HealthConfig(), num_layers=2, num_nodes=6)
    gids = np.array([0, 1, 2])
    mask = np.ones(3)
    g.tick(gids, mask, store_updated=True)
    assert g.staleness[:, :3].max() == 0 and g.staleness[:, 3:].min() == 1
    g.tick(gids, mask, store_updated=False)       # skip-store straggler step
    assert g.staleness[:, :3].min() == 1 and g.staleness[:, 3:].min() == 2
    halo = np.array([3, 4])
    assert g.halo_staleness(halo, np.ones(2)) == 2
    assert g.halo_staleness(halo, np.zeros(2)) == 0   # fully masked halo
    g.reset_staleness()
    assert g.staleness.max() == 0


def test_guard_rho_budget():
    cfg = HealthConfig(rho_budget=3)
    g = HealthGuard(cfg, 1, 4)
    assert g.check_rho_budget(3) is None
    assert "rho budget" in g.check_rho_budget(4)
    strict = HealthGuard(HealthConfig(rho_budget=3, rho_strict=True), 1, 4)
    with pytest.raises(StalenessBudgetError):
        strict.check_rho_budget(4)


def test_staleness_recorded_in_history(small_graph, small_parts, tmp_path):
    tr = _trainer(small_graph, small_parts, str(tmp_path),
                  health=HealthConfig())
    tr.run(15)
    recs = [h for h in tr.history if "loss" in h]
    assert all("halo_staleness" in h for h in recs)
    assert max(h["halo_staleness"] for h in recs) >= 1  # uniform schedule ages rows


# ------------------------------------------------------- hardened checkpoints
def _tree():
    return {"a": np.arange(10.0), "b": {"c": np.ones((3, 3))}}


def test_corrupt_latest_truncated_leaf_falls_back(tmp_path):
    cm = CheckpointManager(tmp_path, keep=3)
    for s in (10, 20, 30):
        cm.save(s, _tree(), {"step": s})
    f = tmp_path / "step_0000000030" / "arr_0.npy"
    f.write_bytes(f.read_bytes()[:40])            # truncate
    restored, extras, step = cm.restore(_tree())
    assert step == 20 and extras["step"] == 20
    np.testing.assert_array_equal(restored["a"], _tree()["a"])
    assert not cm.verify(30) and cm.verify(20)


def test_corrupt_checksum_falls_back(tmp_path):
    cm = CheckpointManager(tmp_path, keep=3)
    for s in (10, 20):
        cm.save(s, _tree(), {"step": s})
    f = tmp_path / "step_0000000020" / "arr_1.npy"
    raw = bytearray(f.read_bytes())
    raw[-1] ^= 0xFF                               # bit-flip payload, same size
    f.write_bytes(bytes(raw))
    _, _, step = cm.restore(_tree())
    assert step == 10
    with pytest.raises(CheckpointError, match="checksum"):
        cm.restore(_tree(), step=20)


def test_mangled_manifest_falls_back(tmp_path):
    cm = CheckpointManager(tmp_path, keep=3)
    for s in (10, 20):
        cm.save(s, _tree(), {"step": s})
    (tmp_path / "step_0000000020" / "manifest.json").write_text("{not json")
    _, _, step = cm.restore(_tree())
    assert step == 10


def test_missing_leaf_raises_named_error(tmp_path):
    cm = CheckpointManager(tmp_path)
    cm.save(10, _tree(), {"step": 10})
    (tmp_path / "step_0000000010" / "arr_1.npy").unlink()
    with pytest.raises(CheckpointError, match=r"step 10.*arr_1\.npy"):
        cm.restore(_tree(), step=10)


def test_num_leaves_mismatch_raises_clear_error(tmp_path):
    cm = CheckpointManager(tmp_path)
    cm.save(10, _tree(), {"step": 10})
    bigger = {**_tree(), "d": np.zeros(4)}
    with pytest.raises(CheckpointError, match="2 leaves.*expects 3"):
        cm.restore(bigger, step=10)


def test_no_verifiable_checkpoint_raises(tmp_path):
    cm = CheckpointManager(tmp_path)
    cm.save(10, _tree(), {"step": 10})
    f = tmp_path / "step_0000000010" / "arr_0.npy"
    f.write_bytes(f.read_bytes()[:10])
    with pytest.raises(CheckpointError, match="no verifiable checkpoint"):
        cm.restore(_tree())


def test_orphaned_tmp_dir_gc(tmp_path):
    orphan = tmp_path / "step_0000000099.tmp.abc123"
    orphan.mkdir(parents=True)
    (orphan / "arr_0.npy").write_bytes(b"partial")
    cm = CheckpointManager(tmp_path)               # init-time GC
    assert not orphan.exists()
    orphan2 = tmp_path / "step_0000000098.tmp.xyz"
    orphan2.mkdir()
    cm.save(10, _tree(), {"step": 10})             # post-save GC
    assert not orphan2.exists()
    assert cm.all_steps() == [10]


def test_manifest_records_leaf_metadata(tmp_path):
    cm = CheckpointManager(tmp_path)
    cm.save(10, _tree(), {"step": 10})
    man = json.loads((tmp_path / "step_0000000010" / "manifest.json")
                     .read_text())
    assert man["format"] == 2 and man["num_leaves"] == 2
    assert [m["shape"] for m in man["leaves"]] == [[10], [3, 3]]
    assert [m["dtype"] for m in man["leaves"]] == ["float64", "float64"]
    arr = np.load(tmp_path / "step_0000000010" / "arr_0.npy")
    assert man["leaves"][0]["crc32"] == \
        zlib.crc32(np.ascontiguousarray(arr).tobytes())


def test_legacy_manifest_still_restores(tmp_path):
    """Format-1 manifests (no leaf metadata) restore without verification."""
    cm = CheckpointManager(tmp_path)
    cm.save(10, _tree(), {"step": 10})
    mpath = tmp_path / "step_0000000010" / "manifest.json"
    man = json.loads(mpath.read_text())
    del man["leaves"], man["format"]
    mpath.write_text(json.dumps(man))
    restored, extras, step = cm.restore(_tree())
    assert step == 10
    np.testing.assert_array_equal(restored["b"]["c"], np.ones((3, 3)))


def test_async_save_byte_identical(tmp_path):
    sync = CheckpointManager(tmp_path / "sync")
    sync.save(5, _tree(), {"step": 5})
    asy = CheckpointManager(tmp_path / "async")
    asy.save(5, _tree(), {"step": 5}, background=True)
    asy.wait()
    sdir, adir = tmp_path / "sync/step_0000000005", \
        tmp_path / "async/step_0000000005"
    files = sorted(p.name for p in sdir.iterdir())
    assert files == sorted(p.name for p in adir.iterdir())
    for name in files:
        assert (sdir / name).read_bytes() == (adir / name).read_bytes()
    asy.close()


def test_async_save_failure_surfaces_on_wait(tmp_path):
    def hook(step, phase):
        if phase == "manifest":
            raise OSError("disk full (injected)")
    cm = CheckpointManager(tmp_path, fault_hook=hook)
    cm.save(5, _tree(), {}, background=True)
    with pytest.raises(OSError, match="disk full"):
        cm.wait()
    assert cm.all_steps() == [] and not list(tmp_path.glob("*.tmp.*"))
    cm.close()


def test_async_ckpt_trainer_resume(small_graph, small_parts, tmp_path):
    """Resume from an async-written checkpoint == uninterrupted run."""
    t1 = _trainer(small_graph, small_parts, str(tmp_path / "a"),
                  async_ckpt=True)
    t1.run(20)
    t1.save()
    t1.run(5)
    loss_cont = [h["loss"] for h in t1.history if "loss" in h][-5:]
    t1.close()

    t2 = _trainer(small_graph, small_parts, str(tmp_path / "a"))
    assert t2.restore()
    assert t2.step_num == 20
    t2.run(5)
    loss_resume = [h["loss"] for h in t2.history if "loss" in h][-5:]
    np.testing.assert_allclose(loss_cont, loss_resume, rtol=1e-6)


def test_trainer_restores_from_corrupt_latest(small_graph, small_parts,
                                              tmp_path):
    """End-to-end: corrupt latest step on disk -> trainer resumes from the
    newest verifiable step and keeps training."""
    t1 = _trainer(small_graph, small_parts, str(tmp_path))
    t1.run(30)                                     # checkpoints at 10, 20, 30
    latest = Path(tmp_path) / "step_0000000030" / "arr_0.npy"
    latest.write_bytes(latest.read_bytes()[:64])
    t2 = _trainer(small_graph, small_parts, str(tmp_path))
    assert t2.restore()
    assert t2.step_num == 20
    hist = t2.run(10)
    assert np.isfinite([h["loss"] for h in hist if "loss" in h][-1])
