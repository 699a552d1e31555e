"""Checkpoint/restart, preemption recovery, elastic rescale, data resume."""
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core import LMC
from repro.data import TokenStream
from repro.graph import ClusterSampler
from repro.models import make_gnn
from repro.optim import sgd
from repro.train import FaultPlan, GNNTrainer, rescale_lmc_state


def _trainer(g, parts, tmp, **kw):
    gnn = make_gnn("gcn", g.feature_dim, 32, g.num_classes, 2)
    s = ClusterSampler(g, 16, 2, parts=parts, seed=1)
    return GNNTrainer(gnn, LMC, g, s, sgd(lr=0.3), ckpt_dir=tmp,
                      ckpt_every=10, **kw)


def test_checkpoint_roundtrip(tmp_path):
    cm = CheckpointManager(tmp_path, keep=2)
    tree = {"a": np.arange(10.0), "b": {"c": np.ones((3, 3))}}
    for step in (10, 20, 30):
        cm.save(step, tree, {"step": step})
    assert cm.all_steps() == [20, 30]  # retention
    restored, extras, step = cm.restore(tree)
    assert step == 30 and extras["step"] == 30
    np.testing.assert_array_equal(restored["a"], tree["a"])


def test_preemption_recovery(small_graph, small_parts, tmp_path):
    inj = FaultPlan(preempt_at=(25,))
    tr = _trainer(small_graph, small_parts, str(tmp_path),
                  failure_injector=inj)
    hist = tr.run(50)
    events = [h for h in hist if h.get("event") == "preemption"]
    assert len(events) == 1 and events[0]["restored"]
    assert tr.step_num == 50
    losses = [h["loss"] for h in hist if "loss" in h]
    assert losses[-1] < losses[0]


def test_resume_is_deterministic(small_graph, small_parts, tmp_path):
    """Restore + continue == uninterrupted run (same batch schedule)."""
    t1 = _trainer(small_graph, small_parts, str(tmp_path / "a"))
    t1.run(20)
    t1.save()
    t1.run(5)
    loss_cont = [h["loss"] for h in t1.history if "loss" in h][-5:]

    t2 = _trainer(small_graph, small_parts, str(tmp_path / "a"))
    assert t2.restore()
    assert t2.step_num == 20
    t2.run(5)
    loss_resume = [h["loss"] for h in t2.history if "loss" in h][-5:]
    np.testing.assert_allclose(loss_cont, loss_resume, rtol=1e-6)


def test_elastic_rescale(small_graph, small_parts, tmp_path):
    tr = _trainer(small_graph, small_parts, str(tmp_path))
    tr.run(10)
    # scale 16 -> 8 clusters; stores survive (per-node state)
    sampler2, store2 = rescale_lmc_state(
        small_graph, tr.store, old_num_parts=16, new_num_parts=8, seed=1)
    assert sampler2.num_parts == 8
    np.testing.assert_array_equal(np.asarray(store2.h), np.asarray(tr.store.h))
    tr.sampler = sampler2
    tr.store = store2
    hist = tr.run(5)
    assert np.isfinite([h["loss"] for h in hist if "loss" in h][-1])


def test_rescale_rebuilds_the_prefetch_pipeline(small_graph, small_parts,
                                                tmp_path):
    """A prefetching trainer whose sampler is replaced draws its next batch
    from the new partition, not from batches built ahead from the old."""
    tr = _trainer(small_graph, small_parts, str(tmp_path), prefetch=2)
    try:
        tr.run(3)
        sampler2, tr.store = rescale_lmc_state(
            small_graph, tr.store, old_num_parts=16, new_num_parts=8, seed=1)
        tr.sampler = sampler2
        want = sampler2.build_batch(
            sampler2.clusters_at(tr.step_num, mode=tr.pipeline_mode))
        batch = next(tr._batch_pipeline())
        np.testing.assert_array_equal(np.asarray(batch.batch_gids),
                                      want.batch_gids)
        np.testing.assert_array_equal(np.asarray(batch.halo_gids),
                                      want.halo_gids)
    finally:
        tr.close()


def test_restore_ignores_a_saved_sampler_state(small_graph, small_parts,
                                               tmp_path):
    """Checkpoints once carried the sampler's RNG state under "sampler";
    restore still reads them, and ignores the entry."""
    t1 = _trainer(small_graph, small_parts, str(tmp_path))
    t1.run(3)
    t1.ckpt.save(t1.step_num, t1._state_tree(),
                 {"step": t1.step_num, "lr": t1.lr,
                  "sampler": {"bit_generator": {"state": {"state": 1}}}})
    t2 = _trainer(small_graph, small_parts, str(tmp_path))
    assert t2.restore() and t2.step_num == 3
    t2.run(1)
    assert np.isfinite(t2.history[-1]["loss"])


def test_token_stream_resume():
    a = TokenStream(1000, 4, 32, seed=7)
    batches = [next(a) for _ in range(5)]
    b = TokenStream(1000, 4, 32, seed=7)
    b.load_state_dict({"step": 3})
    np.testing.assert_array_equal(next(b)["tokens"], batches[3]["tokens"])
    np.testing.assert_array_equal(next(b)["tokens"], batches[4]["tokens"])


def test_straggler_skip_store(small_graph, small_parts, tmp_path):
    tr = _trainer(small_graph, small_parts, str(tmp_path),
                  straggler_deadline=0.0)  # every step after warmup is late
    hist = tr.run(15)
    assert any(h.get("straggler") for h in hist if "loss" in h)
    # training still progresses (store updates skipped, not the params);
    # one batch's loss varies up to 2x with its clusters, so compare the
    # means of the first and last five steps
    losses = [h["loss"] for h in hist if "loss" in h]
    assert np.mean(losses[-5:]) < np.mean(losses[:5])
