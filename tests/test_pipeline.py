"""Tests for repro.data.prefetch.SubgraphPipeline — stream determinism
(prefetch == sync under a fixed seed), minibatch recycling, epoch coverage,
resume, worker-exception propagation and clean shutdown — plus the trainer
integration (GNNTrainer prefetch path vs the schedule-indexed sync path,
and deterministic checkpoint resume through the pipeline)."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.data.prefetch import SubgraphPipeline
from repro.graph import ClusterSampler


def _leaves_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _pipeline_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("subgraph-pipeline") and t.is_alive()]


def _wait_until(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return pred()


def _sampler(graph, parts, seed=1, c=2):
    return ClusterSampler(graph, 16, c, parts=parts, seed=seed)


class _RecordingSampler:
    """Duck-typed sampler wrapper recording the schedule slots built."""

    def __init__(self, inner):
        self._inner = inner
        self.calls: list = []   # (slot, cluster-id tuple)
        self._lock = threading.Lock()

    def clusters_at(self, slot, *, mode="uniform"):
        cids = self._inner.clusters_at(slot, mode=mode)
        with self._lock:
            self.calls.append((int(slot), tuple(int(c) for c in cids)))
        return cids

    def build_batch(self, cids):
        return self._inner.build_batch(cids)


class _FailingSampler(_RecordingSampler):
    """Raises from the worker when building a chosen slot."""

    def __init__(self, inner, fail_slot):
        super().__init__(inner)
        self.fail_slot = fail_slot

    def clusters_at(self, slot, *, mode="uniform"):
        if int(slot) == self.fail_slot:
            raise RuntimeError(f"bad slot {slot}")
        return super().clusters_at(slot, mode=mode)


# ------------------------------------------------------------ construction
@pytest.mark.parametrize("kw", [dict(depth=-1), dict(workers=0),
                                dict(recycle=0), dict(start_step=-1)])
def test_invalid_config_rejected(small_graph, small_parts, kw):
    with pytest.raises(ValueError):
        SubgraphPipeline(_sampler(small_graph, small_parts), **kw)


# ------------------------------------------------------------- determinism
def test_prefetch_equals_sync_stream(small_graph, small_parts):
    """depth=2/workers=2 must yield the exact same batches as depth=0:
    the stream is a pure function of the slot index, not of thread timing."""
    n = 6
    with SubgraphPipeline(_sampler(small_graph, small_parts), depth=0,
                          num_steps=n) as sync:
        ref = list(sync)
    with SubgraphPipeline(_sampler(small_graph, small_parts), depth=2,
                          workers=2, num_steps=n) as pre:
        got = list(pre)
    assert len(ref) == len(got) == n
    for r, g in zip(ref, got):
        assert _leaves_equal(r, g)


def test_resume_replays_uninterrupted_tail(small_graph, small_parts):
    """start_step=k (even mid-recycle-window) must reproduce the tail of a
    run started at 0 — the checkpoint-recovery contract."""
    full = list(SubgraphPipeline(_sampler(small_graph, small_parts),
                                 depth=0, recycle=2, num_steps=10))
    with SubgraphPipeline(_sampler(small_graph, small_parts), depth=2,
                          recycle=2, start_step=5, num_steps=5) as tail:
        resumed = list(tail)
    assert len(resumed) == 5
    for r, g in zip(full[5:], resumed):
        assert _leaves_equal(r, g)


# --------------------------------------------------------------- recycling
def test_recycle_reuses_each_subgraph_rho_times(small_graph, small_parts):
    rho, slots = 3, 4
    with SubgraphPipeline(_sampler(small_graph, small_parts), depth=2,
                          recycle=rho, num_steps=rho * slots) as pipe:
        got = list(pipe)
    assert len(got) == rho * slots
    for i in range(0, len(got), rho):
        window = got[i:i + rho]
        assert all(b is window[0] for b in window)   # same object, ρ steps
    distinct = got[::rho]
    for a, b in zip(distinct, distinct[1:]):
        assert a is not b


def test_epoch_coverage_under_recycling(small_graph, small_parts):
    """mode="epoch" with recycling: every partition is built exactly once
    per B/c distinct slots, and only B/c host builds happen for ρ·B/c steps."""
    rho, c, b = 3, 2, 16
    slots_per_epoch = b // c
    rec = _RecordingSampler(_sampler(small_graph, small_parts, c=c))
    with SubgraphPipeline(rec, depth=2, workers=2, recycle=rho, mode="epoch",
                          num_steps=rho * slots_per_epoch) as pipe:
        n = sum(1 for _ in pipe)
    assert n == rho * slots_per_epoch
    assert len(rec.calls) == slots_per_epoch    # 1/ρ of the steps
    built = [cid for _, cids in rec.calls for cid in cids]
    assert sorted(built) == list(range(b))      # each cluster exactly once


# ------------------------------------------------------- failure & shutdown
def test_worker_exception_surfaces_in_slot_order(small_graph, small_parts):
    fail = _FailingSampler(_sampler(small_graph, small_parts), fail_slot=2)
    with SubgraphPipeline(fail, depth=2, workers=2, num_steps=6) as pipe:
        assert next(pipe) is not None
        assert next(pipe) is not None
        with pytest.raises(RuntimeError, match="bad slot 2"):
            next(pipe)


def test_consumer_raise_mid_epoch_shuts_down_cleanly(small_graph, small_parts):
    """A consumer raising mid-epoch must still stop every worker thread
    (the context manager closes the pipeline without swallowing the error)."""
    with pytest.raises(ValueError, match="consumer bug"):
        with SubgraphPipeline(_sampler(small_graph, small_parts), depth=2,
                              workers=2) as pipe:
            next(pipe)
            next(pipe)
            raise ValueError("consumer bug")
    assert _wait_until(lambda: not _pipeline_threads()), (
        f"pipeline threads survived close(): {_pipeline_threads()}")
    with pytest.raises(StopIteration):
        next(pipe)


def test_close_is_idempotent(small_graph, small_parts):
    pipe = SubgraphPipeline(_sampler(small_graph, small_parts), depth=1)
    next(pipe)
    pipe.close()
    pipe.close()
    assert _wait_until(lambda: not _pipeline_threads())


# ------------------------------------------------------ trainer integration
def _make_trainer(graph, parts, **kw):
    from repro.core import LMC
    from repro.models import make_gnn
    from repro.optim import sgd
    from repro.train import GNNTrainer
    gnn = make_gnn("gcn", graph.feature_dim, 32, graph.num_classes, 2)
    s = _sampler(graph, parts)
    return GNNTrainer(gnn, LMC, graph, s, sgd(lr=0.2), seed=0, **kw)


@pytest.mark.parametrize("backend", ["segment", "ell", "ti"])
def test_trainer_prefetch_matches_sync(small_graph, small_parts, backend):
    """GNNTrainer(prefetch=2) must produce the identical loss trajectory to
    prefetch=0 (same schedule, synchronous builds), on every backend."""
    ta = _make_trainer(small_graph, small_parts, prefetch=0, backend=backend)
    ta.run(6)
    ta.close()
    tb = _make_trainer(small_graph, small_parts, prefetch=2, backend=backend)
    tb.run(6)
    tb.close()
    la = [h["loss"] for h in ta.history]
    lb = [h["loss"] for h in tb.history]
    assert la == lb


def test_trainer_resume_through_pipeline(tmp_path, small_graph, small_parts):
    """Checkpoint restore + pipeline rebuild replays the uninterrupted run."""
    ref = _make_trainer(small_graph, small_parts, prefetch=2, recycle=2)
    ref.run(8)
    ref.close()

    ta = _make_trainer(small_graph, small_parts, prefetch=2, recycle=2,
                       ckpt_dir=str(tmp_path), ckpt_every=4)
    ta.run(4)
    ta.save()
    ta.close()
    tb = _make_trainer(small_graph, small_parts, prefetch=2, recycle=2,
                       ckpt_dir=str(tmp_path), ckpt_every=4)
    assert tb.restore()
    assert tb.step_num == 4
    tb.run(4)
    tb.close()
    assert _leaves_equal(ref.params, tb.params)


def test_trainer_close_stops_workers(small_graph, small_parts):
    tr = _make_trainer(small_graph, small_parts, prefetch=2)
    tr.run(2)
    tr.close()
    assert _wait_until(lambda: not _pipeline_threads())
