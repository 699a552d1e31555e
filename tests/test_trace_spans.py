"""The host spans' counters: what ``SubgraphPipeline.last_fetch`` and each
``GNNTrainer.history`` record say about where a step's host time went."""
import time

import pytest

from repro import tracing
from repro.data.prefetch import SubgraphPipeline
from repro.graph import ClusterSampler

STEP_FIELDS = ("fetch_s", "wait_s", "h2d_s", "dispatch_s", "sync_s",
               "staged", "build_s", "edge_fill", "ell_s", "ell_fill")


def _sampler(graph, parts):
    return ClusterSampler(graph, 16, 2, parts=parts, seed=1)


def _trainer(graph, parts, **kw):
    from repro.core import LMC
    from repro.models import make_gnn
    from repro.optim import sgd
    from repro.train import GNNTrainer
    gnn = make_gnn("gcn", graph.feature_dim, 16, graph.num_classes, 2)
    return GNNTrainer(gnn, LMC, graph, _sampler(graph, parts), sgd(lr=0.2),
                      seed=0, **kw)


def test_span_adds_its_seconds_to_the_record():
    rec = {}
    for _ in range(2):
        with tracing.span("train.fetch", rec):
            time.sleep(0.01)
    assert set(rec) == {"fetch_s"} and rec["fetch_s"] >= 0.02
    with pytest.raises(KeyError):
        with tracing.span("pipeline.wait", rec):
            raise KeyError("inside")
    assert rec["wait_s"] >= 0.0
    with tracing.span("train.sync"):   # no record: a bare annotation
        pass


def test_slow_build_makes_the_fetch_wait(small_graph, small_parts):
    hook = lambda slot: time.sleep(0.2)   # noqa: E731
    with SubgraphPipeline(_sampler(small_graph, small_parts), depth=2,
                          workers=1, build_hook=hook) as pipe:
        next(pipe)
        rec = pipe.last_fetch
    assert rec["wait_s"] > 0.1 and rec["staged"] is False
    assert rec["build_s"] >= 0.2 and rec["h2d_s"] > 0.0


def test_fast_build_and_slow_consumer_get_staged_batches(small_graph,
                                                         small_parts):
    with SubgraphPipeline(_sampler(small_graph, small_parts), depth=2,
                          workers=2) as pipe:
        recs = []
        for _ in range(4):
            next(pipe)
            recs.append(dict(pipe.last_fetch))
            time.sleep(0.3)          # the step: workers fill the queue
    assert recs[0]["staged"] is False and recs[0]["wait_s"] > 0.0
    for rec in recs[2:]:
        # the wait span opens on a staged fetch too, and finds the batch
        assert rec["staged"] is True and rec["wait_s"] < 0.05
        assert rec["build_s"] > 0.0 and rec["h2d_s"] > 0.0


def test_recycled_step_fetches_nothing(small_graph, small_parts):
    with SubgraphPipeline(_sampler(small_graph, small_parts), depth=0,
                          recycle=2) as pipe:
        next(pipe)
        first = dict(pipe.last_fetch)
        next(pipe)
        second = dict(pipe.last_fetch)
    assert first["build_s"] > 0.0 and first["h2d_s"] > 0.0
    # the recycled step consumes the same batch, so its fills are the same
    assert 0.0 < first["edge_fill"] <= 1.0
    assert first["ell_s"] == 0.0 and first["ell_fill"] == 0.0  # no ELL
    assert second == {"wait_s": 0.0, "h2d_s": 0.0, "staged": False,
                      "build_s": 0.0, "ell_s": 0.0,
                      "edge_fill": first["edge_fill"],
                      "ell_fill": first["ell_fill"]}


@pytest.mark.parametrize("prefetch", [None, 0, 2],
                         ids=["legacy", "sync", "prefetch"])
def test_record_parts_sum_within_the_step(small_graph, small_parts,
                                          prefetch):
    tr = _trainer(small_graph, small_parts, prefetch=prefetch)
    try:
        tr.run(4)
    finally:
        tr.close()
    recs = [h for h in tr.history if "loss" in h]
    assert len(recs) == 4
    for rec in recs:
        assert set(STEP_FIELDS) <= set(rec)
        parts = rec["fetch_s"] + rec["dispatch_s"] + rec["sync_s"]
        assert 0.0 < parts <= rec["time_s"]
        assert rec["wait_s"] + rec["h2d_s"] <= rec["fetch_s"]
        assert rec["dispatch_s"] > 0.0 and rec["sync_s"] > 0.0
    if prefetch != 2:
        # built and copied in the step itself, so inside its fetch
        for rec in recs:
            assert 0.0 < rec["build_s"] <= rec["fetch_s"]
            assert rec["h2d_s"] > 0.0
            assert rec["staged"] is False and rec["wait_s"] == 0.0


@pytest.mark.parametrize("prefetch", [None, 0, 2],
                         ids=["legacy", "sync", "prefetch"])
def test_record_carries_the_consumed_batch_edge_fill(small_graph, small_parts,
                                                     prefetch):
    tr = _trainer(small_graph, small_parts, prefetch=prefetch)
    try:
        tr.run(3)
    finally:
        tr.close()
    # the batches the trainer consumed, rebuilt from a twin of its sampler
    twin = _sampler(small_graph, small_parts)
    if prefetch is None:
        consumed = [twin.sample() for _ in range(3)]
    else:
        consumed = [twin.build_batch(twin.clusters_at(i,
                                                      mode=tr.pipeline_mode))
                    for i in range(3)]
    recs = [h for h in tr.history if "loss" in h]
    assert len(recs) == 3
    for rec, sg in zip(recs, consumed):
        assert 0.0 < rec["edge_fill"] <= 1.0
        assert rec["edge_fill"] == sg.n_edges_real / twin.pad_edges


@pytest.mark.parametrize("prefetch", [None, 0, 2],
                         ids=["legacy", "sync", "prefetch"])
def test_record_carries_the_consumed_batch_ell_fill(small_graph, small_parts,
                                                    prefetch):
    """On the ``ell`` backend the record holds the ELL build's seconds
    (``pipeline.ell``, inside ``pipeline.build``) and the consumed batch's
    real (row, neighbour) pairs over its ELL slots."""
    from repro.core import host_batch
    tr = _trainer(small_graph, small_parts, prefetch=prefetch, backend="ell")
    try:
        tr.run(2)
    finally:
        tr.close()
    twin = _sampler(small_graph, small_parts)
    if prefetch is None:
        consumed = [twin.sample() for _ in range(2)]
    else:
        consumed = [twin.build_batch(twin.clusters_at(i,
                                                      mode=tr.pipeline_mode))
                    for i in range(2)]
    recs = [h for h in tr.history if "loss" in h]
    assert len(recs) == 2
    for rec, sg in zip(recs, consumed):
        slots = sum(idx.size for idx in
                    host_batch(sg, backend="ell").ell.bucket_idx)
        assert rec["ell_fill"] == sg.n_edges_real / slots
        assert 0.0 < rec["ell_fill"] < rec["edge_fill"] <= 1.0
        assert 0.0 < rec["ell_s"] <= rec["build_s"]
