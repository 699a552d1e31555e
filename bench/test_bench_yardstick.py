"""CPU tests of the benchmark's yardstick: the graph generator, the trace
reduction, the operation and byte counts, and the comparison's numbers."""
import math

import numpy as np
import pytest

from bench import compare, counts, graphgen
from bench import trace as tracing


def _spec(nodes, deg, classes, feats, seed=0):
    return {"nodes": nodes, "avg_degree": deg, "classes": classes,
            "features": feats, "seed": seed, "p_in_frac": 0.85,
            "feature_snr": 1.5, "label_noise": 0.05, "splits": [0.6, 0.2]}


@pytest.mark.parametrize("preset,seed", [("arxiv-cpu", 0), ("flickr-cpu", 3)])
def test_graph_matches_the_repository_preset(preset, seed):
    from repro.graph.synthetic import DATASET_PRESETS, make_sbm_dataset
    n, deg, k, dx = DATASET_PRESETS[preset]
    got = graphgen.make_graph(_spec(n, deg, k, dx, seed))
    want = make_sbm_dataset(preset, seed=seed)
    for name in ("indptr", "indices", "x", "y", "train_mask", "val_mask",
                 "test_mask"):
        np.testing.assert_array_equal(getattr(got, name), getattr(want, name))


def test_graph_is_symmetric_without_self_loops():
    g = graphgen.make_graph(_spec(300, 6.0, 4, 8, seed=5))
    dst = np.repeat(np.arange(g.num_nodes), np.diff(g.indptr))
    pairs = set(zip(dst.tolist(), g.indices.tolist()))
    assert all((b, a) in pairs for a, b in pairs)
    assert not any(a == b for a, b in pairs)
    assert len(pairs) == g.num_edges


def _trace():
    # device: ops at [0,10), [5,20) (overlap), [30,40), kernel at [50,90)
    dev = [("fusion.1", 0, 10), ("fusion.2", 5, 15), ("copy.3", 30, 10),
           ("ell_spmm.9", 50, 40, "ell_spmm.9 jit(step)/jit(ell_spmm)")]
    host = [(tracing.WINDOW_SPAN, 0, 100), ("bench.step", 0, 100),
            ("bench.batch_wait", 20, 10), ("bench.update_dispatch", 40, 2)]
    return tracing.Trace(device_ops=[dev], host_events=host)


def test_trace_busy_time_is_the_union_of_operations():
    t = _trace()
    lo, hi = t.window()
    assert (lo, hi) == (0.0, 100.0)
    assert tracing.busy_intervals(t.device_ops[0], lo, hi) == [
        (0.0, 20.0), (30.0, 40.0), (50.0, 90.0)]
    assert tracing.busy_seconds(t, lo, hi) == pytest.approx(70e-9)
    # clipped to the window
    assert tracing.busy_seconds(t, 10.0, 60.0) == pytest.approx(30e-9)


def test_trace_kernel_time_and_top_ops():
    t = _trace()
    spmm = tracing.op_seconds(t, 0, 100, lambda text: "ell_spmm" in text)
    assert spmm == pytest.approx(40e-9)
    # without a text, the lower-case name is matched
    assert tracing.op_seconds(t, 0, 100, lambda text: "copy" in text) == \
        pytest.approx(10e-9)
    top = tracing.top_ops(t, 0, 100, k=2)
    assert [name for name, _ in top] == ["ell_spmm.9", "fusion.2"]
    assert top[0][1] == pytest.approx(40e-9)


def test_trace_averages_over_devices():
    t = _trace()
    t.device_ops.append([("fusion.9", 0, 100)])
    assert tracing.busy_seconds(t, 0, 100) == pytest.approx(85e-9)
    assert tracing.op_seconds(t, 0, 100, lambda n: "spmm" in n) == \
        pytest.approx(20e-9)


def test_idle_gaps_are_labelled_by_the_host_span():
    t = _trace()
    gaps = dict(tracing.idle_gaps(t, 0, 100))
    # [20,30) under batch_wait; [40,50) and [90,100) under step only
    assert gaps["bench.batch_wait"] == pytest.approx(10e-9)
    assert gaps["bench.step"] == pytest.approx(20e-9)
    assert sum(gaps.values()) == pytest.approx(30e-9)


@pytest.mark.parametrize("metric,op", [
    ("ell_spmm_roofline", "ell_spmm.9"),
    ("ell_spmm_roofline", "transpose_jvp_jit_ell_spmm___.12"),
    ("lmc_compensate_roofline", "lmc_compensate_kernel.4")])
def test_roofline_readers_find_the_compiled_kernel_names(metric, op):
    """The names are those of the kernels' HLO instructions in the compiled
    LMC step; a trace without them gives nothing to read."""
    import importlib
    from types import SimpleNamespace
    read = importlib.import_module(f"bench.metrics.{metric}").read
    cfg = {**GCN, "num_layers": 3, "hidden_dim": 128, "graph": {
        "features": 128, "classes": 40}}
    sizes = counts.BatchSizes(batch_rows=1000, halo_rows=2000, edges=30000,
                              edge_src_rows=2500, edge_dst_rows=2800)

    def ctx(name):
        t = tracing.Trace(device_ops=[[(name, 0, 10**9)]], host_events=[])
        return SimpleNamespace(config=cfg, trace=t, lo=0.0, hi=2e9,
                               step_sizes=[sizes],
                               peaks=lambda: {"bf16_flops": 197e12,
                                              "hbm_bytes_per_s": 819e9})
    share = read(ctx(op))
    assert 0 < share < 100
    assert read(ctx("fusion.3")) is None


def test_trace_without_window_span_is_an_error():
    t = tracing.Trace(device_ops=[], host_events=[("x", 0, 1)])
    with pytest.raises(ValueError):
        t.window()


GCN = {"arch": "gcn", "num_layers": 2, "hidden_dim": 4,
       "graph": {"features": 3, "classes": 2}}
GCNII = {"arch": "gcnii", "num_layers": 2, "hidden_dim": 4,
         "graph": {"features": 3, "classes": 2}}
SIZES = counts.BatchSizes(batch_rows=2, halo_rows=3, edges=6,
                          edge_src_rows=4, edge_dst_rows=5)


def test_step_flops_by_hand_gcn():
    # n=5 rows, nb=2, E=6, dx=3, d=4, c=2, L=2
    l0 = (6 * 3 + 5 * 3) + 5 * 3 * 4 + 2 * 3 * 4          # no input adjoint
    l1 = (6 * 4 + 5 * 4) + 5 * 4 * 4 + 2 * 4 * 4 + (5 * 4 * 4 + 6 * 4 + 5 * 4)
    head = 5 * 4 * 2 + 2 * 4 * 2 + 5 * 4 * 2
    assert counts.step_flops(GCN, SIZES) == 2 * (l0 + l1 + head)


def test_step_flops_by_hand_gcnii():
    lay = (6 * 4 + 5 * 4) + 5 * 4 * 4 + 2 * 4 * 4 + (5 * 4 * 4 + 6 * 4 + 5 * 4)
    head = 5 * 4 * 2 + 2 * 4 * 2 + 5 * 4 * 2
    emb = 5 * 3 * 4 + 2 * 3 * 4
    assert counts.step_flops(GCNII, SIZES) == 2 * (2 * lay + head + emb)


def test_kernel_counts_by_hand():
    assert counts.spmm(6, 4, 5, 128) == (2 * 6 * 128,
                                         4 * (128 * 9 + 2 * 6))
    assert counts.compensate(3, 128) == (4 * 3 * 128, 4 * (3 * 3 * 128 + 9))
    # GCN, L=2: forward at widths 3 and 4, one transposed call (layer 1)
    calls = counts.spmm_calls(GCN, SIZES)
    assert calls == [counts.spmm(6, 4, 5, 3), counts.spmm(6, 4, 5, 4),
                     counts.spmm(6, 5, 4, 4)]
    assert len(counts.spmm_calls(GCNII, SIZES)) == 4
    assert counts.compensate_calls(GCN, SIZES) == [counts.compensate(3, 4)] * 3


def test_roofline_seconds_takes_the_larger_bound():
    assert counts.roofline_seconds([(100.0, 10.0)], 10.0, 10.0) == 10.0
    assert counts.roofline_seconds([(1.0, 10.0), (100.0, 1.0)], 10.0,
                                   1.0) == 10.0 + 10.0
    flops, nbytes = counts.spmm(1000, 500, 500, 256)
    assert nbytes / 819e9 > flops / 197e12   # the kernels are memory bound


def test_batch_sizes_cut_padding():
    src = np.array([0, 1, 1, 2, 0, 0], np.int32)
    dst = np.array([1, 0, 2, 1, 0, 0], np.int32)
    b = counts.batch_sizes(src, dst, batch_rows=2, halo_rows=1, edges=4)
    assert (b.rows, b.edges, b.edge_src_rows, b.edge_dst_rows) == (3, 4, 3, 3)


def _side(scale=1.0, loss=2.0):
    rng = np.random.default_rng(0)
    p0 = {"layers": {"w": [rng.normal(size=(3, 4))]}, "head": {"b": np.zeros(2)}}
    return {"losses": [loss, loss, loss],
            "grads": {"layers": {"w": [np.full((3, 4), 0.5 * scale)]},
                      "head": {"b": np.full(2, 2.0)}},
            "params0": p0,
            "params3": {"layers": {"w": [p0["layers"]["w"][0] + 0.1 * scale]},
                        "head": {"b": np.full(2, 0.03)}},
            "hbar": np.ones((2, 5, 4)) * scale, "vbar": np.ones((1, 5, 4))}


def test_compare_numbers_by_hand():
    ref = _side()
    assert all(v == 0.0 for v in compare.numbers(_side(), ref).values())
    got = compare.numbers(_side(scale=1.1, loss=2.2), ref)
    assert got["loss_gap"] == pytest.approx(0.1)
    # leaf norms: w 0.5*sqrt(12), b 2*sqrt(2); median of the two is their mean
    w, b = 0.5 * math.sqrt(12), 2 * math.sqrt(2)
    assert got["grad_gap"] == pytest.approx(0.1 * w / ((w + b) / 2))
    assert got["hbar_gap"] == pytest.approx(0.1)
    assert got["vbar_gap"] == 0.0


def test_compare_leaves_out_leaves_without_gradient():
    ref, got = _side(), _side()
    ref["grads"]["head"]["b"] = np.zeros(2)   # moves by rounding alone
    got["params3"]["head"]["b"] = np.full(2, 0.5)
    assert compare.numbers(got, ref)["change_gap"] == 0.0


def test_compare_non_finite_is_infinite():
    got = _side()
    got["losses"][1] = float("nan")
    got["hbar"] = got["hbar"] * np.nan
    n = compare.numbers(got, _side())
    assert n["loss_gap"] == math.inf and n["hbar_gap"] == math.inf
    checked = compare.checks(n, {k: {"limit": 1.0} for k in compare.NAMES})
    assert not compare.passed(checked)
