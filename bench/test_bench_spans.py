"""CPU tests of the readers of the program's scopes and spans: each on a
hand-built trace, the program's own profiler trace, and a tiny traced run."""
import json
import shutil
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, scopes
from bench import trace as tracing
from bench.test_bench_harness import TINY_LIMITS, _shrink

ROOT = Path(__file__).resolve().parents[1]
NEW = ("aggregate_s", "aggregate_t_s", "compensate_s", "store_refresh_s",
       "dense_s", "scoped_share", "wait_idle_s", "h2d_idle_s",
       "build_span_s")
PROGRAM_SPANS = ("train", "train.fetch", "train.dispatch", "train.sync",
                 "train.ckpt", "pipeline.wait", "pipeline.h2d",
                 "pipeline.build")


def _read(name, ctx):
    return harness._load_module(ROOT / "bench" / "metrics" / f"{name}.py"
                                ).read(ctx)


def _ctx(trace, steps=2):
    lo, hi = trace.window()
    return SimpleNamespace(trace=trace, lo=lo, hi=hi, steps=steps)


def _trace():
    """One device, busy 70 of a 100 ns window, 60 of it scoped; the host
    waits over [55, 70), copies over [70, 95) and builds three batches."""
    def op(name, start, dur, path):
        return (name, start, dur, f"{name} jit(step)/{path}")
    dev = [op("fusion.1", 0, 10, "lmc.agg/scatter-add"),
           op("fusion.2", 10, 20, "transpose(jvp(lmc.agg))/scatter-add"),
           op("fusion.3", 30, 5, "lmc.halo/jit(_take)/gather"),
           op("fusion.4", 35, 5, "lmc.store/scatter"),
           op("dot.5", 40, 10, "transpose(jvp(lmc.dense))/dot_general"),
           ("copy.6", 50, 10, "copy.6"),
           op("fusion.7", 80, 10, "lmc.dense/mul")]
    host = [(tracing.WINDOW_SPAN, 0, 100), ("pipeline.wait", 55, 15),
            ("pipeline.h2d", 70, 25), ("pipeline.build", -50, 30),
            ("pipeline.build", 20, 40), ("pipeline.build", 90, 20)]
    return tracing.Trace(device_ops=[dev], host_events=host)


def test_part_of_tells_transposed_aggregation_apart():
    assert scopes.part_of("f jit(step)/transpose(jvp(lmc.agg))/gather") == \
        "aggregate_t"
    assert scopes.part_of("f jit(step)/lmc.agg/gather") == "aggregate"
    assert scopes.part_of("f jit(step)/lmc.halo/x lmc.dense") == "compensate"
    assert scopes.part_of("copy.3") is None


@pytest.mark.parametrize("name,want", [
    ("aggregate_s", 10e-9 / 2), ("aggregate_t_s", 20e-9 / 2),
    ("compensate_s", 5e-9 / 2), ("store_refresh_s", 5e-9 / 2),
    ("dense_s", 20e-9 / 2), ("scoped_share", 100.0 * 60 / 70),
    ("wait_idle_s", 10e-9 / 2), ("h2d_idle_s", 15e-9 / 2),
    ("build_span_s", 30e-9)])
def test_reader_on_a_hand_built_trace(name, want):
    assert _read(name, _ctx(_trace())) == pytest.approx(want)


def test_scoped_parts_sum_to_the_scoped_share_of_busy_time():
    t = _trace()
    lo, hi = t.window()
    parts = scopes.part_seconds(t, lo, hi)
    busy = tracing.busy_seconds(t, lo, hi)
    assert sum(parts.values()) == pytest.approx(
        scopes.scoped_share(t, lo, hi) / 100.0 * busy)


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n >> 7 else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _len(num: int, payload: bytes) -> bytes:
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _int(num: int, value: int) -> bytes:
    return _varint(num << 3) + _varint(value)


def _xspace() -> bytes:
    """A serialized XSpace: one device plane whose op metadata holds a
    ``tf_op`` path (one as a string, one as a reference to an interned
    string) and an unrelated statistic, and a host plane whose ``tf_op`` is
    not a device op's."""
    def stat_md(sid, name):
        return _len(5, _int(1, sid) + _len(2, _int(1, sid) + _len(2, name)))

    def event_md(eid, name, *stats):
        body = _int(1, eid) + _len(2, name) + b"".join(
            _len(5, st) for st in stats)
        return _len(4, _int(1, eid) + _len(2, body))
    tf_op = lambda path: _int(1, 3) + _len(5, path)   # noqa: E731
    device = (_len(2, b"/device:TPU:0") + _len(3, _len(2, b"XLA Ops"))
              + stat_md(3, b"tf_op") + stat_md(4, b"flops")
              + stat_md(9, b"jit(step)/lmc.halo/gather:")
              + event_md(1, b"%fusion.1 = f32[8]{0} fusion()",
                         _int(1, 4) + _int(4, 7),
                         tf_op(b"jit(step)/transpose(jvp(lmc.agg))/x:"))
              + event_md(2, b"%gather.2 = f32[8]{0} gather()",
                         _int(1, 3) + _int(7, 9))
              + event_md(5, b"%copy.5 = f32[8]{0} copy()",
                         _int(1, 4) + _int(4, 1)))
    host = (_len(2, b"/host:CPU") + stat_md(3, b"tf_op")
            + event_md(1, b"train.fetch", tf_op(b"lmc.dense")))
    return _len(1, device) + _len(1, host)


def test_op_paths_from_the_trace_file(tmp_path):
    paths = scopes.paths_in_xspace(_xspace())
    assert paths == {
        "%fusion.1 = f32[8]{0} fusion()":
            "jit(step)/transpose(jvp(lmc.agg))/x:",
        "%gather.2 = f32[8]{0} gather()": "jit(step)/lmc.halo/gather:"}
    # found beside the reader's metrics directory, where the harness
    # writes the window's trace
    run = tmp_path / "bench" / ".trace" / "plugins" / "profile" / "r"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(_xspace())
    reader = tmp_path / "bench" / "metrics" / "dense_s.py"
    assert scopes.op_paths(str(reader)) == paths
    assert scopes.op_paths(str(tmp_path / "none" / "metrics" / "x.py")) == {}
    # a device op with no path in its own text is read by its name
    ops = [(name, 10 * i, 10) for i, name in enumerate(
        ["%fusion.1 = f32[8]{0} fusion()", "%gather.2 = f32[8]{0} gather()",
         "%copy.5 = f32[8]{0} copy()"])]
    t = tracing.Trace(device_ops=[ops],
                      host_events=[(tracing.WINDOW_SPAN, 0, 30)])
    parts = scopes.part_seconds(t, 0, 30, paths)
    assert parts["aggregate_t"] == pytest.approx(10e-9)
    assert parts["compensate"] == pytest.approx(10e-9)
    assert scopes.scoped_share(t, 0, 30, paths) == pytest.approx(200 / 3)
    assert scopes.part_seconds(t, 0, 30) == {}


def test_readers_read_nothing_without_scopes_or_spans():
    """A program that names no scope and no span (or a CPU run, with no
    device trace) gives nothing to read, and no reader raises."""
    t = _trace()
    bare = tracing.Trace(
        device_ops=[[op[:3] for op in t.device_ops[0]]],
        host_events=[e for e in t.host_events if e[0] == tracing.WINDOW_SPAN])
    cpu = tracing.Trace(device_ops=[], host_events=t.host_events)
    for name in NEW:
        assert _read(name, _ctx(bare)) is None, name
        if name != "build_span_s":
            assert _read(name, _ctx(cpu)) is None, name


def test_program_spans_in_a_profiler_trace(tmp_path):
    """Two trainer steps under the profiler: every span of the program is
    in the trace, and the batches are built off the consumer's thread."""
    import jax
    from jax.profiler import ProfileData

    from repro.core import LMC
    from repro.graph import ClusterSampler, make_sbm_dataset, partition_graph
    from repro.models import make_gnn
    from repro.optim import sgd
    from repro.train import GNNTrainer

    g = make_sbm_dataset("ppi-cpu", seed=3)
    sampler = ClusterSampler(g, 8, 2, parts=partition_graph(g, 8, seed=0),
                             seed=1)
    gnn = make_gnn("gcn", g.feature_dim, 16, g.num_classes, 2)
    tr = GNNTrainer(gnn, LMC, g, sampler, sgd(lr=0.2), seed=0, prefetch=1,
                    pipeline_workers=1, ckpt_dir=str(tmp_path / "ckpt"),
                    ckpt_every=1)
    try:
        tr.run(1)                       # compile outside the trace
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            tr.run(2)
        finally:
            jax.profiler.stop_trace()
    finally:
        tr.close()
    names = {e[0] for e in tracing.from_xplane(str(tmp_path / "trace"))
             .host_events}
    assert set(PROGRAM_SPANS) <= names, set(PROGRAM_SPANS) - names

    pb = next((tmp_path / "trace").glob("plugins/profile/*/*.xplane.pb"))
    lines_of = {}   # a line of a host plane is one thread
    for plane in ProfileData.from_file(str(pb)).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                lines_of.setdefault(e.name, set()).add((plane.name, i))
    assert lines_of["pipeline.build"].isdisjoint(lines_of["train.fetch"])


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    """A copy of the benchmark with a tiny GCNII segment cell added."""
    root = tmp_path_factory.mktemp("bench_spans")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    bench = ROOT / "bench"
    cfg = _shrink(json.loads((bench / "configs" / "gcnii-flickr.json")
                             .read_text()), 24)
    cfg["name"] = "tiny-gcnii-flickr"
    (root / "bench/configs/tiny-gcnii-flickr.json").write_text(
        json.dumps(cfg))
    traffic = json.loads((bench / "traffic" / "gas-flickr.segment.json")
                         .read_text())
    traffic.update(parts=8, clusters_per_batch=2)
    (root / "bench/traffic/tiny-gas-flickr.segment.json").write_text(
        json.dumps(traffic))
    (root / "bench/limits/tiny-gcnii-flickr.segment.json").write_text(
        json.dumps({k: {"limit": v} for k, v in TINY_LIMITS.items()}))
    spec["configs"].append({"name": "tiny-gcnii-flickr", "source": "test",
                            "file": "bench/configs/tiny-gcnii-flickr.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-gcnii-flickr.segment",
                              "config": "tiny-gcnii-flickr",
                              "traffic": "tiny-gas-flickr.segment",
                              "chips": 1, "why": "t"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_traced_run_reads_the_build_span(tiny_root):
    result, _ = harness.run_cell(tiny_root, "tiny-gcnii-flickr.segment",
                                 2**31 + 13, 0.5, True, require_tpu=False)
    assert result["correct"] is True
    assert result["metrics"]["build_span_s"]["value"] > 0
    # no device trace on the CPU: the device readers find nothing
    for name in set(NEW) - {"build_span_s"}:
        assert name not in result["metrics"], name
    assert np.isfinite(result["device"]["window_s"]) and "breakdown" in result
