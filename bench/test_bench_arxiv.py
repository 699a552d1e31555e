"""CPU tests of the gcn-arxiv traffic mixes: a tiny copy of the
``gas-arxiv.segment`` twin (a mix no benchmark cell runs yet) beside
``test_bench_harness.py``'s tiny ``ell`` cell, found by file name, correct
when sound and not correct when broken; and the ``ell_build_span_s`` reader,
on hand-built traces and on a tiny traced ``ell`` run."""
import json
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import compare, control, harness
from bench import trace as tracing
from bench.test_bench_harness import (TINY_LIMITS, _altered_row, _half_batch,
                                      _shrink, _unchanged_state)

ROOT = Path(__file__).resolve().parents[1]
# tiny copies: (configuration, traffic mix) of the cells they stand for
TINY = {"tiny-gcn-arxiv.ell": ("gcn-arxiv", "gas-arxiv.ell"),
        "tiny-gcn-arxiv.segment": ("gcn-arxiv", "gas-arxiv.segment")}
SEGMENT = "tiny-gcn-arxiv.segment"


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    """A copy of the benchmark with the tiny arxiv cells added as files
    alone: one configuration at tiny sizes, a traffic mix at 8 parts and a
    limits file for each cell."""
    root = tmp_path_factory.mktemp("bench_arxiv")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    bench = ROOT / "bench"
    cfg = _shrink(json.loads((bench / "configs/gcn-arxiv.json").read_text()),
                  16)
    cfg["name"] = "tiny-gcn-arxiv"
    (root / "bench/configs/tiny-gcn-arxiv.json").write_text(json.dumps(cfg))
    spec["configs"].append({"name": "tiny-gcn-arxiv", "source": "test",
                            "file": "bench/configs/tiny-gcn-arxiv.json",
                            "reduced": [], "why": "test"})
    for wname, (_, tname) in TINY.items():
        traffic = json.loads((bench / "traffic" / f"{tname}.json")
                             .read_text())
        traffic.update(parts=8, clusters_per_batch=2)
        (root / f"bench/traffic/tiny-{tname}.json").write_text(
            json.dumps(traffic))
        (root / f"bench/limits/{wname}.json").write_text(json.dumps(
            {k: {"limit": v} for k, v in TINY_LIMITS.items()}))
        spec["workloads"].append({"name": wname, "config": "tiny-gcn-arxiv",
                                  "traffic": f"tiny-{tname}", "chips": 1,
                                  "why": "t"})
    for m in spec["per_layer"]:   # the kernel cell's readers, in its copy
        if "gcn-arxiv.ell" in m.get("workloads", []):
            m["workloads"].append("tiny-gcn-arxiv.ell")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _run(root, cell, trace=False, patch=None, seed=2**31 + 29):
    return harness.run_cell(root, cell, seed, 0.3, trace, require_tpu=False,
                            patch=patch)


def test_twins_differ_in_the_backend_alone(tiny_root):
    ell = harness.find_cell(tiny_root, "tiny-gcn-arxiv.ell")
    seg = harness.find_cell(tiny_root, SEGMENT)
    assert ell.config == seg.config
    assert seg.traffic == {**ell.traffic, "backend": "segment"}
    kernel_readers = {"ell_spmm_roofline", "lmc_compensate_roofline",
                      "ell_build_span_s"}
    assert kernel_readers <= {m["name"] for m in ell.per_layer}
    assert not kernel_readers & {m["name"] for m in seg.per_layer}


def test_sound_segment_twin_is_correct(tiny_root):
    result, checked = _run(tiny_root, SEGMENT)
    assert result["correct"] is True, checked
    assert set(checked) == set(compare.NAMES)
    assert set(result["metrics"]) == {"step_s", "peak_hbm_gib", "setup_s"}
    assert result["attempted"] >= 1 and result["failed"] == 0


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_row],
                         ids=["unchanged_state", "half_batch", "altered_row"])
def test_broken_segment_twin_is_not_correct(tiny_root, fault):
    result, checked = _run(tiny_root, SEGMENT, patch=fault)
    assert result["correct"] is False, checked


def test_control_fails_the_segment_twin_limits(tiny_root):
    limits = harness.find_cell(tiny_root, SEGMENT).limits
    for r in control.readings(tiny_root, SEGMENT, [5],
                              variants=("control",)):
        assert not compare.passed(compare.checks(r, limits)), r


def _read_ell_build(trace):
    lo, hi = trace.window()
    return harness._load_module(ROOT / "bench/metrics/ell_build_span_s.py"
                                ).read(SimpleNamespace(trace=trace, lo=lo,
                                                       hi=hi, steps=2))


@pytest.mark.parametrize("spans,want", [
    ((("pipeline.build", 10, 60), ("pipeline.ell", 30, 20),
      ("pipeline.ell", 80, 40), ("pipeline.ell", 120, 10)), 30e-9),
    ((("pipeline.build", 10, 60),), None)],
    ids=["ell_spans", "no_ell_span"])
def test_ell_build_span_reader_on_a_hand_built_trace(spans, want):
    """The mean of the ``pipeline.ell`` spans that start in the window (the
    one at 120 starts after it); nothing where the program has none."""
    host = [(tracing.WINDOW_SPAN, 0, 100), *spans]
    trace = tracing.Trace(device_ops=[[("fusion.1", 0, 50, "fusion.1")]],
                          host_events=host)
    got = _read_ell_build(trace)
    assert got == (None if want is None else pytest.approx(want))


def test_traced_ell_run_reads_the_ell_build_span(tiny_root):
    """The program's own ``pipeline.ell`` spans reach the trace from the
    pipeline's worker threads (the CPU has no device trace, so the kernel
    rooflines read nothing here)."""
    result, _ = _run(tiny_root, "tiny-gcn-arxiv.ell", trace=True)
    assert result["correct"] is True
    metrics = result["metrics"]
    assert 0.0 < metrics["ell_build_span_s"]["value"] \
        < metrics["build_span_s"]["value"]
    assert "ell_spmm_roofline" not in metrics
