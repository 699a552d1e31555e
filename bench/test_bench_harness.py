"""CPU tests of the harness: cells, traffic mixes, references and per-layer
metrics are found by file name; a sound run is correct; the program broken
underneath, or the control in its place, is not; off a TPU it refuses."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import compare, control, harness

ROOT = Path(__file__).resolve().parents[1]
# tiny copies: (configuration, traffic mix) of the cells they stand for
TINY = {"tiny-gcn-arxiv.ell": ("gcn-arxiv", "gas-arxiv.ell"),
        "tiny-gcnii-flickr.segment": ("gcnii-flickr", "gas-flickr.segment")}
# limits of the tiny copies, set from CPU readings at these sizes over a
# dozen seeds: the sound program read at most 2.4e-7 (loss), 2.1e-7
# (grad), 4.6e-8 (change), 1.8e-7 (hbar), 7.3e-8 (vbar); the bf16x3 control
# at least 2.6e-6 (hbar) and 2.9e-6 (vbar)
TINY_LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-5, "change_gap": 1e-4,
               "hbar_gap": 2e-6, "vbar_gap": 1e-6}


def _shrink(cfg: dict, features: int) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg["graph"].update(nodes=480, avg_degree=8.0, classes=5,
                        features=features)
    cfg["hidden_dim"] = 32
    cfg["num_layers"] = 3
    return cfg


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    """A copy of the benchmark with tiny cells added as files alone: a
    configuration file at tiny sizes, a traffic mix at 8 parts and a limits
    file for each, and a throwaway per-layer metric."""
    root = tmp_path_factory.mktemp("bench_copy")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    bench = ROOT / "bench"
    for wname, (cname, tname) in TINY.items():
        real = json.loads((bench / "configs" / f"{cname}.json").read_text())
        name = "tiny-" + cname
        cfg = _shrink(real, 24 if real["arch"] == "gcnii" else 16)
        cfg["name"] = name
        (root / f"bench/configs/{name}.json").write_text(json.dumps(cfg))
        traffic = json.loads((bench / "traffic" / f"{tname}.json")
                             .read_text())
        traffic.update(parts=8, clusters_per_batch=2)
        (root / f"bench/traffic/tiny-{tname}.json").write_text(
            json.dumps(traffic))
        (root / f"bench/limits/{wname}.json").write_text(json.dumps(
            {k: {"limit": v} for k, v in TINY_LIMITS.items()}))
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "test"})
        spec["workloads"].append({"name": wname, "config": name,
                                  "traffic": f"tiny-{tname}", "chips": 1,
                                  "why": "t"})
    (root / "bench/metrics/steps_seen.py").write_text(
        "def read(ctx):\n    return float(ctx.steps)\n")
    spec["per_layer"].append({"name": "steps_seen", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "step_s",
                              "workloads": ["tiny-gcnii-flickr.segment"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_added_files_are_found_by_name(tiny_root):
    cell = harness.find_cell(tiny_root, "tiny-gcn-arxiv.ell")
    assert cell.config["name"] == "tiny-gcn-arxiv"
    assert cell.traffic["parts"] == 8 and cell.traffic["backend"] == "ell"
    assert "steps_seen" not in [m["name"] for m in cell.per_layer]
    assert harness.reference_arch(cell).init_params
    other = harness.find_cell(tiny_root, "tiny-gcnii-flickr.segment")
    assert other.traffic["backend"] == "segment"
    assert "steps_seen" in [m["name"] for m in other.per_layer]
    assert "ell_spmm_roofline" not in [m["name"] for m in other.per_layer]
    assert harness.metric_reader(other, "steps_seen")(
        type("Ctx", (), {"steps": 4})) == 4.0


def _run(root, cell, trace=False, patch=None, seed=2**31 + 11):
    return harness.run_cell(root, cell, seed, 0.3, trace, require_tpu=False,
                            patch=patch)


def test_traced_run_reads_the_added_metric(tiny_root):
    result, _ = _run(tiny_root, "tiny-gcnii-flickr.segment", trace=True)
    assert result["correct"] is True
    assert result["metrics"]["steps_seen"]["value"] == result["attempted"]
    assert result["metrics"]["host_build_s"]["value"] > 0
    # the CPU has no device trace: nothing to read, so nothing reported
    assert "mfu" not in result["metrics"]
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("cell", ["tiny-gcn-arxiv.ell",
                                  "tiny-gcnii-flickr.segment"])
def test_sound_run_is_correct(tiny_root, cell):
    result, checked = _run(tiny_root, cell)
    assert result["correct"] is True, checked
    assert set(checked) == set(compare.NAMES)
    assert set(result["metrics"]) == {"step_s", "peak_hbm_gib", "setup_s"}
    assert result["attempted"] >= 1 and result["failed"] == 0


def _unchanged_state(trainer):
    """The step returns its state unchanged."""
    step, update = trainer._step, trainer._update

    def frozen_step(params, store, *a):
        loss, grads, _, metrics = step(params, store, *a)
        return loss, grads, store, metrics

    def frozen_update(grads, state, params, lr):
        return params, state, update(grads, state, params, lr)[2]
    trainer._step, trainer._update = frozen_step, frozen_update


def _half_batch(trainer):
    """Half of the batch's labelled rows left out, the mean over the rest."""
    step = trainer._step

    def half(params, store, batch, *a):
        nb = batch.batch_gids.shape[0]
        lab = batch.labeled_mask
        keep = (np.arange(lab.shape[0]) % 2 == 0) | (np.arange(lab.shape[0])
                                                     >= nb)
        kept = lab * keep
        ratio = lab[:nb].sum() / max(float(kept[:nb].sum()), 1.0)
        return step(params, store, batch._replace(
            labeled_mask=kept, loss_scale=batch.loss_scale * ratio), *a)
    trainer._step = half


def _altered_row(trainer):
    """One refreshed row of the historical embeddings altered where the
    step produces it."""
    step = trainer._step

    def altered(params, store, batch, *a):
        loss, grads, new, metrics = step(params, store, batch, *a)
        row = batch.batch_gids[0]
        return loss, grads, new._replace(h=new.h.at[0, row].add(1.0)), metrics
    trainer._step = altered


@pytest.mark.parametrize("cell", ["tiny-gcn-arxiv.ell",
                                  "tiny-gcnii-flickr.segment"])
@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_row],
                         ids=["unchanged_state", "half_batch", "altered_row"])
def test_broken_program_is_not_correct(tiny_root, cell, fault):
    result, checked = _run(tiny_root, cell, patch=fault)
    assert result["correct"] is False, checked


@pytest.mark.parametrize("cell", sorted(TINY))
def test_control_fails_the_limits(tiny_root, cell):
    limits = harness.find_cell(tiny_root, cell).limits
    for r in control.readings(tiny_root, cell, [5],
                              variants=("control",)):
        assert not compare.passed(compare.checks(r, limits)), r


def _bench_cmd(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "gcnii-flickr.segment",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_off_a_tpu():
    p = _bench_cmd(ROOT)
    assert p.returncode == 3 and p.stdout == "", p.stderr[-2000:]


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    p = _bench_cmd(tmp_path)
    assert p.returncode not in (0, 3) and p.stdout == ""
    assert "No module named 'repro'" in p.stderr


NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$"
UNIT = r"^[A-Za-z0-9_/%.-]{1,16}$"


def test_benchmark_names_files_that_exist():
    import re
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert spec["command"] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    bench = ROOT / "bench"
    for c in spec["configs"]:
        assert re.match(NAME, c["name"]) and c["file"].startswith("bench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert (bench / "reference" / f"{cfg['arch']}.py").exists()
    for w in spec["workloads"]:
        assert re.match(NAME, w["name"]) and w["chips"] in (1, 4)
        assert (bench / "traffic" / f"{w['traffic']}.json").exists()
        limits = json.loads((bench / "limits" / f"{w['name']}.json")
                            .read_text())
        assert set(compare.NAMES) <= set(limits)
        assert all(0 < limits[k]["limit"] for k in compare.NAMES)
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names)) and "setup_s" in names
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and re.match(UNIT, m["unit"])
    for m in spec["per_layer"]:
        assert re.match(NAME, m["name"]) and re.match(UNIT, m["unit"])
        assert (bench / "metrics" / f"{m['name']}.py").exists()
        assert m["moves"] in [e["name"] for e in spec["end_to_end"]]
    for name in ("peaks.json",):
        assert json.loads((bench / name).read_text())["devices"]
