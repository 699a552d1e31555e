"""One run of one benchmark cell: set-up, measured window, trace, check.

Everything that belongs to one cell is found by name, from the root that
holds ``BENCHMARK.json``:

* the configuration: the file its ``configs`` entry names;
* the traffic mix: ``<bench>/traffic/<traffic>.json``;
* the reference architecture: ``<bench>/reference/<arch>.py``;
* the limits of the comparison: ``<bench>/limits/<workload>.json``;
* each per-layer metric: ``<bench>/metrics/<name>.py``, whose ``read(ctx)``
  returns the value, or ``None`` where the run holds nothing to read.

A run builds the graph (seed 0) and its partition, a ``GNNTrainer`` with the
benchmark's own weights drawn from ``--seed`` and the cluster schedule seeded
by ``--seed``, then drives ``GNNTrainer.run(1)`` three times for the
comparison: that compiles and warms every program the window runs. The
window then runs whole steps until ``--seconds`` have passed. Afterwards the
trainer is freed and the plain reference takes the same three steps.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Optional

import numpy as np

from bench import compare, counts, graphgen
from bench import trace as tracing

CHECK_STEPS = 3
MAX_COUNTED_SLOTS = 64


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ finding
@dataclasses.dataclass
class Cell:
    root: Path
    bench: Path
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    per_layer: list      # the BENCHMARK.json entries this cell reports
    end_to_end: list


def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(root: Path, workload: str) -> Cell:
    """Everything the cell ``workload`` needs, by the names it gives."""
    root = Path(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    bench = root / spec["paths"][0]
    wls = {w["name"]: w for w in spec["workloads"]}
    if workload not in wls:
        raise KeyError(f"no workload {workload!r}; have {sorted(wls)}")
    wl = wls[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads((bench / "traffic" / f"{wl['traffic']}.json")
                         .read_text())
    limits = json.loads((bench / "limits" / f"{workload}.json").read_text())

    def mine(m):
        return workload in m.get("workloads", [workload])
    return Cell(root=root, bench=bench, workload=wl, config=config,
                traffic=traffic, limits=limits,
                per_layer=[m for m in spec["per_layer"] if mine(m)],
                end_to_end=[m for m in spec["end_to_end"] if mine(m)])


def reference_arch(cell: Cell):
    return _load_module(cell.bench / "reference" / f"{cell.config['arch']}.py")


def metric_reader(cell: Cell, name: str) -> Callable:
    return _load_module(cell.bench / "metrics" / f"{name}.py").read


def peaks_for(cell: Cell, kind: str) -> dict:
    table = json.loads((cell.bench / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; "
                       f"known: {sorted(table)}")
    return table[kind]


# ------------------------------------------------------------------ helpers
class CompileCounter:
    """Counts programs compiled or fetched from the persistent cache."""

    def __init__(self):
        import jax.monitoring as mon
        self.mon, self.programs, self.seconds = mon, 0, 0.0
        self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def close(self):
        self.mon.unregister_event_duration_listener(self._duration)
        self.mon.unregister_event_listener(self._event)


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def program_graph(g: graphgen.HostGraph):
    from repro.graph import Graph
    return Graph(indptr=g.indptr, indices=g.indices, x=g.x, y=g.y,
                 train_mask=g.train_mask, val_mask=g.val_mask,
                 test_mask=g.test_mask, name="bench")


def weight_key(seed: int):
    """A PRNG key from any whole number (the run seed may pass 32 bits)."""
    import jax
    word = np.random.default_rng([int(seed), 0xB3]).integers(0, 2**31 - 1)
    return jax.random.key(int(word))


def check_device(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def _spans(trainer) -> None:
    """Wrap the trainer's batch fetch, step and update in host spans."""
    import jax

    def wrap(fn, name):
        def wrapped(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return wrapped

    class Fetch:
        def __init__(self, pipe):
            self.pipe = pipe

        def __next__(self):
            with jax.profiler.TraceAnnotation("bench.batch_wait"):
                return next(self.pipe)

    # private hooks of the trainer: where one is gone, its span is skipped
    pipeline = getattr(trainer, "_batch_pipeline", None)
    if callable(pipeline):
        trainer._batch_pipeline = lambda: Fetch(pipeline())
    for attr, name in (("_step", "bench.step_dispatch"),
                       ("_update", "bench.update_dispatch")):
        fn = getattr(trainer, attr, None)
        if callable(fn):
            setattr(trainer, attr, wrap(fn, name))


# ---------------------------------------------------------------------- run
def run_cell(root, workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, require_tpu: bool = True,
             patch: Optional[Callable] = None) -> tuple[dict, dict]:
    """One run; returns (result line, checks). ``patch(trainer)`` may
    break the program underneath (tests)."""
    import jax

    import repro  # noqa: F401  (the system under test; fail early without it)
    t_start = time.time() if t_start is None else t_start
    cell = find_cell(Path(root), workload)
    cfg = cell.config
    devs = (check_device(cell.workload["chips"]) if require_tpu
            else jax.devices()[:1])
    counter = CompileCounter()
    try:
        with jax.default_matmul_precision(cfg["matmul_precision"]):
            out = _run(cell, seed, seconds, trace, t_start, devs, counter,
                       patch)
    finally:
        counter.close()
    return out


def _run(cell, seed, seconds, trace, t_start, devs, counter, patch):
    import jax

    from repro.core import LMC, host_batch
    from repro.graph import ClusterSampler, partition_graph
    from repro.models import make_gnn
    from repro.optim import adamw
    from repro.train import GNNTrainer

    cfg, tr = cell.config, cell.traffic
    dev, n_devices = devs[0], len(devs)
    arch = reference_arch(cell)
    opt_cfg = cfg["optimizer"]
    if (cfg["method"], opt_cfg["name"], cfg["dtype"]) != ("lmc", "adamw",
                                                          "float32"):
        raise ValueError("the reference covers LMC, AdamW and float32 only")
    hg = graphgen.make_graph(cfg["graph"])
    graph = program_graph(hg)
    parts = partition_graph(graph, tr["parts"], seed=0)
    sampler = ClusterSampler(graph, tr["parts"], tr["clusters_per_batch"],
                             parts=parts, seed=int(seed),
                             beta_spec=tuple(cfg["beta_score"]))
    gnn = make_gnn(cfg["arch"], hg.x.shape[1], cfg["hidden_dim"],
                   cfg["graph"]["classes"], cfg["num_layers"],
                   **cfg.get("arch_args", {}))
    opt = adamw(lr=opt_cfg["lr"], b1=opt_cfg["b1"], b2=opt_cfg["b2"],
                eps=opt_cfg["eps"], wd=opt_cfg["weight_decay"],
                clip=opt_cfg["clip_norm"])
    trainer = GNNTrainer(gnn, LMC, graph, sampler, opt, seed=0,
                         backend=tr["backend"], prefetch=tr["prefetch"],
                         recycle=tr["recycle"],
                         pipeline_workers=tr["pipeline_workers"],
                         pipeline_mode=tr["pipeline_mode"])
    weights = jax.jit(lambda k: arch.init_params(k, cfg))(weight_key(seed))
    trainer.params = weights
    params0 = jax.device_get(weights)
    if patch is not None:
        patch(trainer)
    if trace:
        _spans(trainer)

    # the first steps: warm-up, and what the reference is compared with
    prog = {"params0": params0, "losses": []}
    for i in range(CHECK_STEPS):
        trainer.run(1)
        rec = trainer.history[-1]
        prog["losses"].append(float(rec["loss"]))
        if i == 0:
            scale = min(1.0, opt_cfg["clip_norm"]
                        / max(float(rec["grad_norm"]), 1e-9))
            m = jax.device_get(trainer.opt_state["m"])
            prog["grads"] = jax.tree.map(
                lambda a: np.asarray(a) / (1.0 - opt_cfg["b1"]) / scale, m)
            store = jax.device_get(tuple(trainer.store))
            prog["hbar"], prog["vbar"] = store[0], store[1]
    prog["params3"] = jax.device_get(trainer.params)
    jax.block_until_ready((trainer.params, trainer.store))
    setup_programs, setup_compile_s = counter.programs, counter.seconds

    # the measured window
    log_dir = cell.bench / ".trace"
    if trace:
        import shutil
        shutil.rmtree(log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    t0 = time.perf_counter()
    setup_s = time.time() - t_start
    first = len(trainer.history)
    steps = 0
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        while True:
            with jax.profiler.TraceAnnotation("bench.step"):
                trainer.run(1)
            steps += 1
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready((trainer.params, trainer.store))
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    window_programs = counter.programs - setup_programs
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    losses = [r.get("loss") for r in trainer.history[first:]]
    failed = sum(1 for v in losses if v is None or not math.isfinite(v))
    trainer.close()
    del trainer, weights
    gc.collect()

    # per-layer metrics, from the trace and the cell's batches
    breakdown = None
    if trace:
        metrics, breakdown, busy_s, traced_s = _per_layer(
            cell, log_dir, sampler, tr, steps, dev, host_batch)
    else:
        metrics = {
            "step_s": {"value": window_s / steps, "unit": "s"},
            "peak_hbm_gib": {"value": peak / 2**30, "unit": "GiB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        wanted = {m["name"] for m in cell.end_to_end}
        metrics = {k: v for k, v in metrics.items() if k in wanted}

    # the reference, once the program's state is freed
    t_ref = time.perf_counter()
    ref = reference_run(cell, arch, hg, parts, sampler, params0)
    values = compare.numbers(prog, ref)
    checked = compare.checks(values, cell.limits)
    correct = compare.passed(checked) and failed == 0
    _log(f"setup: {setup_s:.3f} s, {setup_programs} programs compiled or "
         f"fetched in {setup_compile_s:.3f} s (persistent cache: "
         f"{counter.hits} hits, {counter.misses} misses)")
    _log(f"window: {steps} steps in {window_s:.3f} s, {window_programs} "
         f"programs compiled in it; reference {time.perf_counter() - t_ref:.3f}"
         f" s; losses program {prog['losses']} reference {ref['losses']}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_devices, "memory_peak_bytes": peak}
    if trace:
        device.update(busy_s=busy_s, window_s=traced_s)
    result = {"correct": bool(correct), "attempted": steps, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window_programs_compiled"] = window_programs
    result["checks"] = checked
    return result, checked


def _per_layer(cell, log_dir, sampler, tr, steps, dev, host_batch):
    """Reduce the trace; time and count the window's batches directly.
    Returns (metrics, breakdown, busy seconds, traced window seconds)."""
    t = tracing.from_xplane(str(log_dir))
    lo, hi = t.window()
    busy_s, window_s = tracing.busy_seconds(t, lo, hi), (hi - lo) / 1e9
    step_slots = [(CHECK_STEPS + i) // tr["recycle"] for i in range(steps)]
    build_s, size_of = [], {}
    for slot in sorted(set(step_slots))[:MAX_COUNTED_SLOTS]:
        c0 = time.perf_counter()
        sg = sampler.build_batch(sampler.clusters_at(slot,
                                                     mode=tr["pipeline_mode"]))
        host_batch(sg, backend=tr["backend"])
        build_s.append(time.perf_counter() - c0)
        size_of[slot] = counts.batch_sizes(sg.edge_src, sg.edge_dst,
                                           sg.n_batch_real, sg.n_halo_real,
                                           sg.n_edges_real)
    last = size_of[max(size_of)]   # past the cap, steps count as the last
    step_sizes = [size_of.get(slot, last) for slot in step_slots]
    ctx = SimpleNamespace(
        config=cell.config, traffic=tr, trace=t, lo=lo, hi=hi,
        busy_s=busy_s, window_s=window_s, steps=steps,
        step_sizes=step_sizes, host_build_s=build_s,
        peaks=lambda: peaks_for(cell, dev.device_kind))
    metrics = {}
    for m in cell.per_layer:
        value = metric_reader(cell, m["name"])(ctx)
        if value is None:
            _log(f"per-layer metric {m['name']}: nothing to read in this "
                 f"run; device ops: {tracing.top_ops(t, lo, hi, 5)}")
        else:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    breakdown = {"device_ops": tracing.top_ops(t, lo, hi),
                 "idle_gaps": tracing.idle_gaps(t, lo, hi)}
    return metrics, breakdown, busy_s, window_s


def reference_run(cell, arch, hg, parts, sampler, params0) -> dict:
    """The plain reference's three steps on the program's three batches."""
    import jax

    from bench.reference import lmc as ref_lmc

    cfg, tr = cell.config, cell.traffic
    consts = ref_lmc.graph_consts(hg)
    step = jax.jit(ref_lmc.make_step(arch, cfg, tr["parts"],
                                     tr["clusters_per_batch"],
                                     ref_lmc.dot_highest))
    return run_reference(step, cfg, consts, parts, sampler, tr, params0)


def run_reference(step, cfg, consts, parts, sampler, tr, params0) -> dict:
    """Three reference steps and AdamW updates from ``params0``."""
    import jax
    import jax.numpy as jnp

    from bench.reference import lmc as ref_lmc

    n, d, L = consts.x.shape[0], cfg["hidden_dim"], cfg["num_layers"]
    o = cfg["optimizer"]
    params = jax.tree.map(jnp.asarray, params0)
    zeros = jax.tree.map(jnp.zeros_like, params)
    state = (jnp.float32(0.0), zeros, zeros)
    H = jnp.zeros((L, n, d), jnp.float32)
    V = jnp.zeros((max(L - 1, 1), n, d), jnp.float32)
    update = jax.jit(lambda g, s, p: ref_lmc.adamw_update(
        g, s, p, lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"], clip_norm=o["clip_norm"]))
    out = {"params0": params0, "losses": []}
    for i in range(CHECK_STEPS):
        cids = sampler.clusters_at(i // tr["recycle"], mode=tr["pipeline_mode"])
        bm = jnp.asarray(ref_lmc.batch_mask(parts, cids))
        loss, grads, H, V = step(params, H, V, consts, bm)
        out["losses"].append(float(loss))
        if i == 0:
            out["grads"] = jax.device_get(grads)
            out["hbar"], out["vbar"] = jax.device_get((H, V))
        params, state, _ = update(grads, state, params)
    out["params3"] = jax.device_get(params)
    return out
