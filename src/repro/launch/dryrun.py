import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile EVERY (arch × shape × mesh) cell.

For each cell this prints/records
  * ``compiled.memory_analysis()``  — proves the step fits per-device HBM,
  * ``compiled.cost_analysis()``    — HLO FLOPs / bytes for §Roofline,
  * parsed per-device collective bytes from the optimized (SPMD) HLO.

Run:
  PYTHONPATH=src python -m repro.launch.dryrun                    # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --multi-pod --gnn  # incl. GNN step

Results land in experiments/dryrun/*.json (one file per cell).
"""
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

COLLECTIVE_RE = re.compile(
    r"^\s*(?:%\S+ = )?"
    r"(?:\(?([a-z0-9\[\],{}\s]*?)\)?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", re.MULTILINE)
SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|f64|s64|c64)\[([\d,]*)\]")
DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "f64": 8, "s64": 8, "c64": 8}


def cost_analysis_dict(compiled) -> dict:
    """`Compiled.cost_analysis()` returns a dict on new jax but a
    one-element list of dicts on jax 0.4.x — normalize to the dict."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by collectives, from the SPMD module text.

    Shapes in the per-device module are shard-local, so the summed output
    bytes approximate per-device received bytes. Ops inside while-loop
    (scan) bodies are counted once — the roofline harness extrapolates
    per-layer costs from unrolled builds instead (see benchmarks/roofline.py).
    """
    per_kind: dict[str, float] = {}
    count = 0
    for line in hlo_text.splitlines():
        m = re.search(r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                      r"collective-permute)(?:-start)?\(", line)
        if not m or "= " not in line:
            continue
        kind = m.group(1)
        lhs = line.split("= ")[0]
        shapes = SHAPE_RE.findall(line.split("= ")[1].split("(")[0])
        nbytes = 0
        for dt, dims in shapes:
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * DTYPE_BYTES[dt]
        if nbytes:
            per_kind[kind] = per_kind.get(kind, 0) + nbytes
            count += 1
    per_kind["num_ops"] = count
    per_kind["total"] = sum(v for k, v in per_kind.items()
                            if k not in ("num_ops",))
    return per_kind


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             opt_level: str | None = None, verbose: bool = True) -> dict:
    from repro.configs import SHAPES, applicable_shapes, get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_cell

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape_name not in applicable_shapes(cfg):
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped",
                "reason": ("no decoder" if shape.kind == "decode" and
                           not cfg.has_decoder else
                           "full-attention arch: long_500k requires "
                           "sub-quadratic attention (DESIGN.md §5)")}

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    lm, step, args, shs = build_cell(cfg, shape, mesh)
    # donate the state the production step donates: params+opt (train) or the
    # KV caches (decode) — memory_analysis then reports the aliased peak
    donate = {"train": (0, 1), "decode": (1,), "prefill": ()}[shape.kind]
    with mesh:
        lowered = jax.jit(step, in_shardings=shs,
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        copts = {}
        if opt_level is not None:
            copts["xla_backend_optimization_level"] = opt_level
        compiled = lowered.compile(compiler_options=copts or None)
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = cost_analysis_dict(compiled)
    coll = collective_bytes(compiled.as_text())
    res = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "multi_pod": multi_pod, "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops": ca.get("flops"), "bytes_accessed": ca.get("bytes accessed"),
        "collectives": coll,
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_per_device_gb": round(
                (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30, 3),
        },
    }
    if verbose:
        print(f"[{res['mesh']}] {arch} × {shape_name}: "
              f"compile {t_compile:.1f}s, "
              f"args {ma.argument_size_in_bytes/2**30:.2f} GiB/dev, "
              f"temp {ma.temp_size_in_bytes/2**30:.2f} GiB/dev, "
              f"collective ops {coll.get('num_ops', 0)}", flush=True)
        print("  memory_analysis:", ma, flush=True)
        print("  cost_analysis: flops=%.3e bytes=%.3e" %
              (ca.get("flops", 0), ca.get("bytes accessed", 0)), flush=True)
    return res


def run_gnn_cell(*, multi_pod: bool, verbose: bool = True) -> dict:
    """Dry-run the paper's own workload: the distributed LMC train step
    (one cluster per data-parallel device, halo compensation via the sharded
    historical stores)."""
    from repro.core import make_train_step, LMC
    from repro.core.distributed import spmd_shardings
    from repro.core.lmc import Batch
    from repro.launch.mesh import make_production_mesh
    from repro.models import make_gnn
    import jax.numpy as jnp

    from repro.dist.sharding import activation_sharding, dp_axis_size

    mesh = make_production_mesh(multi_pod=multi_pod)
    ndp = dp_axis_size(mesh)
    # production-scale synthetic stand-in: 16M nodes, d=512 GCNII
    n_nodes = 16 * 2**20
    d, dx, L, ncls = 512, 512, 4, 64
    per_dev_batch, per_dev_halo, per_dev_edges = 4096, 8192, 262144
    nb, nh, ne = per_dev_batch * ndp, per_dev_halo * ndp, per_dev_edges * ndp

    gnn = make_gnn("gcnii", dx, d, ncls, L)
    step = make_train_step(gnn, LMC, n_nodes)

    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    batch_abs = Batch(
        batch_gids=i32(nb), halo_gids=i32(nh), batch_mask=f32(nb),
        halo_mask=f32(nh), edge_src=i32(ne), edge_dst=i32(ne), edge_w=f32(ne),
        labels=i32(nb + nh), labeled_mask=f32(nb + nh), beta=f32(nh),
        loss_scale=f32(), grad_scale=f32())
    store_abs = type("HS", (), {})
    from repro.core.history import HistoricalState
    store_abs = HistoricalState(h=f32(L, n_nodes, d), v=f32(L - 1, n_nodes, d))
    x_abs, sw_abs = f32(n_nodes, dx), f32(n_nodes)

    batch_sh, store_sh, x_sh, sw_sh, param_sh = spmd_shardings(mesh)
    params_abs = jax.eval_shape(lambda k: gnn.init_params(k), jax.random.key(0))
    params_sh = jax.tree.map(lambda _: param_sh, params_abs,
                             is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    store_sh_t = HistoricalState(h=store_sh["h"], v=store_sh["v"])

    t0 = time.time()
    with activation_sharding(mesh):
        # donate the historical stores: the production trainer updates them
        # in place (H̄/V̄ are step-local state, §Perf GNN iteration)
        lowered = jax.jit(step, in_shardings=(params_sh, store_sh_t, batch_sh,
                                              x_sh, sw_sh),
                          donate_argnums=(1,)).lower(
            params_abs, store_abs, batch_abs, x_abs, sw_abs)
        compiled = lowered.compile()
    t_compile = time.time() - t0
    ma = compiled.memory_analysis()
    ca = cost_analysis_dict(compiled)
    coll = collective_bytes(compiled.as_text())
    res = {
        "arch": "gnn-lmc-gcnii", "shape": f"n{n_nodes}_d{d}_L{L}",
        "mesh": "2x16x16" if multi_pod else "16x16", "multi_pod": multi_pod,
        "status": "ok", "compile_s": round(t_compile, 1),
        "flops": ca.get("flops"), "bytes_accessed": ca.get("bytes accessed"),
        "collectives": coll,
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_per_device_gb": round(
                (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes) / 2**30 / len(jax.devices()) * 1, 3),
        },
    }
    if verbose:
        print(f"[GNN {res['mesh']}] LMC distributed step: compile "
              f"{t_compile:.1f}s, collectives {coll}", flush=True)
        print("  memory_analysis:", ma, flush=True)
    return res


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true",
                    help="only the 2x16x16 mesh (default: both meshes)")
    ap.add_argument("--single-pod", action="store_true",
                    help="only the 16x16 mesh")
    ap.add_argument("--gnn", action="store_true",
                    help="also dry-run the distributed GNN-LMC step")
    ap.add_argument("--opt-level", default=None,
                    help="xla_backend_optimization_level override")
    ap.add_argument("--fail-fast", action="store_true")
    args = ap.parse_args()

    from repro.configs import ARCH_NAMES, SHAPES

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    archs = [args.arch] if args.arch else ARCH_NAMES
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True]
    if args.multi_pod:
        meshes = [True]
    if args.single_pod:
        meshes = [False]

    failures = []
    for multi_pod in meshes:
        if args.gnn:
            res = run_gnn_cell(multi_pod=multi_pod)
            tag = f"gnn_lmc_{'2x16x16' if multi_pod else '16x16'}"
            (OUT_DIR / f"{tag}.json").write_text(json.dumps(res, indent=1))
        for arch in archs:
            for shape in shapes:
                tag = (f"{arch}_{shape}_"
                       f"{'2x16x16' if multi_pod else '16x16'}").replace("/", "_")
                try:
                    res = run_cell(arch, shape, multi_pod=multi_pod,
                                   opt_level=args.opt_level)
                except Exception as e:  # noqa: BLE001 - report, keep sweeping
                    res = {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                           "status": "error", "error": repr(e),
                           "trace": traceback.format_exc()[-2000:]}
                    failures.append(tag)
                    print(f"FAIL {tag}: {e!r}", flush=True)
                    if args.fail_fast:
                        (OUT_DIR / f"{tag}.json").write_text(
                            json.dumps(res, indent=1))
                        raise
                (OUT_DIR / f"{tag}.json").write_text(json.dumps(res, indent=1))
    print(f"\ndry-run complete; failures: {failures or 'none'}")


if __name__ == "__main__":
    main()
