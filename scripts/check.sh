#!/usr/bin/env bash
# CI gate: static analysis + tier-1 test suite + the kernel perf tripwires.
#   scripts/check.sh [extra pytest args...]
# Gate 1 is `python -m repro.analysis src/` (DESIGN.md §8): the
# kernel/sharding invariant rules (R001-R005) fail fast — before the test
# suite or benchmarks spend minutes — on any unsuppressed finding, printing
# the per-rule summary alongside the perf-tripwire output below.
# The spmm/compensate benchmarks rewrite experiments/bench/BENCH_{spmm,
# compensate}.json; fresh kernel-path timings are compared against the
# *committed* baselines (snapshotted before the run) and the gate fails on a
# >1.3x regression of the default (streamed) pallas kernel path, plus the
# vectorized ELL builder's >=10x speedup over the legacy loop.
# The pipeline benchmark (DESIGN.md §9) adds two more tripwires: the
# prefetch-path step must stay within 1.25x of the pure-compute step, and
# the recycled overlap fraction must not drop more than 0.25 below the
# committed baseline.
# The supervisor benchmark (DESIGN.md §10) gates the health-guard overhead:
# a guarded step must stay <= 1.10x the unguarded step median
# (BENCH_supervisor.json), and the fault-injection matrix (preemption /
# pipeline-worker crash / mid-save ckpt failure / NaN batch, each recovering
# to a stream-deterministic resume) runs in gate 1, before the full suite.
# The backends benchmark (DESIGN.md §11) races segment/ell/ti through one
# sampler stream and gates the store-free ti estimator: step time <= ell
# (strict on compiled backends, jitter headroom under the CPU interpreter),
# zero store bytes/step, and terminal-loss parity on full-fidelity runs.
# The serve benchmark (DESIGN.md §12) gates the serving tier: clean p99
# latency <= 1.3x the committed baseline at the fixed gated QPS level,
# degraded-rung (ti) val-accuracy within 0.05 of the exact rung, and zero
# dropped in-flight requests on drain; the serving fault matrix (hung
# batch / poisoned store rows / queue-overflow burst / worker crash) runs
# in gate 1b alongside the training matrix.
# scripts/coverage_gate.py enforces line-coverage floors over
# repro.core+repro.kernels and repro.serve before the benchmarks run.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

python -m repro.analysis src/

# gate 1b: the fault-injection matrix fails fast — a broken recovery path
# invalidates every longer-running gate below it
python -m pytest -q tests/test_supervisor.py tests/test_serve.py -k "matrix"

# docstring hygiene (ruff D rules scoped in ruff.toml); optional: the pinned
# container may not ship ruff, and the bespoke `repro.analysis` pass above is
# the authoritative gate
if command -v ruff >/dev/null 2>&1; then
    ruff check .
else
    echo "check: ruff not installed; skipping lint (config in ruff.toml)"
fi

python -m pytest -x -q "$@"

# coverage floor (DESIGN.md §11): line coverage of repro.core+repro.kernels
# under the targeted numerics suites must stay >= the floor in
# scripts/coverage_gate.py (stdlib settrace tracer — the container has no
# coverage.py; the script upgrades itself automatically when one appears)
python scripts/coverage_gate.py

# snapshot the *committed* baselines (HEAD, not the working tree — the
# benches below rewrite the working-tree files, and ratcheting against the
# previous run would let a slow <1.3x-per-run regression through)
BASE_DIR=$(mktemp -d)
trap 'rm -rf "$BASE_DIR"' EXIT
for f in experiments/bench/BENCH_spmm.json experiments/bench/BENCH_compensate.json \
         experiments/bench/BENCH_pipeline.json experiments/bench/BENCH_backends.json \
         experiments/bench/BENCH_serve.json; do
    git show "HEAD:$f" > "$BASE_DIR/$(basename "$f")" 2>/dev/null \
        || rm -f "$BASE_DIR/$(basename "$f")"   # not committed yet: no gate
done

python -m benchmarks.run --fast --only spmm_kernel
python -m benchmarks.run --fast --only compensate
python -m benchmarks.run --fast --only pipeline
python -m benchmarks.run --fast --only supervisor
python -m benchmarks.run --fast --only backends
python -m benchmarks.run --fast --only serve

BASELINE_DIR="$BASE_DIR" python - <<'EOF'
import json
import os
from pathlib import Path

TOL = 1.3   # fail on >1.3x slowdown of any kernel-path row
base_dir = Path(os.environ["BASELINE_DIR"])

rows = json.load(open("experiments/bench/BENCH_spmm.json"))["rows"]
speedup = rows["build_ell_vectorized_50k"]["speedup_vs_loop"]
assert speedup >= 10.0, f"vectorized build_ell only {speedup:.1f}x faster"
print(f"check OK: build_ell vectorized {speedup:.1f}x over the loop")

for name in ("BENCH_spmm.json", "BENCH_compensate.json"):
    bpath = base_dir / name
    if not bpath.exists():
        print(f"check: no committed baseline for {name}; skipping tripwire")
        continue
    base = json.load(open(bpath))
    fresh = json.load(open(f"experiments/bench/{name}"))
    if base.get("backend") != fresh.get("backend"):
        # interpret-vs-compiled timings are not comparable across machines
        print(f"check: {name} baseline backend {base.get('backend')!r} != "
              f"{fresh.get('backend')!r}; skipping tripwire")
        continue
    for key, row in fresh["rows"].items():
        # gate the kernel path at the size it was baselined at
        # (default_path rows); the 4x-cap rows are informational
        if not key.startswith("pallas_") or not row.get("default_path"):
            continue
        old = base["rows"].get(key)
        if old is None or "us_per_call" not in row:
            continue
        ratio = row["us_per_call"] / max(old["us_per_call"], 1e-9)
        assert ratio <= TOL, (
            f"{name}:{key} regressed {ratio:.2f}x "
            f"({old['us_per_call']:.0f}us -> {row['us_per_call']:.0f}us)")
        print(f"check OK: {name}:{key} {ratio:.2f}x vs baseline")

# pipeline tripwires (DESIGN.md §9): absolute prefetch-overhead bound plus
# an overlap-fraction regression gate against the committed baseline
PIPE_RATIO_TOL = 1.25    # fast-mode headroom over the 1.15x acceptance bar
OVERLAP_DROP_TOL = 0.25  # absolute drop allowed in the recycled overlap
fresh = json.load(open("experiments/bench/BENCH_pipeline.json"))["rows"]
pr = fresh["step_prefetch"]["ratio_vs_compute"]
assert pr <= PIPE_RATIO_TOL, (
    f"pipeline:step_prefetch costs {pr:.2f}x the pure-compute step "
    f"(bound {PIPE_RATIO_TOL}x)")
print(f"check OK: pipeline:step_prefetch {pr:.2f}x vs pure compute")
par = fresh["recycle_parity"]
if par.get("gate"):
    assert par["rel_gap"] <= 0.05, (
        f"pipeline:recycle_parity gap {par['rel_gap']:.1%} > 5% "
        f"at {par['steps']} steps")
    print(f"check OK: pipeline:recycle_parity {par['rel_gap']:.1%}")
bpath = base_dir / "BENCH_pipeline.json"
if bpath.exists():
    old = json.load(open(bpath))["rows"]["overlap"]["overlap_fraction_recycle4"]
    new = fresh["overlap"]["overlap_fraction_recycle4"]
    assert new >= old - OVERLAP_DROP_TOL, (
        f"pipeline:overlap_fraction_recycle4 dropped {old:.2f} -> {new:.2f} "
        f"(> {OVERLAP_DROP_TOL} below the committed baseline)")
    print(f"check OK: pipeline:overlap_fraction_recycle4 {new:.2f} "
          f"(baseline {old:.2f})")
else:
    print("check: no committed baseline for BENCH_pipeline.json; "
          "skipping overlap tripwire")

# supervisor tripwire (DESIGN.md §10): the numerical-health guard must stay
# essentially free — its inputs are host floats the step already syncs for
# the history record, so > 1.10x means someone put work on the hot path
GUARD_RATIO_TOL = 1.10
sup = json.load(open("experiments/bench/BENCH_supervisor.json"))["rows"]
gr = sup["step_guarded"]["ratio_vs_unguarded"]
assert gr <= GUARD_RATIO_TOL, (
    f"supervisor:step_guarded costs {gr:.2f}x the unguarded step "
    f"(bound {GUARD_RATIO_TOL}x)")
print(f"check OK: supervisor:step_guarded {gr:.2f}x vs unguarded")
sp = sup["ckpt_async_save"]["async_speedup"]
assert sp >= 1.0, (
    f"supervisor:ckpt_async_save is {sp:.2f}x sync — background saves "
    f"should never cost the training thread more than synchronous ones")
print(f"check OK: supervisor:ckpt_async_save {sp:.1f}x cheaper on the "
      f"hot path")

# backend tripwires (DESIGN.md §11): ti removes every historical-store
# read/write from the step, so it must never cost more than ell.  On a
# compiled backend that bound is strict (1.0x); under the CPU interpreter
# the Pallas SpMM dominates and single-epoch jitter (~±15%) swamps the
# compensate traffic ti saves, so the CPU gate carries jitter headroom —
# it still trips if ti systematically does *more* work than ell.
bb = json.load(open("experiments/bench/BENCH_backends.json"))
TI_RATIO_TOL = 1.0 if bb.get("backend") != "cpu" else 1.15
tv = bb["rows"]["ti_vs_ell"]
assert tv["step_ratio"] <= TI_RATIO_TOL, (
    f"backends:ti step costs {tv['step_ratio']:.2f}x the ell step "
    f"(bound {TI_RATIO_TOL}x on backend {bb.get('backend')!r})")
print(f"check OK: backends:ti {tv['step_ratio']:.2f}x vs ell "
      f"(bound {TI_RATIO_TOL}x)")
for k in ("store_read_bytes_per_step", "store_write_bytes_per_step"):
    assert bb["rows"]["ti"][k] == 0, f"backends:ti nonzero {k}"
print("check OK: backends:ti store traffic 0+0 bytes/step")
if tv.get("gate"):
    assert tv["loss_rel_gap"] <= 0.05, (
        f"backends:ti terminal loss diverges {tv['loss_rel_gap']:.1%} "
        f"from ell at {tv['steps']} steps")
    print(f"check OK: backends:ti_vs_ell loss gap {tv['loss_rel_gap']:.1%}")

# serving tripwires (DESIGN.md §12): p99 regression at the gated QPS level,
# degraded-rung answer quality, and drain accounting
SERVE_P99_TOL = 1.3      # same budget as the kernel-path tripwires
SERVE_PARITY_TOL = 0.05  # ti val-accuracy may trail exact by at most this
sv = json.load(open("experiments/bench/BENCH_serve.json"))
srows = sv["rows"]
gated = [k for k, r in srows.items()
         if k.endswith("_clean") and r.get("default_path")]
bpath = base_dir / "BENCH_serve.json"
if not bpath.exists():
    print("check: no committed baseline for BENCH_serve.json; "
          "skipping p99 tripwire")
else:
    base = json.load(open(bpath))
    if base.get("backend") != sv.get("backend"):
        print(f"check: BENCH_serve.json baseline backend "
              f"{base.get('backend')!r} != {sv.get('backend')!r}; "
              f"skipping p99 tripwire")
    else:
        for key in gated:
            old = base["rows"].get(key)
            if old is None or "p99_us" not in old:
                continue
            ratio = srows[key]["p99_us"] / max(old["p99_us"], 1e-9)
            assert ratio <= SERVE_P99_TOL, (
                f"serve:{key} p99 regressed {ratio:.2f}x "
                f"({old['p99_us']:.0f}us -> {srows[key]['p99_us']:.0f}us)")
            print(f"check OK: serve:{key} p99 {ratio:.2f}x vs baseline")
par = srows["parity_ti"]
assert par["val_acc_gap"] <= SERVE_PARITY_TOL, (
    f"serve:parity_ti degraded rung trails exact by "
    f"{par['val_acc_gap']:.3f} val accuracy (bound {SERVE_PARITY_TOL})")
print(f"check OK: serve:parity_ti acc gap {par['val_acc_gap']:.3f} "
      f"(agreement {par['top1_agreement']:.1%})")
dr = srows["drain"]
assert dr["dropped"] == 0 and dr["clean_exit"], (
    f"serve:drain dropped {dr['dropped']} of {dr['submitted']} in-flight "
    f"requests (clean_exit={dr['clean_exit']})")
print(f"check OK: serve:drain {dr['resolved_ok']}/{dr['submitted']} "
      f"resolved, 0 dropped")
EOF
