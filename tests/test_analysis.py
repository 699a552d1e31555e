"""repro.analysis: per-rule trigger/non-trigger fixtures + the self-host gate.

Every rule gets at least one minimal source fixture that must fire and one
that must stay silent (including the deliberately unpaired DMA wait and the
oversized resident BlockSpec the acceptance criteria call out), the pragma
mechanism is exercised both ways (suppresses with a reason, refuses without),
and the whole catalog runs self-hosted over src/ — the tier-1 guarantee that
the tree carries zero unsuppressed findings.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

from repro.analysis import analyze_source, run_analysis, summarize
from repro.analysis.engine import all_rules

REPO = Path(__file__).resolve().parent.parent
SRC = REPO / "src"


def findings(src, rule=None, path="fixture.py"):
    fs = analyze_source(textwrap.dedent(src), path=path)
    if rule is not None:
        fs = [f for f in fs if f.rule == rule]
    return fs


def live(src, rule=None, path="fixture.py"):
    return [f for f in findings(src, rule, path) if not f.suppressed]


# ---------------------------------------------------------------- R001
CONCAT_BAD = """
    import jax.numpy as jnp
    def f(a, b):
        return jnp.concatenate([a, b], axis=0)
"""

STACK_BAD = """
    import jax.numpy as jnp
    def f(xs):
        return jnp.stack(xs)
"""

CONCAT_ALIASED = """
    from jax.numpy import concatenate as cat
    def f(a, b):
        return cat([a, b])
"""

CONCAT_OK = """
    import numpy as np
    from repro.dist.sharding import concat_rows
    def f(a, b):
        host = np.concatenate([a, b])        # host-side numpy: fine
        return concat_rows([a, b], axis=0)
"""

CONCAT_PRAGMA = """
    import jax.numpy as jnp
    def f(a, b):
        # lint: ok(R001) operands are per-host python scalars, never sharded
        return jnp.concatenate([a, b], axis=0)
"""


def test_r001_flags_concat_stack_and_aliases():
    assert len(live(CONCAT_BAD, "R001")) == 1
    assert len(live(STACK_BAD, "R001")) == 1
    assert len(live(CONCAT_ALIASED, "R001")) == 1


def test_r001_silent_on_numpy_and_concat_rows():
    assert live(CONCAT_OK, "R001") == []


def test_r001_allowlists_sharding_module():
    assert live(CONCAT_BAD, "R001",
                path="src/repro/dist/sharding.py") == []


def test_r001_pragma_suppresses_with_reason():
    fs = findings(CONCAT_PRAGMA, "R001")
    assert len(fs) == 1 and fs[0].suppressed
    assert "scalars" in fs[0].reason


# ---------------------------------------------------------------- R002
_DMA_PRELUDE = """
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
"""

DMA_UNPAIRED_START = _DMA_PRELUDE + """
    def kern(h_ref, o_ref, buf_ref, sem_ref):
        pltpu.make_async_copy(h_ref.at[0], buf_ref.at[0], sem_ref.at[0]).start()
        o_ref[:] = buf_ref[0]
"""

DMA_UNPAIRED_WAIT = _DMA_PRELUDE + """
    def kern(h_ref, o_ref, buf_ref, sem_ref):
        pltpu.make_async_copy(h_ref.at[0], buf_ref.at[0], sem_ref.at[0]).wait()
        o_ref[:] = buf_ref[0]
"""

DMA_PAIRED = _DMA_PRELUDE + """
    def kern(h_ref, o_ref, buf_ref, sem_ref):
        pltpu.make_async_copy(h_ref.at[0], buf_ref.at[0], sem_ref.at[0]).start()
        o_ref[:] = o_ref[:] * 0
        pltpu.make_async_copy(h_ref.at[0], buf_ref.at[0], sem_ref.at[0]).wait()
"""

DMA_NAMED_PAIRED = _DMA_PRELUDE + """
    def kern(h_ref, o_ref, buf_ref, sem_ref):
        dma = pltpu.make_async_copy(h_ref.at[0], buf_ref.at[0], sem_ref.at[0])
        dma.start()
        dma.wait()
"""

DMA_NAMED_NO_WAIT = _DMA_PRELUDE + """
    def kern(h_ref, o_ref, buf_ref, sem_ref):
        dma = pltpu.make_async_copy(h_ref.at[0], buf_ref.at[0], sem_ref.at[0])
        dma.start()
"""

# the repo's double-buffer idiom: a helper applying an `op` parameter
DMA_HELPER_BOTH = _DMA_PRELUDE + """
    def kern(idx_ref, h_ref, o_ref, buf_ref, sem_ref):
        def plane(k, slot, op):
            op(pltpu.make_async_copy(h_ref.at[k], buf_ref.at[slot],
                                     sem_ref.at[slot]))
        plane(0, 0, lambda dma: dma.start())
        plane(0, 0, lambda dma: dma.wait())
"""

DMA_HELPER_START_ONLY = _DMA_PRELUDE + """
    def kern(idx_ref, h_ref, o_ref, buf_ref, sem_ref):
        def plane(k, slot, op):
            op(pltpu.make_async_copy(h_ref.at[k], buf_ref.at[slot],
                                     sem_ref.at[slot]))
        plane(0, 0, lambda dma: dma.start())
        plane(1, 1, lambda dma: dma.start())
"""

DMA_SLOT_MISMATCH = _DMA_PRELUDE + """
    import jax.numpy as jnp
    def kern(h_ref, o_ref, buf_ref, sem_ref):
        pltpu.make_async_copy(h_ref.at[0], buf_ref.at[0], sem_ref.at[0]).start()
        pltpu.make_async_copy(h_ref.at[0], buf_ref.at[0], sem_ref.at[0]).wait()
    def call(h):
        return pl.pallas_call(
            kern,
            out_shape=h,
            scratch_shapes=[pltpu.VMEM((3, 256, 128), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))],
        )(h)
"""

DMA_REM_MISMATCH = _DMA_PRELUDE + """
    import jax.numpy as jnp
    def kern(h_ref, o_ref, buf_ref, sem_ref):
        slot = jax.lax.rem(pl.program_id(0), 3)
        pltpu.make_async_copy(h_ref.at[0], buf_ref.at[slot],
                              sem_ref.at[slot]).start()
        pltpu.make_async_copy(h_ref.at[0], buf_ref.at[slot],
                              sem_ref.at[slot]).wait()
    def call(h):
        return pl.pallas_call(
            kern,
            out_shape=h,
            scratch_shapes=[pltpu.VMEM((2, 256, 128), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))],
        )(h)
"""


def test_r002_unpaired_start_and_wait():
    (f,) = live(DMA_UNPAIRED_START, "R002")
    assert "never waited" in f.message
    (f,) = live(DMA_UNPAIRED_WAIT, "R002")
    assert "never started" in f.message and "deadlock" in f.message


def test_r002_silent_on_paired_copies():
    assert live(DMA_PAIRED, "R002") == []
    assert live(DMA_NAMED_PAIRED, "R002") == []


def test_r002_named_handle_without_wait():
    (f,) = live(DMA_NAMED_NO_WAIT, "R002")
    assert "never `.wait()`ed" in f.message


def test_r002_helper_op_idiom():
    assert live(DMA_HELPER_BOTH, "R002") == []
    (f,) = live(DMA_HELPER_START_ONLY, "R002")
    assert "plane" in f.message and ".wait()" in f.message


def test_r002_slot_count_vs_semaphore_shape():
    (f,) = live(DMA_SLOT_MISMATCH, "R002")
    assert "3 slot(s)" in f.message and "2" in f.message


def test_r002_rem_modulus_vs_semaphores():
    (f,) = live(DMA_REM_MISMATCH, "R002")
    assert "rem(_, 3)" in f.message


# ---------------------------------------------------------------- R003
VMEM_OVERSIZED = """
    from jax.experimental import pallas as pl
    def f():
        # (32768, 256) f32 = 32 MiB: over the ~12 MiB Mosaic ceiling
        return pl.BlockSpec((32768, 256), lambda i, j: (i, j))
"""

VMEM_UNBOUNDED = """
    from jax.experimental import pallas as pl
    def f(h, block_d: int = 128):
        m = h.shape[0]
        return pl.BlockSpec((m, block_d), lambda i, j: (0, j))
"""

VMEM_OK_DEFAULTS = """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    import jax.numpy as jnp
    def f(block_rows: int = 256, block_d: int = 128):
        spec = pl.BlockSpec((block_rows, block_d), lambda i, j: (i, j))
        scratch = pltpu.VMEM((2, block_rows, block_d), jnp.float32)
        return spec, scratch
"""

VMEM_SCRATCH_OVERSIZED = """
    from jax.experimental.pallas import tpu as pltpu
    import jax.numpy as jnp
    def f():
        return pltpu.VMEM((4096, 1024), jnp.float32)   # 16 MiB
"""

VMEM_AGGREGATE = """
    from jax.experimental.pallas import tpu as pltpu
    import jax.numpy as jnp
    def f():
        a = pltpu.VMEM((2048, 1024), jnp.float32)      # 8 MiB
        b = pltpu.VMEM((2048, 1024), jnp.float32)      # 8 MiB: sum 16 MiB
        return a, b
"""

VMEM_BF16_UNDER = """
    from jax.experimental.pallas import tpu as pltpu
    import jax.numpy as jnp
    def f():
        # 4096*1024 bf16 = 8 MiB: only over budget if dtype size is wrong
        return pltpu.VMEM((4096, 1024), jnp.bfloat16)
"""


def test_r003_oversized_blockspec():
    (f,) = live(VMEM_OVERSIZED, "R003")
    assert "32.0 MiB" in f.message


def test_r003_unbounded_resident_block():
    (f,) = live(VMEM_UNBOUNDED, "R003")
    assert "runtime-valued" in f.message and "`pl.ANY`" in f.message

SMEM_BLOCK = """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    def f(n, k):
        return pl.BlockSpec((n, k), lambda i: (i, 0),
                            memory_space=pltpu.SMEM)
"""


def test_r003_ignores_smem_blocks():
    """An SMEM BlockSpec is not VMEM residency, whatever its shape."""
    assert live(SMEM_BLOCK, "R003") == []
    (f,) = live(SMEM_BLOCK.replace("pltpu.SMEM", "pltpu.VMEM"), "R003")
    assert "runtime-valued" in f.message


def test_r003_resolves_param_defaults_and_dtypes():
    assert live(VMEM_OK_DEFAULTS, "R003") == []
    assert live(VMEM_BF16_UNDER, "R003") == []
    (f,) = live(VMEM_SCRATCH_OVERSIZED, "R003")
    assert "16.0 MiB" in f.message


def test_r003_aggregate_budget():
    (f,) = live(VMEM_AGGREGATE, "R003")
    assert "sum to 16.0 MiB" in f.message


# ---------------------------------------------------------------- R004
JIT_BRANCH = """
    import jax
    @jax.jit
    def f(x):
        if x > 0:
            return x
        return -x
"""

JIT_ITEM = """
    import jax
    @jax.jit
    def f(x):
        return x.sum().item()
"""

JIT_NP_ASARRAY = """
    import jax
    import numpy as np
    @jax.jit
    def f(x):
        return np.asarray(x)
"""

JIT_STATIC_BRANCH = """
    import functools
    import jax
    @functools.partial(jax.jit, static_argnames=("flag",))
    def f(x, flag):
        if flag:
            return x
        return -x
"""

JIT_SAFE_TESTS = """
    import jax
    @jax.jit
    def f(x, y):
        if y is None:
            return x
        if x.shape[0] > 2:
            return x + y
        return x - y
"""

VJP_BRANCH = """
    import jax
    @jax.custom_vjp
    def f(x):
        return x
    def f_fwd(x):
        return f(x), (x,)
    def f_bwd(res, ct):
        (x,) = res
        if ct > 0:
            return (ct,)
        return (-ct,)
    f.defvjp(f_fwd, f_bwd)
"""

UNJITTED_BRANCH = """
    def f(x):
        if x > 0:
            return x
        return -x
"""


def test_r004_branch_on_traced_param():
    (f,) = live(JIT_BRANCH, "R004")
    assert "`if` on traced value(s) `x`" in f.message


def test_r004_host_syncs():
    (f,) = live(JIT_ITEM, "R004")
    assert ".item()" in f.message
    (f,) = live(JIT_NP_ASARRAY, "R004")
    assert "numpy.asarray" in f.message


def test_r004_static_argnames_exempt():
    assert live(JIT_STATIC_BRANCH, "R004") == []


def test_r004_structural_and_shape_tests_exempt():
    assert live(JIT_SAFE_TESTS, "R004") == []


def test_r004_covers_defvjp_registered_functions():
    fs = live(VJP_BRANCH, "R004")
    assert len(fs) == 1 and "ct" in fs[0].message


def test_r004_ignores_untraced_functions():
    assert live(UNJITTED_BRANCH, "R004") == []


# ---------------------------------------------------------------- R005
VJP_OK = """
    import functools
    import jax
    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def f(flag, x, y):
        return x * y
    def f_fwd(flag, x, y):
        return f(flag, x, y), (x, y)
    def f_bwd(flag, res, ct):
        x, y = res
        return (ct * y, ct * x)
    f.defvjp(f_fwd, f_bwd)
"""

VJP_RESIDUAL_DRIFT = VJP_OK.replace("x, y = res", "x, y, z = res")

VJP_BWD_PARAMS = VJP_OK.replace("def f_bwd(flag, res, ct):",
                                "def f_bwd(res, ct):")

VJP_BWD_RETURN = VJP_OK.replace("return (ct * y, ct * x)",
                                "return (ct * y, ct * x, None)")

VJP_FWD_PARAMS = VJP_OK.replace("def f_fwd(flag, x, y):",
                                "def f_fwd(flag, x):")

VJP_FWD_RETURN = VJP_OK.replace("return f(flag, x, y), (x, y)",
                                "return f(flag, x, y), x, y")


def test_r005_consistent_trio_is_silent():
    assert live(VJP_OK, "R005") == []


def test_r005_residual_arity_drift():
    (f,) = live(VJP_RESIDUAL_DRIFT, "R005")
    assert "unpacks 3" in f.message and "saves 2" in f.message


def test_r005_bwd_param_count():
    (f,) = live(VJP_BWD_PARAMS, "R005")
    assert "takes 2 parameter(s), expected 3" in f.message


def test_r005_bwd_return_arity():
    (f,) = live(VJP_BWD_RETURN, "R005")
    assert "returns 3 cotangent(s), expected 2" in f.message


def test_r005_fwd_signature_and_return():
    (f,) = live(VJP_FWD_PARAMS, "R005")
    assert "takes 2 parameter(s) but the primal" in f.message
    (f,) = live(VJP_FWD_RETURN, "R005")
    assert "must return `(out, residuals)`" in f.message


# ---------------------------------------------------------------- R006
QUEUE_PATH = "src/repro/serve/fixture.py"      # in the rule's scoped dirs

QUEUE_UNBOUNDED = """
    import queue
    q = queue.Queue()
    lq = queue.LifoQueue(maxsize=0)
    sq = queue.SimpleQueue()
"""

QUEUE_BOUNDED = """
    import queue
    q = queue.Queue(maxsize=8)
    p = queue.PriorityQueue(16)
"""

QUEUE_BLOCKING = """
    def f(q, t):
        item = q.get()
        q.put(item)
        t.join()
"""

QUEUE_NONBLOCKING = """
    def f(q, t, xs, d):
        a = q.get(timeout=0.1)
        b = q.get(block=False)
        c = q.get_nowait()
        q.put(a, timeout=1.0)
        q.put_nowait(b)
        t.join(timeout=2.0)
        s = ",".join(xs)          # str.join takes an arg: not the queue shape
        v = d.get("k", 0)         # dict.get with default: not the queue shape
        return a, b, c, s, v
"""

QUEUE_PRAGMA = """
    import queue
    # lint: ok(R006) request ordering needs FIFO of unbounded test fixtures
    q = queue.Queue()
"""


def test_r006_flags_unbounded_queues():
    fs = live(QUEUE_UNBOUNDED, "R006", path=QUEUE_PATH)
    assert len(fs) == 3
    assert any("SimpleQueue" in f.message for f in fs)
    assert all("maxsize" in f.message for f in fs[:2])


def test_r006_silent_on_bounded_queues():
    assert live(QUEUE_BOUNDED, "R006", path=QUEUE_PATH) == []


def test_r006_flags_blocking_calls():
    fs = live(QUEUE_BLOCKING, "R006", path=QUEUE_PATH)
    assert sorted(f.message.split("`")[1] for f in fs) == \
        [".get()", ".join()", ".put()"]
    assert all("timeout=" in f.message for f in fs)


def test_r006_silent_on_timeout_nowait_and_lookalikes():
    assert live(QUEUE_NONBLOCKING, "R006", path=QUEUE_PATH) == []


def test_r006_scoped_to_threaded_tiers():
    """The same source outside src/repro/{data,serve} is not this rule's
    business — kernels and training code get to block."""
    assert live(QUEUE_UNBOUNDED, "R006", path="src/repro/train/loop.py") == []
    assert live(QUEUE_BLOCKING, "R006", path="benchmarks/bench_serve.py") == []


def test_r006_pragma_suppresses_with_reason():
    assert live(QUEUE_PRAGMA, "R006", path=QUEUE_PATH) == []
    (f,) = [f for f in findings(QUEUE_PRAGMA, "R006", path=QUEUE_PATH)]
    assert f.suppressed and "FIFO" in f.reason


# ------------------------------------------------------- pragmas & engine
def test_reasonless_pragma_does_not_suppress():
    src = CONCAT_PRAGMA.replace(
        "# lint: ok(R001) operands are per-host python scalars, never sharded",
        "# lint: ok(R001)")
    fs = findings(src)
    assert any(f.rule == "R001" and not f.suppressed for f in fs)
    assert any(f.rule == "R000" and "reason" in f.message for f in fs)


def test_pragma_in_comment_block_above():
    src = """
    import jax.numpy as jnp
    def f(a, b):
        # lint: ok(R001) fixture: operands replicated
        # (continued explanation on a second comment line)
        return jnp.concatenate([a, b], axis=0)
    """
    assert live(src, "R001") == []


def test_multi_rule_pragma():
    src = """
    import jax.numpy as jnp
    def f(a, b):
        # lint: ok(R001,R004) fixture: replicated scalars
        return jnp.stack([a, b])
    """
    assert live(src, "R001") == []


def test_syntax_error_is_a_finding():
    fs = findings("def f(:\n")
    assert fs and fs[0].rule == "R000" and "parse" in fs[0].message


def test_rule_catalog_ids_unique_and_documented():
    rules = all_rules()
    ids = [r.id for r in rules]
    assert ids == sorted(set(ids)) == ["R001", "R002", "R003", "R004",
                                       "R005", "R006"]
    assert all(r.name and r.doc for r in rules)


# ------------------------------------------------------- self-host + CLI
def test_self_hosted_src_is_clean():
    """The standing guarantee: zero unsuppressed findings over src/."""
    fs = run_analysis([SRC])
    bad = [f for f in fs if not f.suppressed]
    assert bad == [], "\n" + "\n".join(f.format() for f in bad)
    # ...and the audits it machine-checks are actually present as pragmas
    assert any(f.rule == "R001" and f.suppressed for f in fs)
    assert any(f.rule == "R003" and f.suppressed for f in fs)


def test_cli_exit_codes(tmp_path):
    env_src = str(SRC)
    ok = subprocess.run(
        [sys.executable, "-m", "repro.analysis", env_src],
        capture_output=True, text=True, env=_env())
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "unsuppressed finding" in ok.stdout

    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(CONCAT_BAD))
    res = subprocess.run(
        [sys.executable, "-m", "repro.analysis", str(bad)],
        capture_output=True, text=True, env=_env())
    assert res.returncode == 1
    assert "R001" in res.stdout

    unknown = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--rule", "R999", env_src],
        capture_output=True, text=True, env=_env())
    assert unknown.returncode == 2


def test_cli_rule_filter_and_json(tmp_path):
    import json as _json
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(CONCAT_BAD))
    res = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--rule", "R002",
         str(bad)], capture_output=True, text=True, env=_env())
    assert res.returncode == 0          # R001 site, but only R002 requested
    res = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--json", str(bad)],
        capture_output=True, text=True, env=_env())
    assert res.returncode == 1
    data = _json.loads(res.stdout)
    assert any(f["rule"] == "R001" for f in data)


def test_summary_has_per_rule_lines():
    out = summarize(run_analysis([SRC]))
    for rid in ("R001", "R002", "R003", "R004", "R005", "R006"):
        assert rid in out
    assert "0 unsuppressed" in out


def _env():
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC) + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return env
