"""R002/R003: the streamed-DMA and VMEM-residency invariants of the kernels.

R002 pallas-dma — the double-buffered HBM→VMEM gather protocol
(`kernels/ell_spmm.py`, `kernels/compensate.py`, DESIGN.md §3) only works if
every `pltpu.make_async_copy` start has a matching wait on the same
semaphore, slot indices alternate over exactly the semaphore count, and the
slot-major scratch buffer has one slot per semaphore. A missed wait reads
garbage into the accumulator *silently* on hardware (interpret mode emulates
the semaphores, so CPU CI catches only what it executes); a slot/semaphore
mismatch aliases in-flight copies. Three static checks:

  * every `make_async_copy` handle is consumed — `.start()`ed and `.wait()`ed
    directly (counts per semaphore expression must balance within a kernel),
    via a local name, or via the repo's helper idiom `op(make_async_copy(…))`
    where `op` is a parameter that module callers bind to *both* a
    `lambda dma: dma.start()` and a `lambda dma: dma.wait()`;
  * slot-major VMEM scratch (rank ≥ 3, literal slot dim) next to a
    `pltpu.SemaphoreType.DMA((n,))` must have exactly n slots;
  * literal moduli in `jax.lax.rem(_, c)` slot arithmetic inside DMA kernels
    must equal the semaphore count.

R003 vmem-budget — Mosaic rejects kernels whose per-grid-step residency
exceeds ~12 MiB of VMEM, and the failure surfaces at compile time on TPU
only: this CPU container's interpret mode happily runs any block size, which
is exactly how the pre-streaming resident-block cap (~24k gather rows) went
unnoticed until TPU lowering. The deleted trace-time guards are replaced
statically: BlockSpec block shapes and VMEM scratch shapes are evaluated from
literals + enclosing-function parameter defaults; a shape with a
runtime-valued dim (an operand row count) is an unbounded resident block and
must stream or carry a pragma, and resolvable shapes are summed per kernel
entry point against the 12 MiB budget.
"""
from __future__ import annotations

import ast
from typing import Iterator, Optional

from repro.analysis import astutils
from repro.analysis.engine import ModuleInfo, RawFinding, Rule

_MAC = "jax.experimental.pallas.tpu.make_async_copy"
_SEM_DMA = "jax.experimental.pallas.tpu.SemaphoreType.DMA"
_VMEM = "jax.experimental.pallas.tpu.VMEM"
_BLOCKSPEC_SUFFIX = ".BlockSpec"
_REM = "jax.lax.rem"

VMEM_BUDGET_BYTES = 12 * 2 ** 20     # Mosaic's practical per-step ceiling

_DTYPE_BYTES = {
    "float64": 8, "int64": 8, "uint64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool_": 1, "float8_e4m3fn": 1, "float8_e5m2": 1,
}


def _mac_calls(mod: ModuleInfo) -> list[ast.Call]:
    return [n for n in ast.walk(mod.tree)
            if astutils.call_qualname(n, mod.aliases) == _MAC]


def _outermost_function(node: ast.AST, mod: ModuleInfo
                        ) -> Optional[ast.FunctionDef]:
    chain = astutils.enclosing_functions(node, mod.parents)
    return chain[-1] if chain else None


def _lambda_dma_kind(node: ast.AST) -> Optional[str]:
    """`lambda dma: dma.start()` -> "start" (likewise wait); else None."""
    if isinstance(node, ast.Lambda) and isinstance(node.body, ast.Call):
        f = node.body.func
        if isinstance(f, ast.Attribute) and f.attr in ("start", "wait"):
            return f.attr
    return None


class DmaPairingRule(Rule):
    id = "R002"
    name = "pallas-dma"
    doc = __doc__

    def check(self, mod: ModuleInfo) -> Iterator[RawFinding]:
        macs = _mac_calls(mod)
        if macs:
            yield from self._check_consumption(mod, macs)
            yield from self._check_slots(mod, macs)

    # -- start/wait pairing ------------------------------------------------
    def _check_consumption(self, mod: ModuleInfo, macs: list[ast.Call]
                           ) -> Iterator[RawFinding]:
        # per kernel scope: sem-expression -> [(kind, node)] for direct uses
        direct: dict[ast.AST, dict[str, list[tuple[str, ast.Call]]]] = {}
        # DMA-applying helper params: (helper_def, param, index) -> mac node
        helpers: dict[tuple[ast.FunctionDef, str], ast.Call] = {}

        for mac in macs:
            scope = _outermost_function(mac, mod)
            parent = mod.parents.get(mac)
            grand = mod.parents.get(parent) if parent is not None else None
            # pltpu.make_async_copy(...).start() / .wait()
            if (isinstance(parent, ast.Attribute)
                    and parent.attr in ("start", "wait")
                    and isinstance(grand, ast.Call) and grand.func is parent):
                key = self._sem_key(mac)
                direct.setdefault(scope, {}).setdefault(key, []).append(
                    (parent.attr, mac))
                continue
            # op(pltpu.make_async_copy(...)) where `op` is an enclosing param
            if (isinstance(parent, ast.Call) and mac in parent.args
                    and isinstance(parent.func, ast.Name)):
                fname = parent.func.id
                encl = astutils.enclosing_functions(mac, mod.parents)
                owner = next((f for f in encl
                              if fname in astutils.param_names(f)), None)
                if owner is not None:
                    helpers[(owner, fname)] = mac
                    continue
                yield mac, (f"DMA handle passed to `{fname}`, which is not a "
                            "start/wait-applying parameter of an enclosing "
                            "function — cannot verify start/wait pairing")
                continue
            # dma = pltpu.make_async_copy(...); dma.start(); dma.wait()
            if (isinstance(parent, ast.Assign) and len(parent.targets) == 1
                    and isinstance(parent.targets[0], ast.Name)):
                dname = parent.targets[0].id
                kinds = self._name_consumption(scope or mod.tree, dname)
                if "start" not in kinds:
                    yield mac, (f"DMA handle `{dname}` is never `.start()`ed "
                                "in its kernel")
                if "wait" not in kinds:
                    yield mac, (f"DMA handle `{dname}` is `.start()`ed but "
                                "never `.wait()`ed — on hardware the compute "
                                "reads the scratch before the copy lands")
                continue
            yield mac, ("`make_async_copy` handle is neither started nor "
                        "waited (dropped on the floor)")

        for scope, by_sem in direct.items():
            for key, uses in by_sem.items():
                starts = [n for k, n in uses if k == "start"]
                waits = [n for k, n in uses if k == "wait"]
                if len(starts) > len(waits):
                    yield starts[len(waits)], (
                        "async copy started but never waited on semaphore "
                        f"`{key}` ({len(starts)} start(s) vs {len(waits)} "
                        "wait(s) in this kernel)")
                elif len(waits) > len(starts):
                    yield waits[len(starts)], (
                        "async copy waited but never started on semaphore "
                        f"`{key}` ({len(waits)} wait(s) vs {len(starts)} "
                        "start(s) in this kernel) — this wait deadlocks on "
                        "hardware")

        for (owner, pname), mac in helpers.items():
            kinds = self._helper_callers(mod, owner, pname)
            if kinds is None:
                yield owner, (f"DMA helper `{owner.name}` applies parameter "
                              f"`{pname}` to a `make_async_copy`, but no "
                              "caller passes a recognizable start/wait lambda")
            else:
                for missing in ("start", "wait") :
                    if missing not in kinds:
                        other = "wait" if missing == "start" else "start"
                        yield owner, (
                            f"DMA helper `{owner.name}` is only ever called "
                            f"with a `.{other}()` lambda for `{pname}` — "
                            f"every started copy needs a matching "
                            f"`.{missing}()` call")

    def _sem_key(self, mac: ast.Call) -> str:
        # make_async_copy(src, dst, sem): key on the semaphore expression so
        # starts and waits must balance per semaphore, not just per kernel
        if len(mac.args) >= 3:
            return ast.unparse(mac.args[2])
        return "<unknown-sem>"

    def _name_consumption(self, scope: ast.AST, name: str) -> set:
        kinds = set()
        for n in ast.walk(scope):
            if (isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
                    and n.func.attr in ("start", "wait")
                    and isinstance(n.func.value, ast.Name)
                    and n.func.value.id == name):
                kinds.add(n.func.attr)
        return kinds

    def _helper_callers(self, mod: ModuleInfo, owner: ast.FunctionDef,
                        pname: str) -> Optional[set]:
        params = astutils.param_names(owner)
        pidx = params.index(pname)
        kinds: set = set()
        found = False
        for n in ast.walk(mod.tree):
            if (isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                    and n.func.id == owner.name):
                arg: Optional[ast.AST] = None
                if pidx < len(n.args):
                    arg = n.args[pidx]
                else:
                    arg = next((kw.value for kw in n.keywords
                                if kw.arg == pname), None)
                kind = _lambda_dma_kind(arg) if arg is not None else None
                if kind is not None:
                    found = True
                    kinds.add(kind)
        return kinds if found else None

    # -- slot-count / semaphore-shape consistency --------------------------
    def _check_slots(self, mod: ModuleInfo, macs: list[ast.Call]
                     ) -> Iterator[RawFinding]:
        sem_counts: list[tuple[ast.Call, int]] = []
        for n in ast.walk(mod.tree):
            if astutils.call_qualname(n, mod.aliases) == _SEM_DMA and n.args:
                dims = astutils.const_eval_dims(n.args[0], {})
                if dims and all(d is not None for d in dims):
                    count = 1
                    for d in dims:
                        count *= d
                    sem_counts.append((n, count))

        for sem_call, count in sem_counts:
            # slot-major scratch buffers declared alongside the semaphore
            # array (same scratch_shapes list) must have `count` slots
            parent = mod.parents.get(sem_call)
            if not isinstance(parent, (ast.List, ast.Tuple)):
                continue
            for sib in parent.elts:
                if astutils.call_qualname(sib, mod.aliases) != _VMEM:
                    continue
                if not sib.args or not isinstance(sib.args[0],
                                                  (ast.Tuple, ast.List)):
                    continue
                shape = sib.args[0].elts
                if len(shape) < 3:          # not slot-major double buffering
                    continue
                slots = astutils.const_eval(shape[0], {})
                if slots is not None and slots != count:
                    yield sib, (
                        f"slot-major VMEM scratch has {slots} slot(s) but "
                        f"the DMA semaphore array has {count} — in-flight "
                        "copies would share/miss semaphores")

        if len({c for _, c in sem_counts}) == 1 and sem_counts:
            count = sem_counts[0][1]
            scopes = {_outermost_function(mac, mod) for mac in macs}
            for func in scopes:
                if func is None:
                    continue
                for n in ast.walk(func):
                    if (astutils.call_qualname(n, mod.aliases) == _REM
                            and len(n.args) == 2):
                        c = astutils.const_eval(n.args[1], {})
                        if c is not None and c != count:
                            yield n, (
                                f"slot arithmetic `rem(_, {c})` does not "
                                f"alternate over the {count} DMA semaphore "
                                "slot(s)")


class VmemBudgetRule(Rule):
    id = "R003"
    name = "vmem-budget"
    doc = __doc__

    def check(self, mod: ModuleInfo) -> Iterator[RawFinding]:
        blocks = self._vmem_blocks(mod)
        per_func: dict[ast.FunctionDef, int] = {}
        oversized: set = set()
        for node, dims, nbytes, what in blocks:
            scope = _outermost_function(node, mod)
            if nbytes is None:
                missing = ", ".join(ast.unparse(e)
                                    for e, d in dims if d is None)
                yield node, (
                    f"{what} shape ({missing}, …) has runtime-valued dim(s): "
                    "the resident block is not statically bounded and Mosaic "
                    "rejects it past ~12 MiB at compile time (TPU-only — "
                    "interpret mode runs any size). Stream the operand "
                    "(`pl.ANY` + async-copy gather) or annotate with "
                    "`# lint: ok(R003) <static bound argument>`")
                continue
            if nbytes > VMEM_BUDGET_BYTES:
                oversized.add(scope)
                yield node, (
                    f"{what} is {nbytes / 2**20:.1f} MiB per grid step — "
                    f"over the ~{VMEM_BUDGET_BYTES // 2**20} MiB Mosaic VMEM "
                    "budget; shrink the block or stream it")
            if scope is not None:
                per_func[scope] = per_func.get(scope, 0) + nbytes
        for scope, total in per_func.items():
            if total > VMEM_BUDGET_BYTES and scope not in oversized:
                yield scope, (
                    f"statically resolvable VMEM blocks in `{scope.name}` "
                    f"sum to {total / 2**20:.1f} MiB per grid step — over "
                    f"the ~{VMEM_BUDGET_BYTES // 2**20} MiB Mosaic budget")

    def _vmem_blocks(self, mod: ModuleInfo):
        """Yield (node, [(dim_expr, val|None)], bytes|None, description) for
        every BlockSpec block shape and VMEM scratch shape in the module."""
        mod_env = astutils.module_const_env(mod.tree)
        out = []
        for node in ast.walk(mod.tree):
            qn = astutils.call_qualname(node, mod.aliases)
            if qn is None:
                continue
            is_blockspec = qn.endswith(_BLOCKSPEC_SUFFIX)
            is_vmem = qn == _VMEM
            if not (is_blockspec or is_vmem):
                continue
            if not node.args or not isinstance(node.args[0],
                                               (ast.Tuple, ast.List)):
                continue   # e.g. BlockSpec(memory_space=pl.ANY): HBM, fine
            if is_blockspec and any(
                    kw.arg == "memory_space"
                    and (astutils.qualname(kw.value, mod.aliases)
                         or "").endswith(".SMEM") for kw in node.keywords):
                continue   # SMEM block: not VMEM residency
            env = dict(mod_env)
            for func in reversed(
                    astutils.enclosing_functions(node, mod.parents)):
                env.update(astutils.param_default_env(func))
            elts = node.args[0].elts
            dims = [(e, astutils.const_eval(e, env)) for e in elts]
            what = ("BlockSpec block" if is_blockspec else "VMEM scratch")
            if any(d is None for _, d in dims):
                out.append((node, dims, None, what))
                continue
            nbytes = self._dtype_bytes(node, is_vmem, mod)
            for _, d in dims:
                nbytes *= d
            out.append((node, dims, nbytes, what))
        return out

    def _dtype_bytes(self, node: ast.Call, is_vmem: bool,
                     mod: ModuleInfo) -> int:
        if is_vmem and len(node.args) >= 2:
            qn = astutils.qualname(node.args[1], mod.aliases)
            if qn is not None and qn.split(".")[-1] in _DTYPE_BYTES:
                return _DTYPE_BYTES[qn.split(".")[-1]]
        return 4   # unknown/operand-derived dtype: assume f32
