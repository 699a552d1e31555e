"""Fault-tolerant, health-supervised training loop (GNN + LMC).

Production behaviors implemented (tests: test_fault_tolerance.py,
test_supervisor.py):
  * periodic atomic checkpoints of (params, opt state, historical stores,
    lr, step counter) — synchronous or, with ``async_ckpt=True``, written
    on a background thread off the hot path;
  * crash/preemption recovery: on failure the loop restores the newest
    *verifiable* checkpoint and continues (a corrupt/truncated latest
    checkpoint falls back to the previous one — checkpoint.CheckpointError);
  * numerical-health supervision (``health=HealthConfig(...)``): every step
    is checked for NaN/Inf loss/grad-norm, loss spikes against a rolling
    baseline, and (periodically) store corruption *before* its update is
    applied; a divergent step triggers the configured policy — rollback to
    the last good checkpoint (bounded by ``max_retries``, optional
    lr-backoff) or skip-batch — and per-layer store-staleness counters
    enforce Thm 2's ρ-budget (DESIGN.md §10);
  * layered fault injection (``train.health.FaultPlan``): preemptions,
    pipeline-worker crashes, mid-save checkpoint failures and NaN-poisoned
    batches all recover to a stream-deterministic resume;
  * straggler mitigation: a per-step deadline (k × running-median step time);
    a straggler step's *store updates* can be dropped without violating LMC's
    convergence assumptions (staleness is bounded by Thm 2's ρ-term — see
    DESIGN.md §4), which is what `straggler_policy="skip-store"` does;
  * deterministic resume: the batch stream is a pure function of (sampler
    seed, step index), so a restore rebuilds the pipeline at its step.

``backend="ell"`` switches the jit'd step onto the Pallas bucketed-ELL
SpMM/compensate kernels (compiled on TPU, interpreter fallback on CPU);
batches are then built with their adjacency re-bucketed host-side
(`to_device_batch(sg, backend="ell")`). ``backend="ti"`` keeps the ELL
aggregation but compensates halo rows with the store-free message-invariance
estimator (DESIGN.md §11) — pair it with ``method=repro.core.TI`` so the
(unread) store refresh is skipped too.

Every batch comes from the ``SubgraphPipeline`` (repro.data.prefetch,
DESIGN.md §9). With ``prefetch >= 1`` sampling + ELL bucketing move to
background threads and host→device transfers double-buffer behind the
step; the default ``prefetch=0`` builds each batch inline, on the same
schedule. ``recycle=ρ`` reuses each subgraph for ρ consecutive steps.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointError, CheckpointManager
from repro.core import (HistoricalState, MBMethod, from_graph, accuracy,
                        init_history, make_train_step)
from repro.data.prefetch import NO_FETCH, SubgraphPipeline
from repro.graph import ClusterSampler
from repro.models.gnn import GNN
from repro.optim.optimizers import Optimizer
from repro.tracing import span
from repro.train.health import (FaultPlan, HealthConfig, HealthGuard,
                                PipelineFault, SimulatedPreemption,
                                TrainingDivergedError)

# running-median straggler baseline: bounded so the median scan stays O(1)
# in run length (satellite of DESIGN.md §10; was an unbounded list)
_STEP_TIME_WINDOW = 512


class _Divergence(RuntimeError):
    """Internal: a step failed its health check before being applied."""


class GNNTrainer:
    """Orchestrates sampling, the jit'd LMC step, optimizer updates,
    checkpointing, health supervision and fault handling for one run.

    Not thread-safe: one trainer per (single) training thread; background
    work (batch construction, async checkpoint writes) is delegated to
    ``SubgraphPipeline`` workers / the ``CheckpointManager`` writer thread.
    Call :meth:`close` (or drop the trainer) to stop those workers.
    """

    def __init__(self, gnn: GNN, method: MBMethod, graph, sampler: ClusterSampler,
                 optimizer: Optimizer, *, ckpt_dir: Optional[str] = None,
                 ckpt_every: int = 50, seed: int = 0,
                 failure_injector: Optional[FaultPlan] = None,
                 health: Optional[HealthConfig] = None,
                 max_retries: int = 3,
                 async_ckpt: bool = False,
                 straggler_deadline: float = 4.0,
                 straggler_policy: str = "skip-store",
                 backend: str = "segment",
                 prefetch: int = 0,
                 recycle: int = 1,
                 pipeline_workers: int = 2,
                 pipeline_mode: str = "uniform"):
        """Build the jit'd step and (lazily) the batch pipeline.

        Args:
            gnn / method / graph / sampler / optimizer: the model, the
                mini-batch method config (LMC/GAS/...), the host graph, its
                cluster sampler and the optimizer.
            ckpt_dir / ckpt_every: enable periodic atomic checkpoints.
            seed: parameter-init PRNG seed.
            failure_injector: a ``train.health.FaultPlan`` scheduling any
                mix of injected faults (preemptions, pipeline-worker
                crashes, mid-save checkpoint failures, NaN batches).
            health: enable the numerical-health guard with this config
                (``HealthConfig()`` for defaults); None disables all
                health checks (the pre-supervisor hot path).
            max_retries: recovery budget — consecutive recovery actions
                (rollbacks / skipped batches / pipeline rebuilds) allowed
                without an intervening healthy step before the run aborts
                with ``TrainingDivergedError``.
            async_ckpt: write checkpoints on a background thread (the hot
                path only pays the device→host snapshot; files are
                byte-identical to synchronous saves).
            straggler_deadline / straggler_policy: per-step deadline as a
                multiple of the running-median step time; ``"skip-store"``
                drops a straggler step's store update (Thm 2-safe).
            backend: aggregation/compensation hot path, ``"segment"`` |
                ``"ell"`` | ``"ti"`` (store-free message invariance).
            prefetch: queue depth of the batch pipeline. ``0`` (default)
                builds each batch inline, in the step's fetch; ``>= 1``
                builds ahead on background threads with double-buffered
                transfers. Both draw the same schedule-indexed stream.
            recycle: reuse each sampled subgraph for this many consecutive
                steps (ρ).
            pipeline_workers: builder threads when prefetching.
            pipeline_mode: schedule of the pipeline path — ``"uniform"``
                (iid cluster draws, Alg. 1 line 4) or ``"epoch"`` (shuffled
                epochs: every cluster exactly once per B/c distinct slots).
        """
        self.gnn = gnn
        self.method = method
        self.graph = graph
        # built lazily so it always starts at the current step (resume-safe)
        self._pipeline: Optional[SubgraphPipeline] = None
        self.sampler = sampler
        self.opt = optimizer
        self.data = from_graph(graph)
        self.failure_injector = failure_injector
        self.straggler_deadline = straggler_deadline
        self.straggler_policy = straggler_policy
        self.backend = backend  # hot path: "segment" | "ell" | "ti"
        if recycle < 1:
            raise ValueError(f"recycle must be >= 1, got {recycle}")
        if max_retries < 1:
            raise ValueError(f"max_retries must be >= 1, got {max_retries}")
        self.prefetch = int(prefetch)
        self.recycle = int(recycle)
        self.pipeline_workers = int(pipeline_workers)
        self.pipeline_mode = pipeline_mode

        self.params = gnn.init_params(jax.random.key(seed))
        self.opt_state = optimizer.init(self.params, _as_pspec_tree(self.params))
        self.store = init_history(gnn.num_layers, graph.num_nodes,
                                  gnn.hidden_dim)
        self.step_num = 0
        self.lr = float(optimizer.lr)   # mutable: rollback lr-backoff
        # no buffer donation: the straggler skip-store policy, health
        # rollback and elastic rescale all need the pre-step state alive
        self._step = jax.jit(make_train_step(gnn, method, graph.num_nodes,
                                             backend=backend))
        # lr rides as a traced array argument so backoff never retraces
        self._update = jax.jit(
            lambda g, s, p, lr: optimizer.update(g, s, p, lr))
        fault_hook = (failure_injector.ckpt_hook
                      if failure_injector is not None else None)
        self.ckpt = (CheckpointManager(ckpt_dir, fault_hook=fault_hook)
                     if ckpt_dir else None)
        self.ckpt_every = ckpt_every
        self.async_ckpt = bool(async_ckpt)
        self.health = health
        self.guard = (HealthGuard(health, gnn.num_layers, graph.num_nodes)
                      if health is not None else None)
        self.max_retries = int(max_retries)
        self._retries_left = self.max_retries
        self._step_times: deque[float] = deque(maxlen=_STEP_TIME_WINDOW)
        self.history: list[dict] = []

    # ----------------------------------------------------------------- state
    @property
    def sampler(self) -> ClusterSampler:
        """The cluster sampler the batch pipeline draws from. Assigning a
        new one (``rescale_lmc_state``'s) rebuilds the pipeline at the
        current step."""
        return self._sampler

    @sampler.setter
    def sampler(self, sampler: ClusterSampler) -> None:
        self._reset_pipeline()
        self._sampler = sampler

    def _state_tree(self):
        return {"params": self.params, "opt": self.opt_state,
                "store": tuple(self.store)}

    def save(self) -> None:
        """Write an atomic checkpoint (params/opt/stores/lr/step).

        With ``async_ckpt`` the write happens on the manager's background
        thread; this call only pays the device→host snapshot. A failed
        write (injected or real) surfaces as OSError here — the caller's
        recovery is simply to keep training, since the atomic publication
        protocol leaves the previous checkpoint intact.
        """
        if self.ckpt is None:
            return
        extras = {"step": self.step_num, "lr": self.lr}
        self.ckpt.save(self.step_num, self._state_tree(), extras,
                       background=self.async_ckpt)

    def restore(self) -> bool:
        """Restore the newest verifiable checkpoint; False when none exists.

        Corrupt/truncated checkpoints are skipped (checkpoint.manager walks
        newest-first with per-leaf checksum verification); a ``"sampler"``
        entry in an older checkpoint's extras is ignored. Also discards any
        in-flight batch pipeline: the stream is a pure function of the step
        index, so rebuilding it at the restored step replays exactly the
        batches the uninterrupted run would have seen.
        """
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return False
        try:
            tree, extras, step = self.ckpt.restore(self._state_tree())
        except CheckpointError as e:
            # no verifiable checkpoint at all: report and start clean
            self.history.append({"step": self.step_num,
                                 "event": "restore-failed", "error": str(e)})
            return False
        self.params = tree["params"]
        self.opt_state = tree["opt"]
        self.store = HistoricalState(*tree["store"])
        self.step_num = extras["step"]
        self.lr = float(extras.get("lr", self.lr))
        if self.guard is not None:
            # counters don't ride the checkpoint: restart conservative (all
            # rows fresh-at-restore; true staleness is ≤ checkpoint interval)
            self.guard.reset_staleness()
        self._reset_pipeline()
        return True

    # ------------------------------------------------------------- pipeline
    def _batch_pipeline(self) -> SubgraphPipeline:
        """The async batch source, (re)built lazily at the current step."""
        if self._pipeline is None:
            hook = (self.failure_injector.pipeline_hook
                    if self.failure_injector is not None else None)
            self._pipeline = SubgraphPipeline(
                self.sampler, backend=self.backend, depth=self.prefetch,
                workers=self.pipeline_workers, recycle=self.recycle,
                mode=self.pipeline_mode, start_step=self.step_num,
                build_hook=hook)
        return self._pipeline

    def _reset_pipeline(self) -> None:
        """Close the pipeline; the next step rebuilds it at ``step_num``."""
        if self._pipeline is not None:
            self._pipeline.close()
            self._pipeline = None

    def close(self) -> None:
        """Stop background pipeline workers + checkpoint writer (idempotent)."""
        self._reset_pipeline()
        if self.ckpt is not None:
            self.ckpt.close()

    # ------------------------------------------------------------------ run
    def run(self, num_steps: int, *, eval_every: int = 0) -> list[dict]:
        """Train for ``num_steps`` more steps; returns the history list.

        The supervisor loop: every fault class recovers here without
        operator intervention —

        * simulated preemption → restore the newest verifiable checkpoint
          and continue (the batch pipeline is rebuilt at the restored step,
          so the resumed stream is identical to an uninterrupted run);
        * pipeline-worker crash → rebuild the pipeline at the current step
          and retry the same slot (stream is slot-indexed, so the retry
          fetches the identical batch);
        * divergent step (NaN/Inf/spike, from the health guard) → policy
          ``"rollback"`` (restore + optional lr-backoff) or ``"skip-batch"``
          (drop the poisoned update, advance);
        * checkpoint-write failure → record and continue; the previous
          checkpoint is still intact (atomic publication).

        Consecutive recoveries are bounded by ``max_retries`` — when the
        budget is exhausted without a healthy step in between, the run
        aborts with :class:`TrainingDivergedError` rather than live-locking.
        """
        target = self.step_num + num_steps
        while self.step_num < target:
            try:
                with jax.profiler.StepTraceAnnotation("train",
                                                      step_num=self.step_num):
                    self._one_step()
                self._retries_left = self.max_retries  # healthy step: reset
            except SimulatedPreemption:
                # crash recovery: restore last checkpoint and continue; a
                # failed restore still discards the pipeline so the aborted
                # step's already-consumed batch is re-fetched, not skipped
                restored = self.restore()
                if not restored:
                    self._reset_pipeline()
                self.history.append({"step": self.step_num,
                                     "event": "preemption",
                                     "restored": restored})
                continue
            except PipelineFault as e:
                self._spend_retry(f"pipeline fault: {e}")
                self._reset_pipeline()   # rebuild at step_num: same slot
                self.history.append({"step": self.step_num,
                                     "event": "pipeline-fault",
                                     "error": str(e)})
                continue
            except _Divergence as e:
                self._spend_retry(f"divergence: {e}")
                self._recover_divergence(str(e))
                continue
            if self.ckpt and self.step_num % self.ckpt_every == 0:
                try:
                    with span("train.ckpt"):
                        self.save()
                except OSError as e:   # includes injected CheckpointWriteFault
                    self.history.append({"step": self.step_num,
                                         "event": "ckpt-write-failed",
                                         "error": str(e)})
            if eval_every and self.step_num % eval_every == 0:
                self.history.append({"step": self.step_num,
                                     "val_acc": float(self.eval("val"))})
        return self.history

    def _spend_retry(self, reason: str) -> None:
        """Consume one unit of the recovery budget or abort the run."""
        self._retries_left -= 1
        if self._retries_left < 0:
            raise TrainingDivergedError(
                f"recovery budget exhausted ({self.max_retries} retries) "
                f"at step {self.step_num}; last incident: {reason}")

    def _recover_divergence(self, reason: str) -> None:
        """Execute the health policy for a rejected (never-applied) step."""
        policy = self.health.policy if self.health else "skip-batch"
        if policy == "rollback":
            restored = self.restore()
            if restored:
                if self.health.lr_backoff < 1.0:
                    self.lr *= self.health.lr_backoff
                self.history.append({"step": self.step_num,
                                     "event": "health-rollback",
                                     "reason": reason, "lr": self.lr})
                return
            # nothing verifiable to roll back to: degrade to skip-batch
        # skip-batch: the poisoned update was never applied; advance past
        # the consumed batch (the pipeline already moved to the next slot)
        self.step_num += 1
        if self.guard is not None:
            # the store kept its old rows — every row ages one step
            self.guard.staleness += 1
        self.history.append({"step": self.step_num,
                             "event": "health-skip-batch", "reason": reason,
                             "policy": policy})

    def _one_step(self) -> None:
        """One step; its record splits ``time_s`` into the seconds of the
        host spans (``fetch_s`` holding ``wait_s`` and ``h2d_s``,
        ``dispatch_s``, ``sync_s``) and tells whether the batch was
        ``staged`` on the device ahead of time, what it took to build
        (``build_s``, its ELL part ``ell_s``) and how full its padded edge
        list and ELL are (``edge_fill``, ``ell_fill``, ``ell_issue_fill``),
        as ``SubgraphPipeline.last_fetch`` defines them."""
        t0 = time.perf_counter()
        parts = dict(NO_FETCH)
        with span("train.fetch", parts):
            batch = next(self._batch_pipeline())  # may raise PipelineFault
            parts.update(self._pipeline.last_fetch)
        if self.failure_injector is not None:
            self.failure_injector.maybe_fail(self.step_num)
            batch = self.failure_injector.corrupt_batch(self.step_num, batch)
        with span("train.dispatch", parts):
            loss, grads, new_store, metrics = self._step(
                self.params, self.store, batch, self.data.x, self.data.self_w)
            new_params, new_opt, gnorm = self._update(
                grads, self.opt_state, self.params, jnp.float32(self.lr))
        with span("train.sync", parts):
            lossf, gnormf = float(loss), float(gnorm)
            accf = float(metrics["train_acc"])

        # ---- health gate: nothing below is applied if this step diverged
        if self.guard is not None:
            reason = self.guard.check_step(lossf, gnormf)
            if reason is None and self.guard.store_check_due(self.step_num):
                reason = self.guard.check_store(
                    HistoricalState(*new_store)
                    if not isinstance(new_store, HistoricalState)
                    else new_store)
            if reason is not None:
                raise _Divergence(reason)

        self.params, self.opt_state = new_params, new_opt
        dt = time.perf_counter() - t0
        # straggler mitigation: drop the (stale-tolerant) store update when
        # this step blew its deadline, so the next step isn't gated on it
        med = float(np.median(self._step_times)) if self._step_times else dt
        is_straggler = (len(self._step_times) >= 8
                        and dt > self.straggler_deadline * med)
        store_updated = not (is_straggler
                             and self.straggler_policy == "skip-store")
        if store_updated:
            self.store = new_store
        rec = {"step": self.step_num + 1, "loss": lossf,
               "train_acc": accf, "grad_norm": gnormf, "time_s": dt,
               "straggler": bool(is_straggler), **parts}
        if self.guard is not None:
            self.guard.observe(lossf)
            # one fused device->host transfer for the staleness bookkeeping
            # (4 separate np.asarray syncs measurably inflate the step)
            bg, bm, hg, hm = jax.device_get(
                (batch.batch_gids, batch.batch_mask,
                 batch.halo_gids, batch.halo_mask))
            halo_stale = self.guard.halo_staleness(hg, hm)
            self.guard.tick(bg, bm, store_updated)
            rec["halo_staleness"] = halo_stale
            rho_msg = self.guard.check_rho_budget(halo_stale)
            if rho_msg is not None:
                rec["staleness_violation"] = rho_msg
        self._step_times.append(dt)
        self.step_num += 1
        self.history.append(rec)

    # ----------------------------------------------------------------- eval
    def eval(self, split: str = "val") -> float:
        """Full-graph accuracy on the given split ("train"|"val"|"test")."""
        mask = {"val": self.graph.val_mask, "test": self.graph.test_mask,
                "train": self.graph.train_mask}[split]
        return accuracy(self.gnn, self.params, self.data,
                        jnp.asarray(mask.astype(np.float32)))


def _as_pspec_tree(params):
    from repro.models.spec import PSpec
    return jax.tree.map(
        lambda p: PSpec(tuple(p.shape), (None,) * p.ndim, dtype=p.dtype),
        params)
