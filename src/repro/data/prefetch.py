"""Host-side prefetch: overlap batch construction with device compute.

Two layers live here (DESIGN.md §9):

* :class:`Prefetcher` — a generic background-thread iterator wrapper with a
  bounded buffer, in-order delivery, exception propagation and prompt
  ``close()``. It knows nothing about graphs.
* :class:`SubgraphPipeline` — the LMC training pipeline built on top of it: a
  thread pool pulls schedule slots from ``ClusterSampler.clusters_at`` (a pure
  function of the slot index, so worker arrival order cannot perturb the
  stream), builds padded ``Batch`` + fixed-capacity ELL buckets on the host,
  hands them through the ``Prefetcher`` queue, and double-buffers the
  host→device transfer: while the consumer runs step k, the transfer for the
  next batch is already staged with ``jax.device_put``. ``recycle=ρ`` reuses
  each sampled subgraph for ρ consecutive steps (LazyGNN-style minibatch
  recycling) before resampling; LMC's bounded-staleness historical stores
  keep this within the Thm 2 staleness budget because the store-refresh path
  is unchanged — every recycled step still rewrites its store rows.

The pipeline's host work runs under the spans of ``repro.tracing``:
``pipeline.build`` on the building thread, ``pipeline.wait`` where the
consumer takes its next batch (blocking on the queue unless one is staged),
``pipeline.h2d`` around every ``jax.device_put``, and ``pipeline.ell``
around the ELL build inside ``pipeline.build``.
:attr:`SubgraphPipeline.last_fetch` holds their seconds for the step last
yielded, and the yielded batch's edge and ELL fills.
"""
from __future__ import annotations

import itertools
import queue
import threading
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterator, Optional

from repro.tracing import span

# SubgraphPipeline.last_fetch of a step that fetched nothing
NO_FETCH = {"wait_s": 0.0, "h2d_s": 0.0, "staged": False, "build_s": 0.0,
            "ell_s": 0.0}
# the fields of last_fetch that describe the yielded batch (core.batch_fills)
FILLS = ("edge_fill", "ell_fill", "ell_issue_fill")


class _Done:
    """Private end-of-stream sentinel (unique object, never yielded by a
    source — unlike e.g. the StopIteration class itself)."""


class _Raised:
    """Wraps an exception raised inside the worker for re-raise in the
    consumer thread."""

    def __init__(self, exc: BaseException):
        self.exc = exc


class Prefetcher:
    """Background-thread prefetch with a bounded buffer (double buffering
    by default).

    * Items are yielded in source order; at most ``depth`` batches are ever
      buffered ahead of the consumer (bounded lookahead, so host memory for
      batch construction stays O(depth)).
    * An exception raised by the source propagates to the consumer from
      ``__next__`` — after all items produced before it have been consumed.
    * ``close()`` stops the worker thread promptly even when it is blocked
      in a full-queue ``put`` and joins it; it is idempotent and is also
      called on GC. Iterating after ``close()`` raises ``StopIteration``.

    Thread-safety: one producer (the internal worker) and one consumer
    thread; ``__next__``/``poll`` must not be called concurrently from
    multiple threads.
    """

    # worker wakes up at this period to notice close() while blocked on a
    # full queue; latency of close(), not of the data path
    _PUT_POLL_S = 0.05

    def __init__(self, source: Iterator, depth: int = 2):
        """Start prefetching from ``source`` with a ``depth``-item buffer."""
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self.source = source
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self._held = None   # terminal item peeked by poll(), kept in order
        self._exhausted = False
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        """Blocking put that aborts (returns False) once close() is called."""
        while not self._stop.is_set():
            try:
                self.q.put(item, timeout=self._PUT_POLL_S)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self) -> None:
        try:
            for item in self.source:
                if not self._put(item):
                    return
        except BaseException as exc:  # noqa: BLE001 — re-raised in consumer
            self._put(_Raised(exc))
            return
        self._put(_Done)

    def __iter__(self):
        """Return self (single-consumer iterator)."""
        return self

    def __next__(self):
        """Next item in source order; blocks until one is buffered."""
        if self._exhausted:
            raise StopIteration
        if self._held is not None:
            item, self._held = self._held, None
            return self._resolve(item)
        while True:
            if self._stop.is_set():
                raise StopIteration
            try:
                item = self.q.get(timeout=self._PUT_POLL_S)
                break
            except queue.Empty:
                continue
        return self._resolve(item)

    def poll(self):
        """Non-blocking variant of ``__next__``: an item if one is already
        buffered, else ``None`` (also ``None`` at end-of-stream).

        Terminal items (end-of-stream, or an exception raised by the
        source) are *held back* rather than consumed here, so they surface
        from the next blocking ``__next__`` at their exact position in the
        stream. The pipeline uses poll() to opportunistically stage the next
        device transfer without stalling the train step — an error for a
        later slot must not fire while an earlier slot is being fetched.
        """
        if self._exhausted or self._stop.is_set() or self._held is not None:
            return None
        try:
            item = self.q.get_nowait()
        except queue.Empty:
            return None
        if item is _Done or isinstance(item, _Raised):
            self._held = item
            return None
        return item

    def _resolve(self, item):
        """Map a queue item to (value | StopIteration | re-raised error)."""
        if item is _Done:
            self._exhausted = True
            raise StopIteration
        if isinstance(item, _Raised):
            self._exhausted = True
            raise item.exc
        return item

    def close(self) -> None:
        """Stop and join the worker; idempotent, also invoked on GC."""
        self._stop.set()
        # drain so a worker blocked mid-put sees _stop on its next poll and
        # the queue's buffered batches are released promptly
        while True:
            try:
                self.q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5.0)

    def __del__(self):
        """Best-effort close when the prefetcher is garbage collected."""
        try:
            self.close()
        except Exception:
            pass


class SubgraphPipeline:
    """Async subgraph sampling pipeline with minibatch recycling.

    Yields device-ready ``repro.core.Batch`` objects, one per *training
    step*. Internally a ``ThreadPoolExecutor`` builds schedule slots ahead of
    the consumer (``sampler.build_batch`` + ``host_batch``: pure numpy, no
    JAX calls on worker threads), a :class:`Prefetcher` buffers up to
    ``depth`` built batches, and the consumer side keeps one extra batch
    staged on device (``jax.device_put`` issued while the previous step is
    still running — double-buffered host→device transfer).

    Determinism contract: the stream is a pure function of
    ``(sampler.seed, mode, recycle, step index)``. Slot ``i`` (steps
    ``[i*recycle, (i+1)*recycle)``) always carries the clusters
    ``sampler.clusters_at(i, mode=mode)``, regardless of ``depth``,
    ``workers`` or thread scheduling; ``depth=0`` builds the identical stream
    synchronously in the consumer thread. Resuming from ``start_step`` k
    replays exactly the tail of a run started at 0 (checkpoint recovery).

    Recycling (``recycle=ρ > 1``): each built subgraph is yielded for ρ
    consecutive steps before the next slot is fetched, amortizing the host
    sampling + bucketing cost 1/ρ. Under ``mode="epoch"`` an "epoch" becomes
    ρ·B/c steps but still visits every cluster exactly once per B/c distinct
    slots. Safe for LMC because the historical stores are refreshed by every
    step — including recycled ones — so staleness stays within the Thm 2
    ρ-term (DESIGN.md §9 discusses the bound).

    Lifecycle: iterate (``for batch in pipe`` / ``next(pipe)``), then
    ``close()`` — or use it as a context manager, which closes on exit even
    when the consumer raises mid-epoch. A worker-side exception surfaces in
    the consumer at the failed slot's position in the stream; buffered
    earlier batches drain first. After ``close()`` iteration raises
    ``StopIteration``.

    Thread-safety: single consumer thread; the sampler's schedule API
    (``clusters_at``/``build_batch``) is called concurrently from workers
    and must stay read-only (``ClusterSampler``'s is).

    Counters: after each ``next``, :attr:`last_fetch` holds ``wait_s``
    (taking the batch: blocked on the queue, or ~0 when staged), ``h2d_s`` (in ``jax.device_put``, the staged
    next batch's included), ``staged`` (the batch came from the device-side
    double buffer), ``build_s`` (the seconds that built the yielded
    slot, on its worker), ``ell_s`` (the part of ``build_s`` that built its
    ELL; 0 without one), ``edge_fill`` (the yielded batch's real edges over
    its padded edge count), ``ell_fill`` (its real (row, neighbour)
    pairs over its ELL slots) and ``ell_issue_fill`` (the same pairs over
    the row copies the kernel issues per lane block; both 0 without an
    ELL). A recycled step fetches
    nothing: its seconds are 0, ``staged`` is False, and the fills are the
    recycled batch's.
    """

    def __init__(self, sampler, *, backend: str = "segment", depth: int = 2,
                 workers: int = 2, recycle: int = 1, mode: str = "uniform",
                 start_step: int = 0, num_steps: Optional[int] = None,
                 ell_buckets=(8, 32, 128),
                 build_hook: Optional[Callable[[int], None]] = None):
        """Configure and (for ``depth >= 1``) start the background pipeline.

        Args:
            sampler: a ``ClusterSampler`` (any object with ``clusters_at`` +
                ``build_batch``); its schedule API must be thread-safe.
            backend: ``"segment"``, ``"ell"`` or ``"ti"`` — whether workers
                also bucket each batch's adjacency into the Pallas kernels'
                ELL layout (``"ti"`` additionally rides the subgraph's
                message-invariance scales along; see core/lmc.host_batch).
            depth: prefetch queue depth. ``0`` disables all threading: the
                synchronous fallback path, same stream (tiny graphs,
                debugging). ``>= 1`` bounds host lookahead to
                ``depth + workers`` built batches plus one staged on device.
            workers: thread-pool size for host-side batch construction.
            recycle: ρ — consecutive steps each sampled subgraph is reused.
            mode: ``"uniform"`` (iid slots, Alg. 1 line 4) or ``"epoch"``
                (shuffled epochs, every cluster once per B/c slots).
            start_step: global step to resume from (slot ``start_step //
                recycle``, mid-recycle-window offsets included).
            num_steps: stop after this many yields (``None`` = unbounded).
            ell_buckets: ELL degree-bucket sizes for ``backend="ell"``.
            build_hook: optional ``hook(slot)`` invoked (on the building
                thread) before each slot is built — the fault-injection
                seam (``train.health.FaultPlan.pipeline_hook``): raising
                here surfaces at that slot's position in the stream like
                any worker exception, and the consumer can rebuild the
                pipeline at the same step for a deterministic retry.
        """
        if depth < 0:
            raise ValueError(f"depth must be >= 0, got {depth}")
        if workers < 1:
            raise ValueError(f"workers must be >= 1, got {workers}")
        if recycle < 1:
            raise ValueError(f"recycle must be >= 1, got {recycle}")
        if start_step < 0:
            raise ValueError(f"start_step must be >= 0, got {start_step}")
        self.sampler = sampler
        self.backend = backend
        self.depth = int(depth)
        self.workers = int(workers)
        self.recycle = int(recycle)
        self.mode = mode
        self.ell_buckets = ell_buckets
        self.build_hook = build_hook
        self._step = int(start_step)
        self._end_step = None if num_steps is None else self._step + int(num_steps)
        self._cur_slot = -1
        self._cur_batch = None
        self._cur_fill = dict.fromkeys(FILLS, 0.0)
        self._staged = None          # (device batch, build record), next slot
        self.last_fetch = dict(NO_FETCH)
        self._closed = False
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pf: Optional[Prefetcher] = None
        if self.depth >= 1:
            first_slot = self._step // self.recycle
            end_slot = (None if self._end_step is None
                        else -(-self._end_step // self.recycle))
            self._pool = ThreadPoolExecutor(
                max_workers=self.workers,
                thread_name_prefix="subgraph-pipeline")
            self._pf = Prefetcher(self._built_stream(first_slot, end_slot),
                                  depth=self.depth)

    # ------------------------------------------------------------- producer
    def _build_host(self, slot: int, record: dict):
        """Worker-side: schedule slot -> host (numpy) Batch; adds the ELL
        build's seconds and the batch's fills to ``record``. Pure numpy."""
        from repro.core.lmc import batch_fills, host_batch
        if self.build_hook is not None:
            self.build_hook(slot)
        cids = self.sampler.clusters_at(slot, mode=self.mode)
        sg = self.sampler.build_batch(cids)
        hb = host_batch(sg, backend=self.backend,
                        ell_buckets=self.ell_buckets, record=record)
        record.update(batch_fills(sg, hb))
        return hb

    def _timed_build(self, slot: int):
        """``_build_host`` under the ``pipeline.build`` span; returns
        (host Batch, {"build_s", "ell_s": seconds, and its fills})."""
        took = {}
        with span("pipeline.build", took):
            hb = self._build_host(slot, took)
        return hb, took

    def _built_stream(self, first_slot: int, end_slot: Optional[int]):
        """Generator the Prefetcher drives: in-order (host batch, build
        record) pairs, as ``_timed_build`` returns them.

        Keeps up to ``workers`` build futures in flight; ``.result()``
        re-raises worker exceptions in slot order so the Prefetcher's
        exception contract holds unchanged.
        """
        slots = (itertools.count(first_slot) if end_slot is None
                 else iter(range(first_slot, end_slot)))
        pending: deque = deque()
        try:
            while True:
                while len(pending) < self.workers:
                    try:
                        s = next(slots)
                    except StopIteration:
                        break
                    pending.append(self._pool.submit(self._timed_build, s))
                if not pending:
                    return
                yield pending.popleft().result()
        finally:
            for f in pending:
                f.cancel()

    # ------------------------------------------------------------- consumer
    def _fetch_next_slot(self):
        """Device batch for the next schedule slot, advancing the stream.

        With prefetch: take the staged transfer if one exists, else block on
        the queue + ``device_put``; then opportunistically stage the transfer
        for the following slot (this is the device-side double buffer).
        Without prefetch (``depth=0``): build + transfer inline. Fills
        :attr:`last_fetch`.
        """
        import jax
        rec = self.last_fetch
        if self._pf is None:
            hb, built = self._timed_build(self._step // self.recycle)
            rec.update(built)
            with span("pipeline.h2d", rec):
                return jax.device_put(hb)
        rec["staged"] = self._staged is not None
        # the wait span opens on every fetch, a staged one too (where it
        # reads ~0 s), so a trace tells "never waited" from "no such span"
        with span("pipeline.wait", rec):
            # may raise StopIteration
            batch, built = self._staged or next(self._pf)
        rec.update(built)
        self._staged = None
        if not rec["staged"]:
            with span("pipeline.h2d", rec):
                batch = jax.device_put(batch)
        nxt = self._pf.poll()
        if nxt is not None:
            hb, built = nxt
            with span("pipeline.h2d", rec):
                self._staged = (jax.device_put(hb), built)
        return batch

    def __iter__(self):
        """Return self (single-consumer iterator)."""
        return self

    def __next__(self):
        """Device Batch for the next training step (recycling-aware)."""
        if self._closed:
            raise StopIteration
        if self._end_step is not None and self._step >= self._end_step:
            raise StopIteration
        slot = self._step // self.recycle
        self.last_fetch = dict(NO_FETCH)
        if slot != self._cur_slot:
            self._cur_batch = self._fetch_next_slot()
            self._cur_slot = slot
            self._cur_fill = {k: self.last_fetch[k] for k in FILLS}
        self.last_fetch.update(self._cur_fill)
        self._step += 1
        return self._cur_batch

    @property
    def step(self) -> int:
        """Global index of the next step this pipeline will yield."""
        return self._step

    def close(self) -> None:
        """Shut down the queue and thread pool; idempotent, also on GC.

        Safe to call with builds still in flight (consumer raised mid-epoch):
        the Prefetcher unblocks/joins its worker, then queued-but-unstarted
        builds are cancelled and the pool joins.
        """
        if self._closed:
            return
        self._closed = True
        self._cur_batch = self._staged = None
        if self._pf is not None:
            self._pf.close()
        if self._pool is not None:
            self._pool.shutdown(wait=True, cancel_futures=True)

    def __enter__(self):
        """Context-manager entry: the pipeline itself."""
        return self

    def __exit__(self, exc_type, exc, tb):
        """Context-manager exit: always close, never swallow the exception."""
        self.close()
        return False

    def __del__(self):
        """Best-effort close when the pipeline is garbage collected."""
        try:
            self.close()
        except Exception:
            pass
