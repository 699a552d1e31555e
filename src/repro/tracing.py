"""What the profiler sees of a training run: device scopes and host spans.

Device scopes name the op sets of the LMC train and infer steps
(``core/lmc.py``, ``models/gnn.py``). Each is a ``jax.named_scope``, so it
prefixes the ``op_name`` metadata of every op traced under it; XLA gives a
fusion the metadata of its root op. No scope is opened inside another, so
no op carries two. Under ``jax.vjp`` a forward op's path reads
``jvp(<scope>)`` and a transposed op's ``transpose(jvp(<scope>))``:

* :data:`AGG` — aggregation (segment SpMM or the bucketed ELL SpMM);
* :data:`HALO` — halo compensation (store gather and blend, or the
  ``lmc_compensate`` kernel);
* :data:`STORE` — store refresh (the scatter of the batch rows);
* :data:`DENSE` — everything else: feature gather and embed, the layers'
  transforms, head, loss, adjoint glue and gradient scaling.

Host spans are :func:`span`: a ``jax.profiler.TraceAnnotation`` (inert
without an active profiler, on the device trace's clock with one) that also
adds its ``time.perf_counter()`` duration to a record, so the trainer's
history carries the same split with no profiler running.
"""
from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional

import jax

AGG = "lmc.agg"
HALO = "lmc.halo"
STORE = "lmc.store"
DENSE = "lmc.dense"
SCOPES = (AGG, HALO, STORE, DENSE)


@contextlib.contextmanager
def span(name: str, into: Optional[dict] = None) -> Iterator[None]:
    """Host span ``name``; with ``into``, add its seconds to the field named
    by its last part: ``"train.fetch"`` adds to ``into["fetch_s"]``."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        if into is not None:
            key = name.rsplit(".", 1)[-1] + "_s"
            into[key] = into.get(key, 0.0) + (time.perf_counter() - t0)
