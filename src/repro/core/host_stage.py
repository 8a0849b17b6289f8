"""Host-stage worker thread for the async dispatch pipeline.

In ``stage_dispatch="async"`` mode the per-layer host stage no longer
blocks the dispatch thread on the FlashD2H write-back: the engine
dispatches the device->host stripe gather (a queued XLA op), hands the
*device arrays* to a :class:`HostStageWorker` job, and immediately goes
on to dispatch ``attend(l)`` / ``select(l+1)``.  The worker converts the
stripes (``np.asarray`` — the actual blocking transfer), stages them
into the DRAM pools (``save_new_tokens_fused`` + ``flush``), and records
completion per *key* (we key jobs by attention-layer index).

Correctness hinges on two fences the engine issues:

- ``fence(lidx)`` before any ``load_blocks_fused(lidx, ...)`` gather
  while a write-back job for that layer is outstanding (the
  *writeback-before-gather* / restore-before-use invariant), and
- ``drain()`` at the end of every iteration, before sampling and before
  any request release drops a DRAM pool the worker may still write
  (the *writeback-before-drop* invariant).

Exceptions raised by a job are captured and re-raised on the dispatch
thread at the next ``fence``/``drain``/``submit`` touching the worker,
so a failed write-back fails the iteration instead of vanishing on a
daemon thread.

JAX's value semantics make the off-thread conversion safe without
copying: the dispatched gather closes over the pool *value* at dispatch
time, so later pool-mutating stages (which produce new buffers — the
donated input buffers are only reused once no live reference remains)
never alter what the worker reads back.

The quantized offload tier (``EngineConfig.offload_quant="int8"``) rides
these same jobs unchanged: quantization happens inside the pool's
``flush`` (per-block, on this worker thread), so the fence semantics
above are exactly what guarantees a gather never observes a
half-quantized block — ``fence(lidx)`` orders the whole
stage-quantize-store sequence before any same-layer gather, and
``drain()`` orders it before pool teardown.  Only the booked byte counts
differ (wire size; see ``HostPool.stage``).
"""
from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Dict, Optional

from repro.obs import tracing


class HostStageError(RuntimeError):
    """A host-stage job failed; carries the original exception as cause."""


class _Job:
    __slots__ = ("key", "fn", "args", "done")

    def __init__(self, key: Any, fn: Callable[..., None], args: tuple):
        self.key = key
        self.fn = fn
        self.args = args
        self.done = threading.Event()


class HostStageWorker:
    """Single daemon thread executing host-stage jobs in FIFO order.

    FIFO execution means jobs for the same key complete in submission
    order, so ``fence(key)`` only needs to wait for the *last* job
    submitted under that key.
    """

    def __init__(self, name: str = "host-stage", tracer=None):
        self._q: "queue.Queue[Optional[_Job]]" = queue.Queue()
        self._last: Dict[Any, _Job] = {}       # key -> most recent job
        self._lock = threading.Lock()
        self._exc: Optional[BaseException] = None
        self._closed = False
        self.jobs_run = 0
        self.busy_s = 0.0                      # total time inside job fns
                                               # (the worker.job spans)
        self.tracer = tracer if tracer is not None else tracing.DEFAULT
        self._thread = threading.Thread(target=self._loop, name=name,
                                        daemon=True)
        self._thread.start()

    # -- worker side --------------------------------------------------------
    def _loop(self) -> None:
        while True:
            job = self._q.get()
            if job is None:
                return
            try:
                if self._exc is None:          # fail fast after first error
                    with self.tracer.span("worker.job", key=job.key) as sp:
                        job.fn(*job.args)
                    self.busy_s += sp.dur
                    self.jobs_run += 1
            except BaseException as e:         # noqa: BLE001 - re-raised
                self._exc = e                  # on the dispatch thread
            finally:
                job.done.set()

    # -- dispatch-thread side ----------------------------------------------
    def _raise_pending(self) -> None:
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise HostStageError(
                f"host-stage job failed: {exc!r}") from exc

    def submit(self, key: Any, fn: Callable[..., None], *args: Any) -> None:
        """Enqueue ``fn(*args)`` under ``key``; raises pending job errors."""
        self._raise_pending()
        if self._closed:
            raise HostStageError("submit() after close()")
        job = _Job(key, fn, args)
        with self._lock:
            self._last[key] = job
        self._q.put(job)

    def pending(self, key: Any) -> bool:
        """True while a job submitted under ``key`` has not completed."""
        with self._lock:
            job = self._last.get(key)
        return job is not None and not job.done.is_set()

    def fence(self, key: Any) -> None:
        """Block until every job submitted under ``key`` has completed."""
        with self._lock:
            job = self._last.get(key)
        if job is not None:
            job.done.wait()
        self._raise_pending()

    def drain(self) -> None:
        """Block until every submitted job has completed."""
        with self._lock:
            jobs = list(self._last.values())
        for job in jobs:
            job.done.wait()
        # anything still queued was submitted concurrently by this thread —
        # there is a single producer, so _last covers the full queue.
        self._raise_pending()

    def close(self) -> None:
        """Drain outstanding work and stop the thread (idempotent).

        Errors from outstanding jobs surface here rather than being
        swallowed by shutdown.
        """
        if self._closed:
            return
        self._closed = True
        self._q.put(None)
        self._thread.join()
        self._raise_pending()

    @property
    def closed(self) -> bool:
        return self._closed
