"""Pipelined async SpGEMM serving: submit/collect over the executor's
pipeline protocol.

FSpGEMM's throughput trick (PAPER Sec. 4) is operand double-buffering:
while one partial product computes, the next rows' operands are already
streaming into on-chip buffers, so the multiply pipeline never stalls on
data movement. The synchronous ``SpGEMMPlan.execute`` is exactly that
stall in host form — rebind, H2D, kernel, assembly, and D2H serialized
per step. :class:`SpGEMMPipeline` removes it:

* ``submit(a_vals, b_vals)`` *dispatches* a step — H2D staging + value
  rebind as one device program, the scheduled kernel and output assembly
  as another (``repro.spgemm.executor``'s ``pipe_*`` protocol) — and returns
  a :class:`SpGEMMTicket` immediately. Nothing blocks: JAX async dispatch
  queues the programs, so step ``s + 1``'s staging overlaps step ``s``'s
  kernel, and each in-flight step owns its own staged packed A/B block
  arrays on device (per shard on sharded plans) — a ``depth``-deep
  operand buffer ring, the paper's double buffer at ``depth=2``.
* ``collect(ticket)`` materializes that step's CSR (the only blocking
  call, D2H). Tickets may be collected out of submission order;
  ``collect()`` with no argument takes the oldest outstanding.
* in-flight work is bounded by ``depth``: a ``submit`` past the bound
  raises :class:`PipelineFullError` (explicit backpressure), and
  ``stream(value_iter)`` / ``__iter__`` manage the bound for you,
  yielding ordered results.

Results are **bitwise-equal** to sequential ``execute`` calls: the
pipeline's jits run exactly the fused cores' ops, and submission is stateless with
respect to the plan's staged values (like ``execute_batch``), so a
pipelined stream of N steps reproduces N synchronous executes exactly —
on element, block, batched, and sharded plans.

Error handling: a step whose dispatch or device execution fails stores
the exception on its ticket; ``collect`` of that ticket re-raises it
while every other in-flight step stays collectable. While any ticket is
in flight the owning plan refuses buffer teardown
(``release_values``/``release``/cache eviction raise) — close or drain
the pipeline first. ``SpGEMMPipeline`` is a context manager; exiting
discards anything still in flight.

Spans: ``submit`` is one ``spgemm.submit`` span holding the step's
``spgemm.dispatch``, and ``collect`` opens the step's ``spgemm.collect``;
both carry the ticket index as ``step`` (``repro.spgemm.plan`` lists the
spans).
"""
from __future__ import annotations

import threading
import weakref
from typing import Iterable, Iterator, Optional, Tuple, Union

from jax.profiler import TraceAnnotation

__all__ = [
    "PipelineFullError",
    "SpGEMMPipeline",
    "SpGEMMTicket",
]


class PipelineFullError(RuntimeError):
    """``submit`` past the pipeline's in-flight ``depth`` bound."""


class _Prepared:
    """A validated, host-side-prepared submission (built by
    ``SpGEMMPlan._pipe_check``): execution mode, operands (cast host
    arrays for value modes, staged device arrays for block mode), batch
    size (``None`` single-shot), the executes-counter increment, and the
    host arrays that block mode staged for it (``spgemm.dispatch`` counts
    their bytes)."""

    __slots__ = ("mode", "a", "b", "batch", "n_execs", "sent")

    def __init__(self, mode, a, b, batch, n_execs, sent=()):
        self.mode = mode
        self.a = a
        self.b = b
        self.batch = batch
        self.n_execs = n_execs
        self.sent = sent


class _Step:
    """One in-flight pipeline step: its dispatched device result (packed C
    values; a list of chunk arrays for batch submissions) or the error
    its dispatch raised."""

    __slots__ = ("prep", "packed", "error")

    def __init__(self, prep):
        self.prep = prep
        self.packed = None
        self.error: Optional[BaseException] = None


class SpGEMMTicket:
    """Ordered handle for one submitted step; redeem with
    :meth:`result` (or ``pipeline.collect(ticket)``)."""

    __slots__ = ("_pipe", "index", "batch")

    def __init__(self, pipe: "SpGEMMPipeline", index: int,
                 batch: Optional[int]):
        self._pipe = pipe
        self.index = index
        self.batch = batch  # None for single-shot, batch size otherwise

    def result(self):
        """Block until this step's C is on host and return it (a CSR, or
        a list of CSRs for a batched submission)."""
        return self._pipe.collect(self)

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return (f"SpGEMMTicket(index={self.index}"
                + (f", batch={self.batch}" if self.batch else "") + ")")


def _discard_outstanding(plan, steps: dict, lock: threading.Lock) -> None:
    """Drop every outstanding step and balance the plan's in-flight
    count. Module-level (no pipeline reference) so ``weakref.finalize``
    can run it after the pipeline itself is collected."""
    with lock:
        n = len(steps)
        steps.clear()
    for _ in range(n):
        plan._pipe_end()


ValueItem = Union[Tuple, dict]


class SpGEMMPipeline:
    """Bounded-depth async serving pipeline over one
    :class:`~repro.spgemm.plan.SpGEMMPlan`.

    ``depth`` bounds in-flight steps (2 = the paper's double buffer:
    one step staging while one computes). Construct directly or via
    ``plan.pipeline(depth=...)``; typical streaming use::

        with plan.pipeline(depth=2) as pipe:
            for c in pipe.stream(stream.value_iter(steps=100)):
                consume(c)

    or explicit submit/collect::

        t0 = pipe.submit(a0, b0)
        t1 = pipe.submit(a1, b1)   # overlaps t0's kernel
        c0 = pipe.collect(t0)      # or collect(t1) first: out-of-order OK
        c1 = t1.result()

    Thread-safe; a single pipeline's submissions are ordered by ticket
    index. Submission is stateless w.r.t. the plan's staged values (the
    no-arg ``submit()`` reuses them, like no-arg ``execute``).
    """

    def __init__(self, plan, depth: int = 2):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self.plan = plan
        self.depth = int(depth)
        self._lock = threading.Lock()
        self._steps: dict = {}  # index -> _Step (outstanding only)
        self._next = 0
        self._closed = False
        # Abandonment guard: a pipeline (or a lone execute_async ticket)
        # dropped with outstanding steps must not pin the plan's
        # in-flight count forever. The finalizer discards whatever is
        # still outstanding when the pipeline is garbage-collected;
        # close() runs the same discard eagerly (finalize is call-once,
        # so the two never double-release).
        self._finalizer = weakref.finalize(
            self, _discard_outstanding, plan, self._steps, self._lock)

    # -- introspection -----------------------------------------------------

    @property
    def in_flight(self) -> int:
        """Outstanding (submitted, not yet collected) steps."""
        with self._lock:
            return len(self._steps)

    @property
    def free_slots(self) -> int:
        """Submissions currently possible without
        :class:`PipelineFullError` (0 once closed).

        Advisory under concurrency in general, but exact for a
        single-submitter arrangement (the gateway's dispatcher): collects
        only *free* slots, so the value cannot shrink between a check and
        that submitter's next ``submit``."""
        with self._lock:
            if self._closed:
                return 0
            return max(0, self.depth - len(self._steps))

    def __len__(self) -> int:
        return self.in_flight

    # -- submit / collect --------------------------------------------------

    def submit(self, a_vals=None, b_vals=None) -> SpGEMMTicket:
        """Dispatch one step; returns immediately with a ticket.

        Operand shapes follow ``execute``/``execute_batch``: ``[nnz]``
        value vectors (element plans) or packed block arrays (block
        plans), with an optional leading batch axis (the ticket then
        redeems to a list of CSRs, exactly ``execute_batch``'s output).
        Passing neither reuses the plan's staged values. Raises
        :class:`PipelineFullError` when ``depth`` steps are already in
        flight — collect one first (``stream`` does this for you).
        Invalid operands raise here, without consuming a slot; failures
        *after* validation (dispatch or device errors) are stored on the
        ticket and re-raised by ``collect``.
        """
        with TraceAnnotation("spgemm.submit") as span, self._lock:
            if self._closed:
                raise RuntimeError("pipeline is closed")
            if len(self._steps) >= self.depth:
                raise PipelineFullError(
                    f"pipeline depth {self.depth} exhausted "
                    f"({len(self._steps)} step(s) in flight); collect a "
                    f"result before submitting more"
                )
            prep = self.plan._pipe_check(a_vals, b_vals)
            self.plan._pipe_begin(prep.n_execs)
            index = self._next
            self._next += 1
            span.set_metadata(step=index)
            step = _Step(prep)
            try:
                step.packed = self.plan._pipe_dispatch(prep, index)
            except Exception as e:
                # Poisoned step: the slot is held (collect re-raises and
                # frees it); other in-flight steps are unaffected.
                step.error = e
            except BaseException:
                # KeyboardInterrupt/SystemExit must propagate, not hide
                # in a ticket; undo the in-flight accounting first.
                self.plan._pipe_end()
                raise
            self._steps[index] = step
            return SpGEMMTicket(self, index, prep.batch)

    def collect(self, ticket: Optional[SpGEMMTicket] = None):
        """Materialize one step's result (blocking D2H).

        ``ticket=None`` collects the oldest outstanding step. Returns a
        CSR (single-shot) or a list of CSRs (batched submission) sharing
        the plan's precomputed ``indptr``/``indices``. Re-raises the
        step's stored error, if any; the ticket's slot is freed either
        way.
        """
        with self._lock:
            if ticket is None:
                if not self._steps:
                    raise ValueError("nothing in flight to collect")
                index = min(self._steps)
            else:
                if ticket._pipe is not self:
                    raise ValueError(
                        "ticket belongs to a different pipeline")
                index = ticket.index
                if index not in self._steps:
                    raise ValueError(
                        f"ticket {index} was already collected")
            step = self._steps.pop(index)
        try:
            if step.error is not None:
                raise step.error
            return self.plan._pipe_collect(step.prep, step.packed, index)
        finally:
            self.plan._pipe_end()

    # -- streaming ---------------------------------------------------------

    def __iter__(self) -> Iterator:
        """Drain: collect every outstanding step, oldest first."""
        while True:
            with self._lock:
                if not self._steps:
                    return
            yield self.collect()

    def stream(self, value_iter: Iterable[ValueItem]) -> Iterator:
        """Pump ``value_iter`` through the pipeline at full depth,
        yielding ordered results.

        Items are ``(a_vals, b_vals)`` tuples or ``{"a_vals": ...,
        "b_vals": ...}`` dicts (what ``SpGEMMValueStream.iter`` /
        ``value_iter`` produce). Keeps ``depth`` steps in flight —
        submitting step ``s + depth`` before collecting step ``s`` — so
        staging overlaps compute throughout; results come back in
        submission order. Abandoning the iterator mid-stream discards
        whatever is still in flight (the plan's in-flight count returns
        to zero).
        """
        try:
            for item in value_iter:
                a_vals, b_vals = self._coerce(item)
                while self.in_flight >= self.depth:
                    yield self.collect()
                self.submit(a_vals, b_vals)
            yield from self
        finally:
            if self.in_flight:  # abandoned mid-stream
                self.close()

    @staticmethod
    def _coerce(item: ValueItem):
        if isinstance(item, dict):
            return item["a_vals"], item["b_vals"]
        a_vals, b_vals = item
        return a_vals, b_vals

    # -- teardown ----------------------------------------------------------

    def close(self) -> None:
        """Discard all outstanding steps (their device work is abandoned,
        results never materialize on host) and refuse further submits.
        Releases the plan's in-flight accounting, so buffer teardown
        (``release_values`` etc.) becomes legal again."""
        with self._lock:
            self._closed = True
        _discard_outstanding(self.plan, self._steps, self._lock)

    def __enter__(self) -> "SpGEMMPipeline":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()
