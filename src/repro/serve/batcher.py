"""Wait-or-flush request batching — fill fold_batch-tuned batch sizes.

One FIFO queue per bucket.  A bucket flushes when either:

* **full** — it holds at least ``target_batch`` requests: the batch the
  plans were tuned for is ready, dispatch immediately; or
* **deadline** — its oldest request has waited ``max_wait_s``: dispatch
  the partial batch (padded up to the bucket shape by the server) so p99
  queue wait is bounded by the configured deadline rather than by traffic.

Resilience hooks (ISSUE 10, ``serve/resilience.py``):

* queues are **bounded** — ``max_queue_depth`` caps each bucket's FIFO
  and :meth:`Batcher.put` sheds the overflow with
  :class:`~repro.serve.bucketing.QueueFullError` instead of letting an
  overloaded bucket grow without bound;
* requests carry an optional absolute **deadline**;
  :meth:`Batcher.pop_expired` removes the expired ones *before* batches
  form, so a dead-on-arrival request fails fast with
  ``DeadlineExceeded`` rather than occupying a batch slot;
* :meth:`Batcher.pop_all` empties every queue at shutdown so ``stop()``
  can fail whatever could not be drained — a request must never be left
  unfulfilled.

Time is injected (``ready(now=...)``) so flush decisions are
deterministic under test; the server passes ``time.monotonic()``.
All methods are thread-safe (``submit`` runs on caller threads, the drain
loop on the server thread).
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Dict, List, Optional, Tuple

from repro.serve.bucketing import BucketKey, BucketSpec, QueueFullError

FLUSH_FULL = "full"
FLUSH_DEADLINE = "deadline"

# Result-slot sentinel: distinguishes "not fulfilled yet" from a
# legitimately-None payload, so Request.result can never hand an
# unfulfilled wait back as a real result (it raises TimeoutError instead).
_UNSET = object()


class Request:
    """One in-flight request: payload + a thread-safe result slot."""

    __slots__ = ("rid", "model", "inputs", "precision", "t_enqueue",
                 "deadline", "t_done", "batch", "_event", "_value", "_error")

    def __init__(self, rid: int, model: str, inputs, precision: str,
                 t_enqueue: float, deadline: Optional[float] = None):
        self.rid = rid
        self.model = model
        self.inputs = inputs
        self.precision = precision
        self.t_enqueue = t_enqueue
        # Absolute monotonic-clock deadline (None = no deadline): past it
        # the request fails fast with DeadlineExceeded instead of being
        # executed (serve/resilience.py).
        self.deadline = deadline
        self.t_done: Optional[float] = None
        # Index of the batch that served it (the server's ``batch`` span
        # id), None until its batch forms.
        self.batch: Optional[int] = None
        self._event = threading.Event()
        self._value = _UNSET
        self._error: Optional[BaseException] = None

    def set_result(self, value, t_done: float) -> None:
        self._value = value
        self.t_done = t_done
        self._event.set()

    def set_error(self, err: BaseException, t_done: float) -> None:
        self._error = err
        self.t_done = t_done
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def expired(self, now: float) -> bool:
        return self.deadline is not None and now >= self.deadline

    def result(self, timeout: Optional[float] = None):
        """The fulfilled payload; raises rather than guessing.

        An unfulfilled wait raises ``TimeoutError`` — it must never
        return ``None``, which would be indistinguishable from a real
        ``None`` payload (the ``_UNSET`` sentinel keeps the two apart
        even if a caller races the fulfilling thread).  A request failed
        by the server re-raises its typed error (``DeadlineExceeded``,
        the ladder-exhausted fault, ...).
        """
        if not self._event.wait(timeout):
            raise TimeoutError(f"request {self.rid} not served "
                               f"within {timeout}s")
        if self._error is not None:
            raise self._error
        if self._value is _UNSET:  # fulfilled event without a payload:
            raise RuntimeError(     # an invariant violation, not a result
                f"request {self.rid} signalled done with no result/error")
        return self._value

    @property
    def latency_s(self) -> Optional[float]:
        """Enqueue-to-result wall time (None while in flight)."""
        return None if self.t_done is None else self.t_done - self.t_enqueue


class Batcher:
    """Per-bucket FIFO queues with the wait-or-flush policy."""

    def __init__(self, *, max_wait_s: float = 0.05,
                 max_queue_depth: Optional[int] = None):
        self.max_wait_s = float(max_wait_s)
        self.max_queue_depth = (None if max_queue_depth is None
                                else int(max_queue_depth))
        self._lock = threading.Lock()
        self._queues: Dict[BucketKey, deque] = {}
        self._specs: Dict[BucketKey, BucketSpec] = {}

    def put(self, spec: BucketSpec, request: Request) -> None:
        """Enqueue one request; sheds with :class:`QueueFullError` when the
        bucket's queue is at ``max_queue_depth`` (the request is NOT
        enqueued — the caller owns failing/raising it)."""
        with self._lock:
            self._specs[spec.key] = spec
            q = self._queues.setdefault(spec.key, deque())
            if (self.max_queue_depth is not None
                    and len(q) >= self.max_queue_depth):
                raise QueueFullError(
                    f"bucket {spec.key} queue is full "
                    f"({len(q)}/{self.max_queue_depth}); shedding")
            q.append(request)

    def pending(self) -> int:
        with self._lock:
            return sum(len(q) for q in self._queues.values())

    def next_deadline(self) -> Optional[float]:
        """Earliest time any queued request's wait deadline expires (the
        server's sleep bound), or None if nothing is queued."""
        with self._lock:
            heads = [q[0].t_enqueue for q in self._queues.values() if q]
        return min(heads) + self.max_wait_s if heads else None

    def pop_expired(self, now: float) -> List[Tuple[BucketSpec, list]]:
        """Remove every request whose deadline passed; FIFO order kept.

        Called by the server at the top of each ``serve_once`` tick with
        the same ``now`` it hands to :meth:`ready`, so an expired request
        fails fast with ``DeadlineExceeded`` instead of occupying a slot
        in the batch that forms right after.
        """
        out: List[Tuple[BucketSpec, list]] = []
        with self._lock:
            for key, q in self._queues.items():
                dead = [r for r in q if r.expired(now)]
                if dead:
                    live = [r for r in q if not r.expired(now)]
                    q.clear()
                    q.extend(live)
                    out.append((self._specs[key], dead))
        return out

    def pop_all(self) -> List[Tuple[BucketSpec, list]]:
        """Empty every queue (shutdown): the caller fulfils or fails each
        popped request so none is left waiting forever."""
        out: List[Tuple[BucketSpec, list]] = []
        with self._lock:
            for key, q in self._queues.items():
                if q:
                    out.append((self._specs[key], list(q)))
                    q.clear()
        return out

    def due(self, reqs: list, reason: str, now: float) -> float:
        """When a batch that :meth:`ready` popped at ``now`` became due: a
        full batch at its last member's enqueue, a deadline flush once its
        head had waited ``max_wait_s``, a forced flush at ``now``."""
        if reason == FLUSH_FULL:
            return reqs[-1].t_enqueue
        return min(reqs[0].t_enqueue + self.max_wait_s, now)

    def ready(self, now: float, *,
              force: bool = False) -> List[Tuple[BucketSpec, list, str]]:
        """Pop every batch due at ``now`` as (spec, requests, reason).

        Full batches flush regardless of age; a remaining partial flushes
        once its oldest member has waited ``max_wait_s`` (or immediately
        with ``force=True`` — shutdown/drain).
        """
        out: List[Tuple[BucketSpec, list, str]] = []
        with self._lock:
            for key, q in self._queues.items():
                spec = self._specs[key]
                target = max(spec.target_batch, 1)
                while len(q) >= target:
                    out.append((spec, [q.popleft() for _ in range(target)],
                                FLUSH_FULL))
                if q and (force or
                          now - q[0].t_enqueue >= self.max_wait_s):
                    out.append((spec, list(q), FLUSH_DEADLINE))
                    q.clear()
        return out
