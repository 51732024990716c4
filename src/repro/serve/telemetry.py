"""Host spans and counters of the serving loop, under one set of names.

``with phase(name, stats, **ids):`` times one step of the loop twice: as
a profiler span (``jax.profiler.TraceAnnotation``, on the profiler's
clock, which the device trace shares) and as ``time.monotonic()`` seconds
added to ``stats.<COUNTERS[name]>``, a cumulative counter that
``TconvServer.stats()`` reports whether or not a profile is being taken.
Outside a profile the span costs a constructor call and the counter two
clock reads; there is no buffer, exporter or switch of its own.

The drain thread's spans do not nest and together cover its loop, so a
span's duration is its self time, and a device idle gap in a trace lies
inside the step the loop was in.  Each counter has one writer, the
thread running ``TconvServer.serve_once``.
"""

from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

#: Span name -> the counter it adds its seconds to.  ``serve.submit``
#: runs on the callers' threads and only traces.
COUNTERS = {
    "serve.wait": "drain_wait_s",    # drain thread blocked, no batch due
    "serve.form": "form_s",          # expiries and ``Batcher.ready``
    "serve.pad": "pad_s",            # zero-padded host batch
    "serve.put": "put_s",            # host -> device started (``slab``)
    "serve.dispatch": "dispatch_s",  # the call that enqueues the program
    "serve.fetch": "fetch_s",        # ``np.asarray``: device done, D2H
    "serve.fulfil": "fulfil_s",      # results handed out, counters updated
}


class phase:
    """Context manager: a span named ``name`` carrying ``ids``, and, when
    ``stats`` is given, its seconds added to that object's counter for
    ``name``.  Entering returns the span, whose ``set_metadata(**ids)``
    adds ids known only inside the block."""

    __slots__ = ("_span", "_stats", "_field", "_t0")

    def __init__(self, name: str, stats=None, **ids):
        self._span = TraceAnnotation(name, **ids)
        self._stats = stats
        self._field = None if stats is None else COUNTERS[name]

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.monotonic()
        return self._span

    def __exit__(self, *exc) -> None:
        dt = time.monotonic() - self._t0
        self._span.__exit__(*exc)
        if self._stats is not None:
            setattr(self._stats, self._field,
                    getattr(self._stats, self._field) + dt)
