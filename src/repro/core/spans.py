"""Named host spans on the profiler's clock.

Each layer of the host side marks its work with :func:`span`, a
``jax.profiler.TraceAnnotation`` named ``fase:<layer>:<what>``.  A span
is recorded only while a profiler session runs (``jax.profiler.trace``,
``jax.profiler.start_trace`` or a ``ProfilerSession``), on the host plane
and the clock of the runtime's program launches and the device's program
executions.  With no session a span costs one TraceMe construction.

Layer prefixes, so that a reader selects a layer by prefix:

* ``fase:chunk``: one chunk of the target, from its launch to the end of
  the first read after it (on ``JaxTarget``, ``fase:sync:chunk_record``,
  the read of the chunk's state record, which waits on the chunk; on a
  fleet, one global chunk: ``FleetTarget.run_global``'s launch and its
  read of every board's clock);
* ``fase:rt:``: the host runtime (``run``, ``load``, ``finish``,
  ``poll``, ``dispatch``, ``exception``, ``hfutex``, ``syscall``,
  ``sys:<name>``, ``pagefault``);
* ``fase:sess:submit``: one transaction through the session and link
  model;
* ``fase:sync:<what>``: a target accessor that brings a device value to
  the host (``chunk_record``, ``shadow_fill``, ``fetch_batch``,
  ``page_read``, ``trace_drain``); a read ``JaxTarget`` answers from its
  state shadow has none; ``fase:acc:<accessor>``: one that only
  launches a program.  A fleet's ``FleetTargetView`` uses the same names.

Every call site goes through :func:`span`, so a test may put a recorder
in its place.
"""
from __future__ import annotations

import functools

from jax.profiler import TraceAnnotation

PREFIX = "fase:"


def span(name: str):
    """The span ``fase:<name>``, as a context manager."""
    return TraceAnnotation(PREFIX + name)


def traced(name: str):
    """Decorate a function so that each call runs inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
