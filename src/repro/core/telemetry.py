"""Named host spans and counters of the sort's own layers.

Spans are ``jax.profiler.TraceAnnotation``s, so the profiler's trace is
their exporter: they land on the host plane, on the device trace's
clock, and a span opened while no profiler runs is a no-op TraceMe.
Counters are plain in-process integers; :func:`counts` is their
snapshot.  The device-side counterpart is ``jax.named_scope`` in the
executors (``core/bucket_sort.py``, ``core/distributed_sort.py``),
which names the compiled ops.

Names (PERF.md lists each with the metric that reads it):

  spans     sort.<entry> (sort.argsort, sort.sort, ...,
            sort.sharded_argsort), and inside it sort.plan,
            sort.encode, sort.launch (one per attempt), sort.decode
  counters  sort.keys (real keys, padding left out; n_global on the
            mesh), sort.moved_elements (one device) and
            sort.exchange_slots (the mesh: d * d * c_pair, the slots
            per array the bucket all_to_all carries, padding
            included), sort.mesh_moved_elements (the mesh: d times one
            device's moved elements over the four phase plans), each
            once per call, at the launch;
            sort.traces, sort.sharded_traces (once per trace of the
            jitted executors)

The entry's spans and its per-call counters describe eager calls.  An
entry called inside an outer ``jax.jit`` runs its Python once per
trace: its counters then advance once per trace, and its spans time
tracing, not dispatch.
"""

from __future__ import annotations

import collections
import threading

import jax

_COUNTS: collections.Counter = collections.Counter()
_LOCK = threading.Lock()


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``name``, for use as a context manager."""
    return jax.profiler.TraceAnnotation(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    with _LOCK:
        _COUNTS[name] += n


def counts() -> dict[str, int]:
    """A snapshot of every counter."""
    with _LOCK:
        return dict(_COUNTS)
