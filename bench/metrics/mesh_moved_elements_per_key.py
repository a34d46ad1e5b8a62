"""mesh_moved_elements_per_key: elements per array that relocation and
compaction write on all chips of the sharded sort for each key sorted,
from the program's own counters (``sort.mesh_moved_elements`` over
``sort.keys``, ``repro.core.telemetry``; both are counted once per eager
call of ``make_sharded_sort``'s function, at the launch).  The mesh's
counterpart of ``moved_elements_per_key``: ``d`` times the sum of the
four phase plans' moved elements over ``n_global``, so a phase that
loses a bucket level reads lower.  Layer: executor.

Like ``moved_elements_per_key``, the value is a property of the plan,
not a measurement of the traced window: the reader divides the counters
over the whole life of the process and does not read ``r``.  A program
without the telemetry module or this counter gives no reading.
"""


def read(r):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    counts = telemetry.counts()
    moved = counts.get("sort.mesh_moved_elements")
    if not counts.get("sort.keys") or not moved:
        return None
    return moved / counts["sort.keys"]
