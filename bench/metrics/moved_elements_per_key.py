"""moved_elements_per_key: elements per array that relocation and
compaction write for each key sorted, from the program's own counters
(``sort.moved_elements`` over ``sort.keys``, ``repro.core.telemetry``;
both are counted once per eager call, at the launch).  Layer: executor.

The value is a property of the plan (levels, capacities, padding), not
a measurement of the traced window: the reader divides the counters
over the whole life of the process (warm-up and every call of the
window) and does not read ``r``.  Every call of a cell runs one plan,
so that ratio is the ratio of each call.  A program without the
telemetry module or its counters gives no reading.
"""


def read(r):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    counts = telemetry.counts()
    if not counts.get("sort.keys"):
        return None
    return counts.get("sort.moved_elements", 0) / counts["sort.keys"]
