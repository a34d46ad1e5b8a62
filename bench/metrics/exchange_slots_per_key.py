"""exchange_slots_per_key: slots per array that the sharded sort's
bucket ``all_to_all`` carries between chips for each key sorted,
padding included, from the program's own counters
(``sort.exchange_slots`` over ``sort.keys``, ``repro.core.telemetry``;
both are counted once per eager call of ``make_sharded_sort``'s
function, at the launch).  Above 1 by the padding of the static per-pair
capacity ``c_pair``.  Layer: mesh.

Like ``moved_elements_per_key``, the value is a property of the plan
(``d * d * c_pair / n_global``), not a measurement of the traced
window: the reader divides the counters over the whole life of the
process and does not read ``r``.  A program without the telemetry
module or these counters gives no reading.
"""


def read(r):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    counts = telemetry.counts()
    if not counts.get("sort.keys") or not counts.get("sort.exchange_slots"):
        return None
    return counts["sort.exchange_slots"] / counts["sort.keys"]
