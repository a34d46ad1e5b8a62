"""collective_ms: device time per call of the collectives between chips
(all_to_all, all_gather, ...), on the chip where it is longest.  Ops on
the synchronous and the asynchronous line are merged, so an op that
shows on both counts once.  Layer: mesh."""

from harness.profile import union_ns


def read(r):
    per: dict = {}
    for async_ops in (False, True):
        for o in r.ops_in_window(async_ops):
            if r.layers["mesh"].search(o.name):
                per.setdefault(o.device, []).append((o.start_ns, o.end_ns))
    if not per or r.calls == 0:
        return None
    return max(union_ns(v) for v in per.values()) / r.calls / 1e6
