"""dispatch_ms: host time per call from entering the entry until it
returns the unready result (the benchmark's ``bench.dispatch`` span):
key encoding, plan lookup and the launch of every program the call
runs.  Layer: entry."""


def read(r):
    lo, hi = r.window
    spans = [e - s for s, e in r.trace.spans.get("bench.dispatch", ())
             if s >= lo and e <= hi]
    if not spans:
        return None
    return sum(spans) / len(spans) / 1e6
