"""device_idle_share: the share of the traced window in which no XLA op
ran, on the least idle chip, in percent.  Layer: device."""


def read(r):
    if r.window_ns <= 0 or not r.trace.devices:
        return None
    busy = r.busy_ns()
    return 100.0 * (1.0 - max(busy.values()) / r.window_ns)
