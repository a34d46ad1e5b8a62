"""pallas_ms: device time per call of the Pallas kernels, on the chip
where it is longest.  Layer: kernels."""


def read(r):
    per_device = r.layer_ns("kernels")
    if not per_device or r.calls == 0 or max(per_device.values()) == 0:
        return None
    return max(per_device.values()) / r.calls / 1e6
