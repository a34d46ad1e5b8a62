"""xla_ms: device time per call of the ops that are neither a Pallas
kernel nor a collective (relocation and compaction gathers, cumsums,
pads, copies), on the chip where it is longest.  Layer: executor."""

from harness.profile import EXECUTOR


def read(r):
    per_device = r.layer_ns(EXECUTOR)
    if not per_device or r.calls == 0 or max(per_device.values()) == 0:
        return None
    return max(per_device.values()) / r.calls / 1e6
