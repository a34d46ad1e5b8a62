"""pallas_roofline: the Pallas kernels' share of the chip's HBM roofline.

For every executed kernel whose operands or results sit in HBM, the
least time its HBM traffic takes at the peak bandwidth (results plus
operands, counted from the shapes in the op's HLO text; arrays that the
layout places in VMEM are left out) over the time it took, summed over
those kernels, in percent.  HBM bound only: the v5e publishes no VPU
peak for int32 compare and select, so a sorting network that is bound
by its compares reads low here.  Layer: kernels."""

from harness.profile import call_bytes


def read(r):
    least_ns = took_ns = 0.0
    for o in r.ops_in_window():
        if r.layers["kernels"].search(o.name) is None:
            continue
        nbytes = call_bytes(o.name, hbm_only=True)
        if nbytes and o.dur_ns > 0:
            least_ns += nbytes / r.peaks["hbm_bytes_per_s"] * 1e9
            took_ns += o.dur_ns
    if took_ns == 0:
        return None
    return 100.0 * least_ns / took_ns
