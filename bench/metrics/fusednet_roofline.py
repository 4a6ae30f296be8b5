"""The fusednet megakernel's share of its roofline: the least time the chip
could take for its launches (the larger of int8 operations over the int8
peak and bytes over HBM bandwidth, at the rows and versions each launch is
given, from `bench/work.py` and `bench/peaks.json`) over their device time."""
# the megakernel's pallas_call, as the chip's trace names it (HLO instruction
# `binary_forward_planes.<n>`, a Mosaic `tpu_custom_call`)
KERNEL = "binary_forward_planes"


def read(run):
    if run.trace is None or run.peak is None:
        return None
    events = run.kernel_events(KERNEL)
    if not events:
        return None
    least, _ = run.work.min_seconds(run.widths, run.slot_capacity, run.versions, run.peak)
    device_s = sum(d for _, _, d, *_ in events) * 1e-9
    return 100.0 * least * len(events) / device_s
