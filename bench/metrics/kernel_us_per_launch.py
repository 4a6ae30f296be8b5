"""Mean device time of one launch of the fusednet megakernel, from the trace."""
# the megakernel's pallas_call, as the chip's trace names it (HLO instruction
# `binary_forward_planes.<n>`, a Mosaic `tpu_custom_call`)
KERNEL = "binary_forward_planes"


def read(run):
    if run.trace is None:
        return None
    events = run.kernel_events(KERNEL)
    if not events:
        return None
    return sum(d for _, _, d, *_ in events) / len(events) * 1e-3
